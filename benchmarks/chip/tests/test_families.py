"""Registration through ``families/<model_type>.py``: the two configurations
register with the same twelve fields and values as before families, a
``model_type`` with no module stops the run by name, and a new family is a
new module alone: a scratch ``qwen3_moe`` module in a directory of its own
registers a ``qwen3_moe`` file, and its ``refuse`` stops a file that states
a mechanism the program lacks."""

import copy
import json
import textwrap
from pathlib import Path

import pytest

import families
import harness

CONFIGS = Path(harness.HERE) / "configs"

#: the fields ``register_config`` compared before families, with their
#: values for each file
TWELVE = {
    "qwen3-4b.json": {
        "d_model": 2560, "n_layers": 36, "n_heads": 32, "n_kv_heads": 8,
        "head_dim": 128, "d_ff": 9728, "vocab_size": 151936,
        "tie_embeddings": True, "qkv_bias": False, "qk_norm": True,
        "rope_theta": 1000000.0, "norm_eps": 1e-06,
    },
    "qwen2.5-32b-L6.json": {
        "d_model": 5120, "n_layers": 6, "n_heads": 40, "n_kv_heads": 8,
        "head_dim": 128, "d_ff": 27648, "vocab_size": 152064,
        "tie_embeddings": False, "qkv_bias": True, "qk_norm": False,
        "rope_theta": 1000000.0, "norm_eps": 1e-05,
    },
}


def _config(name: str) -> dict:
    return json.loads((CONFIGS / name).read_text())


@pytest.mark.parametrize("name", sorted(TWELVE))
def test_benchmark_configs_register_with_the_same_twelve_fields(name):
    config = _config(name)
    fields = families.load(config["model_type"]).program_fields(config)
    assert fields == TWELVE[name]
    assert all(type(fields[k]) is type(v) for k, v in TWELVE[name].items())
    assert harness.register_config(config) == config["name"]


@pytest.mark.parametrize("name", sorted(TWELVE))
def test_a_served_config_that_differs_is_refused(name):
    config = _config(name)
    config["program"] = dict(
        config["program"], replace=dict(config["program"]["replace"], d_ff=128)
    )
    with pytest.raises(ValueError, match="d_ff"):
        harness.register_config(config)


def test_a_model_type_without_a_module_stops_by_name():
    config = dict(_config("qwen3-4b.json"), model_type="mamba2")
    with pytest.raises(ValueError, match=r"families/mamba2\.py"):
        harness.register_config(config)


@pytest.mark.parametrize("name", sorted(TWELVE))
def test_a_stated_mechanism_the_program_lacks_is_refused(name):
    config = dict(_config(name), rope_scaling={"type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="rope_scaling"):
        harness.register_config(config)


SCRATCH_FAMILY = '''
    """A scratch ``qwen3_moe`` family: the Qwen3 decoder with sparse experts
    in every layer."""

    COMPUTES = {"hidden_act": "silu", "rope_scaling": None,
                "decoder_sparse_step": 1, "mlp_only_layers": [],
                "shared_expert_intermediate_size": 0}


    def program_fields(c):
        return {
            "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
            "d_ff": c["moe_intermediate_size"],
            "moe_d_ff": c["moe_intermediate_size"],
            "n_experts": c["num_experts"],
            "n_experts_per_token": c["num_experts_per_tok"],
            "vocab_size": c["vocab_size"],
            "tie_embeddings": c["tie_word_embeddings"],
            "qkv_bias": False, "qk_norm": True,
            "rope_theta": float(c["rope_theta"]),
            "norm_eps": float(c["rms_norm_eps"]),
        }


    def refuse(c):
        return sorted(k for k, v in COMPUTES.items() if k in c and c[k] != v)
'''

#: Qwen3-30B-A3B's published keys, cut to 6 layers
QWEN3_MOE = {
    "name": "qwen3-moe-30b-a3b-L6", "model_type": "qwen3_moe",
    "hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 768,
    "num_hidden_layers": 6, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "num_experts": 128, "num_experts_per_tok": 8,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
    "vocab_size": 151936, "tie_word_embeddings": False, "hidden_act": "silu",
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "torch_dtype": "bfloat16",
    "program": {"arch": "qwen3-moe-30b-a3b", "replace": {"n_layers": 6}},
    "weights": {"padded_vocab": 152064},
}


@pytest.fixture
def scratch_family(tmp_path):
    (tmp_path / "qwen3_moe.py").write_text(textwrap.dedent(SCRATCH_FAMILY))
    return tmp_path


def test_a_new_family_is_a_new_module_alone(scratch_family):
    config = copy.deepcopy(QWEN3_MOE)
    # the benchmark's own families have no qwen3_moe module
    with pytest.raises(ValueError, match=r"families/qwen3_moe\.py"):
        harness.register_config(config)
    assert harness.register_config(config, scratch_family) == config["name"]


def test_a_new_family_refuses_what_it_cannot_serve(scratch_family):
    config = dict(copy.deepcopy(QWEN3_MOE), shared_expert_intermediate_size=5632)
    with pytest.raises(ValueError, match="shared_expert_intermediate_size"):
        harness.register_config(config, scratch_family)
    # the dense width in place of the experts' is caught by the comparison
    config = dict(copy.deepcopy(QWEN3_MOE), moe_intermediate_size=6144)
    with pytest.raises(ValueError, match="moe_d_ff"):
        harness.register_config(config, scratch_family)
