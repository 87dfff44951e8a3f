"""How the local serving engine builds its weights: serving precision,
jitted per-leaf init, and per-replica placement."""

import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.config import EngineModelConfig
from repro.core.engines import LocalJaxEngine
from repro.models import params as pm
from repro.models.model import build_model, serving_param_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _eager_init(key, specs):
    """The op-by-op init the jitted per-leaf init must reproduce."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=pm.is_spec)
    out = []
    for k, s in zip(jax.random.split(key, len(leaves)), leaves):
        if s.init == "zeros":
            out.append(jnp.zeros(s.shape, s.dtype))
        elif s.init == "ones":
            out.append(jnp.ones(s.shape, s.dtype))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / math.sqrt(max(fan_in, 1))
            out.append((std * jax.random.normal(k, s.shape)).astype(s.dtype))
    return jax.tree.unflatten(treedef, out)


def test_engine_stores_qwen3_weights_at_bf16():
    eng = LocalJaxEngine(
        EngineModelConfig(provider="local", model_name="qwen3-4b"),
        n_slots=2, max_len=32,
    )
    eng.initialize()
    params = eng._scheduler.params
    layers = params["dense_layers"]
    assert params["embed"]["table"].dtype == jnp.bfloat16
    assert layers["mlp"]["wi"]["kernel"].dtype == jnp.bfloat16
    assert layers["attn"]["wq"]["kernel"].dtype == jnp.bfloat16
    # read at f32 by rms_norm: stays at its declared precision
    assert layers["ln1"]["scale"].dtype == jnp.float32
    assert params["final_norm"]["scale"].dtype == jnp.float32


def test_serving_specs_keep_f32_read_leaves():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    specs = serving_param_specs(build_model(cfg).param_specs())
    moe = specs["moe_layers"]["moe"]
    assert moe["wi"].dtype == jnp.bfloat16
    assert moe["router"].dtype == jnp.float32  # routed in f32


def test_jitted_leaf_init_matches_eager_init_bitwise():
    cfg = get_config("qwen3-4b").reduced()
    specs = serving_param_specs(build_model(cfg).param_specs())
    key = jax.random.key(3)
    got = pm.init_params(key, specs)
    want = _eager_init(key, specs)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


_PLACEMENT_SCRIPT = textwrap.dedent("""
    import jax
    from repro.core.config import EngineModelConfig, InferenceConfig
    from repro.core.session import EvalSession
    from repro.models import params as pm
    import repro.serve.scheduler  # binds its own init_params (KV caches)

    built_on = []
    init = pm.init_params

    def recording_init(key, specs):
        out = init(key, specs)
        built_on.append({d.id for x in jax.tree.leaves(out) for d in x.devices()})
        return out

    pm.init_params = recording_init
    model = EngineModelConfig(provider="local", model_name="qwen3-4b")
    with EvalSession(engine_kwargs={"n_slots": 2, "max_len": 32}) as s:
        s.service_for(model, InferenceConfig(n_replicas=2))
        placed = [
            {d.id for x in jax.tree.leaves(e._scheduler.params)
             for d in x.devices()}
            for e in s.engines.engines()
        ]
    assert built_on == [{0}, {1}], built_on
    assert placed == [{0}, {1}], placed
""")


def test_replica_weights_are_built_on_their_own_device():
    """With two devices, replica i's weights are created on device i —
    never on device 0 and moved afterwards."""
    proc = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT],
        cwd=REPO,
        env={
            **os.environ,
            "PYTHONPATH": os.path.join(REPO, "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_response_text_does_not_depend_on_other_prompts():
    """A hash tokenizer decodes only words it has encoded, so a replica
    that happened to see other prompts must not decode differently: the
    response text is a function of the request alone (the replica-count
    and routing invariance contract)."""
    from repro.core.engines import InferenceRequest
    from repro.data import qa_examples

    header = "Q: What is known about gravity?\nA: it has {} phases\n"
    prompts = [
        header.format(i) + f"Q: {r['question']}\nA:"
        for i, r in enumerate(qa_examples(12, seed=0))
    ]
    model = EngineModelConfig(provider="local", model_name="qwen3-4b")

    def serve(batch):
        eng = LocalJaxEngine(model, n_slots=4, max_len=64)
        reqs = [InferenceRequest(p, max_tokens=12) for p in batch]
        return [r.text for r in eng.infer_batch(reqs)]

    together = serve(prompts)
    assert serve(prompts[1::2]) == together[1::2]
