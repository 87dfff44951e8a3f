"""The reference rebuilds the served model's weights from the seed, and its
forward pass agrees with the program's at a small size on the CPU."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from reference import dense_gqa

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(params=["tiny-qwen3.json", "tiny-qwen2.json"])
def config(request):
    return json.loads((DATA / request.param).read_text())


def _program(config, seed):
    from repro.configs import get_config
    from repro.models import params as pm
    from repro.models.model import build_model, serving_param_specs

    cfg = get_config(harness.register_config(config))
    model = build_model(cfg, remat="none")
    params = pm.init_params(
        jax.random.key(seed), serving_param_specs(model.param_specs())
    )
    return cfg, model, params


def test_weights_match_the_served_model(config):
    seed = 2**31 - 7
    _, _, params = _program(config, seed)
    ref = dense_gqa.make_weights(config, seed)
    a, ta = jax.tree.flatten(params)
    b, tb = jax.tree.flatten(ref)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert bool(jnp.all(x == y))


def test_logits_agree_with_the_served_forward(config):
    seed = 11
    cfg, model, params = _program(config, seed)
    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(4, config["vocab_size"], n)) for n in (9, 23)]
    want = []
    for s in seqs:
        logits, _ = model.forward(
            params, {"tokens": jnp.asarray([s], jnp.int32)}, dtype=jnp.float32
        )
        want.append(np.asarray(logits[0, :, : config["vocab_size"]]))
    ref = dense_gqa.Reference(config, seed)
    positions = [list(range(len(s))) for s in seqs]
    hid = ref.hidden(seqs, positions)
    targets = np.concatenate([np.argmax(w, axis=1) for w in want]).astype(np.int32)
    best, at, arg = ref.logit_stats(hid, targets)
    top = np.concatenate([np.max(w, axis=1) for w in want])
    np.testing.assert_allclose(best, top, atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(arg, targets)
