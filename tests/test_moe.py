"""The dropless expert layer and one chip's share of it: the shares of an
expert-parallel deployment sum to the uncut layer, chunked prefill of a
share equals whole prefill, the grouped-matmul kernel matches its jnp
oracle, and the layer's counters count what a scripted routing gives —
through the scheduler too, where fleets sum them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.service import aggregate_batcher_stats
from repro.kernels.grouped_matmul import expert_gmm, expert_gmm_ref
from repro.models import moe
from repro.models import params as pm
from repro.models.model import active_param_count, build_model
from repro.serve.scheduler import ContinuousBatcher, Request

#: reduced qwen3-moe: 8 experts, top-2, width 64, expert width 64
CFG = ARCHS["qwen3-moe-30b-a3b"].reduced()


def _share(cfg, held, offset):
    return cfg.replace(n_experts_held=held, expert_offset=offset)


def _slice(params, offset, held):
    """The stacks of experts ``offset .. offset + held`` of an uncut layer."""
    out = dict(params)
    for name in ("wg", "wi", "wo"):
        out[name] = params[name][offset : offset + held]
    return out


def _plain_layer(params, cfg, x):
    """Token by token: softmax over all experts, top-k renormalized, the
    held experts' SiLU-gated MLPs weighted and summed."""
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    r = np.asarray(params["router"], np.float64)
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        logits = row @ r
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[: cfg.n_experts_per_token]
        w = p[top] / p[top].sum()
        for e, we in zip(top, w):
            h = e - cfg.expert_offset
            if not 0 <= h < cfg.experts_held:
                continue
            g = row @ np.asarray(params["wg"][h], np.float64)
            u = row @ np.asarray(params["wi"][h], np.float64)
            silu = g / (1.0 + np.exp(-g))
            out[t] += we * ((silu * u) @ np.asarray(params["wo"][h], np.float64))
    return out


@pytest.fixture
def layer():
    specs = moe.moe_specs(CFG)
    params = pm.init_params(jax.random.key(3), specs)
    x = jax.random.normal(jax.random.key(4), (2, 9, CFG.d_model), jnp.float32)
    return params, x


def test_held_layer_matches_the_plain_equations(layer):
    params, x = layer
    out, _, _ = moe.held_experts(params, CFG, x)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, CFG.d_model), _plain_layer(params, CFG, x),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("n_shares", [2, 4, 8])
def test_shares_sum_to_the_uncut_layer(layer, n_shares):
    """Each chip of an expert-parallel deployment computes its experts'
    part; the parts, on one input, add up to the whole layer."""
    params, x = layer
    whole, _, _ = moe.held_experts(params, CFG, x)
    held = CFG.n_experts // n_shares
    parts = [
        moe.held_experts(
            _slice(params, i * held, held), _share(CFG, held, i * held), x
        )[0]
        for i in range(n_shares)
    ]
    np.testing.assert_allclose(
        np.asarray(sum(parts)), np.asarray(whole), atol=2e-5, rtol=2e-5
    )
    pairs = sum(
        int(moe.held_experts(_slice(params, i * held, held),
                             _share(CFG, held, i * held), x)[2]["expert_pairs"])
        for i in range(n_shares)
    )
    assert pairs == x.shape[0] * x.shape[1] * CFG.n_experts_per_token


def test_a_share_matches_the_plain_equations(layer):
    params, x = layer
    cfg = _share(CFG, 2, 4)
    out, _, _ = moe.held_experts(_slice(params, 4, 2), cfg, x)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, CFG.d_model), _plain_layer(
            _slice(params, 4, 2), cfg, x),
        atol=2e-5, rtol=2e-5,
    )


def test_a_share_prefills_in_chunks_and_decodes_as_its_forward():
    """A chip's share through the serving programs: chunks of 8 rows (the
    last one padded, boundaries crossed) fill the cache as one exact-length
    prefill does, and decoding from it gives the full forward's logits."""
    cfg = _share(CFG, 2, 4)
    model = build_model(cfg, remat="none")
    params = pm.init_params(jax.random.key(0), model.param_specs())
    toks = list(np.random.default_rng(2).integers(3, cfg.vocab_size, 24))
    full, _ = model.forward(
        params, {"tokens": jnp.asarray([toks], jnp.int32)}, dtype=jnp.float32
    )
    specs = model.cache_specs(1, 32, jnp.float32)
    _, exact = model.prefill(
        params, {"tokens": jnp.asarray([toks[:20]], jnp.int32)},
        pm.init_params(jax.random.key(1), specs), dtype=jnp.float32,
    )
    row = pm.init_params(jax.random.key(1), specs)
    for c in range(0, 20, 8):
        part = toks[c : min(c + 8, 20)]
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, : len(part)] = part
        lg, row, counts = model.prefill_chunk(
            params, jnp.asarray(chunk), row, jnp.int32(c), jnp.int32(20),
            dtype=jnp.float32,
        )
        assert int(counts["expert_picks"]) == cfg.n_layers * 2 * len(part)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, 19]),
                               atol=5e-5)
    for g, w in zip(jax.tree.leaves(row), jax.tree.leaves(exact)):
        np.testing.assert_allclose(np.asarray(g)[:, :, :20],
                                   np.asarray(w)[:, :, :20], atol=5e-5)
    for t in range(20, 24):
        lg, row, _ = model.decode_step(
            params, jnp.asarray([[toks[t]]], jnp.int32), row,
            jnp.asarray([t], jnp.int32), dtype=jnp.float32,
        )
        np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, t]),
                                   atol=5e-5)


def _scripted(valid=None):
    """Eight tokens whose routing is set by the router: token t picks
    experts (t, t + 1) mod 8 with the higher weight on t."""
    cfg = CFG.replace(d_model=8)
    router = np.zeros((8, 8), np.float32)
    for t in range(8):
        router[t, t] = 4.0
        router[t, (t + 1) % 8] = 3.0
    params = pm.init_params(jax.random.key(0), moe.moe_specs(cfg))
    params["router"] = jnp.asarray(router)
    x = jnp.eye(8, dtype=jnp.float32)[None]
    return cfg, params, x


@pytest.mark.parametrize(
    "held, offset, valid, want",
    [
        # all 8 held: every pick is a pair; all experts touched
        (8, 0, None, (16, 8, 16)),
        # experts 2, 3: picked by tokens 1, 2 (expert 2) and 2, 3 (expert 3)
        (2, 2, None, (4, 2, 16)),
        # tokens 4..7 are padding: experts 6, 7 get only token 5's and 6's
        # picks of 6 (token 5 picks 5, 6; token 6 picks 6, 7) -> none valid
        (2, 6, [1, 1, 1, 1, 0, 0, 0, 0], (0, 0, 8)),
        # experts 0..3 with tokens 0..3 valid: picks 0,1 1,2 2,3 3,4 -> 7 held
        (4, 0, [1, 1, 1, 1, 0, 0, 0, 0], (7, 4, 8)),
    ],
)
def test_counters_follow_a_scripted_routing(held, offset, valid, want):
    cfg, params, x = _scripted()
    cfg = _share(cfg, held, offset)
    params = _slice(params, offset, held)
    rows = None if valid is None else jnp.asarray(valid, bool)[None]
    out, _, counts = moe.held_experts(params, cfg, x, rows)
    got = tuple(int(counts[k]) for k in ("expert_pairs", "experts_touched",
                                         "expert_picks"))
    assert got == want
    if valid is not None:
        # padding rows route to nothing and get nothing
        assert not np.asarray(out)[0, np.asarray(valid) == 0].any()


@pytest.mark.parametrize(
    "sizes",
    [[16, 0, 48, 0], [0, 0, 0, 0], [32, 16, 16, 64], [0, 0, 0, 128]],
)
def test_expert_gmm_kernel_matches_the_oracle(sizes):
    """Empty groups, uneven groups and rows past every group; one layer of
    stacked experts read in place is the same as its slice."""
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.normal(size=(160, 256)), jnp.bfloat16)
    stacks = jnp.asarray(rng.normal(size=(3, 4, 256, 384)), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    got = expert_gmm(lhs, stacks[1], gs, tile_rows=16, interpret=True)
    want = expert_gmm_ref(lhs, stacks[1], gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-3)
    assert not np.asarray(got)[sum(sizes):].any()
    layer = jnp.int32(1)
    in_place = expert_gmm(lhs, stacks, gs, layer, tile_rows=16, interpret=True)
    assert np.array_equal(np.asarray(in_place), np.asarray(got))
    assert np.array_equal(
        np.asarray(expert_gmm_ref(lhs, stacks, gs, layer)), np.asarray(want)
    )


def test_specs_size_the_expert_stacks_by_the_held_count():
    cfg = _share(ARCHS["qwen3-moe-30b-a3b"], 16, 0)
    specs = moe.moe_specs(cfg)
    assert specs["wg"].shape == (16, 2048, 768)
    assert specs["router"].shape == (2048, 128)
    model = build_model(cfg)
    full = build_model(ARCHS["qwen3-moe-30b-a3b"])
    held = active_param_count(cfg, model.param_specs())
    whole = active_param_count(ARCHS["qwen3-moe-30b-a3b"], full.param_specs())
    # a token meets k/E of the held stacks: an eighth of the whole model's
    # expert work, the same attention, router and head
    experts = 48 * 3 * 2048 * 768 * 8
    assert whole - held == pytest.approx(experts * 7 / 8)


def _batcher(cfg, n_slots=3):
    model = build_model(cfg, remat="none")
    params = pm.init_params(jax.random.key(0), model.param_specs())
    return ContinuousBatcher(
        model, cfg, params, n_slots=n_slots, max_len=64, page_size=8
    )


@pytest.mark.parametrize("held, offset", [(0, 0), (2, 4)])
def test_the_scheduler_counts_what_its_steps_routed(held, offset):
    cfg = _share(CFG, held, offset)
    b = _batcher(cfg)
    lens = [5, 11, 17, 7]
    for i, n in enumerate(lens):
        # distinct first tokens: no prompt is served from the prefix cache
        b.submit(Request(i, list(range(3 + 20 * i, 3 + 20 * i + n)),
                         max_new_tokens=4))
    b.run_to_completion()
    s = b.stats
    k, layers = cfg.n_experts_per_token, cfg.n_layers
    assert s.expert_picks_prefill == k * layers * sum(lens)
    assert s.expert_picks_decode == k * layers * s.active_slot_steps
    if held == 0:
        assert s.expert_pairs_prefill == s.expert_picks_prefill
        assert s.expert_pairs_decode == s.expert_picks_decode
    else:
        assert 0 < s.expert_pairs_decode < s.expert_picks_decode
    assert s.experts_touched_decode <= cfg.experts_held * layers * s.steps
    assert s.as_dict()["expert_pairs_decode"] == s.expert_pairs_decode


def test_a_dense_step_returns_no_counters():
    cfg = ARCHS["qwen3-4b"].reduced()
    b = _batcher(cfg)
    b.submit(Request(0, [3, 4, 5], max_new_tokens=3))
    b.run_to_completion()
    assert b.stats.expert_picks_decode == b.stats.expert_pairs_prefill == 0
    model = build_model(cfg, remat="none")
    params = pm.init_params(jax.random.key(0), model.param_specs())
    cache = pm.init_params(jax.random.key(1), model.cache_specs(1, 16))
    _, _, counts = model.decode_step(
        params, jnp.zeros((1, 1), jnp.int32), cache, jnp.zeros((1,), jnp.int32)
    )
    assert counts == {}


def test_fleet_stats_sum_the_expert_counters():
    parts = [
        {"n_slots": 4, "steps": 10, "expert_pairs_decode": 30 + i,
         "experts_touched_decode": 5, "expert_picks_prefill": 100 * i}
        for i in range(4)
    ]
    agg = aggregate_batcher_stats(parts)
    assert agg["expert_pairs_decode"] == 126
    assert agg["experts_touched_decode"] == 20
    assert agg["expert_picks_prefill"] == 600
    assert agg["expert_pairs_prefill"] == 0


def test_an_expert_model_is_refused_on_a_replica_mesh():
    """Under sharding rules the layer would be the capacity dispatch, which
    drops tokens by chunk size: serving refuses it, so it has one expert
    path."""
    from jax.sharding import Mesh

    from repro.sharding import SERVE_RULES, ShardingRules

    rules = ShardingRules(SERVE_RULES, Mesh(np.array(jax.devices()[:1]), ("model",)))
    model = build_model(CFG, remat="none")
    with pytest.raises(ValueError, match="one device per replica"):
        ContinuousBatcher(model, CFG, None, n_slots=2, max_len=64, rules=rules)
