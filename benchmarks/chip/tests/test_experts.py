"""The expert configuration: ``families/qwen3_moe.py`` registers the cut
file and refuses what the program cannot serve, its counts are pinned, the
reference rebuilds the served weights and agrees with the engine's logits
through the paged cache, the expert readers read the program's counters
and kernel, summed over replicas, and the fp8 control fails the check."""

import copy
import json
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import check
import families
import harness
from reference import qwen3_moe

from repro.core.service import aggregate_batcher_stats

DATA = Path(__file__).resolve().parent / "data"
CUT = json.loads((Path(harness.HERE) / "configs" / "qwen3-moe-30b-a3b-ep8.json").read_text())
TINY = json.loads((DATA / "tiny-qwen3-moe.json").read_text())
FAMILY = families.load("qwen3_moe")
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def test_the_cut_file_registers_with_the_held_experts():
    fields = FAMILY.program_fields(CUT)
    assert fields["n_experts"] == 128 and fields["experts_held"] == 16
    assert fields["expert_offset"] == 0 and fields["moe_d_ff"] == 768
    assert fields["n_experts_per_token"] == 8 and "d_ff" not in fields
    assert harness.register_config(copy.deepcopy(CUT)) == CUT["name"]
    from repro.configs import get_config

    cfg = get_config(CUT["name"])
    assert (cfg.n_experts_held, cfg.expert_offset, cfg.n_layers) == (16, 0, 48)


@pytest.mark.parametrize(
    "key, value",
    [
        ("decoder_sparse_step", 2), ("mlp_only_layers", [0, 1]),
        ("norm_topk_prob", False), ("shared_expert_intermediate_size", 5632),
        ("num_shared_experts", 1), ("rope_scaling", {"type": "yarn", "factor": 4.0}),
        ("use_sliding_window", True), ("hidden_act", "gelu"),
        ("attention_bias", True),
    ],
)
def test_each_mechanism_the_program_lacks_is_refused(key, value):
    config = dict(copy.deepcopy(CUT), **{key: value})
    assert FAMILY.refuse(config) == [key]
    with pytest.raises(ValueError, match=key):
        harness.register_config(config)


def test_a_served_share_that_differs_is_refused():
    config = copy.deepcopy(CUT)
    config["program"]["replace"]["n_experts_held"] = 32
    with pytest.raises(ValueError, match="experts_held"):
        harness.register_config(config)


def test_counts_of_the_cut_file_are_pinned():
    # 48 x (18.87 M attention + 0.26 M router + 75.50 M experts) + the head
    assert FAMILY.attn_params(CUT) == 18_874_368
    assert FAMILY.expert_params(CUT) * 16 == 75_497_472
    stored = FAMILY.weight_bytes(CUT) + 2 * CUT["weights"]["padded_vocab"] * 2048
    stored -= 2 * 2048 * (CUT["weights"]["padded_vocab"] - CUT["vocab_size"])
    # with the embedding: 10.33 GB of bf16 weights
    assert stored / 1e9 == pytest.approx(10.33, abs=0.005)
    assert FAMILY.kv_bytes_per_position(CUT) == 2 * 48 * 4 * 128 * 2
    flops, nbytes = FAMILY.expert_gmm(CUT, 100, 10)
    assert flops == 2 * 100 * 3 * 2048 * 768
    assert nbytes == (10 * 3 * 2048 * 768 + 100 * 3 * (2048 + 768)) * 2


def _window(before, after):
    return types.SimpleNamespace(
        t0=0.0, t1=10.0, seconds=10.0,
        stats0=aggregate_batcher_stats(before), stats1=aggregate_batcher_stats(after),
        replicas0=[], replicas1=[],
    )


def _stats(steps, admissions, pairs, touched, scale=1):
    return {
        "n_slots": 16, "steps": steps, "admissions": admissions,
        "expert_pairs_decode": pairs * scale, "experts_touched_decode": touched * scale,
        "expert_picks_decode": 8 * pairs * scale,
        "expert_pairs_prefill": 4 * pairs * scale,
        "experts_touched_prefill": 2 * touched * scale,
        "expert_picks_prefill": 32 * pairs * scale,
    }


def test_expert_work_follows_the_window_counters():
    """A decode step's expert work is the window's pairs and experts touched
    per step; the rest is the shapes' count."""
    def ctx(pairs, touched):
        return types.SimpleNamespace(window=_window(
            [_stats(100, 10, 0, 0)], [_stats(200, 20, pairs, touched)]
        ))

    flops, nbytes = FAMILY.decode_step(CUT, [100, 200], ctx(1600, 500))
    base_flops, base_bytes = FAMILY.decode_step(CUT, [100, 200], ctx(0, 0))
    e_flops, e_bytes = FAMILY.expert_gmm(CUT, 16.0, 5.0)
    head = 2 * 2048 * 151936
    assert base_flops == sum(FAMILY.token_flops(CUT, n) + head for n in (100, 200))
    assert (flops, nbytes) == pytest.approx((base_flops + e_flops, base_bytes + e_bytes))
    # a prefill's: the window's per admission (4 x 1600 pairs over 10)
    p_flops, _ = FAMILY.prefill(CUT, 64, 0, ctx(1600, 500))
    p_base, _ = FAMILY.prefill(CUT, 64, 0, ctx(0, 0))
    assert p_flops - p_base == pytest.approx(FAMILY.expert_gmm(CUT, 640.0, 100.0)[0])
    with pytest.raises(ValueError, match="counters"):
        FAMILY.decode_step(CUT, [100], None)


def _trace(kernel_ns, other_ns):
    plane = "/device:TPU:0"
    return [
        [plane, "XLA Modules", "jit__paged_decode_impl(1)", 0.0, kernel_ns + other_ns, None],
        [plane, "XLA Ops", "%expert_gmm.3 = f32[368,768] custom-call()", 0.0, kernel_ns, None],
        [plane, "XLA Ops", "%fusion.1 = f32[16,2048] fusion()", kernel_ns, other_ns, None],
    ]


def _ctx(window, events):
    return harness.ReadContext(copy.deepcopy(CUT), {}, PEAKS, window, None, events, 1)


def test_expert_readers_read_the_kernel_and_the_counters():
    before, after = _stats(0, 0, 0, 0), _stats(100, 10, 1000, 300)
    ctx = _ctx(_window([before], [after]), _trace(6e9, 4e9))
    assert harness.load_reader("expert_time_share").read(ctx) == pytest.approx(60.0)
    pairs, touched = 1000 + 4000, 300 + 600
    bound = max(a / b for a, b in zip(
        FAMILY.expert_gmm(CUT, pairs, touched),
        (PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"]),
    ))
    got = harness.load_reader("expert_gmm_roofline").read(ctx)
    assert got == pytest.approx(100.0 * bound / 6.0)


def test_expert_readers_read_the_fleet_as_the_sum_of_its_replicas():
    one = _ctx(_window([_stats(0, 0, 0, 0)], [_stats(100, 10, 1000, 300)]),
               _trace(6e9, 4e9))
    four = _ctx(_window([_stats(0, 0, 0, 0)] * 4,
                        [_stats(100, 10, 250, 75)] * 4), _trace(6e9, 4e9))
    for name in ("expert_gmm_roofline", "expert_time_share"):
        assert harness.load_reader(name).read(four) == pytest.approx(
            harness.load_reader(name).read(one)
        )


def test_expert_readers_read_nothing_where_the_program_counts_nothing():
    dense = {"n_slots": 16, "steps": 10, "admissions": 2}
    ctx = _ctx(_window([dense], [dict(dense, steps=20)]), _trace(0, 4e9))
    assert harness.load_reader("expert_gmm_roofline").read(ctx) is None
    assert harness.load_reader("expert_time_share").read(ctx) is None


def _program(config, seed):
    from repro.configs import get_config
    from repro.models import params as pm
    from repro.models.model import build_model, serving_param_specs

    cfg = get_config(harness.register_config(copy.deepcopy(config)))
    model = build_model(cfg, remat="none")
    params = pm.init_params(
        jax.random.key(seed), serving_param_specs(model.param_specs())
    )
    return cfg, model, params


def test_weights_match_the_served_model():
    seed = 2**31 - 7
    _, _, params = _program(TINY, seed)
    ref = qwen3_moe.make_weights(TINY, seed)
    a, ta = jax.tree.flatten(params)
    b, tb = jax.tree.flatten(ref)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert bool((x == y).all())


@pytest.mark.parametrize("seed", [11, 2**31 + 6])
def test_engine_logits_through_the_paged_cache_agree_with_the_reference(
    seed, monkeypatch
):
    """Prefill in chunks of 8 (boundaries crossed), pages of 4, then decode
    through the paged cache: every served token's reference logit lies
    within the cell's limit of the reference's best."""
    from repro.serve import scheduler as sch

    monkeypatch.setattr(sch, "PREFILL_WIDTH", 8)
    cfg, model, params = _program(TINY, seed % (2**31 - 1))
    b = sch.ContinuousBatcher(
        model, cfg, params, n_slots=3, max_len=64, page_size=4,
    )
    rng = np.random.default_rng(seed % 1000)
    prompts = [list(rng.integers(4, TINY["vocab_size"], n)) for n in (5, 19, 23, 12)]
    for i, p in enumerate(prompts):
        b.submit(sch.Request(i, [int(t) for t in p], max_new_tokens=10))
    done = {c.request_id: c.tokens for c in b.run_to_completion()}
    assert b.stats.expert_pairs_prefill > 0 and b.stats.expert_pairs_decode > 0
    reference = qwen3_moe.Reference(TINY, seed % (2**31 - 1))
    requests = [(prompts[i], done[i]) for i in range(len(prompts))]
    numbers, _ = check.logits(reference, requests, False)
    served = sum(len(t) for t in done.values())
    assert numbers["served_tokens"] == served == len(reference.margins)
    # at 64 wide a router flip moves the logits by more than bf16 rounding
    # does: positions with a near-tie of a held expert are compared apart
    seqs = [list(p) + list(t[:-1]) for p, t in requests]
    positions = [[len(p) - 1 + j for j in range(len(t))] for p, t in requests]
    targets = np.concatenate([t for _, t in requests]).astype(np.int32)
    best, at, _ = reference.logit_stats(reference.hidden(seqs, positions), targets)
    clear = reference.margins >= qwen3_moe.TIE_Z
    assert clear.mean() >= 0.8
    # bf16 serving at this width reads up to ~0.025 on clear positions
    assert np.max((best - at)[clear]) <= 0.05


def test_router_margins_mark_near_ties_of_held_experts():
    """Each compared position's margin is its smallest over layers; a tie
    between two experts this chip does not hold is no tie here."""
    reference = qwen3_moe.Reference(TINY, 3)
    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(4, TINY["vocab_size"], 30))]
    hid = reference.hidden(seqs, [list(range(10, 30))])
    assert hid.shape == (20, TINY["hidden_size"])
    assert reference.margins.shape == (20,) and np.all(reference.margins >= 0)
    assert reference.tie_share() == np.mean(reference.margins < qwen3_moe.TIE_Z)
    # the control's pass leaves the program's margins as they were
    kept = reference.margins.copy()
    reference.hidden(seqs, [list(range(10, 30))], quantized=True)
    assert np.array_equal(reference.margins, kept)
    # finite only where the k-th or (k+1)-th expert of some layer is held
    assert np.isinf(reference.margins).any() and np.isfinite(reference.margins).any()
