"""Sharding rule resolution + serving scheduler behaviour."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.configs import ARCHS
from repro.core.ratelimit import TokenBucket
from repro.models import params as pm
from repro.models.model import build_model
from repro.serve import ContinuousBatcher, Request
from repro.serve.scheduler import batch_axis_tree


# Rule tests use an abstract mesh so they run on the single CPU device.
def _mesh(shape, axes):
    devs = np.array(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return jax.sharding.Mesh(devs.reshape(shape), axes)


@pytest.fixture
def rules16():
    mesh = _mesh((4, 4), ("data", "model"))
    return sh.ShardingRules(sh.TRAIN_RULES, mesh)


def test_even_division_shards(rules16):
    spec = rules16.spec_for_axes(("embed", "mlp"), (64, 128))
    assert spec == P("data", "model")


def test_uneven_falls_back_to_replication(rules16):
    # 10 heads on a 4-way model axis: replicate rather than fail
    spec = rules16.spec_for_axes(("batch", None, "heads", None), (8, 9, 10, 64))
    assert spec == P("data")  # trailing Nones trimmed


def test_axis_used_once(rules16):
    # both vocab and mlp want "model": second falls back
    spec = rules16.spec_for_axes(("vocab", "mlp"), (256, 256))
    assert spec == P("model")


def test_pod_fallback_rules():
    m3 = _mesh((2, 2, 2), ("pod", "data", "model"))
    r3 = sh.ShardingRules(sh.TRAIN_RULES, m3)
    assert r3.spec_for_axes(("batch", None), (8, 4)) == P(("pod", "data"))
    # single-pod mesh: same logical name resolves to the fallback rule
    m2 = _mesh((2, 2), ("data", "model"))
    r2 = sh.ShardingRules(sh.TRAIN_RULES, m2)
    assert r2.spec_for_axes(("batch", None), (8, 4)) == P("data")


def test_serve_rules_cache_seq():
    m2 = _mesh((2, 2), ("data", "model"))
    r = sh.ShardingRules(sh.SERVE_RULES, m2)
    axes = ("layers", "batch", "cache_seq", "kv_heads", None)
    spec = r.spec_for_axes(axes, (8, 16, 1024, 8, 64))
    assert spec == P(None, "data", "model")
    # batch=1 long-context: batch drops, seq keeps model
    spec1 = r.spec_for_axes(axes, (8, 1, 1024, 8, 64))
    assert spec1 == P(None, None, "model")


def test_batch_axis_tree():
    cfg = ARCHS["zamba2-7b"].reduced()
    model = build_model(cfg)
    axes = batch_axis_tree(model.cache_specs(4, 32))
    leaves = jax.tree.leaves(axes)
    assert all(isinstance(a, int) for a in leaves)
    # grouped mamba caches have the batch axis at position 2 (G, K, B, ...)
    assert max(leaves) >= 2


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def _scheduler(n_slots=3, arch="qwen3-4b", **kw):
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg, remat="none")
    params = pm.init_params(jax.random.key(0), model.param_specs())
    return ContinuousBatcher(
        model, cfg, params, n_slots=n_slots, max_len=64, eos_id=1, **kw
    ), cfg


def test_scheduler_completes_all(rng):
    sched, cfg = _scheduler()
    for i in range(7):
        sched.submit(
            Request(i, prompt_tokens=list(rng.randint(3, 100, 4 + i % 3)),
                    max_new_tokens=5)
        )
    done = sched.run_to_completion()
    assert sorted(c.request_id for c in done) == list(range(7))
    assert all(len(c.tokens) <= 5 for c in done)
    assert all(c.finished_reason in ("eos", "length") for c in done)


def test_scheduler_multiplexes_slots(rng):
    sched, _ = _scheduler(n_slots=2)
    for i in range(5):
        sched.submit(Request(i, prompt_tokens=[5, 6, 7], max_new_tokens=4))
    done = sched.run_to_completion()
    assert len(done) == 5
    # 5 requests x 4 tokens on 2 slots: needs >= 10 decode iterations
    assert sched.steps_run >= 8


def test_scheduler_occupancy_counters(rng):
    """Persistent-mode counters: admissions, occupancy, tokens/step,
    prompt-length recompile tracking, incremental completion draining."""
    sched, _ = _scheduler(n_slots=2)
    for i in range(5):
        # two distinct prompt lengths share the one chunk prefill program
        sched.submit(
            Request(i, prompt_tokens=[5, 6, 7][: 2 + i % 2], max_new_tokens=4)
        )
    drained: list = []
    while sched.queue or sched.slots_busy:
        assert 0 <= sched.slots_busy <= 2
        sched.step()
        drained.extend(sched.drain_completions())
    drained.extend(sched.run_to_completion())  # flush terminal slots
    drained.extend(sched.drain_completions())
    seen = {c.request_id for c in drained}
    assert seen == set(range(5))
    st = sched.stats
    assert st.admissions == 5
    assert st.completions >= 5
    assert st.prefill_recompiles == 1
    assert st.steps == sched.steps_run
    assert 0.0 < st.occupancy <= 1.0
    assert 0.0 < st.tokens_per_step <= 2.0
    assert st.tokens_generated == st.active_slot_steps
    d = st.as_dict()
    assert d["n_slots"] == 2 and d["admissions"] == 5
    assert sched.completions == []  # drained incrementally


def test_exact_length_prefill_counts_one_program_per_length():
    """An SSM cannot take padded prompts, so it compiles one prefill
    program per distinct prompt length."""
    sched, _ = _scheduler(n_slots=2, arch="mamba2-2.7b")
    assert not sched._chunked
    for i in range(4):
        sched.submit(
            Request(i, prompt_tokens=[5, 6, 7][: 2 + i % 2], max_new_tokens=2)
        )
    assert len(sched.run_to_completion()) == 4
    assert sched.stats.prefill_recompiles == 2


def test_scheduler_greedy_deterministic(rng):
    s1, _ = _scheduler()
    s2, _ = _scheduler()
    toks = list(rng.randint(3, 90, 6))
    s1.submit(Request(0, prompt_tokens=toks, max_new_tokens=6))
    s2.submit(Request(0, prompt_tokens=toks, max_new_tokens=6))
    d1 = s1.run_to_completion()
    d2 = s2.run_to_completion()
    assert d1[0].tokens == d2[0].tokens


def test_scheduler_paged_parity_and_prefix_sharing(rng):
    """Paged KV cache (16- and 64-token pages, with and without prefix
    sharing) produces byte-identical token streams to the contiguous
    cache; sharing is observable in the stats and leaks nothing."""
    prefix = list(range(10, 28))  # 18 tokens -> one full 16-token page
    reqs = []
    for i in range(6):
        toks = prefix + [100 + i] if i % 2 == 0 else [50 + i, 51 + i, 52 + i]
        reqs.append(Request(i, prompt_tokens=toks, max_new_tokens=6))

    def run(**kw):
        sched, _ = _scheduler(**kw)
        for r in reqs:
            sched.submit(r)
        done = {c.request_id: c for c in sched.run_to_completion()}
        return sched, [done[i].tokens for i in range(6)]

    s0, r0 = run()
    s16, r16 = run(page_size=16)
    s64, r64 = run(page_size=64)
    s16n, r16n = run(page_size=16, prefix_cache=False)
    assert r16 == r0
    assert r64 == r0
    assert r16n == r0
    # requests 2 and 4 each reuse request 0's resident prefix page
    assert s16.stats.prefix_pages_hit == 2
    assert s16.stats.prefix_tokens_saved == 32
    assert s16.stats.cow_copies == 0
    # 64-token pages can't share an 18-token prefix; sharing disabled -> 0
    assert s64.stats.prefix_pages_hit == 0
    assert s16n.stats.prefix_pages_hit == 0
    for s in (s16, s64, s16n):
        s.manager.check_no_leaks()
        assert s.manager.pages_active == 0


def test_scheduler_prefills_deferred_counts_once(rng):
    """Regression (ISSUE 8 S1): a capped refill defers each waiting
    request at most once per step — not once per still-free slot scan.
    4 one-token requests, 2 slots, cap 1: the queue waits behind one
    free slot for 3 rounds -> exactly 3 deferrals (the old accounting
    added len(queue) per round: 3 + 2 + 1 = 6)."""
    sched, _ = _scheduler(n_slots=2, max_prefills_per_step=1)
    for i in range(4):
        sched.submit(Request(i, prompt_tokens=[5, 6, 7], max_new_tokens=1))
    done = sched.run_to_completion()
    assert sorted(c.request_id for c in done) == list(range(4))
    assert sched.stats.admissions == 4
    assert sched.stats.prefills_deferred == 3


def test_scheduler_slot_release_same_step(rng):
    """Regression (ISSUE 8 S2): a slot whose sample just finished is
    reaped *before* refill, so a back-to-back queue keeps one slot at
    100% occupancy with no idle step between requests."""
    sched, _ = _scheduler(n_slots=1)
    for i in range(4):
        sched.submit(Request(i, prompt_tokens=[5, 6, 7], max_new_tokens=3))
    done = sched.run_to_completion()
    assert len(done) == 4
    assert sched.stats.occupancy == 1.0


def test_scheduler_truncated_completion_at_max_steps(rng):
    """Regression (ISSUE 8 S3): exhausting max_steps emits a 'truncated'
    completion for the in-flight request instead of dropping it."""
    sched, _ = _scheduler(n_slots=1)
    sched.submit(Request(0, prompt_tokens=[5, 6, 7], max_new_tokens=50))
    done = sched.run_to_completion(max_steps=3)
    assert len(done) == 1
    assert done[0].finished_reason == "truncated"
    assert 1 <= len(done[0].tokens) <= 4
    assert sched.stats.completions == 1
    assert sched.slots_busy == 0


def test_scheduler_admission_control(rng):
    calls = []
    clockv = [0.0]

    def clock():
        return clockv[0]

    def sleep(s):
        clockv[0] += s

    bucket = TokenBucket(1e9, 1e9, 1, clock=clock, sleep=sleep)

    def admission(est):
        calls.append(est)
        return bucket.acquire(est)

    sched, _ = _scheduler(admission=admission)
    sched.submit(Request(0, prompt_tokens=[4, 5], max_new_tokens=3))
    sched.run_to_completion()
    assert calls == [5]  # prompt 2 + max_new 3
