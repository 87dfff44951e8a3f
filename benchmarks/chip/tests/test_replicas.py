"""What the benchmark reads over replicas, on synthetic four-replica
windows: the router's balance, the scheduler readers over replicas whose
``BatcherStats`` the service sums, the trace readers over four device
planes, and the check's sample with every replica in it.  Each reads a
one-replica window as it did before replicas."""

import json
import types
from pathlib import Path

import pytest

import check
import harness
import tracing
import work
from probes import Probes

from repro.core.service import aggregate_batcher_stats

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _batcher(steps, active, admissions, saved, slots=4):
    return {"n_slots": slots, "steps": steps, "active_slot_steps": active,
            "admissions": admissions, "prefix_tokens_saved": saved,
            "queue_wait_s": 2.0 * admissions, "first_token_s": 0.04 * admissions}


def _replica(i, completed, batcher):
    return {"index": i, "completed": completed, "batcher": batcher}


# replica i steps 100 + 10 i times in the window, with 4 - (i == 3) of its
# 4 slots busy; it completes 120, 118, 125 and 100 examples
BEFORE = [_replica(i, 1000, _batcher(500, 2000, 300, 9000)) for i in range(4)]
AFTER = [
    _replica(i, 1000 + done, _batcher(500 + steps, 2000 + busy * steps, 330, 9900))
    for i, (done, steps, busy) in enumerate(
        [(120, 100, 4), (118, 110, 4), (125, 120, 4), (100, 130, 3)]
    )
]


def _window(before, after, **kw):
    return types.SimpleNamespace(
        t0=0.0, t1=10.0, seconds=10.0,
        stats0=aggregate_batcher_stats([r["batcher"] for r in before]),
        stats1=aggregate_batcher_stats([r["batcher"] for r in after]),
        replicas0=before if len(before) > 1 else [],
        replicas1=after if len(after) > 1 else [], **kw,
    )


def _ctx(window, chips=1, probes=None, events=None, config=None):
    return harness.ReadContext(
        config or {}, {}, PEAKS, window, probes or Probes(trace=True), events or [],
        chips,
    )


def _read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


def test_replica_balance_is_the_fewest_over_the_most_completed():
    assert _read("replica_balance", _ctx(_window(BEFORE, AFTER))) == pytest.approx(80.0)


def test_replica_balance_reads_nothing_on_one_replica_and_refuses_an_idle_window():
    assert _read("replica_balance", _ctx(_window(BEFORE[:1], AFTER[:1]))) is None
    with pytest.raises(RuntimeError, match="replica_balance"):
        _read("replica_balance", _ctx(_window(BEFORE, BEFORE)))


def test_slot_occupancy_over_replicas_is_busy_slot_steps_over_capacity():
    busy = 4 * 100 + 4 * 110 + 4 * 120 + 3 * 130
    capacity = 4 * (100 + 110 + 120 + 130)
    got = _read("slot_occupancy", _ctx(_window(BEFORE, AFTER)))
    assert got == pytest.approx(100.0 * busy / capacity)
    # one replica: as before replicas, steps times slots of the one batcher
    one = _read("slot_occupancy", _ctx(_window(BEFORE[3:], AFTER[3:])))
    assert one == 100.0 * (3 * 130) / (130 * 4)


@pytest.mark.parametrize("metric,key", [("queue_wait_ms", "queue_wait_s"),
                                        ("first_token_ms", "first_token_s")])
def test_counter_readers_average_over_every_replica_admission(metric, key):
    per = {"queue_wait_ms": 2000.0, "first_token_ms": 40.0}[metric]
    assert _read(metric, _ctx(_window(BEFORE, AFTER))) == pytest.approx(per)


def test_prefix_hit_share_sums_over_replicas():
    probes = Probes(trace=True)
    probes.prefills = [(1.0, 1.1, 100, 90)] * 8 + [(2.0, 2.1, 100, 0)] * 4
    got = _read("prefix_hit_share", _ctx(_window(BEFORE, AFTER), probes=probes))
    assert got == pytest.approx(100.0 * 4 * 900 / 1200)


def test_mfu_is_a_share_of_every_chip_the_cell_uses():
    probes = Probes(trace=True)
    probes.steps = [(1.0, 1.1, [40, 60])] * 4
    probes.prefills = [(2.0, 2.1, 300, 256)] * 4
    config = json.loads((DATA / "tiny-qwen3.json").read_text())
    one = _read("mfu", _ctx(_window(BEFORE[:1], AFTER[:1]), 1, probes, config=config))
    four = _read("mfu", _ctx(_window(BEFORE, AFTER), 4, probes, config=config))
    assert four == pytest.approx(one / 4)
    # one chip: the share of one chip's peak, as before the chips were counted
    flops = 4 * (work.decode_step(config, [40, 60])[0] + work.prefill(config, 300, 256)[0])
    assert one == 100.0 * flops / (10.0 * PEAKS["bf16_flops"])
    assert 0 < one < 100


def _planes(n):
    """``n`` chips, each busy for 6 of its 10 ns, running the decode step."""
    events = []
    for d in range(n):
        plane = f"/device:TPU:{d}"
        events += [
            [plane, "XLA Ops", "fusion.1", 0.0, 4.0, "jit__paged_decode_impl"],
            [plane, "XLA Ops", "fusion.2", 6.0, 2.0, "jit__paged_decode_impl"],
            [plane, "XLA Modules", "jit__paged_decode_impl(1)", 0.0, 8.0, None],
        ]
    return events


def test_trace_readers_over_four_device_planes():
    window = types.SimpleNamespace(t0=0.0, t1=10e-9, seconds=10e-9)
    one, four = _planes(1), _planes(4)
    assert tracing.busy_seconds(four) == tracing.busy_seconds(one) == 6e-9
    idle = [_read("device_idle_share.serve", _ctx(window, events=e)) for e in (one, four)]
    assert idle[0] == idle[1] == pytest.approx(40.0)
    # device time sums over planes, as the bound sums over every replica's steps
    assert tracing.program_seconds(four, ("jit__paged_decode_impl",)) == pytest.approx(
        4 * tracing.program_seconds(one, ("jit__paged_decode_impl",))
    )


def _served(n_per_replica):
    probes = Probes()
    for r, n in enumerate(n_per_replica):
        for k in range(n):
            prompt = f"r{r}p{k}"
            probes.served[prompt] = ([1, 2, 3], [4] * (1 + (r == 2 and k == 0)), "length")
            probes.done_t[prompt] = 1.0
            probes.replica[prompt] = r
    return probes


def test_sample_takes_per_replica_requests_of_each_replica():
    probes = _served([40, 30, 20, 60])
    for seed in (1, 2**31 + 5, 77):
        sample = check.sample_requests(probes, seed, 48, 12)
        assert len(sample) == 48
        # the longest (replica 2's first) is in it
        assert [4, 4] in [s[1] for s in sample]
    # without per_replica the sample is the seeded draw of before
    plain = check.sample_requests(probes, 5, 48)
    assert len(plain) == 48 and plain == check.sample_requests(probes, 5, 48, 0)


def test_sample_has_every_replica_in_it():
    probes = _served([40, 30, 20, 60])
    # tag each request's served tokens with its replica to count the sample
    for p in probes.served:
        r = probes.replica[p]
        probes.served[p] = ([r], probes.served[p][1], "length")
    sample = check.sample_requests(probes, 2**31 + 1, 48, 12)
    counts = [sum(s[0] == [r] for s in sample) for r in range(4)]
    assert counts == [12, 12, 12, 12]


def test_replicas_short_counts_replicas_that_served_too_few():
    assert check.replicas_short(_served([40, 30, 20, 60]), 4, 12) == 0
    assert check.replicas_short(_served([40, 11, 20, 60]), 4, 12) == 1
    # a replica that served nothing is short too
    assert check.replicas_short(_served([40, 30, 20]), 4, 12) == 1
