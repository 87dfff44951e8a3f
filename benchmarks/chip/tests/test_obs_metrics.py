"""The per-layer metrics that read what the program records itself: its
``BatcherStats`` counters and its spans (``repro.obs``), on synthetic
windows.  Each reads its value off the window alone, returns nothing for
a program that records nothing, and raises where a program that records
shows no work or lost spans."""

import sys
import types

import pytest

import harness
from probes import Probes

from repro import obs


def _ctx(stats0=None, stats1=None):
    window = types.SimpleNamespace(
        t0=100.0, t1=110.0, seconds=10.0, stats0=stats0 or {}, stats1=stats1 or {}
    )
    return harness.ReadContext({}, {}, None, window, Probes(trace=True), [])


def _log(monkeypatch, spans, dropped=0, first_drop=None):
    recs = [obs.Span(sid, name, t0, t1, parent, 1, attrs)
            for sid, name, t0, t1, parent, attrs in spans]
    monkeypatch.setattr(obs, "records", lambda: obs.Log(recs, dropped, first_drop))


def _read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


COUNTERS = [("queue_wait_ms", "queue_wait_s"), ("first_token_ms", "first_token_s")]


@pytest.mark.parametrize("metric,key", COUNTERS)
def test_counter_readers_average_over_the_window_admissions(metric, key):
    ctx = _ctx({"admissions": 10, key: 5.0}, {"admissions": 30, key: 25.0})
    assert _read(metric, ctx) == pytest.approx(1000.0)


@pytest.mark.parametrize("metric,key", COUNTERS)
def test_counter_readers_skip_a_program_without_the_counter(metric, key):
    assert _read(metric, _ctx({"admissions": 1}, {"admissions": 5})) is None


@pytest.mark.parametrize("metric,key", COUNTERS)
def test_counter_readers_refuse_a_window_without_admissions(metric, key):
    with pytest.raises(RuntimeError, match="no admission"):
        _read(metric, _ctx({"admissions": 4, key: 1.0}, {"admissions": 4, key: 1.0}))


STEPS = [
    (1, "sched.step", 101.0, 103.0, None, {}),
    (2, "sched.decode.sync", 102.0, 102.5, 1, {}),
    # opens before the window: only its part inside counts
    (3, "sched.step", 99.0, 100.5, None, {}),
    (4, "sched.step", 109.0, 111.0, None, {}),
    (5, "sched.refill", 109.2, 110.8, 4, {}),
    (6, "sched.admit", 109.3, 110.7, 5, {"rid": 7}),
    (7, "sched.first_token", 109.5, 110.5, 6, {}),
    # a sync outside any step is not the scheduler's
    (8, "sched.decode.sync", 105.0, 106.0, None, {}),
    (9, "sched.step", 111.0, 112.0, None, {}),
]


def test_scheduler_host_share_leaves_out_device_waits(monkeypatch):
    _log(monkeypatch, STEPS)
    # steps 2 + 0.5 + 1 s in the window, less the 0.5 s sync and 0.5 s of
    # the first token inside the window
    assert _read("scheduler_host_share", _ctx()) == pytest.approx(25.0)


LOOKUPS = [
    (1, "storage.read_log", 101.0, 101.5, 2, {"files": 512}),
    (2, "cache.lookup", 100.9, 101.6, None, {}),
    (3, "storage.read_segment", 101.0, 101.1, 4, {}),
    (4, "storage.read_log", 102.0, 102.4, 5, {"files": 512}),
    (5, "cache.lookup", 101.9, 102.6, None, {}),
    # a manifest's log, read outside any lookup
    (6, "storage.read_log", 103.0, 103.1, None, {"files": 3}),
    # a lookup that began before the window
    (7, "storage.read_log", 99.5, 100.2, 8, {"files": 511}),
    (8, "cache.lookup", 99.0, 100.3, None, {}),
]


def test_log_reads_per_lookup_counts_files_inside_lookups(monkeypatch):
    _log(monkeypatch, LOOKUPS)
    assert _read("log_reads_per_lookup", _ctx()) == 512.0


@pytest.mark.parametrize("metric,spans", [
    ("scheduler_host_share", STEPS), ("log_reads_per_lookup", LOOKUPS),
])
def test_span_readers_refuse_a_log_that_dropped_spans(monkeypatch, metric, spans):
    _log(monkeypatch, spans, dropped=3, first_drop=104.0)
    with pytest.raises(RuntimeError, match="dropped 3"):
        _read(metric, _ctx())
    # spans dropped after the window closed do not touch it
    _log(monkeypatch, spans, dropped=3, first_drop=120.0)
    assert _read(metric, _ctx()) > 0


@pytest.mark.parametrize("metric,kept", [
    ("scheduler_host_share", ("sched.decode.sync",)),
    ("log_reads_per_lookup", ("storage.read_log", "storage.read_segment")),
])
def test_span_readers_refuse_a_window_without_their_spans(monkeypatch, metric, kept):
    every = STEPS + LOOKUPS
    _log(monkeypatch, [s for s in every if s[1] in kept])
    with pytest.raises(RuntimeError, match="no "):
        _read(metric, _ctx())


@pytest.mark.parametrize("metric", ["scheduler_host_share", "log_reads_per_lookup"])
def test_span_readers_skip_a_program_without_spans(monkeypatch, metric):
    import repro

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    assert _read(metric, _ctx()) is None
