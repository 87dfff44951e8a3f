"""Program spans (``repro.obs``) and the scheduler's request stamps.

Spans record only inside a profiler session; the counters
``queue_wait_s``/``first_token_s`` are always on and sum the stamps."""

import threading

import jax
import pytest

from repro import obs
from repro.configs import ARCHS
from repro.core import (
    CachePolicy,
    EvalSession,
    EvalTask,
    InferenceConfig,
    MetricConfig,
)
from repro.core.cache import CacheEntry, ResponseCache
from repro.core.config import EngineModelConfig
from repro.core.engines import InferenceRequest, LocalJaxEngine
from repro.core.service import InferenceService, aggregate_batcher_stats
from repro.data import qa_examples
from repro.models import params as pm
from repro.models.model import build_model
from repro.serve import ContinuousBatcher, Request
from repro.storage.deltalite import DeltaLite


@pytest.fixture(autouse=True)
def _empty_log():
    obs.clear()
    yield
    obs.clear()


@pytest.fixture(scope="module")
def qwen3():
    cfg = ARCHS["qwen3-4b"].reduced()
    model = build_model(cfg, remat="none")
    return model, cfg, pm.init_params(jax.random.key(0), model.param_specs())


def _batcher(qwen3, **kw):
    model, cfg, params = qwen3
    return ContinuousBatcher(
        model, cfg, params, n_slots=2, max_len=64, eos_id=1, page_size=16, **kw
    )


PROMPTS = [list(range(10, 30)) + [40 + i] for i in range(3)] + [[7, 8, 9]]


def _serve(qwen3):
    sched = _batcher(qwen3)
    for i, toks in enumerate(PROMPTS):
        sched.submit(Request(i, prompt_tokens=toks, max_new_tokens=4))
    done = {c.request_id: c for c in sched.run_to_completion()}
    return sched, [done[i] for i in range(len(PROMPTS))]


def _by_sid(spans):
    return {s.sid: s for s in spans}


def _ancestors(span, by_sid):
    out = []
    while span.parent is not None:
        span = by_sid[span.parent]
        out.append(span)
    return out


def test_no_profiler_session_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("a", rid=1) as sp:
        sp.set(rid=2)
    assert sp is obs.NO_SPAN
    assert obs.span("b") is obs.NO_SPAN
    with obs.timed("c") as t:
        pass
    assert t.seconds >= 0.0
    log = obs.records()
    assert log.spans == [] and log.dropped == 0


def test_spans_nest_per_thread_and_carry_attrs(tmp_path):
    def worker():
        with obs.span("other"):
            pass

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", rid=3) as outer:
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
            with obs.span("inner") as inner:
                inner.set(files=5)
    assert not th.is_alive()
    spans = {s.name: s for s in obs.records().spans}
    assert spans["outer"].parent is None and spans["outer"].attrs == {"rid": 3}
    assert spans["inner"].parent == spans["outer"].sid
    assert spans["inner"].attrs == {"files": 5}
    assert spans["other"].parent is None
    assert spans["other"].thread != spans["outer"].thread
    o, i = spans["outer"], spans["inner"]
    assert o.t0 <= i.t0 <= i.t1 <= o.t1


def test_full_log_drops_and_counts(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 2)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with obs.span("s"):
                pass
    log = obs.records()
    assert len(log.spans) == 2
    assert log.dropped == 3 and log.first_drop >= log.spans[-1].t1


def test_scheduler_spans_nest_with_request_ids(qwen3, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _serve(qwen3)
    spans = obs.records().spans
    by_sid = _by_sid(spans)
    admits = [s for s in spans if s.name == "sched.admit"]
    assert sorted(s.attrs["rid"] for s in admits) == list(range(len(PROMPTS)))
    for a in admits:
        assert [p.name for p in _ancestors(a, by_sid)] == [
            "sched.refill", "sched.step"
        ]
    for name in ("sched.row_init", "sched.prefill", "sched.page_write",
                 "sched.first_token"):
        inner = [s for s in spans if s.name == name]
        assert len(inner) == len(PROMPTS)
        for s in inner:
            assert by_sid[s.parent].name == "sched.admit"
            assert by_sid[s.parent].t0 <= s.t0 <= s.t1 <= by_sid[s.parent].t1
    # the three 21-token prompts share one 16-token page after the first
    reads = [s for s in spans if s.name == "sched.prefix_read"]
    assert [by_sid[s.parent].attrs["rid"] for s in reads] == [1, 2]
    steps = [s for s in spans if s.name == "sched.step"]
    for name in ("sched.decode.tables", "sched.decode.dispatch",
                 "sched.decode.sync", "sched.decode.commit"):
        inner = [s for s in spans if s.name == name]
        assert inner and all(by_sid[s.parent].name == "sched.step" for s in inner)
    assert len(steps) >= len([s for s in spans if s.name == "sched.decode.sync"])


def test_stamps_sum_to_counters_and_latency_counts_the_queue(qwen3):
    sched, comps = _serve(qwen3)
    waits = sum(c.admitted_at - c.queued_at for c in comps)
    firsts = sum(c.first_token_at - c.admitted_at for c in comps)
    assert sched.stats.queue_wait_s == pytest.approx(waits, rel=1e-12)
    assert sched.stats.first_token_s == pytest.approx(firsts, rel=1e-12)
    # two slots, four requests: the last two wait for a slot to free
    assert comps[3].admitted_at - comps[3].queued_at > 0
    for c in comps:
        assert c.queued_at <= c.admitted_at <= c.first_token_at <= c.finished_at
        assert c.latency_s == c.finished_at - c.queued_at
    as_dict = sched.stats.as_dict()
    agg = aggregate_batcher_stats([as_dict, as_dict])
    assert agg["queue_wait_s"] == 2 * sched.stats.queue_wait_s
    assert agg["first_token_s"] == 2 * sched.stats.first_token_s


def test_tokens_identical_with_and_without_profiler(qwen3, tmp_path):
    _, plain = _serve(qwen3)
    with jax.profiler.trace(str(tmp_path)):
        _, traced = _serve(qwen3)
    assert [c.tokens for c in traced] == [c.tokens for c in plain]
    assert obs.records().spans


def test_service_and_scheduler_spans_share_the_request_id(tmp_path):
    eng = LocalJaxEngine(
        EngineModelConfig(provider="local", model_name="qwen3-4b"),
        n_slots=2, max_len=64, kv_page_size=16,
    )
    prompts = [f"question {i} about the shared context" for i in range(3)]
    with InferenceService(eng) as svc:
        svc.submit(InferenceRequest(prompts[0], max_tokens=2)).result(120)
        obs.clear()
        with jax.profiler.trace(str(tmp_path)):
            tickets = [
                svc.submit(InferenceRequest(p, max_tokens=3)) for p in prompts
            ]
            texts = [t.result(120).text for t in tickets]
    assert all(isinstance(t, str) for t in texts)
    spans = obs.records().spans
    rids = {
        name: sorted(s.attrs["rid"] for s in spans if s.name == name)
        for name in ("service.dispatch", "sched.admit", "service.resolve")
    }
    assert rids["service.dispatch"] == [1, 2, 3]
    assert rids["sched.admit"] == rids["service.resolve"] == [1, 2, 3]
    by_sid = _by_sid(spans)
    for s in spans:
        if s.name == "sched.step":
            assert by_sid[s.parent].name == "service.pump"


def test_deltalite_lookup_reads_each_commit_file_once(tmp_path):
    writer = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    keys = [f"k{i}" for i in range(5)]
    for k in keys:
        writer.put([CacheEntry(k, "m", "p", "q", "a", 1, 1, 0.0, 0.0)])
    cache = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    with jax.profiler.trace(str(tmp_path / "t")):
        for k in keys[:3]:
            assert cache.lookup(k).prompt_hash == k
    spans = obs.records().spans
    lookups = sorted(
        (s for s in spans if s.name == "cache.lookup"), key=lambda s: s.t0
    )
    assert len(lookups) == 3
    # the fresh handle's first lookup opens every commit file; the later
    # lookups on the unchanged table open none
    (log,) = [s for s in spans if s.name == "storage.read_log"]
    assert log.attrs == {"files": 5}
    assert log.parent == lookups[0].sid
    segs = [s for s in spans if s.name == "storage.read_segment"]
    assert len(segs) == 3  # the key index prunes every other segment
    table = DeltaLite(str(tmp_path / "d"), key_column="k")
    obs.clear()
    for i in range(4):
        table.append([{"k": str(i)}])
    with jax.profiler.trace(str(tmp_path / "t2")):
        table.read()
    (log,) = [s for s in obs.records().spans if s.name == "storage.read_log"]
    assert log.attrs == {"files": 4}


def test_stage_spans_share_the_chunk_timing_stamps(tmp_path):
    task = EvalTask(
        task_id="obs",
        model=EngineModelConfig(provider="openai", model_name="gpt-4o-mini"),
        inference=InferenceConfig(batch_size=8, n_workers=2, cache_dir=""),
        metrics=(MetricConfig("exact_match"),),
    ).with_streaming(max_memory_rows=16)
    with EvalSession() as session, jax.profiler.trace(str(tmp_path)):
        res = session.run_task(iter(qa_examples(48, seed=1)), task)
    spans = obs.records().spans
    for stage in ("prepare", "infer", "metrics"):
        mine = [s for s in spans if s.name == f"stage.{stage}"]
        assert len(mine) == 3
        assert res.timing[f"{stage}_s"] == pytest.approx(
            sum(s.t1 - s.t0 for s in mine), rel=1e-12
        )
