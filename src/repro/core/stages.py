"""Composable stage pipeline for the paper's four-stage evaluation (§3).

Each stage conforms to the :class:`Stage` protocol — ``run(artifact,
session) -> artifact`` — and a single typed :class:`EvalArtifact` flows
through the pipeline, accumulating prompts, responses, scores and
aggregates.  The default pipeline is

    PrepareStage -> InferStage -> ScoreStage -> AggregateStage

but new scenarios are a stage swap, not a fork of the runner: the paper's
cache-replay iteration loop re-scores cached responses by replacing
``InferStage`` with :class:`StaticResponsesStage` (zero engine calls), and
custom stages can be inserted anywhere in the list passed to
``EvalSession.run_task``.

Middleware objects observe the pipeline (``on_task_start``,
``on_stage_start``, ``on_stage_end``, ``on_task_end``) and implement
cross-cutting concerns: progress reporting, experiment tracking, and the
session cost-budget abort (:class:`CostBudgetMiddleware`).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Any, Protocol, runtime_checkable

import numpy as np

from repro.core.cache import CacheEntry
from repro.core.config import CachePolicy, EvalTask, cache_key
from repro.core.engines import (
    InferenceRequest,
    InferenceResponse,
    retry_with_backoff,
)
from repro.core.ratelimit import AdaptiveLimiter
from repro.ft.workers import PoolStats
from repro.data.templates import render
from repro.metrics.registry import (
    BINARY_METRICS,
    JUDGE_METRICS,
    MetricContext,
    resolve_metrics,
)
from repro.stats.bootstrap import Interval, compute_ci

# -- results -------------------------------------------------------------------


@dataclasses.dataclass
class MetricValue:
    name: str
    value: float
    ci: tuple[float, float]
    ci_method: str
    n: int
    n_unscored: int = 0

    def __repr__(self) -> str:  # paper §5.6 display format
        return (
            f"MetricValue(value={self.value:.3f}, "
            f"ci=({self.ci[0]:.3f}, {self.ci[1]:.3f}), n={self.n})"
        )


@dataclasses.dataclass
class EvalResult:
    task_id: str
    metrics: dict[str, MetricValue]
    scores: dict[str, np.ndarray]
    responses: list[str]
    failures: list[dict]
    cache_stats: dict
    engine_stats: dict
    timing: dict
    logs: dict
    #: streaming runs only: merged accumulator + bootstrap-replicate state
    #: (:class:`repro.stats.streaming.StreamingStats`).  O(B) per metric —
    #: this is what makes pairwise significance possible for tasks that
    #: never materialize per-example score vectors.
    stream_stats: Any = None

    @property
    def throughput_per_min(self) -> float:
        dt = self.timing.get("infer_s", 0.0)
        # streaming runs discard responses; the count lives in the logs
        n = len(self.responses) or self.logs.get("streaming", {}).get(
            "n_examples", 0
        )
        return n / dt * 60.0 if dt > 0 else float("inf")


# -- artifact ------------------------------------------------------------------


@dataclasses.dataclass
class EvalArtifact:
    """The typed value flowing between stages.

    ``PrepareStage`` fills ``prompts``; ``InferStage`` (or a replacement)
    fills ``texts``/``responses``/``failures``; ``ScoreStage`` fills
    ``scores``; ``AggregateStage`` fills ``metrics``.  Timing is recorded
    by the pipeline loop under ``{stage.name}_s``.
    """

    rows: list[dict]
    task: EvalTask
    prompts: list[str] = dataclasses.field(default_factory=list)
    responses: list[InferenceResponse | None] = dataclasses.field(
        default_factory=list
    )
    texts: list[str] = dataclasses.field(default_factory=list)
    failures: list[dict] = dataclasses.field(default_factory=list)
    scores: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    metrics: dict[str, MetricValue] = dataclasses.field(default_factory=dict)
    cache_stats: dict = dataclasses.field(default_factory=dict)
    engine_stats: dict = dataclasses.field(default_factory=dict)
    timing: dict = dataclasses.field(default_factory=dict)
    logs: dict = dataclasses.field(default_factory=dict)

    def to_result(self) -> EvalResult:
        return EvalResult(
            task_id=self.task.task_id,
            metrics=self.metrics,
            scores=self.scores,
            responses=self.texts,
            failures=self.failures,
            cache_stats=self.cache_stats,
            engine_stats=self.engine_stats,
            timing=self.timing,
            logs=self.logs,
        )


@runtime_checkable
class Stage(Protocol):
    name: str

    def run(self, artifact: EvalArtifact, session: Any) -> EvalArtifact: ...


# -- stage 1: prompt preparation ------------------------------------------------


class PrepareStage:
    name = "prepare"

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        # fail fast on unknown metrics before any paid inference happens
        resolve_metrics(art.task.metrics)
        art.prompts = [
            render(art.task.data.prompt_template, r) for r in art.rows
        ]
        return art


# -- stage 2: distributed inference ---------------------------------------------


@dataclasses.dataclass
class _ShardStats:
    """One shard attempt's own traffic, counted at the call site.

    Concurrent chunk workers share one engine, cache and pool, so deltas
    over their *global* counters would attribute another chunk's traffic
    to this stage.  Counting locally per shard and summing keeps per-task
    (and per-chunk) stats exact regardless of what else runs in parallel.

    Two sinks with different semantics: the *result* stats
    (``art.engine_stats`` / ``art.cache_stats``) sum only the winning
    attempt per shard — deterministic, parity with a serial run — while
    ``session.accounting`` receives every attempt's calls and cost as the
    shard finishes (see :meth:`LockStepInferStage.run`): a speculative
    loser's inference really happened and really cost money, and the
    cost-budget guard must see it.
    """

    calls: int = 0
    cost: float = 0.0
    hits: int = 0
    misses: int = 0
    writes: int = 0
    #: submissions that coalesced onto another submission's flight
    #: (service path only): answered, but nobody paid twice
    coalesced: int = 0


def _sum_shard_stats(parts) -> _ShardStats:
    totals = _ShardStats()
    for st in parts:
        for f in dataclasses.fields(_ShardStats):
            setattr(
                totals, f.name, getattr(totals, f.name) + getattr(st, f.name)
            )
    return totals


def _publish_infer_stats(
    art: EvalArtifact, cache, totals: _ShardStats, pool: dict
) -> None:
    """Assemble ``art.cache_stats`` / ``art.engine_stats`` from summed
    shard stats — shared by the service and lock-step paths so their
    result-stat semantics cannot drift apart."""
    if cache is not None:
        stats = cache.stats()  # entries/version stay session-absolute
        h, m = totals.hits, totals.misses
        stats.update(
            hits=h, misses=m, writes=totals.writes,
            hit_rate=h / (h + m) if h + m else 0.0,
        )
        art.cache_stats = stats
    else:
        art.cache_stats = {}
    art.engine_stats = {
        "calls": totals.calls,
        "total_cost": totals.cost,
        "coalesced": totals.coalesced,
        "pool": pool,
    }


class LockStepInferStage:
    """The legacy inference path: sharded lock-step execution over the
    session worker pool, with per-worker rate limiting at the call site.

    Kept as the benchmark baseline and as the escape hatch behind
    ``InferenceConfig.use_service=False``.  :class:`InferStage` (the
    default) routes the same shard/stats accounting through the shared
    :class:`~repro.core.service.InferenceService` instead, so batches form
    across shards, chunks, tasks and suites rather than within one shard.
    """

    name = "infer"

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        task = art.task
        inf = task.inference
        prompts = art.prompts
        engine = session.engine_for(task.model)
        cache = session.cache_for(inf)
        limiter = session.limiter_for(inf)
        pool = session.pool_for(inf)

        count_lookups = cache is not None and cache.policy not in (
            CachePolicy.DISABLED, CachePolicy.WRITE_ONLY,
        )

        shards = [
            list(range(i, min(i + inf.batch_size, len(prompts))))
            for i in range(0, len(prompts), inf.batch_size)
        ]
        responses: list[InferenceResponse | None] = [None] * len(prompts)
        failures: list[dict] = []
        sleep = session.sleep

        def run_shard(shard_idx: int, idxs: list[int], worker: int):
            st = _ShardStats()
            try:
                return _do_shard(idxs, worker, st), st
            finally:
                # every attempt's spend reaches the session accounting —
                # including speculative losers and failed attempts whose
                # results are discarded by the pool; result-level stats
                # below sum only the winning attempts
                acct = session.accounting
                with acct.lock:
                    acct.engine_calls += st.calls
                    acct.cost_usd += st.cost

        def _do_shard(idxs: list[int], worker: int, st: "_ShardStats"):
            out: list[tuple[int, InferenceResponse, bool]] = []
            to_infer: list[int] = []
            for i in idxs:
                if cache is not None:
                    key = cache.key_for(
                        prompts[i], task.model.model_name, task.model.provider,
                        task.model.temperature, task.model.max_tokens,
                    )
                    hit = cache.lookup(key)
                    if hit is not None:
                        st.hits += 1
                        out.append(
                            (
                                i,
                                InferenceResponse(
                                    text=hit.response_text,
                                    input_tokens=hit.input_tokens or 0,
                                    output_tokens=hit.output_tokens or 0,
                                    latency_ms=0.0,
                                ),
                                True,
                            )
                        )
                        continue
                    if count_lookups:
                        st.misses += 1
                to_infer.append(i)
            w = worker % inf.n_workers
            new_entries: list[CacheEntry] = []
            for i in to_infer:
                est_tokens = len(prompts[i].split()) + task.model.max_tokens
                if isinstance(limiter, AdaptiveLimiter):
                    limiter.acquire(w, est_tokens)
                else:
                    limiter[w].acquire(est_tokens)
                req = InferenceRequest(
                    prompts[i], task.model.max_tokens, task.model.temperature
                )

                def _infer(req=req):
                    st.calls += 1
                    return engine.infer(req)

                resp = retry_with_backoff(
                    _infer,
                    max_retries=inf.max_retries,
                    base_delay=inf.retry_delay,
                    sleep=sleep,
                )
                st.cost += resp.cost_usd
                out.append((i, resp, False))
                if cache is not None and resp.error is None:
                    new_entries.append(
                        CacheEntry(
                            prompt_hash=cache.key_for(
                                prompts[i], task.model.model_name,
                                task.model.provider, task.model.temperature,
                                task.model.max_tokens,
                            ),
                            model_name=task.model.model_name,
                            provider=task.model.provider,
                            prompt_text=prompts[i],
                            response_text=resp.text,
                            input_tokens=resp.input_tokens,
                            output_tokens=resp.output_tokens,
                            latency_ms=resp.latency_ms,
                            created_at=time.time(),
                        )
                    )
            if new_entries:
                st.writes += cache.put(new_entries)
            return out

        n_cached = 0
        in_tok = out_tok = 0
        pool_stats = PoolStats()
        shard_results = pool.map_shards(run_shard, shards, stats_out=pool_stats)
        for sr in shard_results:
            rows, _st = sr.value
            for i, resp, cached in rows:
                responses[i] = resp
                if resp.error is not None:
                    failures.append({"index": i, "error": resp.error})
                elif cached:
                    n_cached += 1
                else:
                    in_tok += resp.input_tokens
                    out_tok += resp.output_tokens
        totals = _sum_shard_stats(sr.value[1] for sr in shard_results)

        art.responses = responses
        art.texts = [
            r.text if r is not None and r.error is None else "" for r in responses
        ]
        art.failures = failures
        _publish_infer_stats(
            art, cache, totals, dataclasses.asdict(pool_stats)
        )

        acct = session.accounting
        with acct.lock:
            acct.input_tokens += in_tok
            acct.output_tokens += out_tok
            if cache is not None:
                acct.cache_hits += n_cached
                acct.cache_misses += len(prompts) - n_cached
        return art


class InferStage:
    """Submit/gather inference through the session's shared
    :class:`~repro.core.service.InferenceService`.

    Every cache miss becomes a service submission *immediately* — before
    any response is gathered — so in-flight batches span shards (and, via
    the shared per-engine service, chunks, tasks and models).  Identical
    in-flight cache keys single-flight: one engine call, N waiters, and
    the spend (call count, cost, tokens, cache write) is credited to
    exactly one shard — the primary submitter's.

    Per-shard stats accounting is preserved exactly: the same shard
    layout, the same local-counting discipline (`_ShardStats`), and in a
    run without concurrent duplicates the same calls/cost/hits/misses/
    writes as :class:`LockStepInferStage`, which remains available behind
    ``InferenceConfig.use_service=False``.

    Shard-level *speculative re-issue* is intentionally subsumed rather
    than re-implemented: a speculative twin of an in-flight engine call is
    precisely the duplicate spend single-flight exists to eliminate, so a
    re-issued chunk's submissions coalesce onto the original flights
    instead of racing them.  Stuck-call mitigation at the request level is
    ``max_retries`` (dispatched centrally); chunk-level speculation still
    covers the non-inference portion of a chunk's work.
    """

    name = "infer"

    def __init__(self) -> None:
        self._lockstep = LockStepInferStage()

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        if not art.task.inference.use_service:
            return self._lockstep.run(art, session)
        return self._run_service(art, session)

    def _run_service(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        task = art.task
        inf = task.inference
        model = task.model
        prompts = art.prompts
        service = session.service_for(model, inf)
        cache = session.cache_for(inf)
        limiter = session.limiter_for(inf)

        count_lookups = cache is not None and cache.policy not in (
            CachePolicy.DISABLED, CachePolicy.WRITE_ONLY,
        )
        shards = [
            list(range(i, min(i + inf.batch_size, len(prompts))))
            for i in range(0, len(prompts), inf.batch_size)
        ]
        responses: list[InferenceResponse | None] = [None] * len(prompts)
        failures: list[dict] = []
        acct = session.accounting
        plans: list[tuple[_ShardStats, list]] = []
        #: gather cursor over the flattened plan entries, so an aborted
        #: gather can sweep the spend of ungathered flights
        gathered = 0
        n_cached = 0
        in_tok = out_tok = 0

        #: stage-local single-flight: the first occurrence of a key in this
        #: stage submits; later occurrences share its ticket.  This keeps
        #: intra-task dedup *deterministic* (independent of dispatch
        #: timing), while the service-level flight table handles the
        #: inherently-racy cross-stage case (concurrent chunks/tasks).
        local: dict[str, Any] = {}

        service.attach(inf.n_workers)
        try:
            # -- submit phase: cache lookups count per shard exactly as the
            # lock-step path counts them; misses go straight to the service
            for idxs in shards:
                st = _ShardStats()
                pending: list[tuple[int, str, Any, bool]] = []
                plans.append((st, pending))
                for i in idxs:
                    key = cache_key(
                        prompts[i], model.model_name, model.provider,
                        model.temperature, model.max_tokens,
                    )
                    if cache is not None:
                        hit = cache.lookup(key)
                        if hit is not None:
                            st.hits += 1
                            n_cached += 1
                            responses[i] = InferenceResponse(
                                text=hit.response_text,
                                input_tokens=hit.input_tokens or 0,
                                output_tokens=hit.output_tokens or 0,
                                latency_ms=0.0,
                            )
                            continue
                        if count_lookups:
                            st.misses += 1
                    if inf.coalesce and key in local:
                        service.note_coalesced()
                        pending.append((i, key, local[key], False))
                        continue
                    est = len(prompts[i].split()) + model.max_tokens
                    ticket = service.submit(
                        InferenceRequest(
                            prompts[i], model.max_tokens, model.temperature
                        ),
                        key=key,
                        coalesce=inf.coalesce,
                        limiter=limiter,
                        est_tokens=est,
                        max_retries=inf.max_retries,
                        retry_delay=inf.retry_delay,
                        deadline_s=inf.request_deadline_s,
                    )
                    local[key] = ticket
                    pending.append((i, key, ticket, True))

            # -- gather phase: per-shard stats, primary submissions only —
            # a coalesced follower's spend belongs to its leader's shard
            for st, pending in plans:
                new_entries: list[CacheEntry] = []
                for i, key, ticket, owner in pending:
                    resp = ticket.result()
                    gathered += 1
                    responses[i] = resp
                    primary = owner and ticket.primary
                    if primary:
                        st.calls += ticket.attempts
                        st.cost += resp.cost_usd
                    else:
                        st.coalesced += 1
                    if resp.error is not None:
                        failures.append({"index": i, "error": resp.error})
                    elif primary:
                        in_tok += resp.input_tokens
                        out_tok += resp.output_tokens
                        if cache is not None:
                            new_entries.append(
                                CacheEntry(
                                    prompt_hash=key,
                                    model_name=model.model_name,
                                    provider=model.provider,
                                    prompt_text=prompts[i],
                                    response_text=resp.text,
                                    input_tokens=resp.input_tokens,
                                    output_tokens=resp.output_tokens,
                                    latency_ms=resp.latency_ms,
                                    created_at=time.time(),
                                )
                            )
                if new_entries:
                    st.writes += cache.put(new_entries)
        finally:
            service.detach(inf.n_workers)
            # spend reaches the session accounting even if the gather
            # aborts mid-shard (REPLAY miss, dispatcher exception): sweep
            # the flights that already resolved but were never gathered —
            # their engine calls happened and cost money.  Calls still in
            # flight at abort time resolve in the service afterwards; only
            # those escape per-task accounting.
            flat = [
                (st, entry) for st, pending in plans for entry in pending
            ]
            for st, (i, key, ticket, owner) in flat[gathered:]:
                if not (owner and ticket.primary and ticket.done()):
                    continue
                try:
                    resp = ticket.result(0.0)
                except BaseException:  # noqa: BLE001 — failed flight: no spend
                    continue
                st.calls += ticket.attempts
                st.cost += resp.cost_usd
            with acct.lock:
                for st, _ in plans:
                    acct.engine_calls += st.calls
                    acct.cost_usd += st.cost
                    acct.coalesced_requests += st.coalesced

        totals = _sum_shard_stats(st for st, _ in plans)

        art.responses = responses
        art.texts = [
            r.text if r is not None and r.error is None else ""
            for r in responses
        ]
        art.failures = failures
        _publish_infer_stats(art, cache, totals, {})
        with acct.lock:
            acct.input_tokens += in_tok
            acct.output_tokens += out_tok
            if cache is not None:
                acct.cache_hits += n_cached
                acct.cache_misses += len(prompts) - n_cached
        return art


class StaticResponsesStage:
    """Stage-swap replacement for :class:`InferStage`: inject precomputed
    response texts (e.g. from a prior :class:`EvalResult`) and re-score
    them with different metrics at zero engine cost."""

    name = "infer"

    def __init__(self, texts: list[str]):
        self._texts = list(texts)

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        if len(self._texts) != len(art.rows):
            raise ValueError(
                f"{len(self._texts)} responses for {len(art.rows)} rows"
            )
        art.texts = list(self._texts)
        art.responses = [None] * len(art.rows)
        art.cache_stats = {}
        art.engine_stats = {"calls": 0, "total_cost": 0.0, "pool": {}}
        return art


# -- stage 3: metric computation -------------------------------------------------


class ScoreStage:
    """Vectorized per-example scoring.  Metric resolution (registry lookup +
    params binding) lives behind this stage via
    :func:`repro.metrics.registry.resolve_metrics`, memoized per metric
    tuple — a streaming run re-enters this stage once per chunk, and the
    stage object is shared across concurrent chunk workers, so resolution
    happens once per task instead of once per chunk."""

    name = "metrics"

    def __init__(self) -> None:
        self._resolved: dict[tuple, list] = {}

    def _metrics_for(self, task: EvalTask) -> list:
        # benign race under concurrent chunk workers: two threads may both
        # resolve, the dict assignment is atomic and the values identical
        resolved = self._resolved.get(task.metrics)
        if resolved is None:
            resolved = resolve_metrics(task.metrics)
            self._resolved[task.metrics] = resolved
        return resolved

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        task = art.task
        judge = session.judge_engine
        if judge is None and any(
            m.type == "llm_judge" or m.name in JUDGE_METRICS
            for m in task.metrics
        ):
            # only judge-backed metrics warrant initializing the task engine
            # here — a lexical-only rescore pipeline stays engine-free
            judge = session.engine_for(task.model)
        ctx = MetricContext(judge_engine=judge, logs=art.logs)
        scores: dict[str, np.ndarray] = {}
        for name, scorer in self._metrics_for(task):
            scores[name] = np.asarray(
                scorer(art.rows, art.texts, ctx), np.float64
            )
        art.scores = scores
        return art


# -- stage 4: statistical aggregation ---------------------------------------------


class AggregateStage:
    name = "stats"

    def run(self, art: EvalArtifact, session: Any) -> EvalArtifact:
        stats_cfg = art.task.statistics
        metric_values: dict[str, MetricValue] = {}
        for name, vals in art.scores.items():
            nan_mask = np.isnan(vals)  # one O(n) scan, reused for both
            ok = vals[~nan_mask]
            n_unscored = int(nan_mask.sum())
            if len(ok) == 0:
                metric_values[name] = MetricValue(
                    name, float("nan"), (float("nan"),) * 2, "none", 0, n_unscored
                )
                continue
            iv: Interval = compute_ci(
                ok,
                method=stats_cfg.ci_method,
                confidence=stats_cfg.confidence_level,
                n_boot=stats_cfg.bootstrap_iterations,
                seed=stats_cfg.seed,
                binary=name in BINARY_METRICS,
            )
            metric_values[name] = MetricValue(
                name, iv.value, (iv.lo, iv.hi), iv.method, iv.n, n_unscored
            )
        art.metrics = metric_values
        return art


def default_stages() -> list[Stage]:
    return [PrepareStage(), InferStage(), ScoreStage(), AggregateStage()]


def rescore_stages(texts: list[str]) -> list[Stage]:
    """Pipeline for the cache-replay iteration loop: re-score existing
    responses without inference."""
    return [
        PrepareStage(),
        StaticResponsesStage(texts),
        ScoreStage(),
        AggregateStage(),
    ]


# -- middleware -----------------------------------------------------------------


class Middleware:
    """No-op base; subclass and override the hooks you need."""

    def on_task_start(self, task: EvalTask, rows: list[dict], session: Any) -> None:
        pass

    def on_stage_start(self, stage: Stage, art: EvalArtifact, session: Any) -> None:
        pass

    def on_stage_end(self, stage: Stage, art: EvalArtifact, session: Any) -> None:
        pass

    def on_chunk_end(self, chunk_index: int, state: dict, session: Any) -> None:
        """Streaming pipeline only: a chunk finished (and was committed to
        the spill manifest, when spill is configured)."""

    def on_task_end(self, task: EvalTask, result: EvalResult, session: Any) -> None:
        pass


class CostBudgetExceeded(RuntimeError):
    """Raised by :class:`CostBudgetMiddleware` when session spend crosses
    the configured budget; aborts the pipeline between stages."""


class CostBudgetMiddleware(Middleware):
    def __init__(self, max_usd: float):
        self.max_usd = max_usd

    def on_stage_end(self, stage, art, session) -> None:
        self._check(session, f"after stage {stage.name!r} of task "
                             f"{art.task.task_id!r}")

    def on_chunk_end(self, chunk_index, state, session) -> None:
        self._check(session, f"after streaming chunk {chunk_index}")

    def _check(self, session, where: str) -> None:
        spent = session.accounting.cost_usd
        if spent > self.max_usd:
            raise CostBudgetExceeded(
                f"session cost ${spent:.4f} exceeds budget "
                f"${self.max_usd:.4f} ({where})"
            )


class ProgressMiddleware(Middleware):
    def __init__(self, stream=None):
        self.stream = stream or sys.stderr
        self._t0: dict[str, float] = {}

    def on_task_start(self, task, rows, session) -> None:
        print(
            f"[{task.task_id}] {len(rows)} examples, "
            f"model={task.model.provider}:{task.model.model_name}",
            file=self.stream,
        )

    def on_stage_start(self, stage, art, session) -> None:
        self._t0[stage.name] = time.monotonic()

    def on_stage_end(self, stage, art, session) -> None:
        dt = time.monotonic() - self._t0.get(stage.name, time.monotonic())
        print(f"[{art.task.task_id}]   {stage.name}: {dt:.2f}s", file=self.stream)

    def on_chunk_end(self, chunk_index, state, session) -> None:
        print(
            f"  chunk {chunk_index}: rows {state['start']}.."
            f"{state['start'] + state['n_rows']}, "
            f"{state['n_failures']} failures",
            file=self.stream,
        )

    def on_task_end(self, task, result, session) -> None:
        vals = ", ".join(
            f"{n}={mv.value:.3f}" for n, mv in result.metrics.items()
        )
        print(f"[{task.task_id}] done: {vals}", file=self.stream)


class TrackingMiddleware(Middleware):
    """Log every completed task to a :class:`repro.core.tracking.RunTracker`."""

    def __init__(self, tracker, **tags: str):
        self.tracker = tracker
        self.tags = tags
        self.run_ids: list[str] = []

    def on_task_end(self, task, result, session) -> None:
        self.run_ids.append(self.tracker.log_run(task, result, **self.tags))
