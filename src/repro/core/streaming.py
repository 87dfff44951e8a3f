"""StreamingPipeline: bounded-memory chunked evaluation with resumable spill.

The default pipeline materializes prompts, responses and per-example scores
for the whole dataset — O(dataset) memory, which contradicts the paper's
"hundreds of thousands or millions of samples" claim.  This pipeline runs
prepare→infer→score per chunk (reusing the exact same stage objects, so
the sharded worker pool, caching, rate limiting and retries all apply
within a chunk), folds each chunk's scores into mergeable streaming
accumulators (:mod:`repro.stats.streaming`), and discards the chunk —
peak per-example state is one chunk, independent of dataset size.

With a ``spill_dir``, every completed chunk commits its partial state to a
:class:`~repro.storage.spill.ChunkManifest` (one DeltaLite commit per
chunk).  A restarted run replays the manifest: committed chunks are
skipped — their accumulator states merged instead of recomputed — and the
final metrics are bit-identical to an uninterrupted run, because the
Poisson-bootstrap weights are keyed by (seed, chunk offset), not by
processing order.

The aggregate CIs come from :func:`repro.stats.streaming.streaming_ci`:
exact analytical intervals from the moments, or the Poisson-bootstrap
percentile interval (Monte-Carlo-equivalent to the in-memory multinomial
bootstrap) for the bootstrap methods.  Replicate state is maintained by a
pluggable :class:`~repro.stats.streaming.BootstrapEngine`
(``StatisticsConfig.backend``): per-metric host Philox weight blocks
("numpy") or the device-resident chunked-partials kernel ("pallas") that
covers every metric of a chunk in one launch.  Either way the finished
result carries the merged O(B) state as ``EvalResult.stream_stats``,
which is what lets suites run paired significance tests between
streaming runs without per-example scores.

:class:`ConcurrentStreamingExecutor` is the parallel counterpart: it
schedules whole chunks onto a chunk-level :class:`~repro.ft.workers.
WorkerPool` window (``StreamingConfig.max_inflight_chunks``), so peak
memory is window x chunk — still independent of dataset size.  The Philox
keying of the bootstrap by (seed, chunk offset) makes chunk states
mergeable in *any* order; the executor nevertheless folds them
deterministically in chunk-index order through a bounded reorder buffer,
so the final metrics and CIs are **bit-identical** to the serial pipeline
(float addition is not associative — completion-order folding would be
statistically equivalent but not byte-equal).  Chunk-level straggler
mitigation reuses the pool's speculative re-issue; with a spill manifest,
racing attempts resolve first-committer-wins through DeltaLite's
conditional append, and the losing attempt's partial state is discarded
(its engine spend still lands in the session accounting — the calls were
real).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import time
from typing import Any, Iterable, Iterator

from repro import obs
from repro.core.config import EvalTask, StatisticsConfig
from repro.core.stages import (
    EvalArtifact,
    EvalResult,
    InferStage,
    MetricValue,
    PrepareStage,
    ScoreStage,
)
from repro.data.datasets import iter_chunks
from repro.ft.workers import WorkerPool
from repro.metrics.registry import BINARY_METRICS, resolve_metrics
from repro.stats.streaming import (
    BootstrapEngine,
    MetricAccumulator,
    StreamingStats,
    make_bootstrap_engine,
    streaming_ci,
)
from repro.storage.spill import ChunkManifest

#: failures kept in the result (full per-example lists defeat O(chunk) memory)
MAX_FAILURE_SAMPLE = 100


class ManifestMismatch(RuntimeError):
    """Manifest row disagrees with the observed chunk layout — the data
    source differs from the run that wrote the manifest."""


class _StopTracker:
    """Adaptive early-stopping state for one streaming run (both
    pipelines).  Owns the three manifest-backed invariants:

    * **one regime per manifest** — the stopping rule's fingerprint is
      committed as the manifest's regime row before any adaptive chunk
      commits; resuming with a different rule (or flipping adaptive
      mode on/off over existing chunks) refuses with a remediation hint
      instead of silently mixing certification regimes.
    * **one stop per run** — the first firing of the rule commits a stop
      row (first-committer-wins for racing drivers); the stop point then
      becomes part of the resume contract.
    * **bit-identical replay** — a resumed run re-consults the rule after
      every merged chunk (committed chunks replay the same accumulator
      states, so the same decision sequence), and any disagreement with
      the recorded stop row is a :class:`ManifestMismatch`, never a
      silent re-opening of sampling.
    """

    def __init__(
        self,
        task: EvalTask,
        manifest: ChunkManifest | None,
        completed: dict[int, dict],
    ):
        self.rule = task.stopping if task.stopping.enabled else None
        self.manifest = manifest
        self.stopped = False
        self.decision: dict | None = None
        self.recorded: dict | None = None
        if manifest is None:
            return
        fp = self.rule.fingerprint() if self.rule is not None else ""
        row = manifest.regime_row()
        if row is None and self.rule is not None:
            if completed:
                raise ManifestMismatch(
                    f"manifest {manifest.run_key} has {len(completed)} "
                    "committed chunk(s) but no certification-regime row — "
                    "it was written by a run without adaptive stopping. "
                    "Resume without a stopping rule, or clear the spill "
                    "dir to start an adaptive run"
                )
            if not manifest.try_record_regime({"rule": fp}):
                row = manifest.regime_row()  # lost the race: validate
        if row is not None and row.get("rule") != fp:
            ours = f"rule {fp}" if self.rule is not None else "stopping disabled"
            raise ManifestMismatch(
                f"manifest {manifest.run_key} was written under "
                f"certification regime {row.get('rule')!r} but this run has "
                f"{ours} — resuming would mix stopping regimes. Resume with "
                "the original StoppingRule, or clear the spill dir to "
                "re-certify under the new rule"
            )
        if self.rule is not None:
            self.recorded = manifest.stop_row()

    def after_chunk(
        self, ci: int, accs: dict[str, MetricAccumulator], n_examples: int
    ) -> bool:
        """Consult the rule after chunk ``ci`` merged; True = stop now.
        Validates (or commits) the manifest stop row as a side effect."""
        if self.rule is None:
            return False
        d = self.rule.should_stop(accs, n_examples)
        rec = self.recorded
        if not d.stop:
            if rec is not None and int(rec["stop_chunk"]) == ci:
                raise ManifestMismatch(
                    f"manifest records a certified stop at chunk {ci} "
                    f"(n={rec['n_examples']}, reason={rec['reason']!r}) but "
                    "this run's rule does not fire there — was the data "
                    "source or the rule changed?"
                )
            return False
        state = {
            "stop_chunk": ci,
            "n_examples": n_examples,
            "reason": d.reason,
            "metric": d.metric,
            "half_width": d.half_width,
            "rule": self.rule.fingerprint(),
        }
        if rec is None and self.manifest is not None:
            if not self.manifest.try_record_stop(state):
                rec = self.manifest.stop_row()  # lost the race: validate
        if rec is not None and (
            int(rec["stop_chunk"]),
            int(rec["n_examples"]),
            rec["reason"],
        ) != (ci, n_examples, d.reason):
            raise ManifestMismatch(
                f"stop decision diverged from the manifest: recorded "
                f"chunk {rec['stop_chunk']} n={rec['n_examples']} "
                f"reason={rec['reason']!r}, this run fired at chunk {ci} "
                f"n={n_examples} reason={d.reason!r} — was the data source "
                "or the rule changed?"
            )
        self.stopped = True
        self.decision = state
        return True

    def finish(self) -> None:
        """Source exhausted without the rule firing — legal, unless the
        manifest promised a stop this run never reached."""
        if self.recorded is not None and not self.stopped:
            raise ManifestMismatch(
                f"manifest records a certified stop at chunk "
                f"{self.recorded['stop_chunk']} "
                f"(n={self.recorded['n_examples']}) that this run never "
                "reached — was the data source shortened?"
            )

    def info(self) -> dict | None:
        """``logs['adaptive']`` payload, or None when stopping is off."""
        if self.rule is None:
            return None
        out = {
            "enabled": True,
            "stopped": self.stopped,
            "rule": self.rule.fingerprint(),
        }
        if self.decision is not None:
            out.update(self.decision)
        return out


class StreamingPipeline:
    def __init__(
        self,
        *,
        chunk_size: int = 1024,
        spill_dir: str = "",
        resume: bool = True,
        max_examples: int = 0,
    ):
        self.chunk_size = chunk_size
        self.spill_dir = spill_dir
        self.resume = resume
        self.max_examples = max_examples

    @classmethod
    def from_task(cls, task: EvalTask) -> "StreamingPipeline":
        s = task.streaming
        return cls(
            chunk_size=s.max_memory_rows,
            spill_dir=s.spill_dir,
            resume=s.resume,
            max_examples=s.max_examples,
        )

    def run(
        self, source: Iterable[dict], task: EvalTask, session: Any
    ) -> EvalResult:
        if self.max_examples > 0:
            source = itertools.islice(source, self.max_examples)
        stages = [PrepareStage(), InferStage(), ScoreStage()]
        stats_cfg = task.statistics
        names = [name for name, _ in resolve_metrics(task.metrics)]
        accs = {m: MetricAccumulator() for m in names}
        # the analytical interval comes straight from the moments; only the
        # bootstrap methods pay for maintaining replicate state (numpy:
        # O(B x chunk) Poisson weight draws per metric; pallas: one
        # chunked-partials kernel launch covering every metric)
        use_boot = stats_cfg.ci_method in ("percentile", "bca")
        engine = make_bootstrap_engine(
            stats_cfg.backend, stats_cfg.bootstrap_iterations,
            stats_cfg.seed, tuple(names),
        ) if use_boot else None
        manifest = (
            ChunkManifest(self.spill_dir, _run_key(task))
            if self.spill_dir
            else None
        )
        completed = (
            manifest.completed() if manifest is not None and self.resume else {}
        )
        stopper = _StopTracker(task, manifest, completed)

        failures: list[dict] = []
        timing: dict[str, float] = {}
        engine_stats = {"calls": 0, "total_cost": 0.0, "coalesced": 0, "pool": {}}
        cache_stats: dict = {}
        n_examples = n_chunks = n_resumed = 0
        max_resident = 0
        start = 0

        for ci, chunk in enumerate(iter_chunks(source, self.chunk_size)):
            n_chunks += 1
            n_examples += len(chunk)
            max_resident = max(max_resident, len(chunk))
            # pop: committed rows carry B-length bootstrap partials, so
            # retaining the whole manifest would be O(n_chunks x B) memory
            row = completed.pop(ci, None)
            if row is not None:
                digest = _chunk_digest(chunk)
                if (
                    row["n_rows"] != len(chunk)
                    or row["start"] != start
                    or row.get("digest") != digest
                ):
                    raise ManifestMismatch(
                        f"chunk {ci}: manifest has start={row['start']} "
                        f"n_rows={row['n_rows']} digest={row.get('digest')}, "
                        f"observed start={start} n_rows={len(chunk)} "
                        f"digest={digest} — was the data source changed?"
                    )
                self._merge_committed(
                    row, accs, engine, failures, timing, engine_stats,
                    cache_stats,
                )
                n_resumed += 1
                start += len(chunk)
                # resumed chunks replay the identical decision sequence:
                # a recorded stop fires here again, bit-identically, and
                # the source iterator is never advanced past it
                if stopper.after_chunk(ci, accs, n_examples):
                    break
                continue

            art = EvalArtifact(rows=chunk, task=task)
            chunk_states: dict[str, dict] = {}
            chunk_timing: dict[str, float] = {}
            for stage in stages:
                with obs.timed(f"stage.{stage.name}") as sp:
                    art = stage.run(art, session)
                chunk_timing[f"{stage.name}_s"] = sp.seconds
            for key, dt in chunk_timing.items():
                timing[key] = timing.get(key, 0.0) + dt

            for m in names:
                acc = MetricAccumulator()
                acc.update(art.scores[m])
                accs[m].merge(acc)
                if manifest is not None:
                    chunk_states.setdefault("metrics", {})[m] = acc.state()
            if engine is not None:
                chunk_engine = engine.spawn()
                chunk_engine.update(art.scores, start)
                engine.merge(chunk_engine)
                if manifest is not None:
                    chunk_states["boot"] = chunk_engine.state()
            chunk_failures = [
                {**f, "index": f["index"] + start} for f in art.failures
            ]
            state = {
                "start": start,
                "n_rows": len(chunk),
                "failures": chunk_failures[:MAX_FAILURE_SAMPLE],
                "n_failures": len(chunk_failures),
                "engine_stats": art.engine_stats,
                "cache_stats": art.cache_stats,
                "timing": chunk_timing,
            }
            if manifest is not None:
                # digest + serialized accumulator states are only needed for
                # the spill commit — the no-spill path skips the O(chunk)
                # hashing and the B-length list conversions entirely
                state["digest"] = _chunk_digest(chunk)
                state.update(chunk_states)
                manifest.record(ci, state)
            _merge_failures(failures, chunk_failures)
            _merge_engine_stats(engine_stats, art.engine_stats)
            _merge_cache_stats(cache_stats, art.cache_stats)
            for mw in session.middleware:
                mw.on_chunk_end(ci, state, session)
            start += len(chunk)
            del art, chunk  # chunk state dies here: O(chunk) memory
            # the stop check sits after the manifest commit: the chunk that
            # satisfied the rule is durable before sampling closes, so a
            # crash here resumes straight to the same certified stop
            if stopper.after_chunk(ci, accs, n_examples):
                break

        stopper.finish()
        capped = 0 < self.max_examples <= n_examples
        if completed and not stopper.stopped and not capped:
            # committed chunks beyond the end of the source: the data source
            # shrank by an exact chunk multiple — same class of error as a
            # mid-chunk mismatch, so refuse rather than silently under-count
            # (after a certified stop, leftover rows are the in-flight
            # chunks a concurrent run committed past the stop point; after
            # reaching a declared max_examples cap, they are a larger prior
            # cap's chunks — both deterministically excluded, never merged)
            raise ManifestMismatch(
                f"manifest has {len(completed)} committed chunk(s) "
                f"({sorted(completed)}) beyond the end of the data source "
                f"({n_chunks} chunks observed) — was the data source changed?"
            )

        t0 = time.monotonic()
        metrics = _finalize_metrics(names, accs, engine, stats_cfg)
        timing["stats_s"] = time.monotonic() - t0

        if cache_stats:
            h, mi = cache_stats.get("hits", 0), cache_stats.get("misses", 0)
            cache_stats["hit_rate"] = h / (h + mi) if h + mi else 0.0
        logs = {
            "streaming": {
                "n_examples": n_examples,
                "n_chunks": n_chunks,
                "n_resumed_chunks": n_resumed,
                "chunk_size": self.chunk_size,
                "max_resident_rows": max_resident,
                "spill_dir": self.spill_dir,
                "stats_backend": stats_cfg.backend if use_boot else "",
            }
        }
        if stopper.info() is not None:
            logs["adaptive"] = stopper.info()
        return EvalResult(
            task_id=task.task_id,
            metrics=metrics,
            scores={},       # per-example scores are never materialized
            responses=[],    # raw responses were discarded per chunk
            failures=failures[:MAX_FAILURE_SAMPLE],
            cache_stats=cache_stats,
            engine_stats=engine_stats,
            timing=timing,
            logs=logs,
            stream_stats=StreamingStats(
                accs=accs, engine=engine,
                chunk_size=self.chunk_size, n_examples=n_examples,
            ),
        )

    @staticmethod
    def _merge_committed(
        row: dict,
        accs: dict[str, MetricAccumulator],
        engine: BootstrapEngine | None,
        failures: list[dict],
        timing: dict[str, float],
        engine_stats: dict,
        cache_stats: dict,
    ) -> None:
        for m, acc in accs.items():
            acc.merge(MetricAccumulator.from_state(row["metrics"][m]))
        if engine is not None:
            try:
                engine.merge_state(row["boot"])
            except ValueError as e:
                # designed refusal (e.g. pallas partials spilled on a TPU
                # host resumed on CPU): surface it as the documented
                # non-reusable-spill error, with a way out
                raise ManifestMismatch(
                    f"committed bootstrap partials are not mergeable by "
                    f"this run's statistics engine ({e}) — resume on the "
                    f"platform that wrote the spill, or clear the spill "
                    f"dir to recompute"
                ) from e
        _merge_failures(failures, row.get("failures", []))
        _merge_engine_stats(engine_stats, row.get("engine_stats", {}))
        _merge_cache_stats(cache_stats, row.get("cache_stats", {}))
        for k, v in row.get("timing", {}).items():
            timing[k] = timing.get(k, 0.0) + v


@dataclasses.dataclass
class ChunkOutcome:
    """One chunk's contribution, produced by a concurrent chunk worker.

    Exactly one outcome per chunk reaches the merge loop: speculative
    duplicates are discarded at the pool level (first finisher) and at the
    manifest level (first committer); ``state`` always carries the
    canonical chunk state — the one committed to the manifest when spill
    is configured.
    """

    index: int
    start: int
    n_rows: int
    state: dict
    resumed: bool = False        # merged from a prior run's manifest row
    deduped: bool = False        # this attempt lost the commit race
    #: live accumulator objects (None when merging a committed row)
    accs: dict[str, MetricAccumulator] | None = None
    engine: BootstrapEngine | None = None


class ConcurrentStreamingExecutor:
    """Parallel streaming evaluation: whole chunks in flight on a bounded
    window, bit-identical to :class:`StreamingPipeline`.

    * **Scheduling** — chunks are pulled lazily from the source and run on
      :meth:`WorkerPool.imap_windowed`: at most ``window`` chunks are
      materialized and executing at once, so peak memory is
      window x chunk (PR 2's O(chunk) guarantee, scaled by the window).
      Chunk-level retries and speculative re-issue of straggler chunks
      come from the same pool machinery the intra-chunk shards use.
    * **Merging** — chunk states are folded in chunk-index order (the
      pool's ordered mode reorders completions; a slot frees only once a
      chunk is yielded, so in-flight + buffered chunks never exceed the
      window), which makes metric totals and Poisson-bootstrap sums
      accumulate in exactly the serial order: the final metrics/CIs are
      byte-equal to a serial run.
    * **Spill** — each chunk worker commits its own manifest row through
      DeltaLite's optimistic-concurrency loop; racing speculative attempts
      resolve first-committer-wins (:meth:`ChunkManifest.try_record`), and
      a losing attempt adopts the committed row so the merged result never
      double-counts engine calls or cache traffic.
    * **Middleware** — ``on_chunk_end`` fires from the merge loop in chunk
      order (never for resumed chunks), matching serial semantics for
      progress, cost-budget aborts and crash injection.
    """

    def __init__(
        self,
        *,
        chunk_size: int = 1024,
        window: int = 2,
        spill_dir: str = "",
        resume: bool = True,
        max_examples: int = 0,
    ):
        self.chunk_size = chunk_size
        self.window = max(1, window)
        self.spill_dir = spill_dir
        self.resume = resume
        self.max_examples = max_examples

    @classmethod
    def from_task(cls, task: EvalTask) -> "ConcurrentStreamingExecutor":
        s = task.streaming
        return cls(
            chunk_size=s.max_memory_rows,
            window=s.max_inflight_chunks,
            spill_dir=s.spill_dir,
            resume=s.resume,
            max_examples=s.max_examples,
        )

    def run(
        self, source: Iterable[dict], task: EvalTask, session: Any
    ) -> EvalResult:
        if self.max_examples > 0:
            source = itertools.islice(source, self.max_examples)
        stages = [PrepareStage(), InferStage(), ScoreStage()]
        stats_cfg = task.statistics
        names = [name for name, _ in resolve_metrics(task.metrics)]
        accs = {m: MetricAccumulator() for m in names}
        use_boot = stats_cfg.ci_method in ("percentile", "bca")
        engine = make_bootstrap_engine(
            stats_cfg.backend, stats_cfg.bootstrap_iterations,
            stats_cfg.seed, tuple(names),
        ) if use_boot else None
        manifest = (
            ChunkManifest(self.spill_dir, _run_key(task))
            if self.spill_dir
            else None
        )
        completed = (
            manifest.completed() if manifest is not None and self.resume else {}
        )
        stopper = _StopTracker(task, manifest, completed)

        inf = task.inference
        chunk_pool = WorkerPool(
            n_workers=self.window,
            max_retries=inf.max_retries,
            straggler_factor=(
                inf.straggler_factor if inf.speculative_reissue else 0.0
            ),
        )

        failures: list[dict] = []
        timing: dict[str, float] = {}
        engine_stats = {"calls": 0, "total_cost": 0.0, "coalesced": 0, "pool": {}}
        cache_stats: dict = {}
        n_examples = n_chunks = n_resumed = 0
        resident = {"rows": 0, "max": 0}

        def items() -> Iterator[tuple[int, int, list[dict]]]:
            # runs on the driver thread inside the pool's scheduling loop:
            # a chunk is materialized only when a window slot is free
            start = 0
            for ci, chunk in enumerate(iter_chunks(source, self.chunk_size)):
                resident["rows"] += len(chunk)
                resident["max"] = max(resident["max"], resident["rows"])
                yield (ci, start, chunk)
                start += len(chunk)

        def process(index: int, item: tuple, worker: int) -> ChunkOutcome:
            ci, start, chunk = item
            return self._process_chunk(
                ci, start, chunk, task, session, stages, names, engine,
                manifest, completed,
            )

        # ordered=True does double duty: chunk states fold in index order
        # (deterministic float accumulation == serial order == bit-identical
        # output) and a window slot frees only at yield, so in-flight plus
        # completed-but-unmerged chunks never exceed the window
        stream = chunk_pool.imap_windowed(
            process, items(), window=self.window, ordered=True
        )
        try:
            for res in stream:
                out: ChunkOutcome = res.value
                resident["rows"] -= out.n_rows
                self._merge_outcome(
                    out, accs, engine, failures, timing, engine_stats,
                    cache_stats,
                )
                completed.pop(out.index, None)
                n_chunks += 1
                n_examples += out.n_rows
                if out.resumed:
                    n_resumed += 1
                else:
                    for mw in session.middleware:
                        mw.on_chunk_end(out.index, out.state, session)
                # the ordered merge folds chunk i only after 0..i-1, so the
                # rule observes the exact accumulator sequence of the serial
                # pipeline and fires at the same chunk — in-flight chunks
                # past the stop drain (committing their manifest rows) but
                # are never merged
                if stopper.after_chunk(out.index, accs, n_examples):
                    break
        finally:
            # a middleware abort (cost budget, crash injection) or a merge
            # error must join the chunk workers NOW, not at GC: in-flight
            # chunks drain — completing their manifest commits, which the
            # next resume will reuse — before the exception propagates,
            # so no worker keeps spending against the session afterwards
            stream.close()

        stopper.finish()
        capped = 0 < self.max_examples <= n_examples
        if completed and not stopper.stopped and not capped:
            raise ManifestMismatch(
                f"manifest has {len(completed)} committed chunk(s) "
                f"({sorted(completed)}) beyond the end of the data source "
                f"({n_chunks} chunks observed) — was the data source changed?"
            )

        t0 = time.monotonic()
        metrics = _finalize_metrics(names, accs, engine, stats_cfg)
        timing["stats_s"] = time.monotonic() - t0

        if cache_stats:
            h, mi = cache_stats.get("hits", 0), cache_stats.get("misses", 0)
            cache_stats["hit_rate"] = h / (h + mi) if h + mi else 0.0
        logs = {
            "streaming": {
                "n_examples": n_examples,
                "n_chunks": n_chunks,
                "n_resumed_chunks": n_resumed,
                "chunk_size": self.chunk_size,
                "max_inflight_chunks": self.window,
                "max_resident_rows": resident["max"],
                "spill_dir": self.spill_dir,
                "chunk_pool": dataclasses.asdict(chunk_pool.stats),
                "stats_backend": stats_cfg.backend if use_boot else "",
            }
        }
        if stopper.info() is not None:
            logs["adaptive"] = stopper.info()
        return EvalResult(
            task_id=task.task_id,
            metrics=metrics,
            scores={},
            responses=[],
            failures=failures[:MAX_FAILURE_SAMPLE],
            cache_stats=cache_stats,
            engine_stats=engine_stats,
            timing=timing,
            logs=logs,
            stream_stats=StreamingStats(
                accs=accs, engine=engine,
                chunk_size=self.chunk_size, n_examples=n_examples,
            ),
        )

    def _process_chunk(
        self, ci: int, start: int, chunk: list[dict], task: EvalTask,
        session: Any, stages: list, names: list[str],
        run_engine: BootstrapEngine | None,
        manifest: ChunkManifest | None,
        completed: dict[int, dict],
    ) -> ChunkOutcome:
        row = completed.get(ci) if manifest is not None else None
        if row is not None:
            digest = _chunk_digest(chunk)
            if (
                row["n_rows"] != len(chunk)
                or row["start"] != start
                or row.get("digest") != digest
            ):
                raise ManifestMismatch(
                    f"chunk {ci}: manifest has start={row['start']} "
                    f"n_rows={row['n_rows']} digest={row.get('digest')}, "
                    f"observed start={start} n_rows={len(chunk)} "
                    f"digest={digest} — was the data source changed?"
                )
            return ChunkOutcome(ci, start, len(chunk), state=row, resumed=True)

        art = EvalArtifact(rows=chunk, task=task)
        chunk_timing: dict[str, float] = {}
        for stage in stages:
            with obs.timed(f"stage.{stage.name}") as sp:
                art = stage.run(art, session)
            chunk_timing[f"{stage.name}_s"] = sp.seconds

        accs: dict[str, MetricAccumulator] = {}
        chunk_engine: BootstrapEngine | None = None
        chunk_states: dict[str, dict] = {}
        for m in names:
            acc = MetricAccumulator()
            acc.update(art.scores[m])
            accs[m] = acc
            if manifest is not None:
                chunk_states.setdefault("metrics", {})[m] = acc.state()
        if run_engine is not None:
            chunk_engine = run_engine.spawn()
            chunk_engine.update(art.scores, start)
            if manifest is not None:
                chunk_states["boot"] = chunk_engine.state()
        chunk_failures = [
            {**f, "index": f["index"] + start} for f in art.failures
        ]
        state = {
            "start": start,
            "n_rows": len(chunk),
            "failures": chunk_failures[:MAX_FAILURE_SAMPLE],
            "n_failures": len(chunk_failures),
            "engine_stats": art.engine_stats,
            "cache_stats": art.cache_stats,
            "timing": chunk_timing,
        }
        if manifest is not None:
            state["digest"] = _chunk_digest(chunk)
            state.update(chunk_states)
            if not manifest.try_record(ci, state):
                # lost the commit race to a speculative twin: adopt the
                # committed row so this chunk's calls/cache traffic are
                # counted exactly once in the merged result
                committed = manifest.get(ci)
                if committed is None:  # pragma: no cover — commit is durable
                    raise RuntimeError(
                        f"chunk {ci}: lost the manifest race but no "
                        "committed row is visible"
                    )
                return ChunkOutcome(
                    ci, start, len(chunk), state=committed, deduped=True
                )
        return ChunkOutcome(
            ci, start, len(chunk), state=state, accs=accs,
            engine=chunk_engine,
        )

    @staticmethod
    def _merge_outcome(
        out: ChunkOutcome,
        accs: dict[str, MetricAccumulator],
        engine: BootstrapEngine | None,
        failures: list[dict],
        timing: dict[str, float],
        engine_stats: dict,
        cache_stats: dict,
    ) -> None:
        if out.accs is None:
            # committed manifest row (resumed chunk or commit-race loser)
            StreamingPipeline._merge_committed(
                out.state, accs, engine, failures, timing, engine_stats,
                cache_stats,
            )
            return
        for m, acc in accs.items():
            acc.merge(out.accs[m])
        if engine is not None:
            engine.merge(out.engine)
        _merge_failures(failures, out.state["failures"])
        _merge_engine_stats(engine_stats, out.state["engine_stats"])
        _merge_cache_stats(cache_stats, out.state["cache_stats"])
        for k, v in out.state["timing"].items():
            timing[k] = timing.get(k, 0.0) + v


def _finalize_metrics(
    names: list[str],
    accs: dict[str, MetricAccumulator],
    engine: BootstrapEngine | None,
    stats_cfg: StatisticsConfig,
) -> dict[str, MetricValue]:
    """Aggregate merged accumulator state into final :class:`MetricValue`s
    (shared by the serial and concurrent streaming paths — same code, same
    floats, same bytes)."""
    metrics: dict[str, MetricValue] = {}
    for m in names:
        acc = accs[m]
        if acc.n == 0:
            metrics[m] = MetricValue(
                m, float("nan"), (float("nan"),) * 2, "none", 0, acc.n_nan
            )
            continue
        iv = streaming_ci(
            acc,
            engine.view(m) if engine is not None else None,
            method=stats_cfg.ci_method,
            confidence=stats_cfg.confidence_level,
            binary=m in BINARY_METRICS,
        )
        metrics[m] = MetricValue(
            m, iv.value, (iv.lo, iv.hi), iv.method, iv.n, acc.n_nan
        )
    return metrics


def _run_key(task: EvalTask) -> str:
    """Resume key: only configuration that affects the results — model,
    data prep, metrics, statistics, and the chunk layout
    (``max_memory_rows`` keys the bootstrap offsets) — decides whether
    committed chunks are reusable.  ``StatisticsConfig.backend`` stays in
    the key on purpose: the two backends draw different weight streams, so
    partials spilled by one are not mergeable by the other.
    Execution-strategy knobs (the whole InferenceConfig: worker count,
    batching, caching, rate limits; spill location; resume flag) are
    normalized away so a restart may legitimately retune them without
    orphaning committed work.  The stopping rule is also popped: chunk
    partials are reusable across rules (the rule decides *when sampling
    closes*, not what any chunk computed), but the manifest's regime row
    pins the certification regime — resuming with a changed rule is an
    explicit :class:`ManifestMismatch`, never a silent fresh directory."""
    payload = json.loads(task.to_json())
    payload.pop("inference", None)
    payload.pop("stopping", None)
    payload["streaming"] = {"max_memory_rows": task.streaming.max_memory_rows}
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()
    ).hexdigest()[:16]


def _chunk_digest(chunk: list[dict]) -> str:
    """Content fingerprint of a chunk's rows: a resumed run must be fed the
    same data, not merely the same chunk layout."""
    payload = json.dumps(chunk, sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _merge_failures(acc: list[dict], new: list[dict]) -> None:
    room = MAX_FAILURE_SAMPLE - len(acc)
    if room > 0:
        acc.extend(new[:room])


def _merge_engine_stats(total: dict, delta: dict) -> None:
    total["calls"] += delta.get("calls") or 0
    total["total_cost"] += delta.get("total_cost", 0.0)
    total["coalesced"] = total.get("coalesced", 0) + (delta.get("coalesced") or 0)
    for k, v in delta.get("pool", {}).items():
        total["pool"][k] = total["pool"].get(k, 0) + v


def _merge_cache_stats(total: dict, delta: dict) -> None:
    for k, v in delta.items():
        if not isinstance(v, (int, float)) or k == "hit_rate":
            continue  # hit_rate is recomputed from the summed counters
        if k in ("hits", "misses", "writes"):
            total[k] = total.get(k, 0) + v
        else:
            total[k] = v  # entries/version stay absolute: latest wins
