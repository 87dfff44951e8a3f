"""Benchmark-side instrumentation: wrappers put around the calls into each
layer of the served program, on the objects one session builds, so that a
run can stamp requests and read what the timed path produced without any
change to the program.

* service: ``InferenceService.submit`` stamps an example's submission;
* engine: ``stream_submit``/``stream_pump`` map engine ids to prompts and
  stamp the moment a response returns;
* scheduler: ``submit`` keeps the prompt token ids, ``drain_completions``
  the served token ids; with tracing on, ``step`` records each decode step's
  live context lengths and ``_prefill_chunks`` each prefill's rows;
* stages: ``ScoreStage.run`` keeps every chunk's rows, texts and scores;
* cache: ``ResponseCache.lookup`` spans (tracing only).

With tracing on, each wrapper also opens a ``jax.profiler.TraceAnnotation``
so the trace's idle gaps can be named after what the host was doing.  The
scheduler's hooks are private names of the program: a traced run of a cell
that serves a model stops if one of them is missing, rather than reading
its per-layer metrics off half the work.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any


#: what a traced run reads off the scheduler of a cell that serves a model
SCHEDULER_HOOKS = ("step", "_prefill_chunks", "slot_pos", "slot_free", "n_slots")


class Probes:
    def __init__(self, trace: bool = False):
        self.trace = trace
        self.lock = threading.Lock()
        self.submit_t: dict[str, float] = {}
        self.done_t: dict[str, float] = {}
        self._rid_prompt: dict[tuple[int, int], str] = {}
        self._rid_tokens: dict[tuple[int, int], list[int]] = {}
        self._served_by_rid: dict[tuple[int, int], tuple] = {}
        #: prompt -> (prompt token ids, served token ids, finished reason)
        self.served: dict[str, tuple[list[int], list[int], str]] = {}
        #: prompt -> index of the replica that served it
        self.replica: dict[str, int] = {}
        #: decode steps: (t0, t1, context lengths of the stepped slots)
        self.steps: list[tuple[float, float, list[int]]] = []
        #: prefills: (t0, t1, prompt tokens, first computed position)
        self.prefills: list[tuple[float, float, int, int]] = []
        #: ScoreStage chunks: (t0, t1, rows, texts, scores)
        self.chunks: list[tuple[float, float, list, list, dict]] = []
        self.lookups: list[tuple[float, float]] = []
        self._restore: list[tuple[Any, str, Any]] = []

    def _span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _patch(self, obj: Any, attr: str, make) -> None:
        orig = getattr(obj, attr)
        self._restore.append((obj, attr, obj.__dict__.get(attr, _ABSENT)
                              if hasattr(obj, "__dict__") else _ABSENT))
        setattr(obj, attr, make(orig))

    # -- attach ------------------------------------------------------------------

    def attach_service(self, service: Any, serving: bool = True) -> None:
        def submit(orig):
            def wrapped(request, **kw):
                self.submit_t[request.prompt] = time.monotonic()
                with self._span("bench.submit"):
                    return orig(request, **kw)
            return wrapped

        self._patch(service, "submit", submit)
        for rep in service.replicas:
            self.attach_engine(rep.engine, serving, rep.index)

    def attach_engine(self, engine: Any, serving: bool = True,
                      replica: int = 0) -> None:
        eid = id(engine)

        def stream_submit(orig):
            def wrapped(request):
                rid = orig(request)
                self._rid_prompt[(eid, rid)] = request.prompt
                return rid
            return wrapped

        def stream_pump(orig):
            def wrapped():
                with self._span("bench.pump"):
                    done = orig()
                t = time.monotonic()
                for rid, _resp in done:
                    prompt = self._rid_prompt.pop((eid, rid), None)
                    if prompt is not None:
                        self.done_t[prompt] = t
                        self.replica[prompt] = replica
                        got = self._served_by_rid.pop((eid, rid), None)
                        if got is not None:
                            self.served[prompt] = got
                return done
            return wrapped

        self._patch(engine, "stream_submit", stream_submit)
        self._patch(engine, "stream_pump", stream_pump)
        sched = getattr(engine, "_scheduler", None)
        if sched is not None:
            self.attach_scheduler(sched, eid, serving)
        elif serving and self.trace:
            raise RuntimeError(
                f"probes: {type(engine).__name__} has no _scheduler to trace"
            )

    def attach_scheduler(self, sched: Any, eid: int, serving: bool = True) -> None:
        if serving and self.trace:
            missing = [a for a in SCHEDULER_HOOKS if not hasattr(sched, a)]
            if missing:
                raise RuntimeError(
                    f"probes: {type(sched).__name__} lacks {missing}; the "
                    "per-layer metrics of a traced run would miss their work"
                )

        def submit(orig):
            def wrapped(req):
                self._rid_tokens[(eid, req.request_id)] = list(req.prompt_tokens)
                return orig(req)
            return wrapped

        def drain(orig):
            def wrapped():
                comps = orig()
                for c in comps:
                    toks = self._rid_tokens.pop((eid, c.request_id), [])
                    self._served_by_rid[(eid, c.request_id)] = (
                        toks, list(c.tokens), c.finished_reason,
                    )
                return comps
            return wrapped

        def step(orig):
            def wrapped():
                t0 = time.monotonic()
                with self._span("bench.decode_step"):
                    n = orig()
                if n:
                    ctx = [
                        int(sched.slot_pos[s])
                        for s in range(sched.n_slots) if not sched.slot_free[s]
                    ]
                    self.steps.append((t0, time.monotonic(), ctx))
                return n
            return wrapped

        def prefill(orig):
            def wrapped(row, ptoks, start):
                t0 = time.monotonic()
                with self._span("bench.prefill"):
                    out = orig(row, ptoks, start)
                self.prefills.append((t0, time.monotonic(), len(ptoks), int(start)))
                return out
            return wrapped

        self._patch(sched, "submit", submit)
        self._patch(sched, "drain_completions", drain)
        if self.trace and hasattr(sched, "step"):
            self._patch(sched, "step", step)
        if self.trace and hasattr(sched, "_prefill_chunks"):
            self._patch(sched, "_prefill_chunks", prefill)

    def attach_score_stage(self, stage_cls: Any) -> None:
        def run(orig):
            def wrapped(stage_self, art, session):
                t0 = time.monotonic()
                with self._span("bench.score"):
                    out = orig(stage_self, art, session)
                self.chunks.append(
                    (t0, time.monotonic(), list(art.rows), list(art.texts),
                     {k: v.copy() for k, v in art.scores.items()})
                )
                return out
            return wrapped

        self._patch(stage_cls, "run", run)

    def attach_cache(self, cache: Any) -> None:
        if not self.trace:
            return

        def lookup(orig):
            def wrapped(key):
                t0 = time.monotonic()
                with self._span("bench.cache_lookup"):
                    out = orig(key)
                self.lookups.append((t0, time.monotonic()))
                return out
            return wrapped

        self._patch(cache, "lookup", lookup)

    def detach(self) -> None:
        """Put every wrapped attribute back as it was."""
        while self._restore:
            obj, attr, prev = self._restore.pop()
            if prev is _ABSENT:
                with contextlib.suppress(AttributeError):
                    delattr(obj, attr)
            else:
                setattr(obj, attr, prev)


class _Absent:
    pass


_ABSENT = _Absent()
