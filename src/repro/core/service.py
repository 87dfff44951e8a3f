"""Shared asynchronous inference service: cross-task continuous batching
with single-flight request coalescing, fanned out across N data-parallel
engine replicas.

Before this module, inference was lock-step per shard: every pipeline
stage blocked on its own ``engine.infer`` calls, the local JAX engine
serialized concurrent callers behind one lock (decode slots idling
whenever a caller's batch had fewer requests than slots), and two
concurrent chunk workers that missed the cache on the same prompt both
paid for the call — the duplicate-spend the content-addressable cache
exists to prevent, leaking back in through concurrency.  The service
inverts control: tasks, chunks, models and suites **submit**
:class:`~repro.core.engines.InferenceRequest` objects and get tickets
(futures) back; dispatch happens centrally —

* **single-flight coalescing** — identical in-flight cache keys share ONE
  engine call.  The first submitter is the *primary* (its shard is
  charged the call, the cost, the tokens, and the cache write); later
  submitters become waiters on the same flight and are counted as
  ``coalesced``.  The cache prevents duplicate spend across time;
  single-flight closes the concurrency window the cache cannot see.
  The flight table is **global across replicas**: a duplicate coalesces
  onto the original flight no matter which replica serves it.
* **central admission** — the per-task rate limiter is acquired by the
  dispatcher immediately before the engine call, not by worker threads
  sleeping inside the pipeline, so budget flows to whatever is runnable.
* **continuous batching** — engines exposing the slot-streaming interface
  (``supports_streaming``: the local JAX engine, the simulated slot
  engine) are driven by ONE persistent batcher loop *per replica*:
  queued prompts are admitted into decode slots as slots free, so
  batches form across shards, chunks, tasks and suites instead of
  inside one shard.  API-style engines get a dispatcher-thread pool per
  replica instead, sized by the pipeline stages currently attached.
* **replica routing** — with ``n_replicas > 1`` one submit queue fans
  out to N engine replicas through a :class:`ReplicaRouter`.  Policies:
  ``least_loaded`` (fewest outstanding requests — busy decode slots plus
  backlog), ``prefix_affinity`` (prompt-prefix hash, so shared few-shot
  headers land on the same batcher and its warmed prefixes), and
  ``round_robin``.  Routing is *stats-plane-invisible*: responses are a
  pure function of the request, so placement never changes a byte of
  evaluation output (see the determinism contract below).

Determinism contract: responses are a pure function of the request key
(prompt, model, provider, temperature, max_tokens) — simulated engines by
construction, the local engine because greedy decode at temperature 0 is
batch-composition independent.  Coalescing and routing therefore never
change a response byte; they only change how many engine calls paid for
it and which replica served it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import queue
import threading
import time
from typing import Any, Callable, Sequence

from repro import obs
from repro.core.engines import (
    InferenceEngine,
    InferenceRequest,
    InferenceResponse,
    RecoverableEngineError,
    is_recoverable,
    retry_with_backoff,
)
from repro.core.ratelimit import AdaptiveLimiter

_SENTINEL = object()


class ReplicaHungError(RuntimeError):
    """Raised by the health probe inside a batcher loop: the replica has
    in-flight work but its engine made no progress (no decode steps, no
    completions) for ``health_probe_steps`` consecutive pumps.  Takes the
    same drain-and-restart path as a crash."""


class _Flight:
    """One engine call and its waiters (single-flight unit)."""

    __slots__ = ("key", "event", "response", "exc", "attempts", "resolved")

    def __init__(self, key: str):
        self.key = key
        self.event = threading.Event()
        self.response: InferenceResponse | None = None
        self.exc: BaseException | None = None
        self.attempts = 0
        #: flipped under the service lock by the FIRST resolution — a
        #: hedged flight can race two completions; the loser only touches
        #: replica bookkeeping (``event`` alone would race: it is set
        #: outside the lock)
        self.resolved = False


class ServiceTicket:
    """Future for one submitted request.  ``primary`` is True for the
    submission that owns the engine call (and therefore the spend); a
    coalesced follower shares the response but owns nothing."""

    __slots__ = ("_flight", "primary")

    def __init__(self, flight: _Flight, primary: bool):
        self._flight = flight
        self.primary = primary

    def done(self) -> bool:
        return self._flight.event.is_set()

    @property
    def attempts(self) -> int:
        """Engine-call attempts the flight took (retries included)."""
        return self._flight.attempts

    def result(self, timeout: float | None = None) -> InferenceResponse:
        if not self._flight.event.wait(timeout):
            raise TimeoutError(
                f"inference ticket not resolved within {timeout}s"
            )
        if self._flight.exc is not None:
            raise self._flight.exc
        assert self._flight.response is not None
        return self._flight.response


@dataclasses.dataclass
class _Submission:
    flight: _Flight
    request: InferenceRequest
    limiter: Any
    est_tokens: float
    max_retries: int
    retry_delay: float
    replica: "_Replica | None" = None
    #: absolute monotonic deadline (None = no deadline); set at submit
    #: time so queue wait counts against it
    deadline_at: float | None = None
    #: this submission IS the hedge leg of an expired flight
    is_hedge: bool = False
    #: a hedge has already been issued for this submission's flight
    hedged: bool = False
    #: deadline expiry already counted (once per primary submission)
    expired: bool = False


class _BatcherState:
    """In-flight bookkeeping for ONE incarnation of a batcher loop.  On a
    crash the supervisor collects :meth:`survivors` and hands them to the
    next incarnation (restart) or fails them (retirement) — submissions a
    replica dies holding are never silently lost."""

    __slots__ = ("pending", "retry_at", "carry", "stall", "last_steps")

    def __init__(self) -> None:
        #: engine stream id -> submission, currently in decode
        self.pending: dict[int, _Submission] = {}
        #: (monotonic due time, submission) backoff-scheduled retries
        self.retry_at: list[tuple[float, _Submission]] = []
        #: submissions owned by the loop but in neither structure above
        #: (crashed mid-dispatch, or carried in from a prior incarnation)
        self.carry: list[_Submission] = []
        #: consecutive pumps without engine progress (health probe)
        self.stall = 0
        self.last_steps = -1

    def survivors(self) -> list[_Submission]:
        subs = (
            list(self.pending.values())
            + [s for _, s in self.retry_at]
            + list(self.carry)
        )
        self.pending.clear()
        self.retry_at.clear()
        self.carry.clear()
        return subs


@dataclasses.dataclass
class ServiceStats:
    submitted: int = 0
    coalesced: int = 0
    dispatched: int = 0   # engine-call attempts actually issued
    completed: int = 0
    retries: int = 0
    errors: int = 0
    #: broken replicas brought back by the bounded-backoff restart path
    restarts: int = 0
    #: primary submissions that outlived their deadline
    deadline_expiries: int = 0
    #: hedge legs actually re-issued to another alive replica
    hedges_issued: int = 0
    #: flights won by the hedge leg (the original was slower/stuck)
    hedges_won: int = 0

    @property
    def dedup_rate(self) -> float:
        return self.coalesced / self.submitted if self.submitted else 0.0

    def as_dict(self) -> dict:
        return {
            "submitted": self.submitted,
            "dispatched": self.dispatched,
            "coalesced": self.coalesced,
            "completed": self.completed,
            "retries": self.retries,
            "errors": self.errors,
            "dedup_rate": round(self.dedup_rate, 4),
            "restarts": self.restarts,
            "deadline_expiries": self.deadline_expiries,
            "hedges_issued": self.hedges_issued,
            "hedges_won": self.hedges_won,
        }


# -- replicas -------------------------------------------------------------------


class _Replica:
    """One engine replica behind the shared submit front: its own FIFO
    queue, its own dispatcher threads (one batcher loop for slot engines,
    a thread pool for API engines), and its own ServiceStats slice.
    Counter fields are guarded by the owning service's lock."""

    __slots__ = (
        "index", "engine", "queue", "wake", "threads",
        "routed", "outstanding", "dispatched", "completed", "errors",
        "broken", "first_failure", "last_progress", "restarts", "cancelled",
    )

    def __init__(self, index: int, engine: InferenceEngine, depth: int):
        self.index = index
        self.engine = engine
        self.queue: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self.wake = threading.Event()
        self.threads: list[threading.Thread] = []
        self.routed = 0        # submissions ever routed here
        self.outstanding = 0   # routed but not yet resolved
        self.dispatched = 0
        self.completed = 0
        self.errors = 0
        self.broken: BaseException | None = None
        #: cause of the replica's FIRST failure, kept across restarts for
        #: the fleet-dead post-mortem message
        self.first_failure: BaseException | None = None
        #: engine step count at the last observed progress (-1 = never)
        self.last_progress = -1
        self.restarts = 0
        #: hedge-loser legs cancelled on this replica
        self.cancelled = 0

    def busy_slots(self) -> int:
        sched = getattr(self.engine, "slots_busy", None)
        if callable(sched):
            return sched()
        return 0

    def stats_dict(self) -> dict:
        d = {
            "index": self.index,
            "routed": self.routed,
            "outstanding": self.outstanding,
            "dispatched": self.dispatched,
            "completed": self.completed,
            "errors": self.errors,
            "broken": self.broken is not None,
            "restarts": self.restarts,
            "cancelled": self.cancelled,
            "last_progress": self.last_progress,
        }
        batcher = self.engine.serving_stats()
        if batcher:
            d["batcher"] = batcher
        return d


@dataclasses.dataclass(frozen=True)
class ReplicaView:
    """Router-visible load snapshot of one (alive) replica."""

    index: int
    queued: int        # submissions waiting in the replica's service queue
    outstanding: int   # routed but unresolved (includes in-engine backlog)
    busy_slots: int = 0

    @property
    def load(self) -> int:
        return self.queued + self.outstanding


class ReplicaRouter:
    """Pluggable replica-placement policy.

    ``route`` picks among the *alive* replicas only; ties break on the
    lowest index so placement is deterministic given fixed stats.

    * ``least_loaded`` — fewest outstanding requests (busy decode slots
      plus queued backlog; the service counts routed-but-unresolved, which
      covers both).
    * ``prefix_affinity`` — stable hash of the first ``prefix_len``
      characters of the prompt: requests sharing a few-shot header or
      system prompt land on the same batcher (and, downstream, the same
      warmed prefix cache), independent of load.
    * ``round_robin`` — strict rotation.
    """

    POLICIES = ("least_loaded", "prefix_affinity", "round_robin")

    def __init__(self, policy: str = "least_loaded", prefix_len: int = 64):
        if policy not in self.POLICIES:
            raise ValueError(
                f"unknown routing policy {policy!r}; one of {self.POLICIES}"
            )
        self.policy = policy
        self.prefix_len = prefix_len
        self._rr = 0  # guarded by the owning service's lock

    def route(self, prompt: str, views: Sequence[ReplicaView]) -> int:
        """Replica index for ``prompt`` among the given (alive) views."""
        if not views:
            raise RuntimeError("no alive replicas to route to")
        if len(views) == 1:
            return views[0].index
        if self.policy == "least_loaded":
            return min(views, key=lambda v: (v.load, v.index)).index
        if self.policy == "prefix_affinity":
            h = hashlib.sha256(
                prompt[: self.prefix_len].encode("utf-8", "replace")
            ).digest()
            return views[int.from_bytes(h[:8], "big") % len(views)].index
        pick = views[self._rr % len(views)].index
        self._rr += 1
        return pick


def aggregate_batcher_stats(parts: Sequence[dict]) -> dict:
    """Fleet-level BatcherStats: counters sum across replicas; occupancy
    is re-derived as total active slot-steps over total slot-step
    capacity, and tokens/step is per (replica, step)."""
    parts = [p for p in parts if p]
    if not parts:
        return {}
    agg = {
        k: sum(p.get(k, 0) for p in parts)
        for k in (
            "n_slots", "steps", "admissions", "completions",
            "tokens_generated", "active_slot_steps", "prefill_recompiles",
            "prefills_deferred", "prefix_pages_hit", "prefix_tokens_saved",
            "cow_copies", "preemptions", "preempted_tokens", "pool_pages",
            "queue_wait_s", "first_token_s",
        )
    }
    # a rate, not a counter: replicas of one config share it, so take max
    # (0 only when no replica runs a paged cache)
    agg["kv_bytes_per_token"] = max(
        (p.get("kv_bytes_per_token", 0) for p in parts), default=0
    )
    cap = sum(p.get("steps", 0) * p.get("n_slots", 0) for p in parts)
    agg["slot_occupancy"] = round(
        agg["active_slot_steps"] / cap if cap else 0.0, 4
    )
    agg["tokens_per_step"] = round(
        agg["tokens_generated"] / agg["steps"] if agg["steps"] else 0.0, 3
    )
    return agg


class InferenceService:
    """Session-owned asynchronous dispatch front for one engine (or N
    data-parallel replicas of it).

    ``submit`` never blocks on inference (only on queue backpressure at
    ``queue_depth`` outstanding requests per replica);
    ``ServiceTicket.result`` gathers.  Construction is cheap — dispatcher
    threads start lazily on first use and are joined by :meth:`close`.
    """

    #: absolute ceiling on dispatcher threads per replica (the rate
    #: limiter, not the thread count, is the real admission control)
    HARD_MAX_DISPATCHERS = 128

    def __init__(
        self,
        engine: InferenceEngine | None = None,
        *,
        engines: Sequence[InferenceEngine] | None = None,
        routing: "str | ReplicaRouter" = "least_loaded",
        queue_depth: int = 256,
        coalesce: bool = True,
        max_batch_wait_ms: float = 2.0,
        n_dispatchers: int = 4,
        sleep: Callable[[float], None] = time.sleep,
        name: str = "",
        max_replica_restarts: int = 0,
        restart_backoff_s: float = 0.05,
        health_probe_steps: int = 0,
    ):
        fleet = list(engines) if engines else []
        if engine is not None and not fleet:
            fleet = [engine]
        if not fleet:
            raise ValueError("InferenceService needs at least one engine")
        streaming = {bool(getattr(e, "supports_streaming", False)) for e in fleet}
        if len(streaming) != 1:
            raise ValueError(
                "replica fleet mixes streaming and non-streaming engines"
            )
        #: replica-0 engine, kept as ``self.engine`` for single-replica
        #: callers and introspection compatibility
        self.engine = fleet[0]
        self.coalesce = coalesce
        self.max_batch_wait_ms = max_batch_wait_ms
        self.name = name
        #: bounded-backoff restarts per broken replica (0 = legacy: the
        #: first crash quarantines the replica for good)
        self.max_replica_restarts = max(0, max_replica_restarts)
        self.restart_backoff_s = restart_backoff_s
        #: pumps without engine progress before a loaded replica is
        #: declared hung and drain-and-restarted (0 = probe disabled)
        self.health_probe_steps = max(0, health_probe_steps)
        self.stats = ServiceStats()
        self.router = (
            routing if isinstance(routing, ReplicaRouter)
            else ReplicaRouter(routing)
        )
        self._sleep = sleep
        self.replicas = [
            _Replica(i, e, queue_depth) for i, e in enumerate(fleet)
        ]
        self._inflight: dict[str, _Flight] = {}
        self._lock = threading.Lock()
        self._base_dispatchers = max(1, n_dispatchers)
        self._attached = 0
        self._closed = False
        self._broken: BaseException | None = None
        self._streaming = streaming.pop()
        self._uniq = itertools.count()

    @property
    def n_replicas(self) -> int:
        return len(self.replicas)

    # -- capacity ---------------------------------------------------------------

    def attach(self, n_workers: int = 1) -> None:
        """A pipeline stage is about to submit: size the dispatch pool for
        its configured parallelism.  Batcher-mode engines need no threads
        beyond one loop per replica — decode slots are the parallelism."""
        with self._lock:
            self._check_open()
            self._attached += max(1, n_workers)
            self._ensure_dispatchers()

    def detach(self, n_workers: int = 1) -> None:
        with self._lock:
            self._attached = max(0, self._attached - max(1, n_workers))
            # threads never shrink: idle dispatchers just block on the queue

    def _threads_per_replica(self) -> int:
        if self._streaming:
            return 1
        return min(
            self.HARD_MAX_DISPATCHERS,
            max(self._base_dispatchers, self._attached),
        )

    def _ensure_dispatchers(self) -> None:  # caller holds self._lock
        target = self._threads_per_replica()
        for rep in self.replicas:
            while len(rep.threads) < target:
                idx = len(rep.threads)
                t = threading.Thread(
                    target=self._batcher_loop if self._streaming
                    else self._dispatch_loop,
                    args=(rep,) if self._streaming else (rep, idx),
                    name=(
                        f"infer-service-{self.name or 'engine'}"
                        f"-r{rep.index}-{idx}"
                    ),
                    daemon=True,
                )
                rep.threads.append(t)
                t.start()

    # -- submission --------------------------------------------------------------

    def _alive_views(self) -> list[ReplicaView]:  # caller holds self._lock
        return [
            ReplicaView(
                index=r.index,
                queued=r.queue.qsize(),
                outstanding=r.outstanding,
                busy_slots=r.busy_slots(),
            )
            for r in self.replicas
            if r.broken is None
        ]

    def submit(
        self,
        request: InferenceRequest,
        *,
        key: str | None = None,
        coalesce: bool | None = None,
        limiter: Any = None,
        est_tokens: float = 0.0,
        max_retries: int = 0,
        retry_delay: float = 1.0,
        deadline_s: float = 0.0,
    ) -> ServiceTicket:
        """Enqueue a request; returns a :class:`ServiceTicket` immediately.

        ``key`` is the content-addressable identity of the request (the
        response-cache key); identical in-flight keys coalesce into one
        engine call unless coalescing is off — the flight table is checked
        *before* routing, so the dedup is global across replicas.
        ``limiter`` (an :class:`~repro.core.ratelimit.AdaptiveLimiter` or
        a list of :class:`~repro.core.ratelimit.TokenBucket`) is acquired
        by the dispatcher right before the engine call."""
        do_coalesce = self.coalesce if coalesce is None else coalesce
        if key is None:
            do_coalesce = False
            key = f"~uniq-{next(self._uniq)}"
        with self._lock:
            self._check_open()
            self.stats.submitted += 1
            if do_coalesce:
                flight = self._inflight.get(key)
                if flight is not None:
                    self.stats.coalesced += 1
                    return ServiceTicket(flight, primary=False)
            views = self._alive_views()
            if not views:
                self.stats.submitted -= 1
                raise RuntimeError(
                    f"InferenceService {self.name!r}: all "
                    f"{self.n_replicas} replicas failed — "
                    + self._fleet_report()
                )
            flight = _Flight(key)
            if do_coalesce:
                self._inflight[key] = flight
            rep = self.replicas[self.router.route(request.prompt, views)]
            rep.routed += 1
            rep.outstanding += 1
            self._ensure_dispatchers()
        # outside the lock: a full replica queue blocks the submitter
        # (backpressure), never the dispatchers
        rep.queue.put(
            _Submission(
                flight, request, limiter, est_tokens, max_retries,
                retry_delay, replica=rep,
                deadline_at=(
                    time.monotonic() + deadline_s if deadline_s > 0 else None
                ),
            )
        )
        rep.wake.set()
        with self._lock:
            dead_now = (
                self._closed or self._broken is not None
                or rep.broken is not None
            )
        if dead_now:
            # close() (or a dispatcher crash) may have drained the queue
            # between our open-check and the put: nobody will read this
            # submission, so fail it — and any fellow stragglers — rather
            # than strand the waiters.  During normal operation this
            # branch is unreachable.
            self._drain_replica(
                rep, exc=rep.broken or RuntimeError("InferenceService closed")
            )
        return ServiceTicket(flight, primary=True)

    def note_coalesced(self, n: int = 1) -> None:
        """Record submissions deduplicated *before* reaching the service
        (e.g. a stage reusing its own ticket for a repeated key), so
        service-level dedup counters reflect total demand."""
        with self._lock:
            self.stats.submitted += n
            self.stats.coalesced += n

    # -- dispatch ---------------------------------------------------------------

    def _admit(self, sub: _Submission, widx: int) -> None:
        lim = sub.limiter
        if lim is None:
            return
        if isinstance(lim, AdaptiveLimiter):
            lim.acquire(widx % lim.n, sub.est_tokens)
        elif isinstance(lim, (list, tuple)):
            lim[widx % len(lim)].acquire(sub.est_tokens)
        else:
            lim.acquire(sub.est_tokens)

    def _resolve(
        self,
        sub_or_flight: "_Submission | _Flight",
        response: InferenceResponse | None = None,
        exc: BaseException | None = None,
    ) -> None:
        if isinstance(sub_or_flight, _Submission):
            flight = sub_or_flight.flight
            rep = sub_or_flight.replica
            is_hedge = sub_or_flight.is_hedge
        else:
            flight, rep, is_hedge = sub_or_flight, None, False
        with self._lock:
            if flight.resolved:
                # hedge-race loser (or a drain hitting an already-resolved
                # flight): the first resolution owns the response and the
                # completion/error counters; only replica-load bookkeeping
                # moves here
                if rep is not None:
                    rep.outstanding = max(0, rep.outstanding - 1)
                return
            flight.resolved = True
            self._inflight.pop(flight.key, None)
            self.stats.completed += 1
            self.stats.retries += max(0, flight.attempts - 1)
            if is_hedge:
                self.stats.hedges_won += 1
            failed = exc is not None or (
                response is not None and response.error is not None
            )
            if failed:
                self.stats.errors += 1
            if rep is not None:
                rep.outstanding = max(0, rep.outstanding - 1)
                rep.completed += 1
                if failed:
                    rep.errors += 1
        flight.response = response
        flight.exc = exc
        flight.event.set()

    def _count_dispatch(self, rep: _Replica) -> None:
        with self._lock:
            self.stats.dispatched += 1
            rep.dispatched += 1

    def _dispatch_loop(self, rep: _Replica, widx: int) -> None:
        """Thread-pool dispatch for API-style engines: one request per
        engine call against this thread's replica, retries via
        :func:`retry_with_backoff`.

        After each call the loop opportunistically drains further queued
        submissions without re-blocking — one condition-variable wakeup
        can serve a whole burst, which matters for fast engines where the
        wakeup itself dominates.  Exactly one stop sentinel is consumed
        per dispatcher (the loop returns the moment it sees one), so
        every dispatcher thread still shuts down."""
        while True:
            item = rep.queue.get()
            while True:
                if item is _SENTINEL:
                    return
                sub: _Submission = item
                try:
                    self._admit(sub, widx)

                    def _call(sub=sub) -> InferenceResponse:
                        sub.flight.attempts += 1
                        self._count_dispatch(rep)
                        return rep.engine.infer(sub.request)

                    resp = retry_with_backoff(
                        _call,
                        max_retries=sub.max_retries,
                        base_delay=sub.retry_delay,
                        sleep=self._sleep,
                    )
                    self._resolve(sub, resp)
                except BaseException as e:  # noqa: BLE001 — waiters must wake
                    self._resolve(sub, exc=e)
                try:
                    item = rep.queue.get_nowait()
                except queue.Empty:
                    break

    def _batcher_loop(self, rep: _Replica) -> None:
        """Replica supervisor: run the batcher, and on a crash (or a
        health-probe hang verdict) either restart the replica with bounded
        backoff — carrying its in-flight submissions into the fresh
        incarnation — or, budget exhausted, fail them and quarantine the
        replica (DESIGN.md §9)."""
        used = 0
        carry: list[_Submission] = []
        while True:
            state = _BatcherState()
            # survivors of the previous incarnation re-dispatch first
            # (directly, not via the bounded queue — the only consumer of
            # that queue is this very thread)
            state.carry = carry
            try:
                self._batcher_run(rep, state)
                return  # clean shutdown via stop sentinel
            except BaseException as e:  # noqa: BLE001
                carry = state.survivors()
                used = self._handle_replica_failure(rep, e, carry, used)
                if used < 0:
                    return

    def _handle_replica_failure(
        self,
        rep: _Replica,
        exc: BaseException,
        carry: list[_Submission],
        used: int,
    ) -> int:
        """Recover or retire a crashed/hung replica.  Returns the restart
        budget consumed so far, or -1 once the replica is dead (its
        survivors failed, the fleet-dead flag set if it was the last).
        A failed ``engine.reset()`` burns a restart and retries."""
        while True:
            with self._lock:
                rep.broken = exc
                if rep.first_failure is None:
                    rep.first_failure = exc
                closed = self._closed
            if closed or used >= self.max_replica_restarts:
                with self._lock:
                    if all(r.broken is not None for r in self.replicas):
                        self._broken = exc
                for sub in carry:
                    self._resolve(sub, exc=exc)
                self._drain_replica(rep, exc=exc)
                return -1
            self._sleep(self.restart_backoff_s * (2.0 ** used))
            used += 1
            try:
                rep.engine.reset()
            except BaseException as e2:  # noqa: BLE001
                exc = e2
                continue
            with self._lock:
                rep.broken = None
                rep.restarts += 1
                self.stats.restarts += 1
            # the caller's fresh incarnation re-dispatches `carry` itself:
            # same request ids are fine — the engine issues new stream
            # ids, and responses are a pure function of the request, so
            # the re-served output is byte-identical to the lost one
            rep.wake.set()
            return used

    def _issue_hedge(self, sub: _Submission, origin: _Replica) -> bool:
        """Re-issue an expired submission's flight to another alive
        replica.  Single-flight semantics survive: both legs share one
        flight, the first resolution wins (see ``_Flight.resolved``), the
        loser is cancelled cooperatively by its owning loop.  Returns True
        once the hedge leg is enqueued."""
        with self._lock:
            if self._closed or sub.flight.resolved:
                return True  # nothing left to hedge
            views = [
                v for v in self._alive_views() if v.index != origin.index
            ]
            if not views:
                return False  # retry on a later pump
            rep2 = self.replicas[
                self.router.route(sub.request.prompt, views)
            ]
            hedge = _Submission(
                sub.flight, sub.request, sub.limiter, sub.est_tokens,
                sub.max_retries, sub.retry_delay, replica=rep2,
                is_hedge=True,
            )
            try:
                # never block a batcher thread on backpressure; a full
                # queue just defers the hedge to the next pump
                rep2.queue.put_nowait(hedge)
            except queue.Full:
                return False
            rep2.routed += 1
            rep2.outstanding += 1
            self.stats.hedges_issued += 1
        rep2.wake.set()
        return True

    def _batcher_run(self, rep: _Replica, state: "_BatcherState") -> None:
        """Persistent continuous-batching loop for one slot-streaming
        replica: admit queued prompts into decode slots as slots free,
        step, deliver completions — one loop per replica, shared by every
        task the session runs.

        Recoverable errors re-admit with exponential backoff through a
        scheduled-retry list (the loop itself must never sleep — other
        slots are decoding); with a no-op injected sleep (virtual-clock
        sessions) retries are immediate, matching the lock-step path's
        behaviour under the same injection.  The rate-limiter index
        round-robins across admissions so list-mode buckets grant their
        full aggregate budget.

        Error taxonomy (DESIGN.md §9): ``RecoverableEngineError`` retries
        with backoff; ``ValueError``/``TypeError`` fail the one ticket with
        its original traceback and the replica lives on; anything else is
        a replica crash — in-flight submissions survive in ``state`` for
        the supervisor's restart path.  Each iteration also enforces
        request deadlines (expiry → hedge to another replica), cancels
        hedge-loser legs, and runs the no-progress health probe."""
        engine = rep.engine
        pending = state.pending
        retry_at = state.retry_at
        wait_s = max(0.0, self.max_batch_wait_ms) / 1000.0
        real_sleep = self._sleep is time.sleep
        stop = False
        admit_rr = 0

        def _engine_steps() -> int:
            try:
                return int(engine.serving_stats().get("steps", 0) or 0)
            except Exception:  # noqa: BLE001 — probe must not kill the loop
                return 0

        def _dispatch(sub: _Submission) -> None:
            nonlocal admit_rr
            try:
                self._admit(sub, admit_rr)
                admit_rr += 1
                sub.flight.attempts += 1
                self._count_dispatch(rep)
                with obs.span("service.dispatch") as sp:
                    rid = engine.stream_submit(sub.request)
                    sp.set(rid=rid)
                pending[rid] = sub
            except RecoverableEngineError as e:
                # transient refusal: burn a backoff slot, not the replica
                if sub.flight.attempts <= sub.max_retries:
                    delay = (
                        sub.retry_delay * 2.0 ** (sub.flight.attempts - 1)
                        if real_sleep else 0.0
                    )
                    retry_at.append((time.monotonic() + delay, sub))
                else:
                    self._resolve(sub, exc=e)
            except (ValueError, TypeError) as e:
                # programming error: fail THIS ticket with the original
                # traceback; the replica stays healthy
                self._resolve(sub, exc=e)
            except BaseException:
                # replica crash: the in-hand submission is in neither
                # `pending` nor the queue — carry it into the restart path
                state.carry.append(sub)
                raise

        # survivors carried over from a crashed incarnation re-dispatch
        # first; a repeat crash lands them back in state.carry/pending
        while state.carry:
            _dispatch(state.carry.pop(0))

        while True:
            was_idle = not pending
            admitted = 0
            if retry_at:
                # pop one at a time: if a dispatch raises, the entries
                # not yet reached are still in retry_at and the supervisor
                # carries them across the restart
                now = time.monotonic()
                i = 0
                while i < len(retry_at):
                    if retry_at[i][0] <= now:
                        _, sub_r = retry_at.pop(i)
                        _dispatch(sub_r)
                        admitted += 1
                    else:
                        i += 1
            while True:
                try:
                    item = rep.queue.get_nowait()
                except queue.Empty:
                    break
                if item is _SENTINEL:
                    stop = True
                    break
                _dispatch(item)
                admitted += 1
            if pending:
                now = time.monotonic()
                for rid in list(pending):
                    sub_p = pending[rid]
                    if sub_p.flight.resolved:
                        # another replica won this flight (hedge or drain):
                        # cancel the local leg, free its slot and pages
                        pending.pop(rid)
                        try:
                            engine.stream_cancel(rid)
                        except Exception:  # noqa: BLE001
                            pass
                        with self._lock:
                            rep.outstanding = max(0, rep.outstanding - 1)
                            rep.cancelled += 1
                        continue
                    if (
                        sub_p.deadline_at is not None
                        and now >= sub_p.deadline_at
                    ):
                        if not sub_p.expired:
                            sub_p.expired = True
                            with self._lock:
                                self.stats.deadline_expiries += 1
                        if not sub_p.hedged:
                            sub_p.hedged = self._issue_hedge(sub_p, rep)
            if stop and not pending and not retry_at:
                return
            if not pending:
                rep.wake.clear()
                rep.wake.wait(timeout=0.005 if retry_at else 0.05)
                continue
            if was_idle and admitted and wait_s and not stop:
                # batch-formation window: a cold batcher waits briefly
                # for co-submitted prompts before spinning up decode
                # (injected sleep — a no-op under virtual clocks)
                self._sleep(wait_s)
                continue
            with obs.span("service.pump"):
                done = engine.stream_pump()
            for rid, resp in done:
                sub2 = pending.pop(rid, None)
                if sub2 is None:
                    continue
                if (
                    is_recoverable(resp.error)
                    and sub2.flight.attempts <= sub2.max_retries
                ):
                    delay = (
                        sub2.retry_delay
                        * 2.0 ** (sub2.flight.attempts - 1)
                        if real_sleep
                        else 0.0
                    )
                    retry_at.append((time.monotonic() + delay, sub2))
                    continue
                with obs.span("service.resolve", rid=rid):
                    self._resolve(sub2, resp)
            # health probe: progress = completions delivered or engine
            # decode steps advancing; a loaded replica that shows neither
            # for health_probe_steps consecutive pumps is hung (a wedged
            # engine raises no exception — only the probe catches it)
            steps_now = _engine_steps()
            progressed = bool(done) or steps_now != state.last_steps
            state.last_steps = steps_now
            if progressed:
                state.stall = 0
                with self._lock:
                    rep.last_progress = steps_now
            elif pending:
                state.stall += 1
                if (
                    self.health_probe_steps
                    and state.stall >= self.health_probe_steps
                ):
                    raise ReplicaHungError(
                        f"replica {rep.index}: no engine progress in "
                        f"{state.stall} pumps with {len(pending)} "
                        f"request(s) in flight"
                    )

    # -- lifecycle ---------------------------------------------------------------

    def _fleet_report(self) -> str:
        """Per-replica post-mortem for fleet-dead errors: every replica's
        first-failure cause, last-progress step and restart count — not
        just the first replica's."""
        parts = []
        for r in self.replicas:
            cause = r.first_failure or r.broken
            parts.append(
                f"replica {r.index}: "
                + (f"{cause!r}" if cause is not None else "alive")
                + f" (last progress step {r.last_progress}, "
                f"restarts {r.restarts})"
            )
        return "; ".join(parts)

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("InferenceService is closed")
        if self._broken is not None:
            raise RuntimeError(
                f"InferenceService dispatch failed: {self._broken!r} — "
                + self._fleet_report()
            )

    def _drain_replica(self, rep: _Replica, exc: BaseException) -> None:
        """Fail every submission queued on one replica; stop sentinels are
        preserved (re-enqueued) so dispatchers racing this drain still
        shut down."""
        sentinels = 0
        while True:
            try:
                item = rep.queue.get_nowait()
            except queue.Empty:
                break
            if item is _SENTINEL:
                sentinels += 1
            else:
                self._resolve(item, exc=exc)
        for _ in range(sentinels):
            rep.queue.put(_SENTINEL)

    def _drain_queue(self, exc: BaseException) -> None:
        for rep in self.replicas:
            self._drain_replica(rep, exc=exc)

    def close(self, timeout: float = 30.0) -> None:
        """Drain and stop: queued work is dispatched to completion (FIFO —
        the stop sentinels sit behind it), in-flight decode finishes, then
        dispatcher threads exit and are joined."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            plan = [(rep, list(rep.threads)) for rep in self.replicas]
        for rep, threads in plan:
            for _ in threads:
                rep.queue.put(_SENTINEL)
            rep.wake.set()
        for rep, threads in plan:
            for t in threads:
                t.join(timeout=timeout)
        # a submit racing close may have enqueued behind the sentinels:
        # fail those tickets rather than strand their waiters
        self._drain_queue(exc=RuntimeError("InferenceService closed"))

    def __enter__(self) -> "InferenceService":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- introspection -----------------------------------------------------------

    def snapshot(self) -> dict:
        """Global service counters, per-replica routing/batcher counters,
        and (for slot engines) the fleet-aggregated occupancy/throughput
        counters under ``"batcher"``."""
        with self._lock:
            d = {
                "engine": self.name,
                "mode": "batcher" if self._streaming else "threads",
                "replicas": self.n_replicas,
                "dispatchers": sum(len(r.threads) for r in self.replicas),
                "inflight": len(self._inflight),
                **self.stats.as_dict(),
            }
            per_replica = [rep.stats_dict() for rep in self.replicas]
        batcher = aggregate_batcher_stats(
            [p.get("batcher", {}) for p in per_replica]
        )
        if batcher:
            d["batcher"] = batcher
        if self.n_replicas > 1:
            d["replica_stats"] = per_replica
        return d
