"""Inference-engine abstraction (paper §3.3) and implementations.

* :class:`LocalJaxEngine` — the primary engine on a pod: serves one of the
  assigned architectures through the continuous-batching scheduler
  (``repro/serve``).  The paper lists local model support as future work
  #1; on a TPU pod it is the default.
* :class:`SimulatedAPIEngine` — deterministic stand-in for the OpenAI /
  Anthropic / Google providers: latency model + price book (Table 6) +
  deterministic responses, so the paper's throughput/caching/cost
  benchmarks reproduce without network access.

``get_engine`` keeps one engine per serialized config per process — the
paper's Listing-1 ``_ENGINE_CACHE`` pattern (amortize initialization across
batches; in JAX terms: compile once, execute many).
"""

from __future__ import annotations

import abc
import dataclasses
import hashlib
import json
import math
import re
import threading
import time
from typing import Any

from repro.core.config import EngineModelConfig, InferenceConfig
from repro.data.tokenizer import HashTokenizer

# -- request/response ---------------------------------------------------------


@dataclasses.dataclass
class InferenceRequest:
    prompt: str
    max_tokens: int = 64
    temperature: float = 0.0


@dataclasses.dataclass
class InferenceResponse:
    text: str
    input_tokens: int
    output_tokens: int
    latency_ms: float
    cost_usd: float = 0.0
    error: str | None = None


class RecoverableEngineError(RuntimeError):
    """Transient engine failure worth retrying — the exception-typed
    counterpart of the 429/5xx error *strings* in
    :data:`RECOVERABLE_ERROR_CODES`.

    The service's retry paths back off and re-attempt on this type only;
    any other exception (a programming error such as ``ValueError`` /
    ``TypeError``) fails the ticket immediately with the original
    traceback instead of burning the backoff budget (DESIGN.md §9)."""


# -- price book (paper Table 6, USD per 1M tokens) -----------------------------

PRICE_BOOK: dict[tuple[str, str], tuple[float, float]] = {
    ("openai", "gpt-4o"): (2.50, 15.00),
    ("openai", "gpt-4o-mini"): (0.15, 0.60),
    ("openai", "gpt-4-turbo"): (10.00, 30.00),
    ("openai", "gpt-3.5-turbo"): (0.50, 1.50),
    ("anthropic", "claude-3-5-sonnet"): (3.00, 15.00),
    ("anthropic", "claude-3-opus"): (15.00, 75.00),
    ("anthropic", "claude-3-sonnet"): (3.00, 15.00),
    ("anthropic", "claude-3-haiku"): (0.25, 1.25),
    ("google", "gemini-1.5-pro"): (1.25, 5.00),
    ("google", "gemini-1.5-flash"): (0.075, 0.30),
    ("google", "gemini-1.0-pro"): (0.50, 1.50),
}


def api_cost(provider: str, model: str, in_tok: int, out_tok: int) -> float:
    pin, pout = PRICE_BOOK.get((provider, model), (0.0, 0.0))
    return (in_tok * pin + out_tok * pout) / 1e6


#: simulated answer quality per model tier (drives benchmark comparisons)
_MODEL_QUALITY: dict[str, float] = {
    "gpt-4o": 0.95, "gpt-4-turbo": 0.93, "gpt-4o-mini": 0.78,
    "gpt-3.5-turbo": 0.70, "claude-3-5-sonnet": 0.95, "claude-3-opus": 0.94,
    "claude-3-sonnet": 0.88, "claude-3-haiku": 0.75, "gemini-1.5-pro": 0.92,
    "gemini-1.5-flash": 0.80, "gemini-1.0-pro": 0.72,
}


# -- serving counters ------------------------------------------------------------


#: the counters an expert layer's step returns (``models.moe.held_experts``)
#: and the ``BatcherStats`` fields that sum them, per phase
EXPERT_COUNTS = ("expert_pairs", "experts_touched", "expert_picks")
EXPERT_STATS = tuple(
    f"{n}_{phase}" for phase in ("decode", "prefill") for n in EXPERT_COUNTS
)


@dataclasses.dataclass
class BatcherStats:
    """Occupancy/throughput counters for a slot-multiplexed decode loop.

    Shared by :class:`~repro.serve.scheduler.ContinuousBatcher` (the real
    JAX decode gang) and :class:`SimulatedSlotEngine` (its deterministic
    stand-in), and surfaced through ``InferenceService.snapshot`` into
    session accounting and the suite report.
    """

    n_slots: int = 0
    steps: int = 0
    #: sum of active slots over all steps — occupancy numerator
    active_slot_steps: int = 0
    tokens_generated: int = 0
    admissions: int = 0
    #: distinct prompt lengths prefilled (exact-length prefill compiles
    #: one program per length; callers bound this by bucketing prompts)
    prefill_recompiles: int = 0
    completions: int = 0
    #: admissions pushed past the current step by the per-step prefill cap
    #: (prefill/decode disaggregation: decode keeps stepping, the prompt
    #: waits one step for a prefill slot instead of stalling the gang)
    prefills_deferred: int = 0
    #: prompt-prefix pages reused from the paged KV cache's prefix index
    #: (zero on contiguous-cache batchers)
    prefix_pages_hit: int = 0
    #: prompt tokens whose prefill was skipped via shared prefix pages
    prefix_tokens_saved: int = 0
    #: defensive copy-on-write page copies (structurally unreachable while
    #: sharing stops short of the final prompt token — see DESIGN.md §8)
    cow_copies: int = 0
    #: decode slots evicted under page-pool pressure; the victim's request
    #: requeues for a full deterministic recompute (DESIGN.md §9)
    preemptions: int = 0
    #: decoded tokens discarded by preemptions (the recompute cost)
    preempted_tokens: int = 0
    #: HBM bytes one cached token costs, quantization-scale buffer
    #: included (0 on contiguous-cache batchers) — int8 pages halve this,
    #: which is exactly where quantization buys pool capacity (DESIGN.md §10)
    kv_bytes_per_token: int = 0
    #: KV page-pool size in pages (0 on contiguous-cache batchers)
    pool_pages: int = 0
    #: seconds requests spent queued before admission, summed over
    #: admissions (a preempted request counts from its first queue entry),
    #: and seconds from admission until the first token reached the host
    #: (prefill, page movement and the first sample's sync); both are added
    #: at admission
    queue_wait_s: float = 0.0
    first_token_s: float = 0.0
    #: expert layers (0 for a dense model), summed over layers, apart for
    #: decode steps and prefill chunks: (token, held expert) pairs computed,
    #: held experts given at least one row, and top-k picks of valid rows
    #: over all experts; each step hands them back with its tokens
    expert_pairs_decode: int = 0
    experts_touched_decode: int = 0
    expert_picks_decode: int = 0
    expert_pairs_prefill: int = 0
    experts_touched_prefill: int = 0
    expert_picks_prefill: int = 0

    def add_expert_counts(self, counts: dict, phase: str) -> None:
        """Add one step's expert-layer counters (``phase``: ``decode`` or
        ``prefill``)."""
        for name in EXPERT_COUNTS:
            key = f"{name}_{phase}"
            setattr(self, key, getattr(self, key) + int(counts[name]))

    @property
    def tokens_per_step(self) -> float:
        return self.tokens_generated / self.steps if self.steps else 0.0

    @property
    def occupancy(self) -> float:
        cap = self.steps * self.n_slots
        return self.active_slot_steps / cap if cap else 0.0

    def as_dict(self) -> dict:
        return {
            "n_slots": self.n_slots,
            "steps": self.steps,
            "admissions": self.admissions,
            "completions": self.completions,
            "tokens_generated": self.tokens_generated,
            "active_slot_steps": self.active_slot_steps,
            "tokens_per_step": round(self.tokens_per_step, 3),
            "slot_occupancy": round(self.occupancy, 4),
            "prefill_recompiles": self.prefill_recompiles,
            "prefills_deferred": self.prefills_deferred,
            "prefix_pages_hit": self.prefix_pages_hit,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "preempted_tokens": self.preempted_tokens,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "pool_pages": self.pool_pages,
            "queue_wait_s": self.queue_wait_s,
            "first_token_s": self.first_token_s,
            **{k: getattr(self, k) for k in EXPERT_STATS},
        }


# -- ABC ------------------------------------------------------------------------


class InferenceEngine(abc.ABC):
    #: engines that expose the slot-streaming interface below are driven by
    #: the :class:`~repro.core.service.InferenceService` with one persistent
    #: batcher loop (continuous batching across tasks) instead of a
    #: thread-per-request dispatch pool
    supports_streaming: bool = False

    @abc.abstractmethod
    def initialize(self) -> None: ...

    @abc.abstractmethod
    def infer(self, request: InferenceRequest) -> InferenceResponse: ...

    @abc.abstractmethod
    def infer_batch(
        self, requests: list[InferenceRequest]
    ) -> list[InferenceResponse]: ...

    @abc.abstractmethod
    def shutdown(self) -> None: ...

    def reset(self) -> None:
        """Engine reset hook for replica restart: drop all in-flight
        serving state (queued and slotted requests, their KV pages) so a
        fresh batcher loop starts clean.  Cumulative counters survive.
        Default: full shutdown + initialize."""
        self.shutdown()
        self.initialize()

    # -- optional slot-streaming interface (``supports_streaming``) ----------

    def stream_submit(self, request: InferenceRequest) -> int:
        """Enqueue a request for continuous-batching decode; returns an id."""
        raise NotImplementedError

    def stream_pump(self) -> list[tuple[int, InferenceResponse]]:
        """Advance decode by one step (admitting queued requests into free
        slots first) and return the requests that finished."""
        raise NotImplementedError

    def stream_pending(self) -> bool:
        """True while queued or in-flight streaming work remains."""
        return False

    def stream_cancel(self, rid: int) -> bool:
        """Abandon a streaming request without producing a completion:
        dequeue it, or free its decode slot and release its KV pages.
        Used by the service to cancel the losing leg of a hedged request.
        Returns True if the request was found and cancelled."""
        return False

    def serving_stats(self) -> dict:
        """:class:`BatcherStats` snapshot for slot engines; ``{}`` otherwise."""
        return {}


# -- simulated API engine ---------------------------------------------------------


def simulated_answer(prompt: str, max_tokens: int, model_name: str) -> str:
    """Deterministic response text — a pure function of (prompt, model) —
    shared by every simulated engine so the coalescing/caching layers can
    be validated byte-for-byte across execution strategies."""
    h = hashlib.sha256(prompt.encode()).hexdigest()
    hv = int(h[:8], 16)
    if prompt.startswith("[Judge]"):
        # deterministic judge behaviour, with a rare malformed response
        # (exercises the unparseable-logging path; paper §5.6 saw 0.12%)
        if hv % 797 == 0:
            return "I cannot assess this response."
        if "Winner:" in prompt or "Response A:" in prompt:
            return f"Winner: {'A' if hv % 2 == 0 else 'B'} — clearer answer."
        scale = 5
        m = re.search(r"1-(\d+) scale", prompt)
        if m:
            scale = int(m.group(1))
        # content-sensitive: degraded responses ("flub" fillers from
        # low-tier simulated models) score lower, plus mild hash noise —
        # so judge metrics track real quality differences
        m2 = re.search(r"Response: (.*)", prompt, re.DOTALL)
        resp = m2.group(1) if m2 else ""
        flubs = resp.count("flub")
        score = max(1, min(scale, scale - flubs + (hv % 2)))
        return f"Score: {score}. Concise and mostly accurate."
    words = prompt.split()
    # deterministic "answer": echo of salient words + hash suffix.
    # Quality scales with the (simulated) model tier so model
    # comparisons observe real, stable differences.
    quality = _MODEL_QUALITY.get(model_name, 0.8)
    salient = [w for w in words if len(w) > 3][: max(3, max_tokens // 4)]
    kept = []
    for i, w in enumerate(salient):
        wh = int(hashlib.sha256(f"{w}{i}{h[:4]}".encode()).hexdigest()[:4], 16)
        if (wh % 1000) / 1000.0 < quality:
            kept.append(w)
        else:
            kept.append(f"flub{wh % 97}")
    return " ".join(kept + [f"ans_{h[:8]}"])


class SimulatedAPIEngine(InferenceEngine):
    """Deterministic provider stand-in.

    Latency = base + per-token * output_tokens (+ deterministic jitter from
    the prompt hash).  Responses are a deterministic transform of the
    prompt, so caching benchmarks observe real hit/miss behaviour.  Set
    ``wall_clock=False`` to account latency without sleeping (fast
    benchmarks compute throughput from accounted latency).
    """

    def __init__(
        self,
        model: EngineModelConfig,
        *,
        base_latency_ms: float = 250.0,
        per_token_ms: float = 0.6,
        wall_clock: bool = False,
        fail_every: int = 0,  # inject a recoverable failure every N calls
    ):
        self.model = model
        self.base_latency_ms = base_latency_ms
        self.per_token_ms = per_token_ms
        self.wall_clock = wall_clock
        self.fail_every = fail_every
        self.calls = 0
        self.total_cost = 0.0
        self.initialized = False
        # counter updates must not lose increments when shards from
        # several concurrent chunks share one simulated engine
        self._counter_lock = threading.Lock()

    def initialize(self) -> None:
        self.initialized = True

    def shutdown(self) -> None:
        self.initialized = False

    @staticmethod
    def _count_tokens(text: str) -> int:
        return max(1, len(text.split()))

    def _respond(self, prompt: str, max_tokens: int) -> str:
        return simulated_answer(prompt, max_tokens, self.model.model_name)

    def infer(self, request: InferenceRequest) -> InferenceResponse:
        with self._counter_lock:
            self.calls += 1
            call_no = self.calls
        if self.fail_every and call_no % self.fail_every == 0:
            return InferenceResponse(
                text="", input_tokens=0, output_tokens=0,
                latency_ms=self.base_latency_ms, error="rate_limited_429",
            )
        text = self._respond(request.prompt, request.max_tokens)
        in_tok = self._count_tokens(request.prompt)
        out_tok = min(self._count_tokens(text), request.max_tokens)
        jitter = int(hashlib.sha256(request.prompt.encode()).hexdigest()[:4], 16)
        latency = self.base_latency_ms + self.per_token_ms * out_tok + jitter % 50
        if self.wall_clock:
            time.sleep(latency / 1000.0)
        cost = api_cost(self.model.provider, self.model.model_name, in_tok, out_tok)
        with self._counter_lock:
            self.total_cost += cost
        return InferenceResponse(
            text=text, input_tokens=in_tok, output_tokens=out_tok,
            latency_ms=latency, cost_usd=cost,
        )

    def infer_batch(self, requests: list[InferenceRequest]) -> list[InferenceResponse]:
        return [self.infer(r) for r in requests]


# -- simulated slot engine ---------------------------------------------------------

#: nominal KV geometry the slot simulator charges page bytes against —
#: shaped like the reduced qwen3 serving config, so simulated byte
#: budgets and capacity ratios track the real batcher's page economics
SIM_KV_HEADS = 8
SIM_HEAD_DIM = 64
SIM_LAYERS = 4


class SimulatedSlotEngine(InferenceEngine):
    """Deterministic slot-multiplexed decode engine (no JAX): models the
    continuous-batching substrate — ``n_slots`` decode slots advancing one
    token per step at ``step_ms`` — with deterministic texts and output
    lengths, so serving benchmarks measure *scheduling*, not model math.

    ``infer_batch`` is the lock-step path: requests decode in gangs of
    ``n_slots`` and the whole gang drains at its slowest member's length
    (exactly what per-shard ``run_to_completion`` does to the JAX engine,
    and what ``engine.infer`` per prompt degrades to — a gang of one).
    The streaming interface refills slots as they free, which is what the
    :class:`~repro.core.service.InferenceService` batcher loop drives.
    Output lengths are long-tail skewed on purpose: that is the regime
    where lock-step waves pay the straggler price every time.
    Its ``queue_wait_s`` and ``first_token_s`` stay 0: it keeps no
    wall-clock stamps of admission.
    """

    supports_streaming = True

    def __init__(
        self,
        model: EngineModelConfig,
        *,
        n_slots: int = 8,
        step_ms: float = 0.5,
        wall_clock: bool = False,
        min_out: int = 4,
        max_out: int = 48,
        max_prefills_per_step: int = 0,
        kv_page_size: int = 0,
        prefix_cache: bool = True,
        prefill_ms_per_token: float = 0.0,
        page_pool: int = 4096,
        page_pool_bytes: int = 0,
        kv_cache_dtype: str = "bf16",
        decode_page_growth: bool = False,
        fault_plan: Any = None,
    ):
        self.model = model
        self.n_slots = n_slots
        self.step_ms = step_ms
        self.wall_clock = wall_clock
        self.min_out = min_out
        self.max_out = max_out
        #: 0 = unlimited; otherwise at most this many queued prompts are
        #: prefilled into free slots per pump (prefill/decode split)
        self.max_prefills_per_step = max_prefills_per_step
        #: simulated prefill cost: each *uncached* prompt token (word)
        #: charges this much wall time at admission, so prefix sharing has
        #: a measurable effect on the streaming path
        self.prefill_ms_per_token = prefill_ms_per_token
        self.kv_page_size = kv_page_size
        #: "bf16" | "int8": accounting-only in the simulator — responses
        #: are pure prompt functions, so quantization changes page *bytes*
        #: (and therefore how many pages a byte budget admits), never text
        self.kv_cache_dtype = kv_cache_dtype
        #: charge one KV page per decoded token past the prompt (the real
        #: batcher's decode growth) so long generations create organic
        #: page pressure — off by default to keep prompt-only accounting
        self.decode_page_growth = decode_page_growth
        if kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bf16' or 'int8', got "
                f"{kv_cache_dtype!r}"
            )
        if kv_cache_dtype == "int8" and not kv_page_size:
            raise ValueError(
                "kv_cache_dtype='int8' requires a paged cache "
                "(kv_page_size > 0)"
            )
        page_bytes = 0
        if kv_page_size:
            # deferred import: repro.serve.scheduler imports this module
            from repro.serve.paged_cache import (
                PagedCacheManager,
                kv_page_bytes,
                pages_for_budget,
            )

            page_bytes = kv_page_bytes(
                kv_page_size, SIM_KV_HEADS, SIM_HEAD_DIM, SIM_LAYERS,
                kv_cache_dtype,
            )
            if page_pool_bytes:
                # byte-budgeted pool: int8 pages are ~half the bytes, so
                # the same budget admits ~2x pages
                page_pool = pages_for_budget(page_pool_bytes, page_bytes)
            self._pages = PagedCacheManager(
                page_pool, kv_page_size, prefix_cache=prefix_cache,
                page_bytes=page_bytes,
            )
        else:
            self._pages = None
        self.calls = 0
        self.total_cost = 0.0
        self.initialized = False
        self.stats = BatcherStats(n_slots=n_slots)
        if kv_page_size:
            self.stats.kv_bytes_per_token = page_bytes // kv_page_size
            self.stats.pool_pages = self._pages.n_pages
        self._lock = threading.Lock()
        self._next_id = 0
        #: streaming admission queue: (rid, request, out_len)
        self._queue: list[tuple[int, InferenceRequest, int]] = []
        self._slots: list[dict | None] = [None] * n_slots
        self._seen_len_buckets: set[int] = set()
        #: deterministic chaos: a ServingFaultSchedule polled every pump
        #: (replica index claimed in engine creation order)
        self._fault_plan = fault_plan
        self.fault_replica = fault_plan.attach() if fault_plan is not None else 0
        #: monotonic pump counter — survives reset() so later faults on
        #: the same replica still fire at their scheduled step
        self._pumps = 0
        self._hang_until = 0

    def initialize(self) -> None:
        self.initialized = True

    def shutdown(self) -> None:
        self.initialized = False

    def _out_len(self, request: InferenceRequest) -> int:
        h = int(hashlib.sha256(request.prompt.encode()).hexdigest()[8:16], 16)
        span = max(1, self.max_out - self.min_out)
        if h % 6 == 0:  # long tail: ~1 in 6 answers runs near max_out
            n = self.max_out - h % (span // 4 + 1)
        else:
            n = self.min_out + h % (span // 3 + 1)
        return max(1, min(request.max_tokens, n))

    def _response(
        self, request: InferenceRequest, out_len: int, latency_ms: float
    ) -> InferenceResponse:
        text = simulated_answer(
            request.prompt, request.max_tokens, self.model.model_name
        )
        return InferenceResponse(
            text=text,
            input_tokens=max(1, len(request.prompt.split())),
            output_tokens=out_len,
            latency_ms=latency_ms,
        )

    def _account_admission(self, request: InferenceRequest) -> None:
        self.stats.admissions += 1
        b, n = 16, max(1, len(request.prompt.split()))
        while b < n:
            b <<= 1
        if b not in self._seen_len_buckets:
            self._seen_len_buckets.add(b)
            self.stats.prefill_recompiles += 1

    def _account_steps(self, steps: int, active_slot_steps: int) -> None:
        self.stats.steps += steps
        self.stats.active_slot_steps += active_slot_steps
        self.stats.tokens_generated += active_slot_steps

    # -- lock-step path --------------------------------------------------------

    def infer(self, request: InferenceRequest) -> InferenceResponse:
        return self.infer_batch([request])[0]

    def infer_batch(self, requests: list[InferenceRequest]) -> list[InferenceResponse]:
        out: list[InferenceResponse] = []
        with self._lock:
            self.initialize()
            for i in range(0, len(requests), self.n_slots):
                wave = requests[i : i + self.n_slots]
                lens = [self._out_len(r) for r in wave]
                wave_steps = max(lens)
                for r in wave:
                    self._account_admission(r)
                self._account_steps(wave_steps, sum(lens))
                self.stats.completions += len(wave)
                # lock-step pays full prefill for every prompt: the wave
                # has no persistent slots, so nothing survives to share
                prefill_ms = self.prefill_ms_per_token * sum(
                    max(1, len(r.prompt.split())) for r in wave
                )
                if self.wall_clock:
                    time.sleep((wave_steps * self.step_ms + prefill_ms) / 1000.0)
                latency = wave_steps * self.step_ms + prefill_ms
                for r, n in zip(wave, lens):
                    self.calls += 1
                    out.append(self._response(r, n, latency))
        return out

    # -- streaming path --------------------------------------------------------

    def stream_submit(self, request: InferenceRequest) -> int:
        with self._lock:
            self.initialize()
            rid = self._next_id
            self._next_id += 1
            self._queue.append((rid, request, self._out_len(request)))
            return rid

    def stream_pending(self) -> bool:
        with self._lock:
            return bool(self._queue) or any(s is not None for s in self._slots)

    def slots_busy(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s is not None) + len(self._queue)

    def _preempt_one_locked(self) -> bool:
        """Evict the victim slot — fewest decoded tokens, index tie-break —
        releasing its pages and requeueing its request at the queue front
        for a full deterministic recompute (byte-identical output)."""
        victims = [
            (s["out"] - s["left"], i)
            for i, s in enumerate(self._slots)
            if s is not None
        ]
        if not victims:
            return False
        decoded, i = min(victims)
        s = self._slots[i]
        if self._pages is not None:
            self._pages.release(s["rid"])
        self._queue.insert(0, (s["rid"], s["req"], s["out"]))
        self._slots[i] = None
        self.stats.preemptions += 1
        self.stats.preempted_tokens += decoded
        return True

    def _page_gate_locked(self) -> bool:
        """Low-watermark admission gate: admit the queue head only if the
        pool covers its worst-case page need while keeping one page per
        busy slot in reserve.  A prompt larger than the whole pool is
        admitted anyway so ``acquire`` raises a clear error instead of
        the request deferring forever."""
        _, req, _ = self._queue[0]
        words = req.prompt.split() or ["<bos>"]
        need = -(-len(words) // self.kv_page_size)
        if need >= self._pages.n_pages:
            return True
        busy = sum(1 for s in self._slots if s is not None)
        return self._pages.pages_free + self._pages.pages_cached >= need + busy

    def _poll_fault_locked(self) -> float:
        """Apply the due scheduled fault, if any; returns extra latency ms
        (``slow_step``).  ``replica_crash`` raises out of the pump."""
        fault = self._fault_plan.poll(self.fault_replica, self._pumps)
        if fault is None:
            return 0.0
        if fault.kind == "replica_crash":
            from repro.ft.failure_sim import SimulatedCrash

            raise SimulatedCrash(
                f"injected replica_crash replica={self.fault_replica} "
                f"pump={self._pumps}"
            )
        if fault.kind == "hang":
            self._hang_until = self._pumps + fault.duration
        elif fault.kind == "page_pressure":
            for _ in range(max(1, fault.duration)):
                if not self._preempt_one_locked():
                    break
        elif fault.kind == "slow_step":
            return fault.delay_s * 1000.0
        return 0.0

    def _grow_decode_pages_locked(self) -> None:
        """Charge each active slot the KV page holding this step's new
        token (the real batcher's ``ensure_position``): long generations
        spill past their prompt pages, so a tight pool preempts under
        *decode* pressure, not just admission pressure.  Pool exhaustion
        preempts the cheapest victim and retries — possibly the growing
        slot itself, in which case its growth is moot this pump."""
        from repro.serve.paged_cache import PagePoolExhausted

        for i, s in enumerate(self._slots):
            if s is None:
                continue
            words = s["req"].prompt.split() or ["<bos>"]
            pos = len(words) + (s["out"] - s["left"])
            while self._slots[i] is not None:
                try:
                    pw = self._pages.ensure_position(s["rid"], pos)
                    if pw.cow_src is not None:
                        self.stats.cow_copies += 1
                    break
                except PagePoolExhausted:
                    if not self._preempt_one_locked():
                        break

    def stream_pump(self) -> list[tuple[int, InferenceResponse]]:
        slow_ms = 0.0
        with self._lock:
            self._pumps += 1
            if self._fault_plan is not None:
                slow_ms = self._poll_fault_locked()
            if self._pumps <= self._hang_until:
                return []  # hung: no admissions, no decode, no progress
            admitted = 0
            prefill_tokens = 0
            for i, s in enumerate(self._slots):
                if s is None and self._queue:
                    if (
                        self.max_prefills_per_step
                        and admitted >= self.max_prefills_per_step
                    ):
                        # each still-queued request a free slot could have
                        # taken this pump defers exactly once per pump it
                        # actually waits (not once per queue neighbour)
                        free_left = sum(
                            1 for s2 in self._slots[i:] if s2 is None
                        )
                        self.stats.prefills_deferred += min(
                            len(self._queue), free_left
                        )
                        break
                    if self._pages is not None and not self._page_gate_locked():
                        # pool pressure: defer the prefill rather than
                        # overcommit pages a decode will need (DESIGN.md §9)
                        free_left = sum(
                            1 for s2 in self._slots[i:] if s2 is None
                        )
                        self.stats.prefills_deferred += min(
                            len(self._queue), free_left
                        )
                        break
                    rid, req, out_len = self._queue.pop(0)
                    self._account_admission(req)
                    admitted += 1
                    words = req.prompt.split() or ["<bos>"]
                    if self._pages is not None:
                        m = self._pages.acquire(rid, words)
                        self._pages.register(rid, words)
                        self.stats.prefix_pages_hit += m.n_shared_pages
                        self.stats.prefix_tokens_saved += m.n_shared_tokens
                        prefill_tokens += len(words) - m.n_shared_tokens
                    else:
                        prefill_tokens += len(words)
                    self._slots[i] = {
                        "rid": rid, "req": req, "left": out_len,
                        "out": out_len, "start_step": self.stats.steps,
                    }
            n_active = sum(1 for s in self._slots if s is not None)
            if not n_active:
                return []
        if self.wall_clock:
            # sleep outside the lock: direct infer calls (judges, legacy
            # paths) interleave between steps instead of stalling behind one
            time.sleep(
                (self.step_ms + self.prefill_ms_per_token * prefill_tokens
                 + slow_ms)
                / 1000.0
            )
        done: list[tuple[int, InferenceResponse]] = []
        with self._lock:
            if self._pages is not None and self.decode_page_growth:
                self._grow_decode_pages_locked()
            self._account_steps(1, n_active)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                s["left"] -= 1
                if s["left"] <= 0:
                    latency = (self.stats.steps - s["start_step"]) * self.step_ms
                    self.calls += 1
                    self.stats.completions += 1
                    if self._pages is not None:
                        self._pages.release(s["rid"])
                    done.append(
                        (s["rid"], self._response(s["req"], s["out"], latency))
                    )
                    self._slots[i] = None
        return done

    def stream_cancel(self, rid: int) -> bool:
        with self._lock:
            for i, (qid, _req, _out) in enumerate(self._queue):
                if qid == rid:
                    del self._queue[i]
                    return True
            for i, s in enumerate(self._slots):
                if s is not None and s["rid"] == rid:
                    if self._pages is not None:
                        self._pages.release(rid)
                    self._slots[i] = None
                    return True
        return False

    def reset(self) -> None:
        """Replica-restart hook: drop queued and slotted requests and
        their pages; cumulative stats and the pump counter survive (the
        fault schedule stays aligned to engine lifetime, not incarnation)."""
        with self._lock:
            if self._pages is not None:
                self._pages.release_all()
            self._queue.clear()
            self._slots = [None] * self.n_slots
            self._hang_until = 0
            self.initialized = True

    def serving_stats(self) -> dict:
        with self._lock:
            return self.stats.as_dict()


# -- local JAX engine ----------------------------------------------------------------


class LocalJaxEngine(InferenceEngine):
    """Serve an assigned architecture via the continuous-batching scheduler.

    Two entry points share one scheduler:

    * ``infer_batch`` — legacy lock-step: submit a batch, drain it to
      completion under the engine lock (concurrent callers serialize);
    * ``stream_submit``/``stream_pump`` — persistent streaming, driven by
      the :class:`~repro.core.service.InferenceService` batcher loop:
      prompts are admitted into decode slots as slots free, so batches
      form across shards, chunks, tasks and suites.

    Greedy decode (temperature 0) is batch-composition independent, so
    both paths produce identical tokens for a given prompt.
    """

    supports_streaming = True

    def __init__(self, model: EngineModelConfig, *, n_slots: int = 8,
                 max_len: int = 256, devices: Any = None,
                 max_prefills_per_step: int = 0,
                 kv_page_size: int = 0, prefix_cache: bool = True,
                 page_pool: int = 0, page_pool_bytes: int = 0,
                 kv_cache_dtype: str = "bf16", fault_plan: Any = None):
        self.model_cfg = model
        self.n_slots = n_slots
        self.max_len = max_len
        #: replica placement: None = default device; one device = pinned
        #: data-parallel replica; several devices = tensor-parallel replica
        #: over a ("data","model") mesh built from this group
        self.devices = tuple(devices) if devices else None
        self.max_prefills_per_step = max_prefills_per_step
        #: 0 = contiguous per-slot KV cache; > 0 = paged pool (page size)
        self.kv_page_size = kv_page_size
        self.prefix_cache = prefix_cache
        #: 0 = auto-sized pool (worst case, never exhausts); > 0 pins the
        #: pool small enough that decode pressure triggers preemption
        self.page_pool = page_pool
        #: byte-budgeted alternative to ``page_pool`` (pages = budget //
        #: page bytes, scale buffers included)
        self.page_pool_bytes = page_pool_bytes
        #: "bf16" = full-precision pool pages; "int8" = absmax-quantized
        #: pages + scales, dequantized at the decode gather (DESIGN.md §10)
        self.kv_cache_dtype = kv_cache_dtype
        self._fault_plan = fault_plan
        self.fault_replica = fault_plan.attach() if fault_plan is not None else 0
        self.initialized = False
        self._scheduler = None
        self._tokenizer = None
        #: one tokenizer per in-flight request: a hash tokenizer decodes
        #: only words it has encoded, so decoding with the request's own
        #: makes the response a function of the request alone, not of
        #: which prompts this replica happened to encode before
        self._request_tok: dict[int, HashTokenizer] = {}
        self._next_id = 0
        # worker threads share one scheduler; it is the batching layer, so
        # concurrent infer_batch calls serialize (slots multiplex inside)
        self._lock = threading.Lock()

    def initialize(self) -> None:
        if self.initialized:
            return
        import jax

        from repro.configs import get_config
        from repro.models import params as pm
        from repro.models.model import build_model, serving_param_specs
        from repro.serve.scheduler import ContinuousBatcher

        cfg = get_config(self.model_cfg.model_name)
        if self.model_cfg.reduced:
            cfg = cfg.reduced()
        self._cfg = cfg
        self._tokenizer = HashTokenizer(cfg.vocab_size)
        model = build_model(cfg, remat="none")
        device = rules = None
        if self.devices and len(self.devices) == 1:
            device = self.devices[0]
        elif self.devices:
            from repro.launch.mesh import make_replica_mesh
            from repro.sharding import SERVE_RULES, ShardingRules

            rules = ShardingRules(
                SERVE_RULES, make_replica_mesh(self.devices)
            )
        # a pinned replica builds its weights on its own device: building
        # them on the default device first would stack every replica's
        # weights there before they move
        with jax.default_device(device):
            params = pm.init_params(
                jax.random.key(self.model_cfg.seed),
                serving_param_specs(model.param_specs()),
            )
        self._scheduler = ContinuousBatcher(
            model, cfg, params,
            n_slots=self.n_slots, max_len=self.max_len,
            eos_id=self._tokenizer.eos_id,
            temperature=self.model_cfg.temperature,
            max_prefills_per_step=self.max_prefills_per_step,
            device=device, rules=rules,
            page_size=self.kv_page_size, prefix_cache=self.prefix_cache,
            page_pool=self.page_pool, page_pool_bytes=self.page_pool_bytes,
            kv_cache_dtype=self.kv_cache_dtype,
        )
        if self._fault_plan is not None:
            self._scheduler.fault_hook = self._fault_plan.as_hook(
                self.fault_replica
            )
        self.initialized = True

    def shutdown(self) -> None:
        self._scheduler = None
        self.initialized = False

    def infer(self, request: InferenceRequest) -> InferenceResponse:
        return self.infer_batch([request])[0]

    def _submit_locked(self, request: InferenceRequest) -> int:
        from repro.serve.scheduler import Request

        self.initialize()
        rid = self._next_id
        self._next_id += 1
        tok = HashTokenizer(self._cfg.vocab_size)
        self._request_tok[rid] = tok
        toks = tok.encode(request.prompt)[: self.max_len // 2]
        self._scheduler.submit(
            Request(
                request_id=rid,
                prompt_tokens=toks or [self._tokenizer.bos_id],
                max_new_tokens=min(
                    request.max_tokens, self.max_len - len(toks) - 1
                ),
            )
        )
        return rid

    def _completion_response(self, c) -> InferenceResponse:
        return InferenceResponse(
            text=self._request_tok.pop(c.request_id).decode(c.tokens),
            input_tokens=c.prompt_len,
            output_tokens=len(c.tokens),
            latency_ms=c.latency_s * 1000.0,
        )

    def infer_batch(self, requests: list[InferenceRequest]) -> list[InferenceResponse]:
        with self._lock:
            return self._infer_batch_locked(requests)

    def _infer_batch_locked(
        self, requests: list[InferenceRequest]
    ) -> list[InferenceResponse]:
        t0 = time.monotonic()
        id_map: dict[int, int] = {}
        for i, r in enumerate(requests):
            id_map[self._submit_locked(r)] = i
        completions = self._scheduler.run_to_completion()
        # the drain may have carried service-submitted streaming requests
        # to completion too; leave those for the next stream_pump
        self._scheduler.completions = [
            c for c in completions if c.request_id not in id_map
        ]
        out: list[InferenceResponse | None] = [None] * len(requests)
        for c in completions:
            if c.request_id not in id_map:
                continue
            out[id_map[c.request_id]] = self._completion_response(c)
        dt = time.monotonic() - t0
        for i, r in enumerate(out):
            if r is None:  # pragma: no cover
                out[i] = InferenceResponse(
                    text="", input_tokens=0, output_tokens=0,
                    latency_ms=dt * 1000.0, error="lost",
                )
        return out  # type: ignore[return-value]

    # -- persistent streaming (InferenceService batcher loop) -------------------

    def stream_submit(self, request: InferenceRequest) -> int:
        with self._lock:
            return self._submit_locked(request)

    def stream_pump(self) -> list[tuple[int, InferenceResponse]]:
        with self._lock:
            sched = self._scheduler
            if sched is None:
                return []
            if sched.queue or sched.slots_busy:
                sched.step()
            return [
                (c.request_id, self._completion_response(c))
                for c in sched.drain_completions()
            ]

    def stream_pending(self) -> bool:
        with self._lock:
            sched = self._scheduler
            return bool(
                sched
                and (sched.queue or sched.slots_busy or sched.completions)
            )

    def slots_busy(self) -> int:
        with self._lock:
            sched = self._scheduler
            if sched is None:
                return 0
            return sched.slots_busy + len(sched.queue)

    def stream_cancel(self, rid: int) -> bool:
        with self._lock:
            sched = self._scheduler
            cancelled = bool(sched) and sched.cancel(rid)
            if cancelled:
                del self._request_tok[rid]
            return cancelled

    def reset(self) -> None:
        """Replica-restart hook: rebuild the scheduler (fresh slots, fresh
        page pool).  Cheap relative to a lost replica; the fault hook is
        re-attached by ``initialize`` so scheduled faults keep firing."""
        with self._lock:
            self._scheduler = None
            self._request_tok.clear()
            self.initialized = False
            self.initialize()

    def serving_stats(self) -> dict:
        with self._lock:
            if self._scheduler is None:
                return {}
            return self._scheduler.stats.as_dict()


# -- registry (Listing 1) ------------------------------------------------------------


def create_engine(model: EngineModelConfig, **kw: Any) -> InferenceEngine:
    if model.provider == "local":
        return LocalJaxEngine(model, **kw)
    if model.provider == "slotsim":
        return SimulatedSlotEngine(model, **kw)
    return SimulatedAPIEngine(model, **kw)


class EngineRegistry:
    """One initialized engine per :class:`EngineModelConfig` (+ extra
    constructor kwargs).  The paper's Listing-1 ``_ENGINE_CACHE`` pattern,
    made an owned object so an :class:`~repro.core.session.EvalSession`
    amortizes initialization across every task it runs — in JAX terms:
    compile once, execute many.
    """

    def __init__(self) -> None:
        self._engines: dict[
            tuple[EngineModelConfig, int, str], InferenceEngine
        ] = {}
        self.initializations = 0
        # concurrent chunk workers may request the same engine at once;
        # initialization must happen exactly once per config
        self._lock = threading.Lock()

    def get(
        self, model: EngineModelConfig, *, replica: int = 0, **kw: Any
    ) -> InferenceEngine:
        """``replica`` distinguishes otherwise-identical data-parallel
        engine instances: replica i of a model is its own engine (own
        batcher, own decode slots), while repeated lookups of the same
        (model, replica, kwargs) still amortize to one initialization."""
        key = (model, replica, json.dumps(kw, sort_keys=True, default=str))
        with self._lock:
            engine = self._engines.get(key)
            if engine is None:
                engine = create_engine(model, **kw)
                engine.initialize()
                self.initializations += 1
                self._engines[key] = engine
        return engine

    def shutdown(self) -> None:
        for engine in self._engines.values():
            engine.shutdown()
        self._engines.clear()

    def __len__(self) -> int:
        return len(self._engines)

    def __contains__(self, model: EngineModelConfig) -> bool:
        return any(k[0] == model for k in self._engines)

    def engines(self) -> list[InferenceEngine]:
        return list(self._engines.values())


_PROCESS_REGISTRY = EngineRegistry()


def get_engine(
    model: EngineModelConfig, inference: InferenceConfig, **kw: Any
) -> InferenceEngine:
    """Process-global engine lookup (legacy); sessions own their own
    :class:`EngineRegistry` instead."""
    del inference  # engines depend only on the model config + kwargs
    return _PROCESS_REGISTRY.get(model, **kw)


#: provider error codes worth retrying (429/5xx; paper §A.4)
RECOVERABLE_ERROR_CODES = ("429", "500", "502", "503")


def is_recoverable(error: str | None) -> bool:
    return error is not None and any(
        code in error for code in RECOVERABLE_ERROR_CODES
    )


def retry_with_backoff(
    fn, *, max_retries: int = 3, base_delay: float = 1.0,
    sleep=time.sleep,
):
    """Exponential backoff for recoverable errors (paper §A.4): the
    429/5xx error strings and :class:`RecoverableEngineError`.  Any other
    exception — a programming error like ``ValueError`` — propagates
    immediately with its original traceback instead of burning the
    backoff budget."""
    last: InferenceResponse | None = None
    for attempt in range(max_retries + 1):
        try:
            resp = fn()
        except RecoverableEngineError:
            if attempt >= max_retries:
                raise
            sleep(base_delay * math.pow(2.0, attempt))
            continue
        if resp.error is None:
            return resp
        if not is_recoverable(resp.error):
            return resp
        last = resp
        if attempt < max_retries:
            sleep(base_delay * math.pow(2.0, attempt))
    return last
