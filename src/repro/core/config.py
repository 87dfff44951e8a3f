"""Hierarchical evaluation-task configuration (paper §3.4).

The complete specification of an evaluation serializes to JSON and is stored
alongside results — reproducibility by construction.
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
import json
from typing import Any

from repro.stats.sequential import StoppingRule


class CachePolicy(str, enum.Enum):
    ENABLED = "enabled"      # lookup before inference, cache new responses
    READ_ONLY = "read_only"  # lookup only
    WRITE_ONLY = "write_only"  # cache warming: always infer, always cache
    REPLAY = "replay"        # strict: error on cache miss (zero API calls)
    DISABLED = "disabled"


@dataclasses.dataclass(frozen=True)
class EngineModelConfig:
    """Which model answers the prompts (provider = 'local' runs on-pod)."""

    provider: str = "local"          # local | openai | anthropic | google
    model_name: str = "qwen3-4b"
    temperature: float = 0.0
    max_tokens: int = 64
    # local-engine extras
    reduced: bool = True             # serve the reduced config (CPU tests)
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class InferenceConfig:
    batch_size: int = 16
    n_workers: int = 4
    rate_limit_rpm: float = 10_000.0
    rate_limit_tpm: float = 2_000_000.0
    adaptive_rate: bool = False
    cache_policy: CachePolicy = CachePolicy.ENABLED
    cache_dir: str = ""
    max_retries: int = 3
    retry_delay: float = 1.0
    # straggler mitigation (ft/)
    speculative_reissue: bool = False
    straggler_factor: float = 3.0
    # shared asynchronous inference service (core/service.py):
    # use_service=False restores the legacy lock-step per-shard path
    use_service: bool = True
    #: outstanding-request bound before submit blocks (backpressure)
    service_queue_depth: int = 256
    #: single-flight coalescing of identical in-flight cache keys
    coalesce: bool = True
    #: batch-formation window for a cold batcher loop (slot engines only)
    max_batch_wait_ms: float = 2.0
    #: data-parallel engine replicas behind one service front: each gets
    #: its own engine instance (own batcher / decode slots; local engines
    #: additionally get their own device group from the mesh) while the
    #: flight table stays global — identical in-flight prompts still pay
    #: one engine call no matter which replica serves them.  Responses are
    #: a pure function of the request, so replica count never changes a
    #: metric byte.
    n_replicas: int = 1
    #: replica-placement policy: least_loaded | prefix_affinity | round_robin
    routing: str = "least_loaded"
    #: prompt-prefix bytes hashed by the prefix_affinity policy
    routing_prefix_len: int = 64
    #: per-step prefill admissions cap for slot engines (0 = unlimited):
    #: disaggregates prefill from decode so a long-prompt backlog queues
    #: for a prefill slot instead of stalling every decode step
    max_prefills_per_step: int = 0
    #: paged KV cache for slot engines: 0 = contiguous per-slot cache,
    #: > 0 = page-pool cache with this many tokens per page (enables
    #: hash-chain prompt-prefix sharing across requests — DESIGN.md §8)
    kv_page_size: int = 0
    #: with a paged cache, share resident prompt-prefix pages across
    #: requests (False = paged allocation only, no cross-request reuse)
    prefix_cache: bool = True
    #: KV page storage precision: "bf16" = full-precision pages, "int8" =
    #: absmax block-quantized pages + per-(page, head) f32 scales; decode
    #: gathers a slot's pages and dequantizes them in jax.numpy before
    #: attention (DESIGN.md §10).  Halves bytes-per-token, so the same
    #: pool byte budget admits ~2x pages; requires kv_page_size > 0.
    #: Outputs stay byte-identical across replicas/routing/page sizes at
    #: *fixed* dtype; int8-vs-bf16 parity
    #: is a tolerance + token-match-rate gate, not bit equality.
    kv_cache_dtype: str = "bf16"
    # fault tolerance for the serving fabric (DESIGN.md §9):
    #: per-request deadline on the streaming path (0 = none).  On expiry
    #: the service hedges: re-issues the ticket to another alive replica;
    #: first completion wins, the loser's slot is cancelled.  Responses
    #: are a pure function of the request, so hedging never changes a
    #: metric byte.
    request_deadline_s: float = 0.0
    #: bounded-backoff restarts per broken replica before its in-flight
    #: work fails over to the fleet-dead path (0 = legacy: first crash
    #: kills the replica for good)
    max_replica_restarts: int = 2
    #: base delay for the exponential replica-restart backoff
    restart_backoff_s: float = 0.05
    #: health probe: a replica with in-flight work but no engine progress
    #: (no decode steps, no completions) for this many consecutive pumps
    #: is marked suspect and drain-and-restarted (0 = disabled)
    health_probe_steps: int = 0


@dataclasses.dataclass(frozen=True)
class MetricConfig:
    name: str                         # registry key, e.g. "exact_match"
    type: str = "lexical"             # lexical | semantic | llm_judge | rag
    params: dict = dataclasses.field(default_factory=dict)

    def __hash__(self) -> int:
        return hash((self.name, self.type, json.dumps(self.params, sort_keys=True)))


@dataclasses.dataclass(frozen=True)
class StatisticsConfig:
    confidence_level: float = 0.95
    bootstrap_iterations: int = 1000
    ci_method: str = "bca"            # percentile | bca | analytical
    significance_threshold: float = 0.05
    seed: int = 0
    #: bootstrap execution backend for streaming aggregation:
    #: "numpy"  — host Philox(seed, chunk_start) weight blocks, one
    #:            (B, chunk) float64 matrix per metric per chunk;
    #: "pallas" — device-resident chunked partials (one kernel launch per
    #:            chunk covers all metrics; counter-mixer PRNG keyed by the
    #:            absolute example position, O(B x n_metrics) host state).
    #: The two backends draw different (each internally deterministic)
    #: weight streams, so the backend is part of the resume key.
    backend: str = "numpy"


@dataclasses.dataclass(frozen=True)
class StreamingConfig:
    """Bounded-memory chunked execution (paper-scale datasets).

    When ``enabled``, the session runs prepare→infer→score per chunk of
    ``max_memory_rows`` examples, folds scores into mergeable accumulators
    (:mod:`repro.stats.streaming`), and discards raw responses — peak
    per-example state is O(chunk), not O(dataset).  With a ``spill_dir``,
    each completed chunk commits its partial state to a DeltaLite manifest
    so an interrupted run resumes by skipping completed chunks.

    ``max_inflight_chunks > 1`` runs that many whole chunks concurrently
    on a chunk-level worker pool (the paper's executor layer lifted from
    shards to chunks): peak resident examples become
    ``max_inflight_chunks x max_memory_rows``, chunk states are merged
    deterministically in chunk order, and results stay bit-identical to
    the serial pipeline.  Like the other execution-strategy knobs it is
    excluded from the resume key — a restart may retune it freely.
    """

    enabled: bool = False
    max_memory_rows: int = 1024       # chunk size == peak resident examples
    spill_dir: str = ""               # "" = no spill, run is not resumable
    resume: bool = True               # skip chunks already in the manifest
    max_inflight_chunks: int = 1      # >1 = concurrent chunk execution
    #: explicit cap on examples consumed from the source (0 = unbounded).
    #: Unlike silently slicing the source, a declared cap lets a resumed
    #: run distinguish "I stopped at my cap" (committed chunks past it are
    #: fine — a later run with a larger cap will merge them) from "the
    #: data source shrank" (refused).  The budget scheduler
    #: (:mod:`repro.core.budget`) raises this cap round by round; it is
    #: excluded from the resume key like the other execution knobs.
    max_examples: int = 0


@dataclasses.dataclass(frozen=True)
class DataConfig:
    prompt_template: str = "{question}"
    input_columns: tuple[str, ...] = ("question",)
    reference_column: str = "reference"


@dataclasses.dataclass(frozen=True)
class EvalTask:
    task_id: str
    model: EngineModelConfig = EngineModelConfig()
    inference: InferenceConfig = InferenceConfig()
    metrics: tuple[MetricConfig, ...] = (MetricConfig("exact_match"),)
    statistics: StatisticsConfig = StatisticsConfig()
    data: DataConfig = DataConfig()
    streaming: StreamingConfig = StreamingConfig()
    #: per-task adaptive early stopping (:mod:`repro.stats.sequential`):
    #: when enabled, the streaming pipelines consult the rule after every
    #: merged chunk and terminate sampling once it fires.  The rule's
    #: statistical fields are validated against the spill manifest on
    #: resume — one manifest, one certification regime.
    stopping: StoppingRule = StoppingRule()

    def with_model(self, model: "EngineModelConfig") -> "EvalTask":
        """Rebind the task to another model (used by suite model sweeps)."""
        return dataclasses.replace(self, model=model)

    def with_streaming(self, **kw: Any) -> "EvalTask":
        """Enable (or reconfigure) bounded-memory streaming execution.
        Unspecified fields keep their current values.  ``concurrency`` is
        accepted as an alias for ``max_inflight_chunks``:
        ``task.with_streaming(concurrency=4)`` runs four chunks in flight.
        """
        kw.setdefault("enabled", True)
        if "concurrency" in kw:
            kw["max_inflight_chunks"] = kw.pop("concurrency")
        return dataclasses.replace(
            self, streaming=dataclasses.replace(self.streaming, **kw)
        )

    def with_stopping(self, **kw: Any) -> "EvalTask":
        """Enable (or reconfigure) adaptive early stopping, e.g.
        ``task.with_stopping(target_half_width=0.02, min_examples=512)``.
        Unspecified fields keep their current values; requires streaming
        execution to have any effect (the in-memory path scores every row
        it was given)."""
        kw.setdefault("enabled", True)
        return dataclasses.replace(
            self, stopping=dataclasses.replace(self.stopping, **kw)
        )

    def with_metrics(self, *metrics: "MetricConfig") -> "EvalTask":
        """Rebind the metric set (used by cache-replay metric iteration)."""
        return dataclasses.replace(self, metrics=tuple(metrics))

    def to_json(self) -> str:
        def default(o: Any):
            if dataclasses.is_dataclass(o):
                return dataclasses.asdict(o)
            if isinstance(o, enum.Enum):
                return o.value
            raise TypeError(type(o))

        return json.dumps(dataclasses.asdict(self), default=default, sort_keys=True)

    def fingerprint(self) -> str:
        return hashlib.sha256(self.to_json().encode()).hexdigest()[:16]


def cache_key(
    prompt: str,
    model_name: str,
    provider: str,
    temperature: float,
    max_tokens: int,
) -> str:
    """Content-addressable key: SHA256(prompt||model||provider||T||max_tokens)."""
    payload = "\x1f".join(
        [prompt, model_name, provider, f"{temperature:.6g}", str(max_tokens)]
    )
    return hashlib.sha256(payload.encode()).hexdigest()
