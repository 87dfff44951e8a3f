"""Smoke run of the evaluation path on TPU, through the entry points a user
calls: ``EvalSession`` -> ``InferenceService`` -> ``LocalJaxEngine`` ->
``ContinuousBatcher`` -> jitted steps -> Pallas kernels.

  python chip_smoke.py              # one chip: serving + statistics phases
  python chip_smoke.py --chips 4    # four chips: 4 pinned replicas vs 1

Run from the root of a checkout, in one process that holds the chips.

* serving: a few-shot QA task on qwen3-4b at its published widths (random
  weights from a seed), once with a contiguous KV cache and once paged with
  prefix sharing; the two must agree byte for byte.
* statistics: a streaming task of 10^5 examples on the simulated API
  engine, bootstrapped by the Pallas kernel; its CIs must agree with the
  host (numpy) backend within Monte-Carlo tolerance.
* ``--chips 4``: only the replica path — the serving task on four replicas,
  one per chip, must match one replica byte for byte.

Exits non-zero without a result line when JAX finds no TPU or any check
fails.  The last line of stdout is one JSON object naming the device.
Timings printed here are set-up (compilation included), not measurements.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core.stages import Middleware  # noqa: E402

#: a few-shot header longer than one 16-token KV page, shared by every
#: prompt, so paged serving has whole prefix pages to share
FEW_SHOT = (
    "Answer each question with one short fact.\n"
    "Q: What is known about gravity?\nA: gravity depends critically on "
    "temperature\n"
    "Q: What is known about glaciers?\nA: glaciers exhibits periodic "
    "behavior\n"
)
N_EXAMPLES = 16
ENGINE_KW = {"n_slots": 8, "max_len": 256}
STREAM_EXAMPLES = 100_000
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) through
    JAX's monitoring events."""

    def __init__(self) -> None:
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def qa_model():
    from repro.core.config import EngineModelConfig

    return EngineModelConfig(
        provider="local", model_name="qwen3-4b", reduced=False, max_tokens=16
    )


def qa_task(kv_page_size: int, n_replicas: int):
    from repro.core.config import (
        DataConfig,
        EvalTask,
        InferenceConfig,
        MetricConfig,
    )

    return EvalTask(
        task_id="qa-few-shot",
        model=qa_model(),
        inference=InferenceConfig(
            kv_page_size=kv_page_size, n_replicas=n_replicas
        ),
        metrics=(
            MetricConfig("exact_match"),
            MetricConfig("token_f1"),
            MetricConfig(
                "bertscore", type="semantic", params={"use_pallas": True}
            ),
        ),
        data=DataConfig(prompt_template=FEW_SHOT + "Q: {question}\nA:"),
    )


class _KeepResponses(Middleware):
    """Keeps the infer stage's engine responses."""

    def __init__(self) -> None:
        self.responses: list = []

    def on_stage_end(self, stage, art, session) -> None:
        if stage.name == "infer":
            self.responses = list(art.responses)


def check_weights(label: str, session, n_replicas: int) -> None:
    """Replica i serves bf16 weights that live on ``jax.devices()[i]``."""
    import jax
    import jax.numpy as jnp

    engines = session.engines.engines()
    check(len(engines) == n_replicas, f"{label}: {len(engines)} engines")
    for i, eng in enumerate(engines):
        table = eng._scheduler.params["embed"]["table"]
        check(table.dtype == jnp.bfloat16, f"{label}: weights {table.dtype}")
        want = jax.devices()[i]
        check(
            table.devices() == {want},
            f"{label}: replica {i} weights on {table.devices()}, want {want}",
        )


def serve_qa(kv_page_size: int, n_replicas: int, counter: CompileCounter):
    """Run the QA task in a session of its own; return what it served."""
    import jax

    from repro.core.session import EvalSession
    from repro.data import qa_examples

    label = f"serving[kv_page_size={kv_page_size}, n_replicas={n_replicas}]"
    keep = _KeepResponses()
    compiles0, t0 = counter.compiles, time.monotonic()
    with EvalSession(engine_kwargs=ENGINE_KW, middleware=[keep]) as session:
        result = session.run_task(
            qa_examples(N_EXAMPLES, seed=0), qa_task(kv_page_size, n_replicas)
        )
        batcher = session.serving_stats()[0]["batcher"]
        check_weights(label, session, n_replicas)
    check(len(keep.responses) == N_EXAMPLES, f"{label}: responses missing")
    for i, r in enumerate(keep.responses):
        check(r is not None and r.error is None, f"{label}: #{i} failed: {r}")
        check(r.output_tokens >= 1, f"{label}: #{i} has no output tokens")
    check(not result.failures, f"{label}: failures {result.failures}")
    wall = time.monotonic() - t0
    gc.collect()  # drop this session's weights before the next one
    mem = [d.memory_stats() or {} for d in jax.devices()[:n_replicas]]
    print(
        f"{label}: compiles={counter.compiles - compiles0} "
        f"prefill_recompiles={batcher['prefill_recompiles']} "
        f"tokens_generated={batcher['tokens_generated']} "
        f"prefix_tokens_saved={batcher.get('prefix_tokens_saved', 0)} "
        f"peak_bytes_in_use={','.join(str(m.get('peak_bytes_in_use')) for m in mem)} "
        f"bytes_in_use_after_close="
        f"{','.join(str(m.get('bytes_in_use')) for m in mem)} "
        f"set_up_and_run_s={wall:.1f}",
        flush=True,
    )
    for m, v in result.metrics.items():
        print(f"  {m}: value={v.value!r} ci={v.ci!r} n={v.n}", flush=True)
    return result, batcher


def _exact(metrics: dict) -> dict:
    """Metric values and CIs as exact strings (repr round-trips a float;
    NaN compares equal to NaN)."""
    return {
        m: repr((v.value, v.ci, v.n, v.n_unscored)) for m, v in metrics.items()
    }


def check_identical(label: str, a, b) -> None:
    differ = [i for i, (x, y) in enumerate(zip(a.responses, b.responses)) if x != y]
    check(
        len(a.responses) == len(b.responses) and not differ,
        f"{label}: responses differ at examples {differ}",
    )
    check(
        _exact(a.metrics) == _exact(b.metrics),
        f"{label}: metrics or CIs differ",
    )
    for m in a.scores:
        check(
            a.scores[m].tobytes() == b.scores[m].tobytes(),
            f"{label}: per-example {m} scores differ",
        )


def serving_phase(counter: CompileCounter) -> None:
    contiguous, _ = serve_qa(0, 1, counter)
    paged, batcher = serve_qa(16, 1, counter)
    check_identical("paged vs contiguous", paged, contiguous)
    check(batcher["prefix_tokens_saved"] > 0, "paged run shared no prefix")
    print("serving: paged == contiguous byte for byte", flush=True)


def statistics_phase(counter: CompileCounter) -> None:
    from repro.core.config import (
        EngineModelConfig,
        EvalTask,
        MetricConfig,
        StatisticsConfig,
        StreamingConfig,
    )
    from repro.core.session import EvalSession
    from repro.data.datasets import iter_mixed_examples

    results = {}
    for backend in ("pallas", "numpy"):
        task = EvalTask(
            task_id="mixed-stream",
            model=EngineModelConfig(provider="openai", model_name="gpt-4o-mini"),
            metrics=(MetricConfig("contains"), MetricConfig("token_f1")),
            statistics=StatisticsConfig(
                backend=backend, bootstrap_iterations=2000,
                ci_method="percentile",
            ),
            streaming=StreamingConfig(enabled=True, max_memory_rows=4096),
        )
        compiles0, t0 = counter.compiles, time.monotonic()
        with EvalSession() as session:
            results[backend] = session.run_task(
                iter_mixed_examples(STREAM_EXAMPLES, seed=1), task
            )
        print(
            f"statistics[{backend}]: examples={STREAM_EXAMPLES} "
            f"compiles={counter.compiles - compiles0} "
            f"set_up_and_run_s={time.monotonic() - t0:.1f}",
            flush=True,
        )
    stream = results["pallas"].stream_stats.engine.stream_id()
    check(stream == "pallas-kernel", f"pallas backend resolved to {stream}")
    for m, num in results["numpy"].metrics.items():
        pal = results["pallas"].metrics[m]
        width = num.ci[1] - num.ci[0]
        print(f"  {m}: pallas ci={pal.ci!r} numpy ci={num.ci!r}", flush=True)
        check(width > 0, f"{m}: degenerate numpy CI {num.ci}")
        check(pal.value == num.value, f"{m}: point estimates differ")
        check(
            abs(pal.ci[0] - num.ci[0]) <= 0.5 * width
            and abs(pal.ci[1] - num.ci[1]) <= 0.5 * width,
            f"{m}: pallas CI {pal.ci} not within MC tolerance of {num.ci}",
        )
    print(f"statistics: stream {stream}, CIs within tolerance", flush=True)


def replica_phase(counter: CompileCounter) -> None:
    four, _ = serve_qa(16, 4, counter)
    one, _ = serve_qa(16, 1, counter)
    check_identical("4 replicas vs 1", four, one)
    print("replicas: 4 pinned replicas == 1 replica byte for byte", flush=True)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the four-replica path and its one-replica twin",
    )
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
            file=sys.stderr,
        )
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    counter.install()
    t0 = time.monotonic()
    if args.chips == 4:
        replica_phase(counter)
    else:
        serving_phase(counter)
        statistics_phase(counter)
    print(
        f"total: compiles={counter.compiles} "
        f"persistent_cache_hits={counter.cache_hits} "
        f"compile_s={counter.seconds:.1f} "
        f"set_up_and_run_s={time.monotonic() - t0:.1f}",
        flush=True,
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
