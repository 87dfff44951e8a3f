"""One run of one cell: set-up, warm-up, the measured window, the check.

The cell is found by name: its entry in ``BENCHMARK.json`` names a
configuration file, a traffic mix (``traffic/<name>.json``) and its limits
(``limits/<cell>.json``); its per-layer metrics are readers in
``metrics/<metric>.py``.  A new cell or metric is new files and entries.

The window drives ``EvalSession.run_suite`` (or ``run_task`` for a single
replay task) over streaming tasks whose sources never end on their own:
they stop at the first chunk boundary after the window closes, the tasks
finish their chunks in flight and compute their CIs, and the check runs on
what they produced.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import families  # noqa: E402
import tracing  # noqa: E402
from probes import Probes  # noqa: E402
from traffic import Mix  # noqa: E402

#: a chunk's results may arrive this long after the window closes
DRAIN_S = 60.0
#: the traced window of a ``--trace 1`` run is at most this long
TRACE_S = 10.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json") from None
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in moves else [])
    ]
    return Cell(
        name=name,
        chips=wl["chips"],
        config=json.loads((root / entry["file"]).read_text()),
        mix=json.loads((HERE / "traffic" / f"{wl['traffic']}.json").read_text()),
        limits=json.loads((HERE / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def register_config(config: dict, family_dir: Path | None = None) -> str:
    """Put the configuration in the program's architecture registry under
    its own name, after checking it against the file's published sizes.

    The check goes through the module of the file's family,
    ``families/<model_type>.py`` (:mod:`families`; ``qwen2`` and ``qwen3``
    today): it refuses published keys the program has no field for, and
    every program field it maps, with ``padded_vocab``, has to equal the
    program's after ``program.replace``.  A ``model_type`` with no module
    stops here; a new family is a new module, and nothing here changes.
    """
    from repro.configs import ARCHS

    family = families.load(config["model_type"], family_dir or families.HERE)
    refused = family.refuse(config)
    if refused:
        raise ValueError(
            f"{config['name']}: families/{config['model_type']}.py refuses "
            f"{refused}: the program has no field for the values stated"
        )
    prog = config["program"]
    cfg = ARCHS[prog["arch"]]
    if prog.get("replace") or cfg.name != config["name"]:
        cfg = cfg.replace(name=config["name"], **prog.get("replace", {}))
    want = dict(
        family.program_fields(config), padded_vocab=config["weights"]["padded_vocab"]
    )
    have = {k: getattr(cfg, k) for k in want}
    if have != want:
        bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
        raise ValueError(f"served config differs from {config['name']}: {bad}")
    ARCHS[config["name"]] = cfg
    return config["name"]


class CompileCounter:
    """Compilations (persistent-cache loads included), by JAX's events."""

    def __init__(self) -> None:
        self.count = 0

    def install(self) -> None:
        import jax

        def on(event: str, duration: float, **_kw) -> None:
            if event == COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on)


class ChunkClock:
    """Middleware stamping the moment each streaming chunk's scores are
    merged into its task's statistics."""

    def __init__(self) -> None:
        from repro.core.stages import Middleware

        clock = self

        class _M(Middleware):
            def on_chunk_end(self, chunk_index, state, session) -> None:
                clock.ends.append((time.monotonic(), state["n_rows"]))

        self.ends: list[tuple[float, int]] = []
        self.middleware = _M()


def _tasks(cell: Cell, model_name: str, weight_seed: int, cache_dir: str = ""):
    from repro.core.config import (
        CachePolicy, DataConfig, EngineModelConfig, EvalTask, InferenceConfig,
        MetricConfig, StatisticsConfig, StreamingConfig,
    )

    spec = cell.mix
    model = EngineModelConfig(
        provider="local", model_name=model_name, reduced=False,
        seed=weight_seed, **spec["model"],
    )
    inf_kw = dict(spec["inference"])
    if cache_dir:
        inf_kw.update(cache_dir=cache_dir, cache_policy=CachePolicy.REPLAY)
    # a local engine has no provider quota: the limiter must never bind
    inf = InferenceConfig(rate_limit_rpm=1e12, rate_limit_tpm=1e15, **inf_kw)
    metrics = tuple(
        MetricConfig(m["name"], m.get("type", "lexical"), dict(m.get("params", {})))
        for m in spec["metrics"]
    )
    return model, inf, [
        EvalTask(
            task_id=t["id"], model=model, inference=inf, metrics=metrics,
            statistics=StatisticsConfig(**spec["statistics"]),
            data=DataConfig(prompt_template=spec["template"]),
            streaming=StreamingConfig(enabled=True, **spec["streaming"]),
        )
        for t in spec["tasks"]
    ]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def _serving_stats(session) -> tuple[dict, list[dict]]:
    """The fleet's ``BatcherStats``, summed over replicas, and each
    replica's ``ServiceStats`` with its own ``BatcherStats`` (none where
    there is one replica)."""
    snaps = session.serving_stats()
    snap = snaps[0] if snaps else {}
    return dict(snap.get("batcher", {})), list(snap.get("replica_stats", []))


def _union(spans: list[tuple[float, float]], t0: float, t1: float) -> float:
    clipped = sorted((max(a, t0), min(b, t1)) for a, b in spans if b > t0 and a < t1)
    total, end = 0.0, -math.inf
    for a, b in clipped:
        if b > end:
            total += b - max(a, end)
            end = b
    return total


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    replicas0: list = dataclasses.field(default_factory=list)
    replicas1: list = dataclasses.field(default_factory=list)
    compiles: int = 0
    trace_dir: str = ""

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _open_window(win: Window, session, counter, trace: bool) -> None:
    import jax

    if trace:
        win.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(win.trace_dir, profiler_options=_profiler_options())
    win.stats0, win.replicas0 = _serving_stats(session)
    win.compiles = counter.count
    # name on stderr anything that compiles inside the window
    jax.config.update("jax_log_compiles", True)
    win.t0 = time.monotonic()
    print("window open", file=sys.stderr, flush=True)


def _close_window(win: Window, session, counter, trace: bool) -> None:
    import jax

    win.t1 = time.monotonic()
    jax.config.update("jax_log_compiles", False)
    print("window closed", file=sys.stderr, flush=True)
    win.stats1, win.replicas1 = _serving_stats(session)
    win.compiles = counter.count - win.compiles
    if trace:
        jax.profiler.stop_trace()


def _run_in_thread(fn) -> tuple[threading.Thread, dict]:
    box: dict = {}

    def target():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — reported by the caller
            box["error"] = e

    t = threading.Thread(target=target, name="bench-window", daemon=True)
    t.start()
    return t, box


def _wait(pred, thread, timeout: float) -> None:
    end = time.monotonic() + timeout
    while not pred() and thread.is_alive() and time.monotonic() < end:
        time.sleep(0.01)


def drive_suite(cell, mix, session, tasks, seconds, trace, probes, counter):
    from repro.core.suite import EvalSuite

    page = cell.mix["inference"].get("kv_page_size") or 16
    warm = EvalSuite("warm-up")
    for i, task in enumerate(tasks):
        # the first task on each replica sweeps its page counts
        if cell.mix["warmup"] == "every_task" or i < mix.n_replicas:
            warm.add_task(task, mix.warmup_rows(i, page))
    session.run_suite(warm, parallel_jobs=len(warm.task_ids()))

    main = EvalSuite("window")
    for i, task in enumerate(tasks):
        main.add_task(task, lambda i=i: mix.stream(i))
    n0 = len(probes.done_t)
    outstanding = len(tasks) * cell.mix["streaming"]["max_memory_rows"]
    thread, box = _run_in_thread(
        lambda: session.run_suite(main, parallel_jobs=len(tasks))
    )
    _wait(lambda: len(probes.done_t) - n0 >= outstanding // 2, thread, 600)
    win = Window()
    _open_window(win, session, counter, trace)
    length = min(seconds, TRACE_S) if trace else seconds
    _wait(lambda: time.monotonic() >= win.t0 + length, thread, length + 1)
    _close_window(win, session, counter, trace)
    mix.stop.set()
    thread.join(length + DRAIN_S + 600)
    if "error" in box:
        raise box["error"]
    if thread.is_alive():
        raise TimeoutError("window tasks did not finish after the window closed")
    results = {k[1]: v for k, v in box["result"].results.items()}
    return win, results


def fill_replay_cache(cell, mix, cache_dir: str, model) -> list[dict]:
    """Write the cached run the way a recording run writes it: one commit
    per shard of examples, keyed by the program's content address."""
    from repro.core.cache import CacheEntry, ResponseCache
    from repro.core.config import CachePolicy

    rows = mix.replay_rows()
    cache = ResponseCache(cache_dir, CachePolicy.ENABLED)
    shard = cell.mix["replay"]["shard"]
    for lo in range(0, len(rows), shard):
        entries = []
        for i in range(lo, min(lo + shard, len(rows))):
            prompt = mix.prompt(rows[i])
            text = mix.replay_response(i, prompt)
            entries.append(CacheEntry(
                prompt_hash=cache.key_for(
                    prompt, model.model_name, model.provider,
                    model.temperature, model.max_tokens,
                ),
                model_name=model.model_name, provider=model.provider,
                prompt_text=prompt, response_text=text,
                input_tokens=len(prompt.split()), output_tokens=len(text.split()),
                latency_ms=0.0, created_at=time.time(),
            ))
        cache.put(entries)
    return rows


def drive_replay(cell, mix, session, task, rows, seconds, trace, probes,
                 counter, clock):
    chunk = cell.mix["streaming"]["max_memory_rows"]

    def stream(first: int, warm: bool):
        i = first
        while True:
            if not warm and i % chunk == 0 and mix.stop.is_set():
                return
            if warm and i >= first + chunk:
                return
            k = i % len(rows)
            yield dict(rows[k], uid=f"{task.task_id}:{i}", cached=k)
            i += 1

    session.run_task(stream(check.WARM_BASE, True), task)
    n0 = len(clock.ends)
    thread, box = _run_in_thread(lambda: session.run_task(stream(0, False), task))
    _wait(lambda: len(clock.ends) - n0 >= 1, thread, 600)
    win = Window()
    _open_window(win, session, counter, trace)
    length = min(seconds, TRACE_S) if trace else seconds
    _wait(lambda: time.monotonic() >= win.t0 + length, thread, length + 1)
    _close_window(win, session, counter, trace)
    mix.stop.set()
    thread.join(length + DRAIN_S + 600)
    if "error" in box:
        raise box["error"]
    if thread.is_alive():
        raise TimeoutError("replay task did not finish after the window closed")
    return win, {task.task_id: box["result"]}


# -- end-to-end metrics ------------------------------------------------------------


def served_rate_and_tail(probes, win: Window) -> tuple[float, float, int]:
    """Examples whose response returned in the window, per second of the
    window, and the 95th percentile of submission-to-return time."""
    done = [
        (t, t - probes.submit_t[p]) for p, t in probes.done_t.items()
        if win.t0 <= t <= win.t1 and p in probes.submit_t
    ]
    lat = np.asarray([d for _, d in done]) * 1e3
    p95 = float(np.percentile(lat, 95)) if len(lat) else math.nan
    return len(done) / win.seconds, p95, len(done)


def chunk_rate(clock: ChunkClock, win: Window) -> tuple[float, int]:
    """Whole chunks over the time they took: the examples of the chunks
    merged after the first merge inside the window, over the time from that
    merge to the last one inside it."""
    ends = sorted(e for e in clock.ends if win.t0 <= e[0] <= win.t1)
    if len(ends) < 2:
        return math.nan, 0
    n = sum(k for _, k in ends[1:])
    return n / (ends[-1][0] - ends[0][0]), n


# -- per-layer metrics ---------------------------------------------------------------


@dataclasses.dataclass
class ReadContext:
    config: dict
    mix: dict
    peaks: dict | None
    window: Window
    probes: Probes
    events: list | None
    chips: int = 1
    union: Any = staticmethod(_union)


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(cell: Cell, ctx: ReadContext) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- one run --------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, control: bool,
             start: float, devices, peaks: dict | None) -> dict:
    from repro.core.session import EvalSession
    from repro.core.stages import ScoreStage

    model_name = register_config(cell.config)
    weight_seed = int(seed) % (2**31 - 1)
    mix = Mix(cell.mix, seed)
    probes = Probes(trace=trace)
    counter = CompileCounter()
    counter.install()
    clock = ChunkClock()
    replay = cell.mix["kind"] == "replay"
    cache_dir = tempfile.mkdtemp(prefix="bench-cache-") if replay else ""
    used = devices[: cell.chips]
    probes.attach_score_stage(ScoreStage)
    try:
        model, inf, tasks = _tasks(cell, model_name, weight_seed, cache_dir)
        session = EvalSession(
            engine_kwargs=dict(cell.mix["engine"]), middleware=[clock.middleware]
        )
        try:
            rows = fill_replay_cache(cell, mix, cache_dir, model) if replay else None
            probes.attach_service(session.service_for(model, inf), not replay)
            if replay:
                probes.attach_cache(session.cache_for(inf))
                win, results = drive_replay(
                    cell, mix, session, tasks[0], rows, seconds, trace, probes,
                    counter, clock,
                )
            else:
                win, results = drive_suite(
                    cell, mix, session, tasks, seconds, trace, probes, counter
                )
            setup_s = win.t0 - start
            memory_peak = _memory_peak(used)
            engine_calls = session.accounting.engine_calls
            failures = sum(len(r.failures) for r in results.values())
            n_examples = sum(
                r.logs.get("streaming", {}).get("n_examples", 0)
                for r in results.values()
            )
        finally:
            session.close()
            probes.detach()
        del session
        gc.collect()

        if replay:
            rate, n_window = chunk_rate(clock, win)
            e2e = {"rescored_examples_per_s": rate, "setup_s": setup_s}
        else:
            rate, p95, n_window = served_rate_and_tail(probes, win)
            e2e = {"examples_per_s": rate, "example_latency_p95_ms": p95,
                   "setup_s": setup_s}

        numbers, controls = {}, {}
        if replay:
            numbers["engine_calls"] = engine_calls
            numbers.update(check.replay_texts(probes, mix))
        else:
            numbers.update(check.prompts_and_texts(
                probes, mix, cell.config["vocab_size"],
                cell.mix["engine"].get("max_len"),
            ))
        n, c = check.scores(probes, control)
        numbers.update(n)
        controls.update(c)
        n, c = check.cis(probes, results, cell.mix["statistics"], control)
        numbers.update(n)
        controls.update(c)
        if not replay:
            reference = _reference(cell.config, weight_seed)
            per_replica = cell.mix["check"].get("per_replica", 0)
            requests = check.sample_requests(
                probes, seed, cell.mix["check"]["sample_requests"], per_replica
            )
            if per_replica:
                numbers["replicas_short"] = check.replicas_short(
                    probes, mix.n_replicas, per_replica
                )
            n, c = check.logits(reference, requests, control)
            numbers.update(n)
            controls.update(c)
            del reference
            gc.collect()
        correct, shown = check.verdict(numbers, cell.limits)
        correct = correct and failures == 0 and n_window > 0
        if control:
            program = {"correct": bool(correct), "checks": shown}
            # the control in the program's place: its own readings, and the
            # program's for the exact numbers it has no reading of
            correct, shown = check.verdict({**numbers, **controls}, cell.limits)
            correct = correct and failures == 0 and n_window > 0
            faults = {"ci_gap.half_chunk": check.half_chunk_ci_gap(
                probes, results, cell.mix["statistics"],
                cell.mix["streaming"]["max_memory_rows"],
            )}

        result = {
            "correct": bool(correct),
            "attempted": int(n_examples),
            "failed": int(failures),
        }
        if trace:
            events = tracing.load(win.trace_dir)
            shutil.rmtree(win.trace_dir, ignore_errors=True)
            ctx = ReadContext(
                cell.config, cell.mix, peaks, win, probes, events, len(used)
            )
            result["metrics"] = read_per_layer(cell, ctx)
            busy = tracing.busy_seconds(events)
        else:
            names = {m["name"]: m["unit"] for m in cell.end_to_end}
            result["metrics"] = {
                k: {"value": v, "unit": names[k]} for k, v in e2e.items() if k in names
            }
        dev = used[0]
        result["device"] = {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(used),
            "memory_peak_bytes": memory_peak,
        }
        if trace:
            result["device"].update(busy_s=busy, window_s=win.seconds)
            result["breakdown"] = {
                "device_ops": tracing.top_ops(events),
                "idle_gaps": tracing.idle_gaps(events),
            }
        result["window"] = {
            "examples": n_window, "compiles": win.compiles,
            "served_tokens": numbers.get("served_tokens", 0),
        }
        if control:
            result.update(program=program, controls=controls, faults=faults)
        result["checks"] = shown
        return result
    finally:
        if cache_dir:
            shutil.rmtree(cache_dir, ignore_errors=True)


def _reference(config: dict, seed: int):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{config['reference']}",
        HERE / "reference" / f"{config['reference']}.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Reference(config, seed)
