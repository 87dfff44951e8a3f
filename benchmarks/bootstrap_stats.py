"""Statistics-stage scaling: numpy (B, n) weight-matrix path vs the
device/blocked chunked-partials backend (ISSUE 4 acceptance).

Measures, for the full bootstrap aggregation of a task's metrics
(replicate accumulation + percentile CI extraction):

* wall-clock — steady-state, after a same-shape warmup so the pallas
  backend's one-time XLA compile is amortized the way it is across a
  streaming run's chunks;
* peak host allocation — the process's resident-set high-water mark
  (``VmHWM``, reset through ``/proc/self/clear_refs`` before the measured
  pass) minus its resident set at the start of the pass, which captures
  allocations tracemalloc cannot see (XLA buffers); the Python-heap
  tracemalloc peak is reported alongside.  Everything runs in this one
  process, so on an accelerator host no child competes for the device.

Also cross-checks CI endpoints of the two weight streams (host Philox vs
kernel counter-mixer, run both natively and through the Pallas
interpreter) within Monte-Carlo tolerance.

Emits ``BENCH_stats.json``.

  PYTHONPATH=src python -m benchmarks.bootstrap_stats [--smoke]
"""

from __future__ import annotations

import gc
import time

from benchmarks import artifacts

#: the acceptance point: device/blocked backend must cut wall-clock >= 5x
#: and peak host allocation >= 10x vs the (B, n) weight-matrix path here
ACCEPT_N, ACCEPT_B = 100_000, 2_000
N_METRICS = 2  # one binary (exact_match-like), one continuous (token_f1-like)


def _make_scores(n: int, seed: int = 0) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    scores = {
        "exact_match": (rng.random(n) < 0.62).astype(np.float64),
        "token_f1": rng.beta(5.0, 2.0, n),
    }
    scores["token_f1"][:: max(n // 211, 2)] = np.nan  # unscorable examples
    return scores


def _aggregate(backend: str, scores: dict, n_boot: int) -> list:
    """The statistics stage, as the streaming pipeline runs it: fold the
    scores into replicate state, extract percentile CIs."""
    from repro.stats import (
        MetricAccumulator,
        make_bootstrap_engine,
        streaming_ci,
    )

    names = tuple(scores)
    engine = make_bootstrap_engine(backend, n_boot, 0, names)
    engine.update(scores, 0)
    out = []
    for m in names:
        acc = MetricAccumulator()
        acc.update(scores[m])
        iv = streaming_ci(acc, engine.view(m), method="percentile")
        out.append((m, iv.value, iv.lo, iv.hi))
    return out


def _status_mb(field: str) -> float:
    """A ``/proc/self/status`` memory field (``VmRSS``, ``VmHWM``) in MB."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(field + ":"):
                return int(line.split()[1]) / 1024
    raise KeyError(field)


def _measure(backend: str, n: int, n_boot: int) -> dict:
    """One measurement: warmup, then a measured pass."""
    import tracemalloc

    scores = _make_scores(n)
    _aggregate(backend, scores, n_boot)  # warmup: XLA compile, pools
    gc.collect()
    with open("/proc/self/clear_refs", "w") as f:
        f.write("5")  # reset VmHWM to the current resident set
    base = _status_mb("VmRSS")
    tracemalloc.start()
    t0 = time.perf_counter()
    cis = _aggregate(backend, scores, n_boot)
    wall = time.perf_counter() - t0
    _, py_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return {
        "wall_s": wall,
        "py_heap_peak_mb": py_peak / 1e6,
        "rss_peak_mb": _status_mb("VmHWM") - base,
        "cis": cis,
    }


def _parity_check(n: int = 2000, n_boot: int = 300) -> dict:
    """Host Philox vs kernel counter-mixer (native and interpreted): CI
    endpoints within Monte-Carlo tolerance of each other."""
    from repro.stats import PallasBootstrapEngine

    scores = _make_scores(n, seed=7)

    class _Interp(PallasBootstrapEngine):
        mode = "interpret"

    cis = {
        "numpy": _aggregate("numpy", scores, n_boot),
        "pallas": _aggregate("pallas", scores, n_boot),
    }
    interp_engine = _Interp(n_boot, 0, tuple(scores))
    interp_engine.update(scores, 0)
    from repro.stats import MetricAccumulator, streaming_ci

    cis["pallas_interpret"] = []
    for m in scores:
        acc = MetricAccumulator()
        acc.update(scores[m])
        iv = streaming_ci(acc, interp_engine.view(m), method="percentile")
        cis["pallas_interpret"].append((m, iv.value, iv.lo, iv.hi))

    ok = True
    for variant in ("pallas", "pallas_interpret"):
        for (m, v, lo, hi), (_, rv, rlo, rhi) in zip(
            cis[variant], cis["numpy"]
        ):
            width = max(rhi - rlo, 1e-9)
            ok &= abs(v - rv) < 1e-9  # the point estimate is exact moments
            ok &= abs(lo - rlo) <= width and abs(hi - rhi) <= width
    return {"n": n, "n_boot": n_boot, "cis": cis, "ok": bool(ok)}


def run(*, smoke: bool = False) -> list[str]:
    if smoke:
        points = [(20_000, 1_000), (ACCEPT_N, ACCEPT_B)]
    else:
        points = [
            (n, b) for n in (20_000, 100_000) for b in (1_000, 2_000)
        ]

    lines: list[str] = []
    rows = []
    for n, n_boot in points:
        row: dict = {"n": n, "n_boot": n_boot, "n_metrics": N_METRICS}
        for backend in ("numpy", "pallas"):
            r = _measure(backend, n, n_boot)
            row[backend] = {
                "wall_s": r["wall_s"],
                "py_heap_peak_mb": r["py_heap_peak_mb"],
                # the path's own high-water allocation over the pass's start
                "host_alloc_mb": max(r["rss_peak_mb"], r["py_heap_peak_mb"]),
            }
        row["speedup"] = row["numpy"]["wall_s"] / max(
            row["pallas"]["wall_s"], 1e-9
        )
        row["host_alloc_ratio"] = row["numpy"]["host_alloc_mb"] / max(
            row["pallas"]["host_alloc_mb"], 1e-3
        )
        rows.append(row)
        lines.append(
            f"bootstrap_stats_n{n}_B{n_boot},{row['pallas']['wall_s'] * 1e6:.0f},"
            f"speedup={row['speedup']:.1f}x "
            f"alloc={row['numpy']['host_alloc_mb']:.0f}MB"
            f"->{row['pallas']['host_alloc_mb']:.0f}MB "
            f"({row['host_alloc_ratio']:.0f}x)"
        )

    parity = _parity_check()
    lines.append(f"bootstrap_stats_parity,0,ok={parity['ok']}")

    accept = next(
        r for r in rows if (r["n"], r["n_boot"]) == (ACCEPT_N, ACCEPT_B)
    )
    payload = {
        "mode": "smoke" if smoke else "default",
        "n_metrics": N_METRICS,
        "points": rows,
        "parity": parity,
        "acceptance": {
            "n": ACCEPT_N,
            "n_boot": ACCEPT_B,
            "speedup": accept["speedup"],
            "host_alloc_ratio": accept["host_alloc_ratio"],
            "ok": bool(
                accept["speedup"] >= 5.0
                and accept["host_alloc_ratio"] >= 10.0
                and parity["ok"]
            ),
        },
    }
    artifacts.write_bench("BENCH_stats.json", payload)

    if not payload["acceptance"]["ok"]:
        raise RuntimeError(
            f"bootstrap stats acceptance failed: {payload['acceptance']}"
        )
    return lines


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()
    for line in run(smoke=args.smoke):
        print(line)
    print(f"wrote {artifacts.bench_path('BENCH_stats.json')}")


if __name__ == "__main__":
    main()
