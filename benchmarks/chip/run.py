"""Run one benchmark cell once and print its result as the last line.

  python3 benchmarks/chip/run.py --workload qwen3-4b.fewshot --seed 7 \
      --seconds 30 --trace 0

Run from the root of a checkout, in one process that holds the chips the
cell asks for.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a window of its own (at most 10 s), with the device's busy time
and a breakdown.  ``--control 1`` puts the controls (the references one
precision lower) in the program's place: ``correct`` and ``checks`` are
then theirs and have to come out false, while ``program`` keeps the
program's own verdict; the benchmark's own runs leave it off.

Exits non-zero without a result when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from ``peaks.json``.  JAX's
persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR`` when it
is set and in ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"),
    )
    # every program, however quick to compile, comes from the cache after
    # the first run: set-up then does the same work on every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"run.py: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 2

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), bool(args.control),
        START, devices, peaks[kind],
    )
    for name, c in result.get("program", {}).get("checks", {}).items():
        print(f"program check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
