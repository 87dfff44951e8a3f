import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and record memory / cost / collective analysis.

The two lines above MUST stay first: jax locks the device count at first
init, and the dry-run needs 512 placeholder CPU devices to build the
2x16x16 production mesh.  Never set this in conftest.py — tests and
benchmarks see the real single device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --all-shapes
  PYTHONPATH=src python -m repro.launch.dryrun --all          # full 32-cell grid
Add --multi-pod for the 512-chip mesh (default: single-pod 16x16).
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, applicable_shapes, get_config
from repro.launch import hlo_analysis
from repro.launch.accounting import account_cell
from repro.launch.cells import all_cells, build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline
from repro.models.model import active_param_count, build_model


def run_cell(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    out_dir: str = "experiments/dryrun",
    microbatches: int | None = None,
    remat: str = "full",
    save_hlo: bool = False,
    tag: str = "",
    skip_accounting: bool = False,
) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    cell = build_cell(
        arch, shape, mesh, microbatches=microbatches, remat=remat
    )

    # --- memory pass: real scanned config -> compile proof + memory stats ---
    with mesh:
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()

    coll = hlo_analysis.collective_stats(hlo_text, chips)
    cfg = get_config(arch)
    scfg = SHAPES[shape]
    active = active_param_count(cfg, build_model(cfg).param_specs())

    # --- accounting pass: unrolled reduced fit (loop-accurate) --------------
    # The roofline table is single-pod only (assignment): multi-pod runs are
    # compile-success + memory proofs, so they skip the accounting lowerings.
    if multi_pod:
        skip_accounting = True
    if skip_accounting:
        hlo_flops = float(cost.get("flops", 0.0))
        hlo_bytes = float(cost.get("bytes accessed", 0.0))
        wire = coll.wire_bytes
        acct_points = None
    else:
        acct = account_cell(arch, shape, mesh, remat=remat)
        hlo_flops = acct.flops_per_device
        hlo_bytes = acct.bytes_per_device
        wire = acct.wire_bytes_per_device
        acct_points = acct.fit_points

    rl = roofline(
        cfg=cfg,
        scfg=scfg,
        chips=chips,
        hlo_flops_per_device=hlo_flops,
        hlo_bytes_per_device=hlo_bytes,
        wire_bytes_per_device=wire,
        active_params=active,
    )

    record = {
        "arch": arch,
        "shape": shape,
        "kind": cell.kind,
        "mesh": mesh_name,
        "chips": chips,
        "ok": True,
        "meta": cell.meta,
        "timing": {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost": {
            "flops_per_device": hlo_flops,
            "bytes_per_device": hlo_bytes,
            "structural_flops": float(cost.get("flops", 0.0)),
            "structural_bytes": float(cost.get("bytes accessed", 0.0)),
            "accounting_fit": acct_points,
        },
        "collectives": {
            "wire_bytes_per_device": wire,
            "structural_wire_bytes": coll.wire_bytes,
            "by_op": coll.by_op,
        },
        "roofline": {
            "compute_s": rl.compute_s,
            "memory_s": rl.memory_s,
            "collective_s": rl.collective_s,
            "dominant": rl.dominant,
            "bound_s": rl.bound_s,
            "model_flops": rl.model_flops,
            "useful_ratio": rl.useful_ratio,
            "mfu_bound": rl.mfu_bound,
            "active_params": active,
        },
    }

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}_{shape}_{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo_text)

    hbm = record["memory"]["peak_bytes_est"] / 1e9
    print(
        f"[dryrun] {arch} x {shape} x {mesh_name}: OK  "
        f"compile={t_compile:.1f}s  peak≈{hbm:.2f}GB/dev  "
        f"flops/dev={hlo_flops:.3e}  wire/dev={wire:.3e}B  "
        f"dominant={rl.dominant}  bound={rl.bound_s*1e3:.2f}ms  "
        f"mfu_bound={rl.mfu_bound:.3f}"
    )
    print(f"  memory_analysis: {mem}")
    interesting = {
        k: v for k, v in cost.items() if k in ("flops", "bytes accessed")
    }
    print(f"  cost_analysis: {interesting}")
    return record


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(ARCHS), default=None)
    p.add_argument("--shape", choices=sorted(SHAPES), default=None)
    p.add_argument("--all-shapes", action="store_true")
    p.add_argument("--all", action="store_true", help="full 32-cell grid")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out-dir", default="experiments/dryrun")
    p.add_argument("--microbatches", type=int, default=None)
    p.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    p.add_argument("--save-hlo", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument(
        "--skip-accounting",
        action="store_true",
        help="memory/compile pass only (no unrolled FLOP-fit lowerings)",
    )
    args = p.parse_args()

    if args.all:
        grid = all_cells()
    elif args.arch and args.all_shapes:
        grid = [(args.arch, s) for s in applicable_shapes(get_config(args.arch))]
    elif args.arch and args.shape:
        grid = [(args.arch, args.shape)]
    else:
        p.error("need --arch/--shape, --arch/--all-shapes, or --all")

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = []
    for arch, shape in grid:
        for mp in meshes:
            try:
                run_cell(
                    arch,
                    shape,
                    multi_pod=mp,
                    out_dir=args.out_dir,
                    microbatches=args.microbatches,
                    remat=args.remat,
                    save_hlo=args.save_hlo,
                    tag=args.tag,
                    skip_accounting=args.skip_accounting,
                )
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] {arch} x {shape} multi_pod={mp}: FAIL {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")


if __name__ == "__main__":
    main()
