"""FLOP / byte / collective accounting via unrolled, reduced lowerings.

``compiled.cost_analysis()`` counts a while-loop body once regardless of
trip count (verified empirically; see ``repro.models.unroll``), so a scanned
model structurally under-reports compute and collective traffic.  The
accounting pass therefore lowers the SAME cell with every scan fully
unrolled — but unrolling an 80-layer model over a 32k-token attention block
grid would explode compile time, so two exact reductions are applied and
extrapolated:

* **Depth**: per-step cost is exactly linear in the repeated-layer count
  (layers for dense/MoE/SSM, shared-attention groups for the hybrid,
  enc+dec layer pairs for whisper).  Measure at two small depths, fit the
  line, evaluate at the true depth.
* **Sequence**: per-step cost is a polynomial of degree <= 2 in S (matmuls
  and embeddings linear; attention block grids and MoE dispatch — capacity
  proportional to S — quadratic; decode steps degree <= 1 in context).
  Measure at 2-3 reduced S points, fit, evaluate at the true S.  Fit points
  are multiples of 512 so MoE capacity rounding stays exactly linear.

Both reductions are exact-by-construction (polynomial interpolation of a
polynomial), not approximations.  Train cells use ``microbatches=1``:
FLOPs / bytes / wire are microbatch-invariant (same tokens, same collective
set); memory realism comes from the separate memory pass in ``dryrun.py``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import hlo_analysis
from repro.launch.cells import build_cell
from repro.models.unroll import unroll_scans

PREFILL_FIT_POINTS = (2048, 4096, 8192)
METRICS = ("flops", "bytes", "wire")


@dataclasses.dataclass
class Accounting:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    fit_points: list[dict]
    fit_seconds: float


def depth_variants(cfg: ModelConfig) -> tuple[ModelConfig, int, ModelConfig, int, int]:
    """(small_cfg, n_small, large_cfg, n_large, n_true) — n is the linear
    depth variable for this family."""
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        g_true = cfg.n_layers // k
        tail = cfg.n_layers - g_true * k
        # g=1 compiles to a different SPMD strategy; 2 vs 3 is stabler
        return (
            cfg.replace(n_layers=2 * k + tail), 2,
            cfg.replace(n_layers=3 * k + tail), 3,
            g_true,
        )
    if cfg.family == "encdec":
        return (
            cfg.replace(n_layers=2, n_encoder_layers=2), 2,
            cfg.replace(n_layers=4, n_encoder_layers=4), 4,
            cfg.n_layers,
        )
    if cfg.n_experts and cfg.first_k_dense:
        fk = cfg.first_k_dense
        return (
            cfg.replace(n_layers=fk + 2), 2,
            cfg.replace(n_layers=fk + 4), 4,
            cfg.n_layers - fk,
        )
    return (
        cfg.replace(n_layers=2), 2,
        cfg.replace(n_layers=4), 4,
        cfg.n_layers,
    )


def _measure(
    arch: str, shape: str, mesh, scfg: ShapeConfig, cfg: ModelConfig, remat: str,
    **cell_kw,
) -> dict:
    cell = build_cell(
        arch, shape, mesh, scfg=scfg, cfg=cfg, microbatches=1, remat=remat,
        **cell_kw,
    )
    t0 = time.time()
    with mesh:
        with unroll_scans():
            lowered = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
            ).lower(*cell.args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        coll = hlo_analysis.collective_stats(compiled.as_text(), mesh.size)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.wire_bytes,
        "compile_s": round(time.time() - t0, 1),
    }


def measure_point(
    arch: str, shape: str, mesh, seq_len: int, *, remat: str = "full", **cell_kw
) -> dict:
    """Full-depth value at one S via two reduced-depth lowerings."""
    scfg0 = SHAPES[shape]
    scfg = ShapeConfig(scfg0.name, seq_len, scfg0.global_batch, scfg0.kind)
    cfg = get_config(arch)
    c_small, n_small, c_large, n_large, n_true = depth_variants(cfg)
    small = _measure(arch, shape, mesh, scfg, c_small, remat, **cell_kw)
    large = _measure(arch, shape, mesh, scfg, c_large, remat, **cell_kw)
    out = {"seq_len": seq_len, "depth_points": [small, large],
           "depths": [n_small, n_large, n_true]}
    for m in METRICS:
        slope = (large[m] - small[m]) / (n_large - n_small)
        if slope < 0:
            # compiler non-monotonicity at tiny depth (e.g. different SPMD
            # strategy at g=1): fall back to proportional scaling from the
            # larger, more representative depth
            out[m] = large[m] * n_true / max(n_large, 1)
        else:
            out[m] = small[m] + slope * (n_true - n_small)
    out["compile_s"] = small["compile_s"] + large["compile_s"]
    return out


def _fit_eval(xs, ys, deg: int, x_true: int) -> float:
    coeffs = np.polyfit(np.asarray(xs, np.float64), np.asarray(ys, np.float64), deg)
    return max(float(np.polyval(coeffs, x_true)), 0.0)


def account_cell(
    arch: str,
    shape: str,
    mesh,
    *,
    remat: str = "full",
    fit_points: tuple[int, ...] | None = None,
    **cell_kw,
) -> Accounting:
    scfg = SHAPES[shape]
    if scfg.kind == "decode":
        # decode has no attention-block loops: the unrolled lowering is
        # cheap even at the true context length -> measure directly
        pts, deg = fit_points or (scfg.seq_len,), 1
    elif scfg.kind == "train":
        # train_4k is 8x8 attention blocks per layer at reduced depth ->
        # also affordable directly (zero extrapolation error)
        pts, deg = fit_points or (scfg.seq_len,), 2
    else:
        pts, deg = fit_points or PREFILL_FIT_POINTS, 2
    pts = tuple(p for p in pts if p <= scfg.seq_len) or (scfg.seq_len,)
    if scfg.seq_len <= max(pts):
        pts = (scfg.seq_len,)

    t0 = time.time()
    samples = [
        measure_point(arch, shape, mesh, s, remat=remat, **cell_kw) for s in pts
    ]
    if len(samples) == 1:
        vals = {m: samples[0][m] for m in METRICS}
    else:
        xs = [s["seq_len"] for s in samples]
        d = min(deg, len(xs) - 1)
        vals = {
            m: _fit_eval(xs, [s[m] for s in samples], d, scfg.seq_len)
            for m in METRICS
        }
    return Accounting(
        flops_per_device=vals["flops"],
        bytes_per_device=vals["bytes"],
        wire_bytes_per_device=vals["wire"],
        fit_points=samples,
        fit_seconds=round(time.time() - t0, 1),
    )
