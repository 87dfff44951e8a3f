"""BERTScore greedy-matching Pallas TPU kernel.

Token-pair similarity is an (Lc x D) . (D x Lr) matmul — MXU work — followed
by masked row/column maxima and mean reductions.  TPU-native design
(DESIGN.md §6): grid = (B, nLr) with the ref-length axis innermost; one
program holds the candidate tile (Lc x D) and one ref tile (bLr x D) in
VMEM, accumulates the running row-max (over ref tiles) in VMEM scratch and
the column-max means incrementally; P/R emit on the last tile.  The F1
epilogue lives in ops.py.

Embeddings are normalized in-kernel (rsqrt of row norms) so the matmul
computes cosine similarity directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _normalize(x: jax.Array) -> jax.Array:
    norm2 = jnp.sum(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.maximum(norm2, 1e-18))


def _kernel(
    cand_ref,   # (1, Lc, D)
    cmask_ref,  # (1, Lc, 1) — column layout: masks rows of the sim tile
    refs_ref,   # (1, bLr, D)
    rmask_ref,  # (1, 1, bLr) — row layout: masks columns of the sim tile
    p_ref,      # (1, 1, 1) out — precision
    r_ref,      # (1, 1, 1) out — recall
    rowmax_ref,  # VMEM (Lc, 1) f32 — running max over ref tiles
    colsum_ref,  # VMEM (1, 1) f32 — sum of col maxima (ref tokens)
    colcnt_ref,  # VMEM (1, 1) f32 — count of valid ref tokens
    *,
    n_tiles: int,
):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        rowmax_ref[...] = jnp.full_like(rowmax_ref, NEG_INF)
        colsum_ref[...] = jnp.zeros_like(colsum_ref)
        colcnt_ref[...] = jnp.zeros_like(colcnt_ref)

    c = _normalize(cand_ref[0].astype(jnp.float32))   # (Lc, D)
    r = _normalize(refs_ref[0].astype(jnp.float32))   # (bLr, D)
    cm = cmask_ref[0] > 0.5                            # (Lc, 1)
    rm = rmask_ref[0] > 0.5                            # (1, bLr)

    sim = jax.lax.dot_general(
        c, r, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Lc, bLr)
    sim = jnp.where(cm & rm, sim, NEG_INF)

    rowmax_ref[...] = jnp.maximum(
        rowmax_ref[...], jnp.max(sim, axis=1, keepdims=True)
    )
    col_max = jnp.max(sim, axis=0, keepdims=True)  # (1, bLr)
    colsum_ref[...] += jnp.sum(jnp.where(rm, col_max, 0.0), keepdims=True)
    colcnt_ref[...] += jnp.sum(rm.astype(jnp.float32), keepdims=True)

    @pl.when(it == n_tiles - 1)
    def _final():
        denom_c = jnp.maximum(
            jnp.sum(cm.astype(jnp.float32), keepdims=True), 1.0
        )
        p_ref[0] = (
            jnp.sum(jnp.where(cm, rowmax_ref[...], 0.0), keepdims=True)
            / denom_c
        )
        r_ref[0] = colsum_ref[...] / jnp.maximum(colcnt_ref[...], 1.0)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def bertscore_pr(
    cand: jax.Array,       # (B, Lc, D)
    ref: jax.Array,        # (B, Lr, D)
    cand_mask: jax.Array,  # (B, Lc)
    ref_mask: jax.Array,   # (B, Lr)
    *,
    block_r: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, lc, d = cand.shape
    lr = ref.shape[1]
    br = min(block_r, lr)
    n_tiles = (lr + br - 1) // br
    pad = n_tiles * br - lr
    if pad:
        ref = jnp.pad(ref, ((0, 0), (0, pad), (0, 0)))
        ref_mask = jnp.pad(ref_mask, ((0, 0), (0, pad)))

    kernel = functools.partial(_kernel, n_tiles=n_tiles)
    # every block's last two dims are (8, 128)-aligned or span the array:
    # masks travel as (B, Lc, 1) columns / (B, 1, Lr) rows, P and R as
    # (B, 1, 1)
    p, r = pl.pallas_call(
        kernel,
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec((1, lc, d), lambda ib, it: (ib, 0, 0)),
            pl.BlockSpec((1, lc, 1), lambda ib, it: (ib, 0, 0)),
            pl.BlockSpec((1, br, d), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec((1, 1, br), lambda ib, it: (ib, 0, it)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1), lambda ib, it: (ib, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda ib, it: (ib, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((lc, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        cand,
        cand_mask.astype(jnp.float32)[:, :, None],
        ref,
        ref_mask.astype(jnp.float32)[:, None, :],
    )
    return p[:, 0, 0], r[:, 0, 0]
