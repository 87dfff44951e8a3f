"""Grouped matrix multiplication over held experts: Pallas TPU kernel.

``lhs`` rows are sorted by group, and every group's rows are padded to a
whole number of row tiles, so a row tile belongs to exactly one group and
multiplies that group's ``rhs`` matrix.  Grid = (N tiles, K tiles, row
tiles), row tiles innermost: for one (N, K) block the consecutive tiles of
a group keep the same ``rhs`` block index, so the pipeline fetches each
held group's weights once per block, and a group with no rows is never
fetched.  Tiles past the groups' total map onto the last active tile's
blocks (no new copy) and skip their compute.  The (M, tn) f32 output block
stays in VMEM across the K and row-tile axes; untouched rows stay zero.
``rhs`` may carry every layer's stacks, (L, G, K, N), with the layer to use
given as a scalar: the kernel then reads that layer's blocks in place,
where a slice taken outside it would be copied out whole first.

The op is named ``expert_gmm`` in the trace (the jitted function and the
``pallas_call`` both carry the name), which is how the benchmark finds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes one VMEM block may take; two of each are live (double buffering)
_BLOCK_BYTES = 4 << 20


def _tile(dim: int, other: int, itemsize: int) -> int:
    """The widest tile of ``dim`` that divides it, is a multiple of 128 (or
    the whole dim) and keeps an ``other`` x tile block under the budget."""
    if dim % 128:
        return dim
    t = dim
    while t > 128 and (dim % t or other * t * itemsize > _BLOCK_BYTES):
        t -= 128
    return t


def tile_groups(group_sizes: jax.Array, m: int, tile_rows: int):
    """(group of each row tile, number of active tiles) for row-tile-aligned
    ``group_sizes``."""
    ends = jnp.cumsum(group_sizes)
    starts = jnp.arange(m // tile_rows, dtype=jnp.int32) * tile_rows
    groups = jnp.searchsorted(ends, starts, side="right").astype(jnp.int32)
    groups = jnp.minimum(groups, group_sizes.shape[0] - 1)
    return groups, (ends[-1] // tile_rows).astype(jnp.int32)[None]


def _kernel(groups_ref, active_ref, layer_ref, lhs_ref, rhs_ref, out_ref, *,
            tile_rows):
    del groups_ref, layer_ref
    k_i, i = pl.program_id(1), pl.program_id(2)

    @pl.when((k_i == 0) & (i == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < active_ref[0])
    def _accumulate():
        r = pl.multiple_of(i * tile_rows, tile_rows)
        out_ref[pl.ds(r, tile_rows), :] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def expert_gmm(
    lhs: jax.Array,          # (M, K), rows sorted by group, tile-aligned
    rhs: jax.Array,          # (G, K, N), or (L, G, K, N) with ``layer``
    group_sizes: jax.Array,  # (G,) int32, multiples of tile_rows, sum <= M
    layer: jax.Array | None = None,  # () int32: the layer of a stacked rhs
    *,
    tile_rows: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Rows of group g times ``rhs[g]`` (``rhs[layer, g]``), as float32
    (M, N); rows past the groups' total are zero."""
    if layer is None:
        rhs, layer = rhs[None], 0
    m, k = lhs.shape
    g, _, n = rhs.shape[1:]
    if m % tile_rows:
        raise ValueError(f"{m} rows are not whole tiles of {tile_rows}")
    tn = _tile(n, m, 4)
    tk = _tile(k, tn, rhs.dtype.itemsize)
    groups, active = tile_groups(group_sizes.astype(jnp.int32), m, tile_rows)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row(i, active_ref):
        # past the last active tile: stay on its blocks, so nothing is copied
        return jnp.minimum(i, jnp.maximum(active_ref[0] - 1, 0))

    def lhs_map(n_i, k_i, i, groups_ref, active_ref, layer_ref):
        return row(i, active_ref), k_i

    def rhs_map(n_i, k_i, i, groups_ref, active_ref, layer_ref):
        return layer_ref[0], groups_ref[row(i, active_ref)], k_i, n_i

    def out_map(n_i, k_i, i, groups_ref, active_ref, layer_ref):
        return 0, n_i

    return pl.pallas_call(
        functools.partial(_kernel, tile_rows=tile_rows),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, m // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, tk), lhs_map),
                pl.BlockSpec((None, None, tk, tn), rhs_map),
            ],
            out_specs=pl.BlockSpec((m, tn), out_map),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        interpret=interpret,
        name="expert_gmm",
    )(groups, active, layer, lhs, rhs)
