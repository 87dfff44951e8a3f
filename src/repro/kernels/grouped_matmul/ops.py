"""Grouped matmul of the expert layer; the platform picks the path: the
Pallas kernel on a TPU, the jnp oracle elsewhere."""

from __future__ import annotations

import jax

from repro.kernels.grouped_matmul.grouped_matmul import expert_gmm as _kernel
from repro.kernels.grouped_matmul.ref import expert_gmm_ref


def expert_gmm(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    layer: jax.Array | None = None,
    *,
    tile_rows: int,
) -> jax.Array:
    """Rows of group g times ``rhs[g]`` (``rhs[layer, g]`` for a stacked
    ``rhs``), as float32; ``group_sizes`` are multiples of ``tile_rows``.
    See :mod:`.grouped_matmul`."""
    if jax.default_backend() == "tpu":
        return _kernel(lhs, rhs, group_sizes, layer, tile_rows=tile_rows)
    return expert_gmm_ref(lhs, rhs, group_sizes, layer)
