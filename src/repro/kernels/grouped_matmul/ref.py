"""Pure-jnp grouped matmul: the oracle for the kernel and the path off TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_gmm_ref(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    layer: jax.Array | None = None,
) -> jax.Array:
    """Rows of group g (consecutive, in group order) times ``rhs[g]`` (of
    ``rhs[layer]`` where a layer is given), as float32 (M, N); rows past the
    groups' total are zero."""
    if layer is not None:
        rhs = rhs[layer]
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(m), side="right")
    out = jnp.zeros((m, rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32)
        out = jnp.where((group == g)[:, None], y, out)
    return out
