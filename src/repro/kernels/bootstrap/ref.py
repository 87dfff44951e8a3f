"""Pure-jnp oracle for the Poisson-bootstrap kernel.

Implements the *identical* counter-based RNG (xorshift-mix) and Poisson(1)
inverse-CDF lookup as the kernel, in plain jnp — kernel vs ref must agree
bit-for-bit on the weights and to float tolerance on the means.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# cumulative Poisson(1) probabilities for k = 0..6 (k=7 tail mass ~8e-5)
POISSON1_CDF = (
    0.36787944117144233,
    0.7357588823428847,
    0.9196986029286058,
    0.9810118431238462,
    0.9963401531726563,
    0.9994058151824183,
    0.9999167588507119,
)


def mix_bits(boot: jax.Array, pos: jax.Array, seed: jax.Array) -> jax.Array:
    """Counter-based 32-bit mixer (murmur3-style finalizer over a seeded
    combination of the bootstrap-row and position counters)."""
    u32 = jnp.uint32
    h = (
        boot.astype(u32) * u32(0x9E3779B1)
        ^ pos.astype(u32) * u32(0x85EBCA77)
        ^ seed.astype(u32)
    )
    h = h ^ (h >> u32(16))
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> u32(13))
    h = h * u32(0xC2B2AE35)
    h = h ^ (h >> u32(16))
    return h


def poisson1_weight(bits: jax.Array) -> jax.Array:
    """Map uniform u32 bits -> Poisson(1) draw via inverse CDF (k <= 7).

    ``bits >> 8`` fits in 24 bits, so the cast through int32 is exact; it
    is there because Mosaic has no uint32 -> float32 conversion."""
    top24 = (bits >> jnp.uint32(8)).astype(jnp.int32)
    u = top24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    w = jnp.zeros_like(u)
    for c in POISSON1_CDF:
        w = w + (u >= jnp.float32(c)).astype(jnp.float32)
    return w


def bootstrap_means_ref(
    data: jax.Array,  # (n,) f32
    n_boot: int,
    seed: int,
) -> jax.Array:
    """(n_boot,) Poisson-bootstrap resample means."""
    n = data.shape[0]
    boot = jnp.arange(n_boot, dtype=jnp.uint32)[:, None]
    pos = jnp.arange(n, dtype=jnp.uint32)[None, :]
    bits = mix_bits(boot, pos, jnp.uint32(seed))
    w = poisson1_weight(bits)  # (n_boot, n)
    sum_wx = w @ data.astype(jnp.float32)
    sum_w = jnp.sum(w, axis=1)
    return sum_wx / jnp.maximum(sum_w, 1.0)


#: row-block size of the blocked reference; fixed so the float accumulation
#: order (and therefore the partials, bit-for-bit) is reproducible across runs
DEFAULT_BLOCK_N = 1024


@functools.partial(jax.jit, static_argnames=("n_boot", "block_n"))
def bootstrap_partials_ref(
    scores: jax.Array,  # (n, m) — NaN marks unscorable examples
    seed: jax.Array,    # () uint32
    start: jax.Array,   # () uint32 — absolute offset of row 0
    *,
    n_boot: int,
    block_n: int = DEFAULT_BLOCK_N,
) -> tuple[jax.Array, jax.Array]:
    """Blocked oracle for the chunked-partials kernel: ``(sum w*x, sum w)``
    replicate pairs of shape ``(n_boot, m)``.

    The weight for (replicate b, example p) depends only on
    ``(seed, start + p, b)`` through :func:`mix_bits`, so partials computed
    over *any* chunking of a dataset merge into the same replicates —
    order-independent and resume-safe.  NaN scores get weight zero
    per-metric (they stay out of both ``sum w*x`` and ``sum w``), matching
    the host path's NaN filtering.  Streams ``block_n`` rows at a time:
    peak memory is O(n_boot x block_n), never the (B, n) weight matrix.
    """
    n, m = scores.shape
    n_blocks = (n + block_n - 1) // block_n
    pad = n_blocks * block_n - n
    x = jnp.pad(
        scores.astype(jnp.float32), ((0, pad), (0, 0)),
        constant_values=jnp.nan,  # padded rows are masked like NaN scores
    ).reshape(n_blocks, block_n, m)
    boot = jnp.arange(n_boot, dtype=jnp.uint32)[:, None]
    offs = jnp.arange(block_n, dtype=jnp.uint32)[None, :]

    def body(carry, blk):
        swx, sw = carry
        xb, ib = blk
        pos = jnp.uint32(start) + ib * jnp.uint32(block_n) + offs
        w = poisson1_weight(mix_bits(boot, pos, jnp.uint32(seed)))
        valid = ~jnp.isnan(xb)               # (block_n, m), per-metric mask
        swx = swx + w @ jnp.where(valid, xb, 0.0)
        sw = sw + w @ valid.astype(jnp.float32)
        return (swx, sw), None

    init = (
        jnp.zeros((n_boot, m), jnp.float32),
        jnp.zeros((n_boot, m), jnp.float32),
    )
    (swx, sw), _ = jax.lax.scan(
        body, init, (x, jnp.arange(n_blocks, dtype=jnp.uint32))
    )
    return swx, sw
