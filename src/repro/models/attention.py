"""Attention blocks: GQA/MQA/MHA, MLA (multi-head latent), cross-attention.

Two execution paths for full-sequence attention (``impl``):

* ``naive``    — full score matrix; only safe for short sequences.
* ``chunked``  — double-scan (q-blocks x k-blocks) online-softmax, the jnp
  twin of the Pallas flash kernel; default for train/prefill. O(block²)
  memory instead of O(S²).  Sequences shorter than 512 take the naive path.

The Pallas flash kernel (:mod:`repro.kernels.flash_attention`) is not wired
into the model; any other ``impl`` raises.

Decode paths use a pre-allocated KV cache, per-sequence positions (so the
continuous-batching scheduler can step ragged batches), and — for MLA — the
*absorbed* formulation that keeps the cache in the compressed latent space.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.params import ParamSpec
from repro.models.unroll import maybe_scan

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

ATTN_IMPLS = ("naive", "chunked")


def _use_chunked(impl: str, seq_len: int) -> bool:
    if impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; expected {ATTN_IMPLS}")
    return impl == "chunked" and seq_len >= 512


# ---------------------------------------------------------------------------
# Dense (non-flash) grouped attention
# ---------------------------------------------------------------------------


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def naive_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dv)
    mask: jax.Array | None,  # broadcastable to (B, K, G, Sq, Sk) or None
    scale: float,
) -> jax.Array:
    b, sq, h, dh = q.shape
    kheads = k.shape[2]
    g = h // kheads
    qg = q.reshape(b, sq, kheads, g, dh)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, -1).astype(q.dtype)


def _divisor_block(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= ``target`` (block-size picker)."""
    target = min(target, s)
    for b in range(target, 0, -1):
        if s % b == 0:
            return b
    return s


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dv)
    *,
    causal: bool,
    scale: float,
    q_offset: int = 0,
    prefix_len: int = 0,
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    """Online-softmax tiled attention (jnp twin of the flash kernel).

    ``prefix_len`` > 0 gives a prefix-LM mask: positions < prefix_len are
    mutually visible (PaliGemma); the causal rule applies after the prefix.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kheads = k.shape[2]
    g = h // kheads
    dv = v.shape[-1]
    block_q = _divisor_block(sq, block_q)
    block_k = _divisor_block(sk, block_k)
    nq, nk = sq // block_q, sk // block_k

    qg = q.reshape(b, nq, block_q, kheads, g, dh).astype(jnp.float32)
    kb = k.reshape(b, nk, block_k, kheads, dh).astype(jnp.float32)
    vb = v.reshape(b, nk, block_k, kheads, dv).astype(jnp.float32)

    q_pos_base = jnp.arange(block_q) + q_offset
    k_pos_base = jnp.arange(block_k)

    def q_block_step(_, qi):
        qblk = qg[:, qi]  # (B, bq, K, G, dh)
        q_pos = q_pos_base + qi * block_q

        def k_block_step(carry, ki):
            m, denom, acc = carry
            kblk, vblk = kb[:, ki], vb[:, ki]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk) * scale
            if causal:
                k_pos = k_pos_base + ki * block_k
                visible = q_pos[:, None] >= k_pos[None, :]
                if prefix_len:
                    in_prefix = (q_pos[:, None] < prefix_len) & (
                        k_pos[None, :] < prefix_len
                    )
                    visible = visible | in_prefix
                s = jnp.where(visible, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            correction = jnp.exp(m - m_new)
            denom_new = denom * correction + jnp.sum(p, axis=-1)
            acc_new = acc * correction[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vblk
            )
            return (m_new, denom_new, acc_new), None

        m0 = jnp.full((b, kheads, g, block_q), NEG_INF, jnp.float32)
        denom0 = jnp.zeros((b, kheads, g, block_q), jnp.float32)
        acc0 = jnp.zeros((b, kheads, g, block_q, dv), jnp.float32)
        (m, denom, acc), _ = maybe_scan(
            k_block_step, (m0, denom0, acc0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(denom, 1e-37)[..., None]  # (B,K,G,bq,dv)
        return None, out

    _, outs = maybe_scan(q_block_step, None, jnp.arange(nq))
    # outs: (nq, B, K, G, bq, dv) -> (B, Sq, H, dv)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 4, 1, 2, 3, 5)
    out = out.reshape(b, nq, block_q, h, dv).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, H, dh)
    k_cache: jax.Array,  # (B, S, K, dh)
    v_cache: jax.Array,  # (B, S, K, dv)
    positions: jax.Array,  # (B,) current token position per sequence
    scale: float,
) -> jax.Array:
    sk = k_cache.shape[1]
    valid = jnp.arange(sk)[None, :] <= positions[:, None]  # (B, S)
    mask = valid[:, None, None, None, :]  # (B, K, G, 1, S)
    return naive_attention(q, k_cache, v_cache, mask, scale)


def make_causal_mask(sq: int, sk: int, prefix_len: int = 0) -> jax.Array:
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    visible = q_pos >= k_pos
    if prefix_len:
        visible = visible | ((q_pos < prefix_len) & (k_pos < prefix_len))
    return visible  # (Sq, Sk)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def gqa_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    h, k, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    bias = cfg.qkv_bias
    spec = {
        "wq": layers.dense_spec(d, h * dh, ("embed", "heads"), bias, "heads"),
        "wk": layers.dense_spec(d, k * dh, ("embed", "kv_heads"), bias, "kv_heads"),
        "wv": layers.dense_spec(d, k * dh, ("embed", "kv_heads"), bias, "kv_heads"),
        "wo": layers.dense_spec(h * dh, d, ("heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        spec["q_norm"] = layers.rms_norm_spec(dh, None)
        spec["k_norm"] = layers.rms_norm_spec(dh, None)
    return spec


def gqa_project_kv(
    params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """Project keys/values (used for self-attn and to build cross caches)."""
    k = _split_heads(layers.dense(params["wk"], x), cfg.n_kv_heads)
    v = _split_heads(layers.dense(params["wv"], x), cfg.n_kv_heads)
    if "k_norm" in params:
        k = layers.rms_norm(params["k_norm"], k, cfg.norm_eps)
    if cfg.pos_emb == "rope" and positions is not None:
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_project_q(
    params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array | None
) -> jax.Array:
    q = _split_heads(layers.dense(params["wq"], x), cfg.n_heads)
    if "q_norm" in params:
        q = layers.rms_norm(params["q_norm"], q, cfg.norm_eps)
    if cfg.pos_emb == "rope" and positions is not None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
    return sharding.constrain(q, ("batch", "seq", "heads", None))


def gqa_full(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    causal: bool,
    prefix_len: int = 0,
    impl: str = "chunked",
    kv: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q = gqa_project_q(params, cfg, x, positions if cfg.pos_emb == "rope" else None)
    if kv is None:
        k, v = gqa_project_kv(
            params, cfg, x, positions if cfg.pos_emb == "rope" else None
        )
    else:
        k, v = kv
    scale = cfg.head_dim**-0.5
    if _use_chunked(impl, s):
        out = chunked_attention(
            q, k, v, causal=causal, scale=scale, prefix_len=prefix_len
        )
    else:
        mask = None
        if causal:
            mask = make_causal_mask(s, k.shape[1], prefix_len)
        out = naive_attention(q, k, v, mask, scale)
    out = sharding.constrain(out, ("batch", "seq", "heads", None))
    return layers.dense(params["wo"], out.reshape(b, s, -1))


def gqa_chunk(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,          # (B, W, D) — hidden states of one prompt chunk
    k_ctx: jax.Array,      # (B, S, K, dh) — the whole cache row, chunk included
    v_ctx: jax.Array,      # (B, S, K, dh)
    positions: jax.Array,  # (W,) global positions of the chunk's rows
) -> jax.Array:
    """Chunk prefill attention: each query attends to every cache position
    at or before its own.  The shapes depend only on the chunk width and
    the cache length, never on where the chunk starts (DESIGN.md §8)."""
    b, w, _ = x.shape
    rope_pos = positions[None, :] if cfg.pos_emb == "rope" else None
    q = gqa_project_q(params, cfg, x, rope_pos)
    mask = jnp.arange(k_ctx.shape[1])[None, :] <= positions[:, None]  # (W, S)
    out = naive_attention(q, k_ctx, v_ctx, mask, cfg.head_dim**-0.5)
    out = sharding.constrain(out, ("batch", "seq", "heads", None))
    return layers.dense(params["wo"], out.reshape(b, w, -1))


def gqa_init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype: Any = jnp.bfloat16
) -> dict:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    axes = ("batch", "cache_seq", "kv_heads", None)
    return {
        "k": ParamSpec(shape, dtype, axes, init="zeros"),
        "v": ParamSpec(shape, dtype, axes, init="zeros"),
    }


def cache_update(
    cache: jax.Array,  # (B, S, ...) — seq axis possibly sharded
    new: jax.Array,    # (B, 1, ...) values for the current position
    positions: jax.Array,  # (B,)
) -> jax.Array:
    """Write one token per row via a masked select instead of a scatter.

    A per-row scatter into a sequence-sharded cache forces the SPMD
    partitioner to all-gather the cache (observed: +43 GB/device on the
    110B decode cell); the one-hot select partitions elementwise and stays
    local under any sharding.
    """
    s = cache.shape[1]
    mask = jnp.arange(s)[None, :] == positions[:, None]  # (B, S)
    mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
    return jnp.where(mask, new.astype(cache.dtype), cache)


def gqa_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, D)
    cache: dict,  # {"k": (B,S,K,dh), "v": ...}
    positions: jax.Array,  # (B,)
) -> tuple[jax.Array, dict]:
    b = x.shape[0]
    pos2d = positions[:, None]  # (B,1) for rope on Sq=1
    q = gqa_project_q(params, cfg, x, pos2d if cfg.pos_emb == "rope" else None)
    k_new, v_new = gqa_project_kv(
        params, cfg, x, pos2d if cfg.pos_emb == "rope" else None
    )
    k_cache = cache_update(cache["k"], k_new, positions)
    v_cache = cache_update(cache["v"], v_new, positions)
    out = decode_attention(q, k_cache, v_cache, positions, cfg.head_dim**-0.5)
    out = layers.dense(params["wo"], out.reshape(b, 1, -1))
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qlr, kvlr = cfg.q_lora_rank, cfg.kv_lora_rank
    spec: dict = {}
    if qlr:
        spec["wq_a"] = layers.dense_spec(d, qlr, ("embed", None))
        spec["q_a_norm"] = layers.rms_norm_spec(qlr, None)
        spec["wq_b"] = layers.dense_spec(qlr, h * (nope + rope), (None, "heads"))
    else:
        spec["wq"] = layers.dense_spec(d, h * (nope + rope), ("embed", "heads"))
    spec["wkv_a"] = layers.dense_spec(d, kvlr + rope, ("embed", None))
    spec["kv_a_norm"] = layers.rms_norm_spec(kvlr, None)
    spec["wkv_b"] = layers.dense_spec(kvlr, h * (nope + dv), ("kv_lora", "heads"))
    spec["wo"] = layers.dense_spec(h * dv, d, ("heads", "embed"))
    return spec


def _mla_q(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.q_lora_rank:
        cq = layers.dense(params["wq_a"], x)
        cq = layers.rms_norm(params["q_a_norm"], cq, cfg.norm_eps)
        q = layers.dense(params["wq_b"], cq)
    else:
        q = layers.dense(params["wq"], x)
    return _split_heads(q, cfg.n_heads)  # (B,S,H,nope+rope)


def _mla_ckv(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    ckv_full = layers.dense(params["wkv_a"], x)  # (B,S,kvlr+rope)
    c_kv, k_rope = jnp.split(ckv_full, [cfg.kv_lora_rank], axis=-1)
    c_kv = layers.rms_norm(params["kv_a_norm"], c_kv, cfg.norm_eps)
    k_rope = layers.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return c_kv, k_rope[:, :, 0, :]  # (B,S,kvlr), (B,S,rope)


def mla_full(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    causal: bool = True,
    impl: str = "chunked",
) -> jax.Array:
    """Naive (decompressed) MLA for train/prefill."""
    b, s, _ = x.shape
    nope, rope_d, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(s)[None, :]
    q = _mla_q(params, cfg, x)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    kv = layers.dense(params["wkv_b"], c_kv)  # (B,S,H*(nope+dv))
    kv = _split_heads(kv, cfg.n_heads)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, cfg.n_heads, rope_d))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (nope + rope_d) ** -0.5
    if _use_chunked(impl, s):
        out = chunked_attention(q, k, v, causal=causal, scale=scale)
    else:
        mask = make_causal_mask(s, s) if causal else None
        out = naive_attention(q, k, v, mask, scale)
    return layers.dense(params["wo"], out.reshape(b, s, -1))


def mla_init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype: Any = jnp.bfloat16
) -> dict:
    return {
        "c_kv": ParamSpec(
            (batch, max_len, cfg.kv_lora_rank),
            dtype,
            ("batch", "cache_seq", None),
            init="zeros",
        ),
        "k_rope": ParamSpec(
            (batch, max_len, cfg.qk_rope_head_dim),
            dtype,
            ("batch", "cache_seq", None),
            init="zeros",
        ),
    }


def mla_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B,1,D)
    cache: dict,  # {"c_kv": (B,S,kvlr), "k_rope": (B,S,rope)}
    positions: jax.Array,  # (B,)
) -> tuple[jax.Array, dict]:
    """Absorbed-matmul MLA decode: the cache stays compressed (the point of
    MLA — (kv_lora + rope) bytes/token instead of 2·H·dh)."""
    b = x.shape[0]
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, kvlr = cfg.v_head_dim, cfg.kv_lora_rank
    pos2d = positions[:, None]

    q = _mla_q(params, cfg, x)  # (B,1,H,nope+rope)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    q_rope = layers.apply_rope(q_rope, pos2d, cfg.rope_theta)

    c_kv_new, k_rope_new = _mla_ckv(params, cfg, x, pos2d)
    c_kv = cache_update(cache["c_kv"], c_kv_new, positions)
    k_rope = cache_update(cache["k_rope"], k_rope_new, positions)

    w_kv_b = params["wkv_b"]["kernel"].reshape(kvlr, h, nope + dv)
    w_uk = w_kv_b[:, :, :nope]  # (kvlr, H, nope)
    w_uv = w_kv_b[:, :, nope:]  # (kvlr, H, dv)

    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope.astype(jnp.float32), w_uk)
    scores = jnp.einsum(
        "bqhl,bsl->bhqs", q_lat, c_kv.astype(jnp.float32)
    ) + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                   k_rope.astype(jnp.float32))
    scores = scores * ((nope + rope_d) ** -0.5)
    valid = (jnp.arange(c_kv.shape[1])[None, :] <= positions[:, None])
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", probs, c_kv.astype(jnp.float32))
    out = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv).astype(x.dtype)
    out = layers.dense(params["wo"], out.reshape(b, 1, -1))
    return out, {"c_kv": c_kv, "k_rope": k_rope}
