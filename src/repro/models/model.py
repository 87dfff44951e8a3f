"""Model assemblies for all six assigned families.

Every family exposes the same surface (duck-typed; see :func:`build_model`):

* ``param_specs()``                   — abstract parameter tree (ParamSpec).
* ``forward(params, batch, ...)``     — full-sequence logits (train path).
* ``cache_specs(batch, max_len)``     — decode-cache ParamSpec tree.
* ``prefill(params, batch, cache)``   — fill cache, return last-pos logits.
* ``decode_step(params, tokens, cache, positions)`` — one decode token.

Layers are **stacked + scanned** (MaxText-style): one ParamSpec per layer
stack with a leading ``layers`` axis, ``jax.lax.scan`` over the stack.  This
keeps HLO size O(1) in depth, which is what makes 512-way SPMD lowering of an
80-layer model tractable.  Activation remat wraps the scan body.

Batch dict convention (all optional except ``tokens``):
  ``tokens``   (B, S) int32   — text tokens
  ``frames``   (B, Se, D)     — whisper: precomputed mel/conv frame embeddings
  ``patches``  (B, Nv, D)     — paligemma: precomputed SigLIP patch embeddings
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, moe, ssm
from repro.models.params import ParamSpec, is_spec, map_with_path
from repro.models.unroll import maybe_scan

PyTree = Any

# decoder positional table sized from the assigned shape grid (DESIGN.md §4.1)
MAX_LEARNED_POS = 32_768


def stack_specs(spec_tree: PyTree, n: int) -> PyTree:
    """Prepend a ``layers`` axis of size ``n`` to every leaf spec."""
    return jax.tree.map(
        lambda s: ParamSpec(
            (n,) + s.shape, s.dtype, ("layers",) + tuple(s.axes), s.init, s.scale
        ),
        spec_tree,
        is_leaf=is_spec,
    )


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {policy!r}")


# ===========================================================================
# Transformer block (dense / MoE / VLM families)
# ===========================================================================


def tblock_specs(cfg: ModelConfig, mlp_kind: str, dense_ff: int = 0) -> dict:
    d = cfg.d_model
    spec: dict = {
        "ln1": layers.rms_norm_spec(d),
        "attn": attn.mla_specs(cfg) if cfg.use_mla else attn.gqa_specs(cfg),
        "ln2": layers.rms_norm_spec(d),
    }
    if mlp_kind == "dense":
        spec["mlp"] = layers.gated_mlp_spec(d, dense_ff or cfg.d_ff)
    elif mlp_kind == "moe":
        spec["moe"] = moe.moe_specs(cfg)
    else:
        raise ValueError(mlp_kind)
    return spec


def tblock_fwd(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    causal: bool = True,
    prefix_len: int = 0,
    impl: str = "chunked",
) -> tuple[jax.Array, jax.Array]:
    h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a = attn.mla_full(params["attn"], cfg, h, causal=causal, impl=impl)
    else:
        a = attn.gqa_full(
            params["attn"], cfg, h, causal=causal, prefix_len=prefix_len, impl=impl
        )
    x, aux, _ = _tblock_ffn(params, cfg, x + a)
    return sharding.constrain(x, ("batch", "seq", "embed")), aux


def tblock_cache_specs(
    cfg: ModelConfig, batch: int, max_len: int, dtype: Any = jnp.bfloat16
) -> dict:
    if cfg.use_mla:
        return attn.mla_init_cache(cfg, batch, max_len, dtype)
    return attn.gqa_init_cache(cfg, batch, max_len, dtype)


def _fill(cache: jax.Array, new: jax.Array) -> jax.Array:
    """Write the prompt's projected values into the cache.

    When the prompt covers the whole cache the update is a plain cast —
    avoiding a dynamic-update-slice the SPMD partitioner would otherwise
    service with an involuntary full rematerialization (observed on the
    MQA kv=1 prefill cells)."""
    s = new.shape[1]
    if s == cache.shape[1]:
        return new.astype(cache.dtype)
    return cache.at[:, :s].set(new.astype(cache.dtype))


def tblock_prefill(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    cache: dict,
    *,
    prefix_len: int = 0,
    impl: str = "chunked",
) -> tuple[jax.Array, dict, jax.Array]:
    """Forward + cache fill (inference prefill)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        c_kv, k_rope = attn._mla_ckv(params["attn"], cfg, h, positions)
        cache = {
            "c_kv": _fill(cache["c_kv"], c_kv),
            "k_rope": _fill(cache["k_rope"], k_rope),
        }
        a = attn.mla_full(params["attn"], cfg, h, causal=True, impl=impl)
    else:
        rope_pos = positions if cfg.pos_emb == "rope" else None
        k, v = attn.gqa_project_kv(params["attn"], cfg, h, rope_pos)
        cache = {"k": _fill(cache["k"], k), "v": _fill(cache["v"], v)}
        a = attn.gqa_full(
            params["attn"], cfg, h, causal=True, prefix_len=prefix_len,
            impl=impl, kv=(k, v),
        )
    x, aux, _ = _tblock_ffn(params, cfg, x + a)
    return x, cache, aux


def _tblock_ffn(
    params: dict, cfg: ModelConfig, x: jax.Array, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, dict]:
    """The block's second half: ``x + ffn(norm(x))``, the MoE aux loss and
    the expert layer's counters (none for a dense MLP).  ``valid`` (B, S)
    marks the rows that are tokens."""
    h = layers.rms_norm(params["ln2"], x, cfg.norm_eps)
    if "moe" in params:
        y, aux, counts = moe.moe_layer(params["moe"], cfg, h, valid)
        return x + y, aux, counts
    y = layers.gated_mlp(params["mlp"], h, cfg.act)
    return x + y, jnp.zeros((), jnp.float32), {}


def tblock_prefill_chunk(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,          # (B, W, D) — one chunk of the prompt
    cache: dict,           # {"k", "v"}: (B, S, K, dh), positions < start filled
    positions: jax.Array,  # (W,) start + arange(W)
    length: jax.Array,     # () prompt length; rows at or past it are padding
) -> tuple[jax.Array, dict, dict]:
    """Prefill one fixed-width chunk of a GQA prompt into a cache row.

    The chunk's keys and values land at positions ``[start, length)``;
    padding rows write nothing, so the row beyond the prompt stays as it
    was, and route to no expert.  Positions before ``start`` are read from
    ``cache`` as they are (computed by earlier chunks, or gathered from
    shared prefix pages).  Returns the rows, the cache and the expert
    layer's counters."""
    w = x.shape[1]
    h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
    rope_pos = positions[None, :] if cfg.pos_emb == "rope" else None
    k, v = attn.gqa_project_kv(params["attn"], cfg, h, rope_pos)
    key_pos = jnp.arange(cache["k"].shape[1])
    rows = jnp.clip(key_pos - positions[0], 0, w - 1)
    fresh = ((key_pos >= positions[0]) & (key_pos < length))[None, :, None, None]

    def merge(old: jax.Array, new: jax.Array) -> jax.Array:
        # the round trip of old values through the cache dtype is exact:
        # they were computed in ``new``'s dtype
        return jnp.where(fresh, jnp.take(new, rows, axis=1), old.astype(new.dtype))

    k_ctx, v_ctx = merge(cache["k"], k), merge(cache["v"], v)
    a = attn.gqa_chunk(params["attn"], cfg, h, k_ctx, v_ctx, positions)
    valid = jnp.broadcast_to(positions < length, x.shape[:2])
    x, _, counts = _tblock_ffn(params, cfg, x + a, valid)
    cache = {
        "k": k_ctx.astype(cache["k"].dtype),
        "v": v_ctx.astype(cache["v"].dtype),
    }
    return x, cache, counts


def tblock_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    cache: dict,
    positions: jax.Array,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, dict, dict]:
    """One token per row; ``valid`` (B, 1) marks the rows that decode.
    Returns the rows, the cache and the expert layer's counters."""
    h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, cache = attn.mla_decode(params["attn"], cfg, h, cache, positions)
    else:
        a, cache = attn.gqa_decode(params["attn"], cfg, h, cache, positions)
    x, _, counts = _tblock_ffn(params, cfg, x + a, valid)
    return x, cache, counts


def _add_counts(total: dict, per_layer: dict) -> dict:
    """Add a scanned stack's per-layer counters, summed over its layers."""
    for k, v in per_layer.items():
        total[k] = total.get(k, 0) + jnp.sum(v)
    return total


# ===========================================================================
# TransformerLM — dense, MoE and VLM families
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ModelConfig
    remat: str = "dots"
    attn_impl: str = "chunked"

    # -- specs ---------------------------------------------------------------

    @property
    def _n_moe_layers(self) -> int:
        return self.cfg.n_layers - self.cfg.first_k_dense if self.cfg.n_experts else 0

    @property
    def _n_dense_layers(self) -> int:
        return self.cfg.n_layers - self._n_moe_layers

    def param_specs(self) -> dict:
        cfg = self.cfg
        spec: dict = {
            "embed": layers.embedding_spec(cfg.padded_vocab, cfg.d_model),
            "final_norm": layers.rms_norm_spec(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            spec["unembed"] = layers.dense_spec(
                cfg.d_model, cfg.padded_vocab, ("embed", "vocab")
            )
        if self._n_dense_layers:
            spec["dense_layers"] = stack_specs(
                tblock_specs(cfg, "dense", cfg.dense_d_ff or cfg.d_ff),
                self._n_dense_layers,
            )
        if self._n_moe_layers:
            spec["moe_layers"] = stack_specs(
                tblock_specs(cfg, "moe"), self._n_moe_layers
            )
        return spec

    # -- embedding helpers ----------------------------------------------------

    def _embed_tokens(self, params: dict, tokens: jax.Array, dtype) -> jax.Array:
        x = layers.embed(params["embed"], tokens, dtype)
        if self.cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(self.cfg.d_model), dtype)
        return sharding.constrain(x, ("batch", "seq", "embed"))

    def _embed_inputs(self, params: dict, batch: dict, dtype) -> tuple[jax.Array, int]:
        """Token (+ vision) embeddings; returns (x, prefix_len)."""
        x = self._embed_tokens(params, batch["tokens"], dtype)
        prefix_len = 0
        if self.cfg.family == "vlm":
            patches = batch["patches"].astype(dtype)
            x = jnp.concatenate([patches, x], axis=1)
            prefix_len = patches.shape[1]
        return x, prefix_len

    def _unembed(self, params: dict, x: jax.Array, dtype) -> jax.Array:
        if self.cfg.tie_embeddings:
            return layers.unembed(params["embed"], x, dtype)
        logits = layers.dense(params["unembed"], x.astype(dtype))
        return sharding.constrain(
            logits.astype(jnp.float32), ("batch", "seq", "vocab")
        )

    # -- scan plumbing ---------------------------------------------------------

    def _scan_stack(self, stack_params, x, body):
        stack, whole = moe.split_experts(stack_params)

        def scan_body(carry, p_layer):
            h, aux = carry
            h, aux_l = body(moe.join_experts(p_layer, whole), h)
            return (h, aux + aux_l), None

        (x, aux), _ = maybe_scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), stack
        )
        return x, aux

    # -- public API -------------------------------------------------------------

    def forward(
        self,
        params: dict,
        batch: dict,
        *,
        dtype: Any = jnp.bfloat16,
        return_hidden: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward.  Returns (logits_f32, aux_loss)."""
        cfg = self.cfg
        x, prefix_len = self._embed_inputs(params, batch, dtype)

        body = _remat(
            lambda p, h: tblock_fwd(
                p, cfg, h, causal=True, prefix_len=prefix_len, impl=self.attn_impl
            ),
            self.remat,
        )
        aux = jnp.zeros((), jnp.float32)
        if "dense_layers" in params:
            x, a = self._scan_stack(params["dense_layers"], x, body)
            aux = aux + a
        if "moe_layers" in params:
            x, a = self._scan_stack(params["moe_layers"], x, body)
            aux = aux + a

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, aux
        return self._unembed(params, x, dtype), aux

    def cache_specs(
        self, batch: int, max_len: int, dtype: Any = jnp.bfloat16
    ) -> dict:
        per_layer = lambda n: stack_specs(
            tblock_cache_specs(self.cfg, batch, max_len, dtype), n
        )
        out: dict = {}
        if self._n_dense_layers:
            out["dense_layers"] = per_layer(self._n_dense_layers)
        if self._n_moe_layers:
            out["moe_layers"] = per_layer(self._n_moe_layers)
        return out

    def prefill(
        self,
        params: dict,
        batch: dict,
        cache: dict,
        *,
        dtype: Any = jnp.bfloat16,
    ) -> tuple[jax.Array, dict]:
        """Run the prompt, fill the cache, return last-position logits."""
        cfg = self.cfg
        x, prefix_len = self._embed_inputs(params, batch, dtype)
        new_cache: dict = {}

        def run(stack_key: str, x):
            stack, whole = moe.split_experts(params[stack_key])

            def scan_body(h, pc):
                p_layer, c_layer = pc
                h, c_layer, _ = tblock_prefill(
                    moe.join_experts(p_layer, whole), cfg, h, c_layer,
                    prefix_len=prefix_len, impl=self.attn_impl,
                )
                return h, c_layer

            x, cs = maybe_scan(scan_body, x, (stack, cache[stack_key]))
            new_cache[stack_key] = cs
            return x

        if "dense_layers" in params:
            x = run("dense_layers", x)
        if "moe_layers" in params:
            x = run("moe_layers", x)

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x[:, -1:], dtype)
        return logits[:, 0], new_cache

    def prefill_chunk(
        self,
        params: dict,
        tokens: jax.Array,  # (B, W) prompt positions start .. start+W
        cache: dict,
        start: jax.Array,   # () int32
        length: jax.Array,  # () int32 prompt length
        *,
        dtype: Any = jnp.bfloat16,
    ) -> tuple[jax.Array, dict]:
        """Prefill one fixed-width chunk of a text prompt (GQA only) into
        a cache row whose positions before ``start`` are already filled;
        return the logits of position ``length - 1`` (meaningful when the
        chunk holds it), the row and the expert layers' counters, summed
        over layers (empty for a dense model).

        ``start`` and ``length`` are traced, so one program serves every
        chunk of every prompt, whether it starts at 0 or after a shared
        prefix: each position's KV is computed by the same program from
        the same inputs either way (DESIGN.md §8)."""
        cfg = self.cfg
        if cfg.use_mla or cfg.family == "vlm":
            raise ValueError(f"chunk prefill needs GQA text prompts: {cfg.name}")
        w = tokens.shape[1]
        positions = start + jnp.arange(w, dtype=jnp.int32)
        x = self._embed_tokens(params, tokens, dtype)
        new_cache: dict = {}
        counts: dict = {}

        for stack_key in ("dense_layers", "moe_layers"):
            if stack_key not in params:
                continue

            stack, whole = moe.split_experts(params[stack_key])

            def scan_body(h, pc):
                p_layer, c_layer = pc
                h, c_layer, n = tblock_prefill_chunk(
                    moe.join_experts(p_layer, whole), cfg, h, c_layer,
                    positions, length,
                )
                return h, (c_layer, n)

            x, (new_cache[stack_key], n) = maybe_scan(
                scan_body, x, (stack, cache[stack_key])
            )
            counts = _add_counts(counts, n)

        last = jnp.clip(length - 1 - start, 0, w - 1)
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return self._unembed(params, x, dtype)[:, 0], new_cache, counts

    def decode_step(
        self,
        params: dict,
        tokens: jax.Array,  # (B, 1)
        cache: dict,
        positions: jax.Array,  # (B,) position of the new token
        *,
        dtype: Any = jnp.bfloat16,
        valid: jax.Array | None = None,  # (B,) rows that decode; None = all
    ) -> tuple[jax.Array, dict, dict]:
        """One token per row: (logits, cache, the expert layers' counters
        summed over layers — empty for a dense model)."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens, dtype)
        rows = None if valid is None else valid[:, None]
        new_cache: dict = {}
        counts: dict = {}

        for stack_key in ("dense_layers", "moe_layers"):
            if stack_key not in params:
                continue

            stack, whole = moe.split_experts(params[stack_key])

            def scan_body(h, pc):
                p_layer, c_layer = pc
                h, c_layer, n = tblock_decode(
                    moe.join_experts(p_layer, whole), cfg, h, c_layer,
                    positions, rows,
                )
                return h, (c_layer, n)

            x, (new_cache[stack_key], n) = maybe_scan(
                scan_body, x, (stack, cache[stack_key])
            )
            counts = _add_counts(counts, n)

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x, dtype)
        return logits[:, 0], new_cache, counts


# ===========================================================================
# MambaLM — pure SSM family
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class MambaLM:
    cfg: ModelConfig
    remat: str = "dots"

    def param_specs(self) -> dict:
        cfg = self.cfg
        block = {
            "ln": layers.rms_norm_spec(cfg.d_model),
            "mixer": ssm.mamba2_specs(cfg),
        }
        return {
            "embed": layers.embedding_spec(cfg.padded_vocab, cfg.d_model),
            "layers": stack_specs(block, cfg.n_layers),
            "final_norm": layers.rms_norm_spec(cfg.d_model),
        }

    def forward(
        self,
        params: dict,
        batch: dict,
        *,
        dtype: Any = jnp.bfloat16,
        return_hidden: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], dtype)
        x = sharding.constrain(x, ("batch", "seq", "embed"))

        def block(p, h):
            y = ssm.mamba2_full(
                p["mixer"], cfg, layers.rms_norm(p["ln"], h, cfg.norm_eps)
            )
            return h + y

        body = _remat(block, self.remat)

        def scan_body(h, p_layer):
            return body(p_layer, h), None

        x, _ = maybe_scan(scan_body, x, params["layers"])
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, jnp.zeros((), jnp.float32)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits, jnp.zeros((), jnp.float32)

    def cache_specs(
        self, batch: int, max_len: int = 0, dtype: Any = jnp.float32
    ) -> dict:
        del max_len  # O(1) state: SSM caches carry no sequence axis
        return {
            "layers": stack_specs(
                ssm.mamba2_init_cache(self.cfg, batch, dtype), self.cfg.n_layers
            )
        }

    def prefill(
        self, params: dict, batch: dict, cache: dict, *, dtype: Any = jnp.bfloat16
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        tokens = batch["tokens"]
        x = layers.embed(params["embed"], tokens, dtype)

        def scan_body(h, pc):
            p, c = pc
            normed = layers.rms_norm(p["ln"], h, cfg.norm_eps)
            y, new_c = ssm.mamba2_prefill(p["mixer"], cfg, normed, c)
            return h + y, new_c

        x, cs = maybe_scan(scan_body, x, (params["layers"], cache["layers"]))
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x[:, -1:], dtype)
        return logits[:, 0], {"layers": cs}

    def decode_step(
        self,
        params: dict,
        tokens: jax.Array,
        cache: dict,
        positions: jax.Array,
        *,
        dtype: Any = jnp.bfloat16,
    ) -> tuple[jax.Array, dict]:
        del positions  # SSM decode is position-free
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens, dtype)

        def scan_body(h, pc):
            p, c = pc
            normed = layers.rms_norm(p["ln"], h, cfg.norm_eps)
            y, new_c = ssm.mamba2_decode(p["mixer"], cfg, normed, c)
            return h + y, new_c

        x, cs = maybe_scan(scan_body, x, (params["layers"], cache["layers"]))
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits[:, 0], {"layers": cs}, {}


# ===========================================================================
# HybridLM — zamba2: Mamba2 backbone + shared attention block
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class HybridLM:
    """``n_layers`` Mamba2 blocks; one *shared* transformer block applied
    after every ``shared_attn_every``-th layer with per-application norm
    gains (DESIGN.md §4.1)."""

    cfg: ModelConfig
    remat: str = "dots"
    attn_impl: str = "chunked"

    @property
    def n_groups(self) -> int:
        return self.cfg.n_layers // self.cfg.shared_attn_every

    @property
    def n_tail(self) -> int:
        return self.cfg.n_layers - self.n_groups * self.cfg.shared_attn_every

    def _mamba_block_spec(self) -> dict:
        return {
            "ln": layers.rms_norm_spec(self.cfg.d_model),
            "mixer": ssm.mamba2_specs(self.cfg),
        }

    def param_specs(self) -> dict:
        cfg = self.cfg
        g, k = self.n_groups, cfg.shared_attn_every
        spec: dict = {
            "embed": layers.embedding_spec(cfg.padded_vocab, cfg.d_model),
            # (G, K, ...) grouped mamba stacks
            "groups": stack_specs(stack_specs(self._mamba_block_spec(), k), g),
            "shared_attn": tblock_specs(cfg, "dense"),
            # per-application input norm for the shared block
            "app_norms": stack_specs(layers.rms_norm_spec(cfg.d_model), g),
            "final_norm": layers.rms_norm_spec(cfg.d_model),
        }
        if self.n_tail:
            spec["tail"] = stack_specs(self._mamba_block_spec(), self.n_tail)
        return spec

    def _mamba_fwd(self, p, h):
        y = ssm.mamba2_full(
            p["mixer"], self.cfg, layers.rms_norm(p["ln"], h, self.cfg.norm_eps)
        )
        return h + y

    def forward(
        self,
        params: dict,
        batch: dict,
        *,
        dtype: Any = jnp.bfloat16,
        return_hidden: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], dtype)
        x = sharding.constrain(x, ("batch", "seq", "embed"))
        mamba_body = _remat(self._mamba_fwd, self.remat)

        shared = params["shared_attn"]

        def attn_app(app_norm, h):
            normed = layers.rms_norm(app_norm, h, cfg.norm_eps)
            out, _ = tblock_fwd(shared, cfg, normed, causal=True, impl=self.attn_impl)
            return h + (out - normed)  # residual around the shared block

        attn_body = _remat(attn_app, self.remat)

        def group_body(h, group):
            p_stack, app_norm = group

            def inner(h2, p_layer):
                return mamba_body(p_layer, h2), None

            h, _ = maybe_scan(inner, h, p_stack)
            h = attn_body(app_norm, h)
            return h, None

        x, _ = maybe_scan(group_body, x, (params["groups"], params["app_norms"]))
        if self.n_tail:
            def inner(h2, p_layer):
                return mamba_body(p_layer, h2), None

            x, _ = maybe_scan(inner, x, params["tail"])

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, jnp.zeros((), jnp.float32)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits, jnp.zeros((), jnp.float32)

    def cache_specs(
        self, batch: int, max_len: int, dtype: Any = jnp.bfloat16
    ) -> dict:
        cfg = self.cfg
        g, k = self.n_groups, cfg.shared_attn_every
        mamba_cache = ssm.mamba2_init_cache(cfg, batch, jnp.float32)
        out: dict = {
            "groups": stack_specs(stack_specs(mamba_cache, k), g),
            # one KV cache per shared-attn application
            "attn": stack_specs(
                attn.gqa_init_cache(cfg, batch, max_len, dtype), g
            ),
        }
        if self.n_tail:
            out["tail"] = stack_specs(mamba_cache, self.n_tail)
        return out

    def prefill(
        self, params: dict, batch: dict, cache: dict, *, dtype: Any = jnp.bfloat16
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], dtype)
        shared = params["shared_attn"]

        def group_body(h, xs):
            p_stack, app_norm, m_cache, a_cache = xs

            def inner(h2, pc):
                p, c = pc
                normed = layers.rms_norm(p["ln"], h2, cfg.norm_eps)
                y, new_c = ssm.mamba2_prefill(p["mixer"], cfg, normed, c)
                return h2 + y, new_c

            h, new_m = maybe_scan(inner, h, (p_stack, m_cache))
            normed = layers.rms_norm(app_norm, h, cfg.norm_eps)
            out, new_a, _ = tblock_prefill(
                shared, cfg, normed, a_cache, impl=self.attn_impl
            )
            h = h + (out - normed)
            return h, (new_m, new_a)

        x, (new_groups, new_attn) = maybe_scan(
            group_body,
            x,
            (params["groups"], params["app_norms"], cache["groups"], cache["attn"]),
        )
        new_cache: dict = {"groups": new_groups, "attn": new_attn}
        if self.n_tail:
            def inner(h2, pc):
                p, c = pc
                normed = layers.rms_norm(p["ln"], h2, cfg.norm_eps)
                y, new_c = ssm.mamba2_prefill(p["mixer"], cfg, normed, c)
                return h2 + y, new_c

            x, new_tail = maybe_scan(inner, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = new_tail

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x[:, -1:], dtype)
        return logits[:, 0], new_cache

    def decode_step(
        self,
        params: dict,
        tokens: jax.Array,
        cache: dict,
        positions: jax.Array,
        *,
        dtype: Any = jnp.bfloat16,
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens, dtype)
        shared = params["shared_attn"]

        def group_body(h, xs):
            p_stack, app_norm, m_cache, a_cache = xs

            def inner(h2, pc):
                p, c = pc
                normed = layers.rms_norm(p["ln"], h2, cfg.norm_eps)
                y, new_c = ssm.mamba2_decode(p["mixer"], cfg, normed, c)
                return h2 + y, new_c

            h, new_m = maybe_scan(inner, h, (p_stack, m_cache))
            normed = layers.rms_norm(app_norm, h, cfg.norm_eps)
            out, new_a, _ = tblock_decode(shared, cfg, normed, a_cache, positions)
            h = h + (out - normed)
            return h, (new_m, new_a)

        x, (new_groups, new_attn) = maybe_scan(
            group_body,
            x,
            (params["groups"], params["app_norms"], cache["groups"], cache["attn"]),
        )
        new_cache: dict = {"groups": new_groups, "attn": new_attn}
        if self.n_tail:
            def inner(h2, pc):
                p, c = pc
                normed = layers.rms_norm(p["ln"], h2, cfg.norm_eps)
                y, new_c = ssm.mamba2_decode(p["mixer"], cfg, normed, c)
                return h2 + y, new_c

            x, new_tail = maybe_scan(inner, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = new_tail

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits[:, 0], new_cache, {}


# ===========================================================================
# EncDecLM — whisper: encoder over frame embeddings + causal decoder w/ cross
# ===========================================================================


def _eblock_specs(cfg: ModelConfig, cross: bool) -> dict:
    d = cfg.d_model
    spec = {
        "ln1": layers.layer_norm_spec(d),
        "attn": attn.gqa_specs(cfg),
        "ln2": layers.layer_norm_spec(d),
        "mlp": layers.mlp_spec(d, cfg.d_ff, bias=True),
    }
    if cross:
        spec["ln_cross"] = layers.layer_norm_spec(d)
        spec["cross"] = attn.gqa_specs(cfg, cross=True)
    return spec


@dataclasses.dataclass(frozen=True)
class EncDecLM:
    cfg: ModelConfig
    remat: str = "dots"
    attn_impl: str = "chunked"

    def param_specs(self) -> dict:
        cfg = self.cfg
        return {
            "embed": layers.embedding_spec(cfg.padded_vocab, cfg.d_model),
            "enc_pos": layers.learned_pos_spec(cfg.encoder_seq, cfg.d_model),
            "dec_pos": layers.learned_pos_spec(MAX_LEARNED_POS, cfg.d_model),
            "encoder": stack_specs(_eblock_specs(cfg, False), cfg.n_encoder_layers),
            "enc_norm": layers.layer_norm_spec(cfg.d_model),
            "decoder": stack_specs(_eblock_specs(cfg, True), cfg.n_layers),
            "final_norm": layers.layer_norm_spec(cfg.d_model),
        }

    # -- encoder ---------------------------------------------------------------

    def encode(self, params: dict, frames: jax.Array, dtype) -> jax.Array:
        cfg = self.cfg
        x = frames.astype(dtype) + params["enc_pos"]["table"][
            None, : frames.shape[1]
        ].astype(dtype)
        x = sharding.constrain(x, ("batch", "seq", "embed"))

        def block(p, h):
            a = attn.gqa_full(
                p["attn"], cfg, layers.layer_norm(p["ln1"], h, cfg.norm_eps),
                causal=False, impl=self.attn_impl,
            )
            h = h + a
            y = layers.mlp(
                p["mlp"], layers.layer_norm(p["ln2"], h, cfg.norm_eps), cfg.act
            )
            return h + y

        body = _remat(block, self.remat)

        def scan_body(h, p):
            return body(p, h), None

        x, _ = maybe_scan(scan_body, x, params["encoder"])
        return layers.layer_norm(params["enc_norm"], x, cfg.norm_eps)

    # -- decoder ---------------------------------------------------------------

    def _dec_embed(self, params, tokens, dtype, pos_offset=None):
        x = layers.embed(params["embed"], tokens, dtype)
        if pos_offset is None:
            pos = params["dec_pos"]["table"][None, : tokens.shape[1]]
        else:
            pos = jnp.take(params["dec_pos"]["table"], pos_offset, axis=0)[:, None]
        return x + pos.astype(dtype)

    def _dec_block(self, p, h, enc_out):
        cfg = self.cfg
        a = attn.gqa_full(
            p["attn"], cfg, layers.layer_norm(p["ln1"], h, cfg.norm_eps),
            causal=True, impl=self.attn_impl,
        )
        h = h + a
        normed = layers.layer_norm(p["ln_cross"], h, cfg.norm_eps)
        kv = attn.gqa_project_kv(p["cross"], cfg, enc_out, None)
        c = attn.gqa_full(
            p["cross"], cfg, normed, causal=False, impl=self.attn_impl, kv=kv
        )
        h = h + c
        y = layers.mlp(p["mlp"], layers.layer_norm(p["ln2"], h, cfg.norm_eps), cfg.act)
        return h + y

    def forward(
        self,
        params: dict,
        batch: dict,
        *,
        dtype: Any = jnp.bfloat16,
        return_hidden: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"], dtype)
        x = self._dec_embed(params, batch["tokens"], dtype)
        body = _remat(lambda p, h: self._dec_block(p, h, enc_out), self.remat)

        def scan_body(h, p):
            return body(p, h), None

        x, _ = maybe_scan(scan_body, x, params["decoder"])
        x = layers.layer_norm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, jnp.zeros((), jnp.float32)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits, jnp.zeros((), jnp.float32)

    # -- caches ------------------------------------------------------------------

    def cache_specs(
        self, batch: int, max_len: int, dtype: Any = jnp.bfloat16
    ) -> dict:
        cfg = self.cfg
        self_kv = attn.gqa_init_cache(cfg, batch, max_len, dtype)
        cross_kv = attn.gqa_init_cache(cfg, batch, cfg.encoder_seq, dtype)
        return {
            "self": stack_specs(self_kv, cfg.n_layers),
            "cross": stack_specs(cross_kv, cfg.n_layers),
        }

    def prefill(
        self, params: dict, batch: dict, cache: dict, *, dtype: Any = jnp.bfloat16
    ) -> tuple[jax.Array, dict]:
        """Encode frames, build cross caches, run prompt through the decoder."""
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"], dtype)
        tokens = batch["tokens"]
        x = self._dec_embed(params, tokens, dtype)

        def scan_body(h, pc):
            p, (self_c, cross_c) = pc
            normed = layers.layer_norm(p["ln1"], h, cfg.norm_eps)
            k, v = attn.gqa_project_kv(p["attn"], cfg, normed, None)
            self_c = {
                "k": _fill(self_c["k"], k),
                "v": _fill(self_c["v"], v),
            }
            a = attn.gqa_full(
                p["attn"], cfg, normed, causal=True, impl=self.attn_impl, kv=(k, v)
            )
            h = h + a
            ck, cv = attn.gqa_project_kv(p["cross"], cfg, enc_out, None)
            cross_c = {
                "k": ck.astype(cross_c["k"].dtype),
                "v": cv.astype(cross_c["v"].dtype),
            }
            normed = layers.layer_norm(p["ln_cross"], h, cfg.norm_eps)
            c = attn.gqa_full(
                p["cross"], cfg, normed, causal=False, impl=self.attn_impl,
                kv=(ck, cv),
            )
            h = h + c
            y = layers.mlp(
                p["mlp"], layers.layer_norm(p["ln2"], h, cfg.norm_eps), cfg.act
            )
            return h + y, (self_c, cross_c)

        x, (new_self, new_cross) = maybe_scan(
            scan_body, x, (params["decoder"], (cache["self"], cache["cross"]))
        )
        x = layers.layer_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x[:, -1:], dtype)
        return logits[:, 0], {"self": new_self, "cross": new_cross}

    def decode_step(
        self,
        params: dict,
        tokens: jax.Array,
        cache: dict,
        positions: jax.Array,
        *,
        dtype: Any = jnp.bfloat16,
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = self._dec_embed(params, tokens, dtype, pos_offset=positions)

        def scan_body(h, pc):
            p, (self_c, cross_c) = pc
            normed = layers.layer_norm(p["ln1"], h, cfg.norm_eps)
            a, self_c = attn.gqa_decode(p["attn"], cfg, normed, self_c, positions)
            h = h + a
            normed = layers.layer_norm(p["ln_cross"], h, cfg.norm_eps)
            q = attn.gqa_project_q(p["cross"], cfg, normed, None)
            c = attn.naive_attention(
                q, cross_c["k"], cross_c["v"], None, cfg.head_dim**-0.5
            )
            c = layers.dense(p["cross"]["wo"], c.reshape(c.shape[0], 1, -1))
            h = h + c
            y = layers.mlp(
                p["mlp"], layers.layer_norm(p["ln2"], h, cfg.norm_eps), cfg.act
            )
            return h + y, (self_c, cross_c)

        x, (new_self, new_cross) = maybe_scan(
            scan_body, x, (params["decoder"], (cache["self"], cache["cross"]))
        )
        x = layers.layer_norm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embed"], x, dtype)
        return logits[:, 0], {"self": new_self, "cross": new_cross}, {}


# ===========================================================================
# Factory + utilities
# ===========================================================================


def build_model(cfg: ModelConfig, **kw: Any):
    if cfg.family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg, **kw)
    if cfg.family == "ssm":
        return MambaLM(cfg, **{k: v for k, v in kw.items() if k != "attn_impl"})
    if cfg.family == "hybrid":
        return HybridLM(cfg, **kw)
    if cfg.family == "encdec":
        return EncDecLM(cfg, **kw)
    raise ValueError(f"unknown family {cfg.family!r}")


def active_param_count(cfg: ModelConfig, specs: PyTree) -> int:
    """Parameters touched per token: a token picks k of the E experts, so
    the held stacks count k/E of their size (the share of them it meets)."""
    from repro.models.params import map_with_path

    total = 0

    def visit(path: tuple[str, ...], s: ParamSpec) -> ParamSpec:
        nonlocal total
        n = s.size
        if cfg.n_experts and "moe" in path and path[-2] == "moe" and path[-1] in (
            "wi", "wg", "wo"
        ):
            n = int(n * cfg.n_experts_per_token / cfg.n_experts)
        total += n
        return s

    map_with_path(visit, specs)
    return total


#: randomly initialized leaves that some forward path reads at float32 — the
#: MoE router logits, the SSM depthwise conv, MLA's absorbed decode matmuls —
#: so bf16 storage would change what the model computes
_F32_READ_LEAVES = ("router", "conv_w", "wkv_b")


def serving_param_specs(specs: PyTree) -> PyTree:
    """Parameter specs at serving precision.

    Every randomly initialized leaf that the forward casts to the compute
    dtype before use (dense kernels, embedding tables, expert stacks) is
    stored at bf16: the cast then happens once at init instead of in
    every step, served tokens are unchanged, and weight memory halves.
    Ones/zeros leaves (norm scales, biases, SSM constants) and the f32-read
    leaves above keep their declared dtype."""

    def cast(path: tuple[str, ...], s: ParamSpec) -> ParamSpec:
        if s.init != "normal" or any(p in _F32_READ_LEAVES for p in path):
            return s
        return dataclasses.replace(s, dtype=jnp.bfloat16)

    return map_with_path(cast, specs)
