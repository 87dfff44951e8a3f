"""Mixture-of-Experts layer (token-choice top-k).

A layer holds ``cfg.experts_held`` experts, ``cfg.expert_offset`` onward:
all of them on one device, or one chip's share of an expert-parallel
deployment, where each chip adds its own experts' part of the result and
the shares sum to the whole layer.  The router always spans all
``n_experts``.

* **Dropless held-expert layer** (:func:`held_experts`, every unsharded
  caller: serving, the CPU forward and training): route in f32 over all
  experts (softmax, top-k, renormalized over the k), keep the (token,
  expert) pairs whose expert is held, place them by expert into row tiles
  of :data:`TILE_ROWS`, run the SiLU-gated expert MLP as a grouped matmul
  over the held stacks (``kernels/grouped_matmul``), and add each pair's
  weighted output back to its token.  Shapes are static whatever
  the routing; only group sizes are data.  Work follows the pairs and the
  experts they touch.
* **Capacity dispatch** (:func:`capacity_dispatch`, only under sharding
  rules: the multi-device dry-run; serving refuses an expert model on a
  multi-device replica) — GShard's
  one-hot ``(B, S, E, C)`` dispatch/combine einsums, which the SPMD
  partitioner shards over the ``expert`` mesh axis.  Each expert accepts at
  most ``C = round_up(k * S * capacity_factor / E, 4)`` tokens per batch
  row; overflowing tokens fall through on the residual path.
* **Aux load-balancing loss** (Switch-style) is returned alongside the
  output so the training loss can add ``router_aux_weight * aux``.
* **Shared experts** (DeepSeek-V2) are plain always-on MLPs added to the
  routed output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ModelConfig
from repro.kernels.grouped_matmul.ops import expert_gmm
from repro.models import layers
from repro.models.params import ParamSpec

#: rows of a grouped-matmul tile; each held expert's pairs are padded to a
#: whole number of tiles (16 is a bf16 sublane tile)
TILE_ROWS = 16


def expert_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Per-expert, per-batch-row token capacity (multiple of 4 for layout)."""
    cap = cfg.n_experts_per_token * seq_len * cfg.capacity_factor / cfg.n_experts
    cap = int(cap + 0.999)
    return max(4, ((cap + 3) // 4) * 4)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig) -> dict:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held
    spec: dict = {
        "router": ParamSpec((d, e), jnp.float32, ("embed", "expert")),
        # the held expert stacks: leading expert axis shards over "model"
        "wi": ParamSpec((held, d, ff), jnp.float32, ("expert", "expert_data", None)),
        "wg": ParamSpec((held, d, ff), jnp.float32, ("expert", "expert_data", None)),
        "wo": ParamSpec((held, ff, d), jnp.float32, ("expert", None, "expert_data")),
    }
    if cfg.n_shared_experts:
        # shared experts = one fused MLP with n_shared * moe_d_ff hidden
        spec["shared"] = layers.gated_mlp_spec(d, cfg.n_shared_experts * ff)
    return spec


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def route(
    params: dict, cfg: ModelConfig, x: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router over all experts, in f32: (probs (..., E), top-k weights
    renormalized over the k, top-k expert ids)."""
    logits = jnp.dot(
        x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.n_experts_per_token)
    weights = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return probs, weights, top_e


def _aux_loss(probs: jax.Array, top_e: jax.Array, e: int) -> jax.Array:
    """Switch load balancing: fraction of tokens routed to each expert times
    its mean router probability, summed, times E."""
    lead = tuple(range(probs.ndim - 1))
    picked = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=-2)
    return e * jnp.sum(jnp.mean(picked, axis=lead) * jnp.mean(probs, axis=lead))


def moe_layer(
    params: dict, cfg: ModelConfig, x: jax.Array, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, dict]:
    """Routed experts (+ shared) on ``x`` (B, S, D); ``valid`` (B, S) marks
    the rows that are tokens.  Returns (out, aux_loss, counts)."""
    if sharding.current_rules() is not None:
        out, aux = capacity_dispatch(params, cfg, x)
        return out, aux, {}
    return held_experts(params, cfg, x, valid)


def held_experts(
    params: dict, cfg: ModelConfig, x: jax.Array, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, dict]:
    """The dropless layer: the held experts' part of the routed output for
    every (token, held expert) pair, plus any shared expert.  ``params``
    holds one layer's stacks, (held, ...), or every layer's with the
    layer's index under ``layer`` (:func:`split_experts`).  ``counts``
    holds this layer's int32 counters: pairs computed, held experts
    given at least one row, and top-k picks of valid rows (the names of
    ``core.engines.EXPERT_COUNTS``)."""
    b, s, d = x.shape
    t, k = b * s, cfg.n_experts_per_token
    held, tm = cfg.experts_held, TILE_ROWS
    xt = x.reshape(t, d)
    rows = jnp.ones((t,), bool) if valid is None else valid.reshape(t)

    with jax.named_scope("route"):
        probs, weights, top_e = route(params, cfg, xt)
        aux = _aux_loss(probs, top_e, cfg.n_experts)

    with jax.named_scope("dispatch"):
        local = top_e - cfg.expert_offset
        mine = ((local >= 0) & (local < held) & rows[:, None]).reshape(-1)
        # (t * k, held): each pair's held expert, none for the others
        onehot = (
            (local.reshape(-1, 1) == jnp.arange(held)) & mine[:, None]
        ).astype(jnp.int32)
        sizes = jnp.sum(onehot, axis=0)
        padded = (sizes + tm - 1) // tm * tm
        start = jnp.cumsum(padded) - padded  # first row of each expert's tiles
        # each pair's row: its expert's start plus the pairs before it there
        before = jnp.cumsum(onehot, axis=0) - onehot
        m = -(-(t * min(k, held) + held * (tm - 1)) // tm) * tm
        dest = jnp.where(
            mine, jnp.sum(onehot * (start + before), axis=1), m
        )
        xs = jnp.zeros((m, d), x.dtype).at[dest].set(
            jnp.repeat(xt, k, axis=0), mode="drop"
        )

    with jax.named_scope("experts"):
        # a scanned stack hands over every layer's experts and the layer
        layer = params.get("layer")

        def gmm(lhs, name):
            w = params[name].astype(x.dtype)
            return expert_gmm(lhs, w, padded, layer, tile_rows=tm)

        gate, up = gmm(xs, "wg"), gmm(xs, "wi")
        h = (layers.activation(cfg.act, gate) * up).astype(x.dtype)
        y = gmm(h, "wo")

    with jax.named_scope("combine"):
        # pairs are token-major: each token's k rows, weighted and summed
        part = jnp.where(mine[:, None], y[jnp.minimum(dest, m - 1)], 0.0)
        out = jnp.sum(part.reshape(t, k, d) * weights[..., None], axis=1)
        out = out.astype(x.dtype).reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + layers.gated_mlp(params["shared"], x, cfg.act)
    counts = {
        "expert_pairs": jnp.sum(sizes),
        "experts_touched": jnp.sum(sizes > 0).astype(jnp.int32),
        "expert_picks": k * jnp.sum(rows).astype(jnp.int32),
    }
    return out, aux.astype(jnp.float32), counts


#: the expert stacks a layer scan leaves whole (:func:`split_experts`)
EXPERT_STACKS = ("wg", "wi", "wo")


def split_experts(stack: dict) -> tuple[dict, dict]:
    """Split a scanned layer stack into what the scan slices per layer and
    the expert stacks it leaves whole, with a ``layer`` index in their
    place: the grouped-matmul kernel reads a layer's experts out of the
    whole stacks, where a slice handed to it would first be copied out,
    every held expert, every layer, every step.  Dense stacks, and stacks
    under sharding rules (capacity dispatch), come back as they are."""
    if "moe" not in stack or sharding.current_rules() is not None:
        return stack, {}
    sliced = dict(stack["moe"])
    whole = {k: sliced.pop(k) for k in EXPERT_STACKS}
    sliced["layer"] = jnp.arange(whole["wg"].shape[0], dtype=jnp.int32)
    return dict(stack, moe=sliced), whole


def join_experts(p_layer: dict, whole: dict) -> dict:
    """One layer's parameters, with the whole expert stacks put back."""
    if not whole:
        return p_layer
    return dict(p_layer, moe=dict(p_layer["moe"], **whole))


# ---------------------------------------------------------------------------
# Capacity dispatch (sharded callers)
# ---------------------------------------------------------------------------


def _top_k_mask(
    probs: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Return (weights, mask) of shape (..., E): top-k gate values, 0 elsewhere."""
    top_vals, _ = jax.lax.top_k(probs, k)
    thresh = top_vals[..., -1:]
    mask = probs >= thresh
    # guard against ties admitting >k experts: keep weights but renormalize
    weights = jnp.where(mask, probs, 0.0)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9
    )
    return weights, mask


def capacity_dispatch(
    params: dict, cfg: ModelConfig, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Routed-experts forward with capacity dropping, for the SPMD
    partitioner.  ``x`` is (B, S, D); returns (out, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_per_token
    if cfg.experts_held != e:
        raise ValueError(
            f"{cfg.name}: the capacity dispatch computes all {e} experts, "
            f"the layer holds {cfg.experts_held}"
        )
    cap = expert_capacity(cfg, s)
    f32 = jnp.float32

    logits = jnp.einsum(
        "bsd,de->bse", x.astype(f32), params["router"].astype(f32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (B,S,E)
    weights, mask = _top_k_mask(probs, k)

    # --- Switch-style aux load-balancing loss ------------------------------
    # fraction of tokens routed to each expert x mean router prob per expert
    frac_tokens = jnp.mean(mask.astype(f32), axis=(0, 1))  # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))  # (E,)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # --- capacity assignment -------------------------------------------------
    # position of each token within its expert's queue (per batch row)
    pos_in_expert = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1  # (B,S,E)
    keep = mask & (pos_in_expert < cap)
    # one-hot over capacity slots: (B,S,E,C)
    slot_oh = jax.nn.one_hot(
        jnp.where(keep, pos_in_expert, -1), cap, dtype=x.dtype
    )
    dispatch = slot_oh  # (B,S,E,C), 1 where token -> (expert, slot)
    combine = slot_oh.astype(f32) * weights[..., None].astype(f32)

    # --- dispatch -> expert MLP -> combine ----------------------------------
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    rules = sharding.current_rules()
    if rules is not None and rules.moe_dispatch == "weight_stationary":
        # decode-time 2D expert sharding: keep weights still, reshard the
        # (tiny) dispatched token block to match the weights' d_model shards
        expert_in = sharding.constrain(
            expert_in, ("expert", None, None, "expert_data")
        )
    else:
        expert_in = sharding.constrain(expert_in, ("expert", "batch", None, None))
    h_g = jnp.einsum("ebcd,edf->ebcf", expert_in, params["wg"].astype(x.dtype))
    h_i = jnp.einsum("ebcd,edf->ebcf", expert_in, params["wi"].astype(x.dtype))
    h = layers.activation(cfg.act, h_g) * h_i
    h = sharding.constrain(h, ("expert", "batch", None, None))
    expert_out = jnp.einsum("ebcf,efd->ebcd", h, params["wo"].astype(x.dtype))

    out = jnp.einsum(
        "bsec,ebcd->bsd", combine.astype(f32), expert_out.astype(f32)
    ).astype(x.dtype)

    if cfg.n_shared_experts:
        out = out + layers.gated_mlp(params["shared"], x, cfg.act)
    out = sharding.constrain(out, ("batch", None, "embed"))
    return out, aux.astype(f32)
