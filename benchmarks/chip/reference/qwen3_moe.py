"""Plain reference for Qwen3-MoE and for one chip's share of it: a
straightforward float32 forward pass in ``jax.numpy`` at
``Precision.HIGHEST``, one layer at a time, with no cache, no batching
tricks and no kernels.  It imports nothing of the served program; the
helpers it shares with the dense reference (norm, RoPE, fp8 rounding,
embedding and logits) are :mod:`reference.dense_gqa`'s.

Weights are rebuilt from the seed by the rule the configuration file
states (``weights.rule``): one ``split`` of ``key(seed)`` over the leaves
in sorted-path order, each normal leaf ``normal(0, 1/sqrt(fan_in))`` drawn
in float32 and stored in bfloat16 (the router in float32), norm scales
one.  The leaves are the served model's: per layer the attention, the
router over all ``E = published.num_experts`` experts and the stacks of the
``H = num_experts`` experts held, ``expert_offset`` onward.

Layer equations (HF ``modeling_qwen3_moe``): attention as Qwen3's
(``dense_gqa``), then with ``u = rms(h)``:
``p = softmax(u·R)`` over all E experts, ``T`` = the top ``k`` of ``p``,
``w_e = p_e / Σ_{T} p`` (``norm_topk_prob``), and the layer adds
``Σ_{e ∈ T, e held} w_e · W2_e(silu(Wg_e·u) * Wi_e·u)``: the held experts'
share of the routed sum, which is what this chip adds to the residual.
Logits are ``rms(x)·U`` over the first ``vocab_size`` rows.

Router near-ties: where the k-th and (k+1)-th router probabilities of a
layer lie closer than rounding the router's input to bfloat16 can move
them, and one of the two experts is held, the program may route the token
to the other and add another expert's share.  ``hidden`` records, for each
compared position, the smallest such gap over layers (``margins``): the log
ratio of the two probabilities, in standard deviations of what rounding
each input element to bfloat16 (a uniform relative error of at most 2^-9)
moves it by.  :meth:`Reference.tie_share` gives the share of them under
:data:`TIE_Z`.  The check leaves no position out.  About half the
positions have such a tie at some layer, yet the gap over the positions
without one is about the gap over all of them (``PERF.md`` §6): a near-tie
at the compared position does not set the cell's ``logit_gap``.

``quantized=True`` is the control: every matrix is rounded to fp8 (e4m3)
with one absmax scale per output channel (per vocabulary row for the
embedding) before use, everything else as above.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense_gqa import HI, _embed, _fp8, _logit_stats, _mat, _rms, _rope

#: standard deviation of the relative error of rounding to bfloat16 (uniform
#: within half an ulp, 2^-9)
BF16_SIGMA = 2.0**-9 / math.sqrt(3.0)
#: a near-tie: the k-th and (k+1)-th probabilities closer than this many
#: standard deviations of the gap that bfloat16 rounding of the router's
#: input gives
TIE_Z = 3.0


class _Leaf:
    """Shape, init and dtype of one weight, as a pytree leaf."""

    def __init__(self, shape: tuple[int, ...], init: str, dtype=jnp.bfloat16):
        self.shape, self.init, self.dtype = shape, init, dtype


def _is_leaf(x: Any) -> bool:
    return isinstance(x, _Leaf)


def weight_layout(c: dict) -> dict:
    """The weight tree of configuration ``c`` (HF keys): the served
    model's leaves."""
    d, n_l = c["hidden_size"], c["num_hidden_layers"]
    h, k = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    ff, held = c["moe_intermediate_size"], c["num_experts"]
    e = c.get("published", {}).get("num_experts", held)
    vp = c["weights"]["padded_vocab"]

    def dense(din: int, dout: int) -> dict:
        return {"kernel": _Leaf((n_l, din, dout), "normal")}

    tree = {
        "moe_layers": {
            "attn": {
                "wq": dense(d, h * dh), "wk": dense(d, k * dh),
                "wv": dense(d, k * dh), "wo": dense(h * dh, d),
                "q_norm": {"scale": _Leaf((n_l, dh), "ones", jnp.float32)},
                "k_norm": {"scale": _Leaf((n_l, dh), "ones", jnp.float32)},
            },
            "ln1": {"scale": _Leaf((n_l, d), "ones", jnp.float32)},
            "ln2": {"scale": _Leaf((n_l, d), "ones", jnp.float32)},
            "moe": {
                "router": _Leaf((n_l, d, e), "normal", jnp.float32),
                "wg": _Leaf((n_l, held, d, ff), "normal"),
                "wi": _Leaf((n_l, held, d, ff), "normal"),
                "wo": _Leaf((n_l, held, ff, d), "normal"),
            },
        },
        "embed": {"table": _Leaf((vp, d), "normal")},
        "final_norm": {"scale": _Leaf((d,), "ones", jnp.float32)},
    }
    if not c["tie_word_embeddings"]:
        tree["unembed"] = {"kernel": _Leaf((d, vp), "normal")}
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make_leaf(key, shape, init, std, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    draw = jax.lax.reduce_precision(
        jax.random.normal(key, shape), exponent_bits=8, mantissa_bits=23
    )
    return (std * draw).astype(dtype)


def make_weights(c: dict, seed: int) -> dict:
    leaves, treedef = jax.tree.flatten(weight_layout(c), is_leaf=_is_leaf)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for key, leaf in zip(keys, leaves):
        fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
        out.append(_make_leaf(
            key, leaf.shape, leaf.init, 1.0 / math.sqrt(fan_in), leaf.dtype
        ))
    return jax.tree.unflatten(treedef, out)


# -- forward -------------------------------------------------------------------


def _attention(x, a, ln1, shape, quantized):
    (h, k, dh, eps, theta, _k, _offset) = shape
    b, t, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    xn = _rms(x, ln1, eps)
    q = jnp.einsum("btd,df->btf", xn, _mat(a["wq"]["kernel"], quantized),
                   precision=HI).reshape(b, t, h, dh)
    kk = jnp.einsum("btd,df->btf", xn, _mat(a["wk"]["kernel"], quantized),
                    precision=HI).reshape(b, t, k, dh)
    v = jnp.einsum("btd,df->btf", xn, _mat(a["wv"]["kernel"], quantized),
                   precision=HI).reshape(b, t, k, dh)
    q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, theta)
    kk = _rope(_rms(kk, a["k_norm"]["scale"], eps), pos, theta)
    g = h // k
    qg = q.reshape(b, t, k, g, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk, precision=HI) / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(b, t, h * dh)
    return x + jnp.einsum("btf,fd->btd", o, _mat(a["wo"]["kernel"], quantized),
                          precision=HI)


def _margin(u, router, logp, idx, top, offset, held):
    """The log ratio of the k-th to the (k+1)-th probability, over its
    spread under bfloat16 rounding of ``u``; inf where neither is held."""
    a, b = idx[..., top - 1], idx[..., top]
    diff = jnp.take(router, a, axis=1) - jnp.take(router, b, axis=1)  # (D, B, T)
    spread = BF16_SIGMA * jnp.sqrt(jnp.einsum("btd,dbt->bt", u * u, diff * diff,
                                              precision=HI))
    gap = (jnp.take_along_axis(logp, a[..., None], -1)
           - jnp.take_along_axis(logp, b[..., None], -1))[..., 0]
    involved = ((a >= offset) & (a < offset + held)) | (
        (b >= offset) & (b < offset + held))
    return jnp.where(involved, gap / jnp.maximum(spread, 1e-30), jnp.inf)


def _experts(u, m, shape, quantized):
    """The held experts' share of the routed sum, and each position's
    near-tie margin (:func:`_margin`)."""
    top, offset = shape[5], shape[6]
    router = m["router"].astype(jnp.float32)
    if quantized:
        router = _fp8(router, 0)
    logp = jax.nn.log_softmax(
        jnp.einsum("btd,de->bte", u, router, precision=HI), -1)
    p = jnp.exp(logp)
    vals, idx = jax.lax.top_k(p, top + 1)
    w = vals[..., :top] / jnp.sum(vals[..., :top], axis=-1, keepdims=True)
    held = m["wg"].shape[0]
    # (B, T, H): each held expert's weight, 0 where the position did not pick it
    pick = jax.nn.one_hot(idx[..., :top] - offset, held, dtype=jnp.float32)
    gates = jnp.einsum("btk,btkh->bth", w, pick)

    def stack(name):
        # (H, in, out): one fp8 scale per expert and output channel
        w = m[name].astype(jnp.float32)
        return _fp8(w, 1) if quantized else w

    gate = jnp.einsum("btd,hdf->bthf", u, stack("wg"), precision=HI)
    up = jnp.einsum("btd,hdf->bthf", u, stack("wi"), precision=HI)
    y = jnp.einsum("bthf,hfd->bthd", jax.nn.silu(gate) * up, stack("wo"),
                   precision=HI)
    out = jnp.einsum("bth,bthd->btd", gates, y, precision=HI)
    return out, _margin(u, router, logp, idx, top, offset, held)


@functools.partial(jax.jit, static_argnames=("shape", "quantized"))
def _layer(x, p, shape, quantized):
    x = _attention(x, p["attn"], p["ln1"]["scale"], shape, quantized)
    u = _rms(x, p["ln2"]["scale"], shape[3])
    out, margin = _experts(u, p["moe"], shape, quantized)
    return x + out, margin


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, c: dict, seed: int):
        self.c = c
        self.w = make_weights(c, seed)
        d, h = c["hidden_size"], c["num_attention_heads"]
        self.shape = (
            h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            c["num_experts_per_tok"], int(c.get("assumed", {}).get("expert_offset", 0)),
        )
        #: per compared position of the last full-precision ``hidden`` call:
        #: its smallest near-tie margin over layers (:func:`_margin`)
        self.margins: np.ndarray | None = None

    def hidden(self, seqs: list[list[int]], positions: list[list[int]],
               quantized: bool = False, batch: int = 8) -> np.ndarray:
        """Final normed hidden states at ``positions`` of each sequence,
        stacked in order: (sum of len(positions), hidden_size) float32."""
        c, w = self.c, self.w
        order = sorted(range(len(seqs)), key=lambda i: len(seqs[i]))
        out: dict[int, np.ndarray] = {}
        margins: dict[int, np.ndarray] = {}
        stack = w["moe_layers"]
        for lo in range(0, len(order), batch):
            idx = order[lo : lo + batch]
            t = max(len(seqs[i]) for i in idx)
            t = -(-t // 64) * 64  # a few padded lengths: a few programs
            toks = np.zeros((len(idx), t), np.int32)
            for r, i in enumerate(idx):
                toks[r, : len(seqs[i])] = seqs[i]
            x = _embed(w["embed"]["table"], jnp.asarray(toks), quantized)
            low = jnp.full(toks.shape, jnp.inf)
            for layer in range(c["num_hidden_layers"]):
                p = jax.tree.map(lambda a, layer=layer: a[layer], stack)
                x, margin = _layer(x, p, self.shape, quantized)
                low = jnp.minimum(low, margin)
            x = _rms(x, w["final_norm"]["scale"], self.shape[3])
            x, low = np.asarray(x), np.asarray(low)
            for r, i in enumerate(idx):
                out[i] = x[r, positions[i]]
                margins[i] = low[r, positions[i]]
        if not quantized:
            self.margins = np.concatenate([margins[i] for i in range(len(seqs))])
        return np.concatenate([out[i] for i in range(len(seqs))], axis=0)

    def tie_share(self) -> float:
        """Share of the last compared positions with a near-tie (margin
        under :data:`TIE_Z`) at some layer."""
        if self.margins is None or not len(self.margins):
            return 0.0
        return float(np.mean(self.margins < TIE_Z))

    def logit_stats(self, hid: np.ndarray, targets: np.ndarray,
                    quantized: bool = False, block: int = 256):
        """Max logit, logit of ``targets`` and argmax over the real
        vocabulary."""
        c = self.c
        tied = bool(c["tie_word_embeddings"])
        w = self.w["embed"]["table"] if tied else self.w["unembed"]["kernel"]
        outs = []
        for lo in range(0, len(hid), block):
            outs.append(_logit_stats(
                w, jnp.asarray(hid[lo : lo + block]),
                jnp.asarray(targets[lo : lo + block], jnp.int32),
                c["vocab_size"], tied, quantized,
            ))
        return tuple(
            np.concatenate([np.asarray(o[j]) for o in outs]) for j in range(3)
        )
