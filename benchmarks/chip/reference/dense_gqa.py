"""Plain reference for the dense GQA decoders of the Qwen2 and Qwen3
families: a straightforward float32 forward pass in ``jax.numpy`` at
``Precision.HIGHEST``, one layer at a time, with no cache, no batching
tricks and no kernels.  It imports nothing of the served program.

Weights are rebuilt from the seed by the rule the configuration file
states (``weights.rule``): one ``split`` of ``key(seed)`` over the leaves
in sorted-path order, each normal leaf ``normal(0, 1/sqrt(fan_in))`` drawn
in float32 and stored in bfloat16, norm scales one and biases zero.

Layer equations (Qwen2/Qwen3, HF ``modeling_qwen2``/``modeling_qwen3``):
``h = x + Wo·attn(rope(qnorm(Wq·rms(x))), rope(knorm(Wk·rms(x))), Wv·rms(x))``
then ``h + W2·(silu(Wg·rms(h)) * Wi·rms(h))``; RoPE rotates halves (NeoX
layout); Qwen2 has q/k/v biases and no q/k norm, Qwen3 the reverse.
Logits are ``rms(x)·E^T`` (tied) or ``rms(x)·U`` over the first
``vocab_size`` rows.

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

HI = jax.lax.Precision.HIGHEST


class _Leaf:
    """Shape and init of one weight, as a pytree leaf."""

    def __init__(self, shape: tuple[int, ...], init: str):
        self.shape, self.init = shape, init


def _is_leaf(x: Any) -> bool:
    return isinstance(x, _Leaf)


def weight_layout(c: dict) -> dict:
    """The weight tree of configuration ``c`` (HF keys)."""
    d, n_l = c["hidden_size"], c["num_hidden_layers"]
    h, k = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    ff, vp = c["intermediate_size"], c["weights"]["padded_vocab"]
    bias = c["model_type"] == "qwen2"

    def dense(din: int, dout: int, with_bias: bool = False) -> dict:
        out = {"kernel": _Leaf((n_l, din, dout), "normal")}
        if with_bias:
            out["bias"] = _Leaf((n_l, dout), "zeros")
        return out

    attn = {
        "wq": dense(d, h * dh, bias),
        "wk": dense(d, k * dh, bias),
        "wv": dense(d, k * dh, bias),
        "wo": dense(h * dh, d),
    }
    if c["model_type"] == "qwen3":
        attn["q_norm"] = {"scale": _Leaf((n_l, dh), "ones")}
        attn["k_norm"] = {"scale": _Leaf((n_l, dh), "ones")}
    tree = {
        "dense_layers": {
            "attn": attn,
            "ln1": {"scale": _Leaf((n_l, d), "ones")},
            "ln2": {"scale": _Leaf((n_l, d), "ones")},
            "mlp": {"wg": dense(d, ff), "wi": dense(d, ff), "wo": dense(ff, d)},
        },
        "embed": {"table": _Leaf((vp, d), "normal")},
        "final_norm": {"scale": _Leaf((d,), "ones")},
    }
    if not c["tie_word_embeddings"]:
        tree["unembed"] = {"kernel": _Leaf((d, vp), "normal")}
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_leaf(key, shape, init, std):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    draw = jax.lax.reduce_precision(
        jax.random.normal(key, shape), exponent_bits=8, mantissa_bits=23
    )
    return (std * draw).astype(jnp.bfloat16)


def make_weights(c: dict, seed: int) -> dict:
    leaves, treedef = jax.tree.flatten(weight_layout(c), is_leaf=_is_leaf)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for key, leaf in zip(keys, leaves):
        fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
        out.append(_make_leaf(key, leaf.shape, leaf.init, 1.0 / math.sqrt(fan_in)))
    return jax.tree.unflatten(treedef, out)


# -- forward -------------------------------------------------------------------


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round to fp8 (e4m3) with one absmax scale per slice along ``axis``."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mat(w: jax.Array, quantized: bool) -> jax.Array:
    # kernels are stored (in, out): one scale per output column
    return _fp8(w, 0) if quantized else w.astype(jnp.float32)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, :, None].astype(jnp.float32) * inv  # (B, T, dh/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("shape", "quantized"))
def _layer(x, p, shape, quantized):
    (h, k, dh, eps, theta, qk_norm) = shape
    b, t, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))

    a = p["attn"]
    xn = _rms(x, p["ln1"]["scale"], eps)
    q = jnp.einsum("btd,df->btf", xn, _mat(a["wq"]["kernel"], quantized),
                   precision=HI)
    kk = jnp.einsum("btd,df->btf", xn, _mat(a["wk"]["kernel"], quantized),
                    precision=HI)
    v = jnp.einsum("btd,df->btf", xn, _mat(a["wv"]["kernel"], quantized),
                   precision=HI)
    if "bias" in a["wq"]:
        q, kk, v = q + a["wq"]["bias"], kk + a["wk"]["bias"], v + a["wv"]["bias"]
    q = q.reshape(b, t, h, dh)
    kk = kk.reshape(b, t, k, dh)
    v = v.reshape(b, t, k, dh)
    if qk_norm:
        q = _rms(q, a["q_norm"]["scale"], eps)
        kk = _rms(kk, a["k_norm"]["scale"], eps)
    q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
    g = h // k
    qg = q.reshape(b, t, k, g, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk, precision=HI) / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(b, t, h * dh)
    x = x + jnp.einsum("btf,fd->btd", o, _mat(a["wo"]["kernel"], quantized),
                       precision=HI)
    xn = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    gate = jnp.einsum("btd,df->btf", xn, _mat(m["wg"]["kernel"], quantized),
                      precision=HI)
    up = jnp.einsum("btd,df->btf", xn, _mat(m["wi"]["kernel"], quantized),
                    precision=HI)
    return x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                          _mat(m["wo"]["kernel"], quantized), precision=HI)


@functools.partial(jax.jit, static_argnames=("quantized",))
def _embed(table, tokens, quantized):
    rows = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    if quantized:
        # one scale per vocabulary row, the rows' own absmax
        rows = _fp8(rows, -1)
    return rows


@functools.partial(jax.jit, static_argnames=("vocab", "tied", "quantized"))
def _logit_stats(w, hid, targets, vocab, tied, quantized):
    """Max logit, logit of ``targets`` and argmax over the real vocabulary."""
    if tied:
        table = w[:vocab].astype(jnp.float32)
        table = _fp8(table, 1) if quantized else table
        logits = jnp.einsum("nd,vd->nv", hid, table, precision=HI)
    else:
        kern = w[:, :vocab]
        logits = jnp.einsum("nd,dv->nv", hid, _mat(kern, quantized), precision=HI)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1), at, jnp.argmax(logits, axis=1)


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, c: dict, seed: int):
        self.c = c
        self.w = make_weights(c, seed)
        d, h = c["hidden_size"], c["num_attention_heads"]
        self.shape = (
            h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            c["model_type"] == "qwen3",
        )

    def hidden(self, seqs: list[list[int]], positions: list[list[int]],
               quantized: bool = False, batch: int = 8) -> np.ndarray:
        """Final normed hidden states at ``positions`` of each sequence,
        stacked in order: (sum of len(positions), hidden_size) float32."""
        c, w = self.c, self.w
        order = sorted(range(len(seqs)), key=lambda i: len(seqs[i]))
        out: dict[int, np.ndarray] = {}
        stack = w["dense_layers"]
        n_layers = c["num_hidden_layers"]
        for lo in range(0, len(order), batch):
            idx = order[lo : lo + batch]
            t = max(len(seqs[i]) for i in idx)
            t = -(-t // 64) * 64  # a few padded lengths: a few programs
            toks = np.zeros((len(idx), t), np.int32)
            for r, i in enumerate(idx):
                toks[r, : len(seqs[i])] = seqs[i]
            x = _embed(w["embed"]["table"], jnp.asarray(toks), quantized)
            for layer in range(n_layers):
                p = jax.tree.map(lambda a, layer=layer: a[layer], stack)
                x = _layer(x, p, self.shape, quantized)
            x = _rms(x, w["final_norm"]["scale"], self.shape[3])
            x = np.asarray(x)
            for r, i in enumerate(idx):
                out[i] = x[r, positions[i]]
        return np.concatenate([out[i] for i in range(len(seqs))], axis=0)

    def logit_stats(self, hid: np.ndarray, targets: np.ndarray,
                    quantized: bool = False, block: int = 256):
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
        return tuple(np.concatenate([np.asarray(o[j]) for o in outs]) for j in range(3))
