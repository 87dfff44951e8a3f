"""Qwen3-MoE (``model_type`` ``qwen3_moe``): Qwen3's attention (q/k RMS
norm, no biases), then in every layer a router over all ``num_experts``
(softmax, top ``num_experts_per_tok``, renormalized over those) and the
SiLU-gated experts the chip holds.

A file may state one chip's share of an expert-parallel deployment: its
``num_experts`` is then the count this chip holds, listed in ``reduced``,
``published.num_experts`` the router's width, and ``assumed.expert_offset``
the first expert held.

The work an expert layer needs depends on the routing, so its counts read
what the program did in the window (``ctx``, the per-layer reader's
context): the ``BatcherStats`` counters of (token, held expert) pairs and of
held experts touched, per decode step and per admission.  FLOPs follow the
pairs; expert weight bytes follow the experts touched (each prefill chunk
reads the experts it touches).  Attention, the router and the head follow
from the shapes as in :mod:`families._dense`.
"""

from __future__ import annotations

from families import _dense

#: published keys whose other values would need a layer the program lacks
COMPUTES = {
    "hidden_act": "silu", "rope_scaling": None, "use_sliding_window": False,
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True,
}
#: keys that would add a shared expert, which the program's Qwen3-MoE lacks
SHARED = ("shared_expert_intermediate_size", "num_shared_experts")


def dims(c: dict) -> dict:
    m = _dense.dims(c)
    m.update(
        ff=c["moe_intermediate_size"], held=c["num_experts"],
        experts=c.get("published", {}).get("num_experts", c["num_experts"]),
        k=c["num_experts_per_tok"],
    )
    return m


def program_fields(c: dict) -> dict:
    """Qwen3's fields, the router's and the held experts'.  ``d_ff`` is left
    out: no layer is dense, so ``intermediate_size`` shapes nothing."""
    m = dims(c)
    fields = _dense.program_fields(c)
    del fields["d_ff"]
    return dict(
        fields, qkv_bias=False, qk_norm=True,
        moe_d_ff=m["ff"], n_experts=m["experts"], experts_held=m["held"],
        expert_offset=c.get("assumed", {}).get("expert_offset", 0),
        n_experts_per_token=m["k"], n_shared_experts=0, first_k_dense=0,
    )


def refuse(c: dict) -> list[str]:
    return sorted(_dense.refused(c, COMPUTES) + [k for k in SHARED if c.get(k)])


def attn_params(c: dict) -> int:
    m = dims(c)
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return m["d"] * (q + 2 * kv) + q * m["d"]


def expert_params(c: dict) -> int:
    """One expert's gate, up and down matrices."""
    m = dims(c)
    return 3 * m["d"] * m["ff"]


def shared_params(c: dict) -> int:
    """Matrix weights of one layer outside its experts: attention and the
    router."""
    m = dims(c)
    return attn_params(c) + m["d"] * m["experts"]


def weight_bytes(c: dict) -> int:
    """Weights one forward pass reads where every held expert is touched:
    every layer's matrices and the output head, once."""
    m = dims(c)
    per_layer = shared_params(c) + m["held"] * expert_params(c)
    return (m["layers"] * per_layer + _dense.head_params(c)) * m["bytes"]


def kv_bytes_per_position(c: dict) -> int:
    return _dense.kv_bytes_per_position(c)


def token_flops(c: dict, context: int) -> int:
    """One token through every layer's attention (attending to ``context``
    positions) and router, without its experts or the output head."""
    m = dims(c)
    attn = 4 * m["heads"] * m["head_dim"] * context
    return m["layers"] * (2 * shared_params(c) + attn)


def expert_gmm(c: dict, pairs: float, touched: float) -> tuple[float, float]:
    """(flops, bytes) of the expert layers' grouped matmuls for ``pairs``
    (token, held expert) pairs on ``touched`` held experts, both summed over
    layers: gate and up over each pair's row, down over its hidden row; each
    touched expert's three matrices read once, each pair's rows in and out."""
    m = dims(c)
    flops = 2 * pairs * expert_params(c)
    nbytes = (touched * expert_params(c) + pairs * 3 * (m["d"] + m["ff"])) * m["bytes"]
    return float(flops), float(nbytes)


def window_experts(ctx, phase: str) -> tuple[float, float]:
    """Mean (pairs, experts touched) over layers of one decode step
    (``phase`` ``decode``) or one admission (``prefill``) in the window,
    from the program's ``BatcherStats`` counters."""
    if ctx is None:
        raise ValueError("qwen3_moe: expert work is counted from a window's counters")
    a, b = ctx.window.stats0, ctx.window.stats1
    key = f"expert_pairs_{phase}"
    if key not in b:
        raise RuntimeError(f"qwen3_moe: the program counts no {key}")
    units = "steps" if phase == "decode" else "admissions"
    n = max(b.get(units, 0) - a.get(units, 0), 1)
    pairs = b[key] - a.get(key, 0)
    touched = b[f"experts_touched_{phase}"] - a.get(f"experts_touched_{phase}", 0)
    return pairs / n, touched / n


def decode_step(c: dict, contexts: list[int], ctx=None) -> tuple[float, float]:
    """(flops, bytes) of one decode step over slots with these context
    lengths: attention, router and head as the dense count has them, the
    window's mean expert pairs and experts touched per step, the live cache
    read once and the new cache row written."""
    m = dims(c)
    pairs, touched = window_experts(ctx, "decode")
    e_flops, e_bytes = expert_gmm(c, pairs, touched)
    flops = sum(token_flops(c, n) + 2 * _dense.head_params(c) for n in contexts)
    kv = kv_bytes_per_position(c)
    nbytes = (m["layers"] * shared_params(c) + _dense.head_params(c)) * m["bytes"]
    nbytes += sum(n - 1 for n in contexts) * kv
    nbytes += len(contexts) * (kv + m["d"] * m["bytes"])
    return float(flops + e_flops), float(nbytes + e_bytes)


def prefill(c: dict, n: int, start: int, ctx=None) -> tuple[float, float]:
    """(flops, bytes) of prefilling prompt positions ``[start, n)``:
    attention, router and head as the dense count has them, the window's
    mean expert pairs and experts touched per admission, the prefix cache
    read once and the new rows' cache written."""
    m = dims(c)
    pairs, touched = window_experts(ctx, "prefill")
    e_flops, e_bytes = expert_gmm(c, pairs, touched)
    flops = sum(token_flops(c, p + 1) for p in range(start, n))
    flops += 2 * _dense.head_params(c)
    kv = kv_bytes_per_position(c)
    nbytes = (m["layers"] * shared_params(c) + _dense.head_params(c)) * m["bytes"]
    nbytes += start * kv + (n - start) * (kv + m["d"] * m["bytes"])
    return float(flops + e_flops), float(nbytes + e_bytes)
