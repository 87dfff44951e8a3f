"""Work counts: the operations and bytes each unit of work needs, from the
configuration (HF keys) and the shapes the traffic gave it.

Only the work the algorithm needs is counted: valid prompt rows, live
context positions, valid words, and every weight and cache value at the
precision the configuration states (``torch_dtype``), however the program
stores it.  A program that pads, recomputes or keeps float32 copies spends
more time for the same count, and its roofline share falls.
"""

from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "d": d,
        "layers": c["num_hidden_layers"],
        "heads": h,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or d // h,
        "ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "bytes": _DTYPE_BYTES[c["torch_dtype"]],
    }


def layer_params(c: dict) -> int:
    """Matrix weights of one layer (norm scales and biases left out)."""
    m = dims(c)
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return m["d"] * (q + 2 * kv) + q * m["d"] + 3 * m["d"] * m["ff"]


def head_params(c: dict) -> int:
    m = dims(c)
    return m["d"] * m["vocab"]


def weight_bytes(c: dict) -> int:
    """Weights one forward pass reads: every layer's matrices and the
    output head, once."""
    m = dims(c)
    return (m["layers"] * layer_params(c) + head_params(c)) * m["bytes"]


def kv_bytes_per_position(c: dict) -> int:
    m = dims(c)
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * m["bytes"]


def token_flops(c: dict, context: int) -> int:
    """One token through every layer, attending to ``context`` positions
    (itself included), without the output head."""
    m = dims(c)
    attn = 4 * m["heads"] * m["head_dim"] * context
    return m["layers"] * (2 * layer_params(c) + attn)


def decode_step(c: dict, contexts: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one decode step over slots with these context
    lengths: one new token each, its logits, the live cache read once and
    the new cache row written."""
    m = dims(c)
    flops = sum(token_flops(c, n) + 2 * head_params(c) for n in contexts)
    kv = kv_bytes_per_position(c)
    nbytes = weight_bytes(c) + sum(n - 1 for n in contexts) * kv
    nbytes += len(contexts) * (kv + m["d"] * m["bytes"])
    return float(flops), float(nbytes)


def prefill(c: dict, n: int, start: int) -> tuple[float, float]:
    """(flops, bytes) of prefilling prompt positions ``[start, n)`` after
    ``start`` positions served from the prefix cache: those rows, the last
    position's logits, the weights read once, the prefix cache read once
    and the new rows' cache written."""
    m = dims(c)
    flops = sum(token_flops(c, p + 1) for p in range(start, n))
    flops += 2 * head_params(c)
    kv = kv_bytes_per_position(c)
    nbytes = weight_bytes(c) + start * kv + (n - start) * (kv + m["d"] * m["bytes"])
    return float(flops), float(nbytes)


def bootstrap_partials(n: int, metrics: int, n_boot: int) -> tuple[float, float]:
    """Weighted sums and weight sums of ``n`` examples x ``metrics`` scores
    for ``n_boot`` replicates: two multiply-adds per weight and score."""
    flops = 2 * 2 * n_boot * n * metrics
    nbytes = 4 * (n * metrics + 2 * n_boot * metrics)
    return float(flops), float(nbytes)


def bertscore_pr(pairs: list[tuple[int, int]], dim: int = 256) -> tuple[float, float]:
    """Cosine similarities of every candidate word against every reference
    word, over ``(candidate words, reference words)`` per example."""
    flops = sum(2 * lc * lr * dim for lc, lr in pairs)
    nbytes = sum((lc + lr) * dim * 4 for lc, lr in pairs)
    return float(flops), float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
