"""The dense GQA decoder's work counts, shared by the Qwen2 and Qwen3
families: every layer runs the same attention and SiLU-gated MLP over all
its weights, so the counts follow from the shapes alone."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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
        "bytes": DTYPE_BYTES[c["torch_dtype"]],
    }


def program_fields(c: dict) -> dict:
    """The fields both Qwen families set alike; each adds its attention's
    bias and norm."""
    return {
        "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
        "tie_embeddings": c["tie_word_embeddings"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": float(c["rms_norm_eps"]),
    }


def refused(c: dict, computes: dict) -> list[str]:
    """Keys of ``c`` whose value is not the one the program's layer
    computes (``computes``: key -> that value)."""
    return sorted(k for k, v in computes.items() if k in c and c[k] != v)


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


def decode_step(c: dict, contexts: list[int], ctx=None) -> tuple[float, float]:
    """(flops, bytes) of one decode step over slots with these context
    lengths: one new token each, its logits, the live cache read once and
    the new cache row written."""
    m = dims(c)
    flops = sum(token_flops(c, n) + 2 * head_params(c) for n in contexts)
    kv = kv_bytes_per_position(c)
    nbytes = weight_bytes(c) + sum(n - 1 for n in contexts) * kv
    nbytes += len(contexts) * (kv + m["d"] * m["bytes"])
    return float(flops), float(nbytes)


def prefill(c: dict, n: int, start: int, ctx=None) -> tuple[float, float]:
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
