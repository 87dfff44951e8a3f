"""Qwen3 (``model_type`` ``qwen3``): the dense GQA decoder with an RMS norm
on each head's queries and keys, and no attention biases."""

from families import _dense
from families._dense import (  # noqa: F401 — the family's work counts
    decode_step,
    kv_bytes_per_position,
    prefill,
    token_flops,
    weight_bytes,
)

#: published keys whose other values would need a layer the program lacks
COMPUTES = {
    "hidden_act": "silu", "rope_scaling": None, "use_sliding_window": False,
    "attention_bias": False,
}


def program_fields(c: dict) -> dict:
    return dict(_dense.program_fields(c), qkv_bias=False, qk_norm=True)


def refuse(c: dict) -> list[str]:
    return _dense.refused(c, COMPUTES)
