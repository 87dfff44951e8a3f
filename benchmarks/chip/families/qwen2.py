"""Qwen2 (``model_type`` ``qwen2``): the dense GQA decoder with biases on
the q, k and v projections and none on the output projection."""

from families import _dense
from families._dense import (  # noqa: F401 — the family's work counts
    decode_step,
    kv_bytes_per_position,
    prefill,
    token_flops,
    weight_bytes,
)

#: published keys whose other values would need a layer the program lacks
COMPUTES = {"hidden_act": "silu", "rope_scaling": None, "use_sliding_window": False}


def program_fields(c: dict) -> dict:
    return dict(_dense.program_fields(c), qkv_bias=True, qk_norm=False)


def refuse(c: dict) -> list[str]:
    return _dense.refused(c, COMPUTES)
