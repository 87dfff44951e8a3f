"""Work counts: the operations and bytes each unit of work needs, from the
configuration (HF keys) and the shapes the traffic gave it.

Only the work the algorithm needs is counted: valid prompt rows, live
context positions, valid words, and every weight and cache value at the
precision the configuration states (``torch_dtype``), however the program
stores it.  A program that pads, recomputes or keeps float32 copies spends
more time for the same count, and its roofline share falls.

A model's counts come from the module of its family,
``families/<model_type>.py`` (:mod:`families`): today ``qwen2`` and
``qwen3``, both the dense decoder of :mod:`families._dense`.  A new family
adds its module there and changes nothing here.  The statistics kernels'
counts are below.
"""

from __future__ import annotations

import families


def weight_bytes(c: dict) -> int:
    """Weights one forward pass reads."""
    return families.load(c["model_type"]).weight_bytes(c)


def kv_bytes_per_position(c: dict) -> int:
    return families.load(c["model_type"]).kv_bytes_per_position(c)


def token_flops(c: dict, context: int) -> int:
    """One token through every layer, attending to ``context`` positions
    (itself included), without the output head."""
    return families.load(c["model_type"]).token_flops(c, context)


def decode_step(c: dict, contexts: list[int], ctx=None) -> tuple[float, float]:
    """(flops, bytes) of one decode step over slots with these context
    lengths; ``ctx``, the reader's context, for a family whose work depends
    on what the program did."""
    return families.load(c["model_type"]).decode_step(c, contexts, ctx)


def prefill(c: dict, n: int, start: int, ctx=None) -> tuple[float, float]:
    """(flops, bytes) of prefilling prompt positions ``[start, n)`` after
    ``start`` positions served from the prefix cache."""
    return families.load(c["model_type"]).prefill(c, n, start, ctx)


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
