"""Jit-able serving steps: prefill / decode / greedy sampling.

``make_serve_step`` builds the function the dry-run lowers for the
``decode_32k`` / ``long_500k`` shapes: ONE new token for every sequence in
the batch against a ``seq_len``-long cache.  Returning the sampled token id
(not the logits) keeps the step's output tiny — on a real pod the (B, V)
logits never leave the chips.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

PyTree = Any


def greedy_sample(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Argmax over the un-padded vocab region. logits (B, Vp) -> (B,) int32."""
    vp = logits.shape[-1]
    if vp > vocab_size:
        logits = jnp.where(jnp.arange(vp) < vocab_size, logits, -jnp.inf)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sample(
    logits: jax.Array, vocab_size: int, temperature: float, key: jax.Array
) -> jax.Array:
    vp = logits.shape[-1]
    if vp > vocab_size:
        logits = jnp.where(jnp.arange(vp) < vocab_size, logits, -jnp.inf)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def make_prefill_fn(model: Any, cfg: ModelConfig) -> Callable:
    """(params, batch, cache) -> (last_logits (B, Vp), cache)."""

    def prefill_fn(params: PyTree, batch: dict, cache: PyTree):
        return model.prefill(params, batch, cache)

    return prefill_fn


def make_decode_fn(model: Any, cfg: ModelConfig) -> Callable:
    """(params, tokens (B,1), cache, positions (B,)[, valid (B,)]) ->
    (logits (B,Vp), cache, counters).  ``valid`` marks the rows that decode
    (an expert layer routes only those); the counters are the expert
    layers' (empty for a dense model)."""

    def decode_fn(
        params: PyTree, tokens: jax.Array, cache: PyTree, positions, valid=None
    ):
        if valid is None:
            return model.decode_step(params, tokens, cache, positions)
        return model.decode_step(params, tokens, cache, positions, valid=valid)

    return decode_fn


def make_serve_step(model: Any, cfg: ModelConfig) -> Callable:
    """The dry-run target: one decode token + greedy sample for the batch."""

    def serve_step(
        params: PyTree,
        cache: PyTree,
        tokens: jax.Array,  # (B, 1) int32 — the tokens sampled last step
        positions: jax.Array,  # (B,) int32 — their positions
    ) -> tuple[jax.Array, PyTree]:
        logits, cache, _ = model.decode_step(params, tokens, cache, positions)
        next_tokens = greedy_sample(logits, cfg.vocab_size)
        return next_tokens, cache

    return serve_step
