"""The paged decode step: each layer gathers its own pages inside the layer
scan, and one scatter writes every layer's new KV rows into the donated
pool.  Its logits are bitwise those of the contiguous step on the same
contexts, it changes no pool row but the ones it writes, and its compiled
program updates the pool in place without a whole (slots x max_len) view."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import params as pm
from repro.models.model import build_model
from repro.serve.scheduler import ContinuousBatcher

#: eight KV heads, which fill a float32 tile, on a dense stack
DENSE = ARCHS["qwen3-4b"].reduced().replace(n_layers=3, n_heads=8, n_kv_heads=8)
#: four KV heads, one dense layer before two expert layers
EXPERT = ARCHS["qwen3-moe-30b-a3b"].reduced().replace(n_layers=3, first_k_dense=1)
N_SLOTS, MAX_LEN = 4, 128


def _batcher(cfg, page_size):
    model = build_model(cfg, remat="none")
    params = pm.init_params(jax.random.key(0), model.param_specs())
    return ContinuousBatcher(
        model, cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, page_size=page_size
    )


def _step_inputs(b):
    """One step's tables and write targets, as ``_paged_step_tables`` lays
    them out: slots 0 and 1 share their first page (a prefix page); slot 1
    writes the first row of a new page; slot 2 is inactive, so its table is
    empty and it writes its stale position to the trash page; slot 3 writes
    near the end of its last page.  Unused table entries are 0."""
    ps, n = b.page_size, b.pages_per_slot
    tables = np.zeros((N_SLOTS, n), np.int32)
    positions = np.array([ps + 3, ps, 7, MAX_LEN - 2], np.int32)
    tables[0, :2] = [0, 1]
    tables[1, :2] = [0, 3]
    tables[3] = 4 + np.arange(n)
    write_pages = tables[np.arange(N_SLOTS), positions // ps]
    write_pages[2] = b._trash_page
    write_offsets = positions % ps
    write_offsets[2] = 0
    return tables, positions, write_pages, write_offsets


@pytest.mark.parametrize("cfg", [DENSE, EXPERT], ids=["dense-8kv", "expert-4kv"])
@pytest.mark.parametrize("page_size", [16, 64])
def test_paged_step_matches_the_contiguous_step(cfg, page_size):
    b = _batcher(cfg, page_size)
    rng = np.random.default_rng(page_size)
    pools = jax.tree.map(
        lambda x: rng.standard_normal(x.shape).astype(np.float32), b.cache
    )
    tables, positions, write_pages, write_offsets = _step_inputs(b)
    tokens = rng.integers(3, cfg.vocab_size, (N_SLOTS, 1)).astype(np.int32)
    valid = (jnp.asarray([True, True, False, True]),) if cfg.n_experts else ()

    # the contiguous cache holds each slot's pages, in table order, per layer
    cache = jax.tree.map(
        lambda p: p[:, tables].reshape(
            (p.shape[0], N_SLOTS, MAX_LEN) + p.shape[3:]
        ),
        pools,
    )
    want_logits, want_cache, *_ = b._decode(
        b.params, jnp.asarray(tokens), jax.tree.map(jnp.asarray, cache),
        jnp.asarray(positions), *valid,
    )
    donated = jax.tree.map(jnp.asarray, pools)
    logits, new_pools, *_ = b._paged_decode(
        b.params, jnp.asarray(tokens), donated,
        jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(write_pages),
        jnp.asarray(write_offsets), *valid,
    )
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    # dense and expert models alike hand the pool over to the step
    assert all(x.is_deleted() for x in jax.tree.leaves(donated))

    slots = np.arange(N_SLOTS)
    for old, new, row in zip(
        jax.tree.leaves(pools), jax.tree.leaves(new_pools),
        jax.tree.leaves(want_cache),
    ):
        new, row = np.asarray(new), np.asarray(row)
        np.testing.assert_array_equal(
            new[:, write_pages, write_offsets], row[:, slots, positions]
        )
        untouched = np.ones(old.shape[1:3], bool)
        untouched[write_pages, write_offsets] = False
        np.testing.assert_array_equal(new[:, untouched], old[:, untouched])


def test_paged_step_updates_the_pool_in_place_without_a_view():
    """The compiled step aliases the donated pool to its output, and its
    temporaries stay below one leaf's (slots x max_len) view: each layer
    holds only its own pages."""
    b = _batcher(DENSE.replace(n_layers=8), 16)

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    compiled = jax.jit(b._paged_decode_impl, donate_argnums=(2,)).lower(
        jax.tree.map(shape, b.params), i32(N_SLOTS, 1),
        jax.tree.map(shape, b.cache), i32(N_SLOTS, b.pages_per_slot),
        i32(N_SLOTS), i32(N_SLOTS), i32(N_SLOTS),
    ).compile()
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(b.cache)
    pool_bytes = sum(x.nbytes for x in leaves)
    k = leaves[0]
    view_bytes = k.shape[0] * N_SLOTS * MAX_LEN * int(np.prod(k.shape[3:])) * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < view_bytes
