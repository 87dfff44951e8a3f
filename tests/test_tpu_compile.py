"""Compile the main path's kernels and the full-width decode step for a
described (not attached) TPU v5e.

Interpret mode cannot show what the Mosaic compiler refuses (block tiling,
unsupported casts) or whether a program fits device memory; these compiles
can, without a chip.  The topology is described inside a fixture, never at
import, so every pytest-xdist worker collects the same tests and only the
worker running this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM as the TPU compiler counts it
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_bootstrap_partials_compiles(one_chip):
    from repro.kernels.bootstrap.bootstrap import bootstrap_partials

    compiled = _compile(
        lambda x, s, o: bootstrap_partials(x, s, o, n_boot=2000),
        _struct((4096, 2), jnp.float32, one_chip),
        _struct((), jnp.uint32, one_chip),
        _struct((), jnp.uint32, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_bertscore_compiles(one_chip):
    from repro.kernels.bertscore.bertscore import bertscore_pr

    b, length, dim = 16, 64, 256  # dim = HashEmbedder's default
    emb = _struct((b, length, dim), jnp.float32, one_chip)
    mask = _struct((b, length), jnp.float32, one_chip)
    compiled = _compile(bertscore_pr, emb, emb, mask, mask)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [368, 1264], ids=["decode16", "chunk128"])
@pytest.mark.parametrize("k, n", [(2048, 768), (768, 2048)], ids=["up", "down"])
def test_expert_gmm_compiles(one_chip, rows, k, n):
    """qwen3-moe's grouped matmul over 16 held experts at its widths, for
    the row counts of a 16-slot decode step and a 128-row prefill chunk."""
    from repro.kernels.grouped_matmul.grouped_matmul import expert_gmm

    compiled = _compile(
        lambda a, b, g: expert_gmm(a, b, g, tile_rows=16),
        _struct((rows, k), jnp.bfloat16, one_chip),
        _struct((16, k, n), jnp.bfloat16, one_chip),
        _struct((16,), jnp.int32, one_chip),
    )
    assert "expert_gmm" in compiled.as_text()


def test_paged_decode_attention_compiles(one_chip):
    from repro.configs import get_config
    from repro.kernels.decode_attention.ops import paged_decode_attention_bshd

    cfg = get_config("qwen3-4b")
    slots, page, pages_per_slot = 8, 16, 16
    pool = slots * pages_per_slot + slots + 1
    kv = _struct(
        (pool, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip
    )
    compiled = _compile(
        paged_decode_attention_bshd,
        _struct((slots, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip),
        kv,
        kv,
        _struct((slots, pages_per_slot), jnp.int32, one_chip),
        _struct((slots,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_prefill_chunk_fits_one_chip(one_chip):
    """qwen3-4b's one prefill program (a 128-row chunk into a 256-position
    cache row) with the serving engine's weight dtype."""
    from repro.configs import get_config
    from repro.models import params as pm
    from repro.models.model import build_model, serving_param_specs
    from repro.serve.scheduler import PREFILL_WIDTH

    cfg = get_config("qwen3-4b")
    model = build_model(cfg, remat="none")

    def on_chip(tree):
        return jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, one_chip), tree
        )

    params = on_chip(pm.shape_structs(serving_param_specs(model.param_specs())))
    row = on_chip(pm.shape_structs(model.cache_specs(1, 256, jnp.float32)))
    scalar = _struct((), jnp.int32, one_chip)
    compiled = _compile(
        model.prefill_chunk,
        params,
        _struct((1, PREFILL_WIDTH), jnp.int32, one_chip),
        row,
        scalar,
        scalar,
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < V5E_HBM_BYTES


def test_full_width_decode_step_fits_one_chip(one_chip):
    """qwen3-4b's decode step with the serving engine's weight dtype and
    cache, as the batcher runs it (8 slots x 256 positions)."""
    from repro.configs import get_config
    from repro.models import params as pm
    from repro.models.model import build_model, serving_param_specs
    from repro.serve.steps import make_decode_fn

    cfg = get_config("qwen3-4b")
    model = build_model(cfg, remat="none")
    slots, max_len = 8, 256

    def on_chip(tree):
        return jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, one_chip), tree
        )

    params = on_chip(pm.shape_structs(serving_param_specs(model.param_specs())))
    assert params["embed"]["table"].dtype == jnp.bfloat16
    cache = on_chip(
        pm.shape_structs(model.cache_specs(slots, max_len, jnp.float32))
    )
    compiled = _compile(
        make_decode_fn(model, cfg),
        params,
        _struct((slots, 1), jnp.int32, one_chip),
        cache,
        _struct((slots,), jnp.int32, one_chip),
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("kv_heads", [4, 8])
def test_paged_row_write_is_in_place_for_any_head_count(one_chip, kv_heads):
    """The paged step's one scatter of every layer's new rows, at the
    expert cell's pool (48 layers, 305 pages of 16 rows, head 128): with
    the pool donated it keeps no copy of it, with 4 KV heads as with 8."""
    from repro.serve.scheduler import _write_rows

    pool = _struct((48, 305, 16, kv_heads, 128), jnp.float32, one_chip)
    slots = _struct((16,), jnp.int32, one_chip)
    compiled = jax.jit(_write_rows, donate_argnums=(0,)).lower(
        pool, _struct((48, 16, kv_heads, 128), jnp.float32, one_chip),
        slots, slots,
    ).compile()
    mem = compiled.memory_analysis()
    pool_bytes = 48 * 305 * 16 * kv_heads * 128 * 4
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100
