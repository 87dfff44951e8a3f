"""Compile a model cell's serving programs for a described TPU v5e (no chip
needed) at the cell's real sizes, and print each program's
``memory_analysis()``.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_memory.py qwen3-4b.fewshot

The programs are the ones the scheduler jits for a paged cache: the paged
decode step, the chunk prefill, the prefix-page read and the page write.
Nothing runs and nothing is allocated: arguments are shapes.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.models import params as pm
    from repro.models.model import build_model, serving_param_specs
    from repro.serve import scheduler as sch

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(name)
    cfg = get_config(harness.register_config(cell.config))
    n_slots = cell.mix["engine"]["n_slots"]
    max_len = cell.mix["engine"]["max_len"]
    page = cell.mix["inference"]["kv_page_size"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def shapes(specs):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
            specs, is_leaf=pm.is_spec,
        )

    model = build_model(cfg, remat="none")
    params = shapes(serving_param_specs(model.param_specs()))
    cache_specs = model.cache_specs(n_slots, max_len, jnp.float32)
    n_pool = cell.mix["engine"].get("page_pool") or n_slots * (max_len // page) + n_slots
    pool = shapes(sch.paged_pool_specs(cache_specs, n_pool + 1, page))
    row = shapes(model.cache_specs(1, max_len, jnp.float32))

    b = object.__new__(sch.ContinuousBatcher)
    b.page_size, b.max_len = page, max_len
    b._batch_axes = sch.batch_axis_tree(cache_specs)
    b._decode_fn = sch.steps_lib.make_decode_fn(model, cfg)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    width = min(sch.PREFILL_WIDTH, max_len)
    programs = {
        "paged_decode": (jax.jit(b._paged_decode_impl), (
            params, i32(n_slots, 1), pool, i32(n_slots, max_len // page),
            i32(n_slots), i32(n_slots), i32(n_slots))),
        "prefill_chunk": (jax.jit(model.prefill_chunk), (
            params, i32(1, width), row, i32(), i32())),
        "read_prefix": (jax.jit(b._read_prefix_impl), (row, pool, i32(16))),
        "write_pages": (jax.jit(b._write_pages_impl, static_argnums=(3,)), (
            pool, row, i32(6), 16)),
    }
    for label, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        print(
            f"{name} {label}: arguments {m.argument_size_in_bytes} "
            f"outputs {m.output_size_in_bytes} temporaries {m.temp_size_in_bytes} "
            f"aliased {m.alias_size_in_bytes} generated code "
            f"{m.generated_code_size_in_bytes}",
            flush=True,
        )


if __name__ == "__main__":
    for cell_name in sys.argv[1:]:
        main(cell_name)
