"""Parameter-spec machinery.

Models declare an *abstract* parameter tree of :class:`ParamSpec` leaves.
From that single declaration we derive:

* ``init_params``      — real arrays (smoke tests / examples),
* ``shape_structs``    — ``jax.ShapeDtypeStruct`` stand-ins (the multi-pod
  dry-run lowers against these; a 236B-param model never allocates),
* ``partition_specs``  — ``PartitionSpec`` tree from logical-axis names via
  the sharding rule table in :mod:`repro.sharding`.

This mirrors how production frameworks (MaxText, T5X) separate the logical
model definition from physical placement.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Abstract description of one parameter tensor."""

    shape: tuple[int, ...]
    dtype: Any = jnp.float32
    #: logical axis names, same length as ``shape``; ``None`` = unsharded axis.
    axes: tuple[str | None, ...] = ()
    #: "normal" (fan-in scaled), "zeros", "ones".
    init: str = "normal"
    #: multiplier on the init scale (e.g. depth-scaled residual inits).
    scale: float = 1.0

    def __post_init__(self) -> None:
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} rank does not match shape {self.shape}"
            )

    @property
    def size(self) -> int:
        return math.prod(self.shape)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


@functools.partial(jax.jit, static_argnums=1)
def _init_one(key: jax.Array, spec: ParamSpec) -> jax.Array:
    # jitted per leaf so the draw, scale and cast fuse: a bf16 leaf never
    # materializes a full-size f32 temporary, which is what lets a
    # full-width model initialize inside device memory
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        # Fan-in scaling: last-but-one axis is the contraction axis by
        # convention (kernels are stored (in, out)).
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = spec.scale / math.sqrt(max(fan_in, 1))
        # the no-op f32 rounding stops XLA from folding ``std`` into the
        # normal draw's own sqrt(2) factor, which would round differently
        # from the unfused ``std * normal`` these values are defined by
        draw = jax.lax.reduce_precision(
            jax.random.normal(key, spec.shape), exponent_bits=8, mantissa_bits=23
        )
        return (std * draw).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(key: jax.Array, specs: PyTree) -> PyTree:
    """Materialize real parameters for a spec tree.

    Arrays land on the default device; callers place a replica's params
    by initializing under ``jax.default_device``."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    arrays = [_init_one(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrays)


def shape_structs(specs: PyTree) -> PyTree:
    """ShapeDtypeStruct stand-ins — zero allocation, used by the dry-run."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec
    )


def logical_axes(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def param_count(specs: PyTree) -> int:
    return sum(s.size for s in jax.tree.leaves(specs, is_leaf=is_spec))


def param_bytes(specs: PyTree) -> int:
    return sum(
        s.size * jnp.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(specs, is_leaf=is_spec)
    )


def cast_specs(specs: PyTree, dtype: Any) -> PyTree:
    """Return a spec tree with every leaf re-typed (e.g. bf16 inference)."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, dtype=dtype), specs, is_leaf=is_spec
    )


def map_with_path(
    fn: Callable[[tuple[str, ...], ParamSpec], Any], specs: PyTree
) -> PyTree:
    """tree-map with the dict path (useful for naming / filtering)."""

    def walk(node: PyTree, path: tuple[str, ...]) -> PyTree:
        if is_spec(node):
            return fn(path, node)
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        raise TypeError(f"unexpected node at {path}: {type(node)}")

    return walk(specs, ())


def summarize(specs: PyTree) -> str:
    """Human-readable parameter inventory."""
    lines: list[str] = []

    def fmt(path: tuple[str, ...], s: ParamSpec) -> ParamSpec:
        lines.append(
            f"{'/'.join(path):60s} {str(s.shape):28s} {np.dtype(s.dtype).name:10s}"
            f" {s.size:,}"
        )
        return s

    map_with_path(fmt, specs)
    lines.append(f"TOTAL params: {param_count(specs):,}")
    return "\n".join(lines)
