"""Continuous-batching scheduler (Orca-style iteration-level batching).

A fixed pool of ``n_slots`` decode slots steps in lock-step (SPMD gang
scheduling — see DESIGN.md §2: Spark's work-stealing does not transfer to a
jitted step, so slots are the unit of multiplexing instead).  Each iteration:

1. finished slots (EOS / max_tokens) emit their completion and free up,
2. free slots are refilled from the request queue (admission-controlled),
3. a single batched decode step advances every active slot by one token.

GQA text models (dense and MoE families) prefill through **one
fixed-width chunk program**: a prompt runs in chunks of ``PREFILL_WIDTH``
rows, zero-padded, each attending to the whole cache row up to its own
positions.  Its shapes never depend on the prompt, so it compiles once and
every position's KV comes out of the same program whether prefill started
at 0 or after a shared prefix — on a TPU, whose compiler rounds programs
of different shapes differently, that is what keeps paged and contiguous
serving byte-identical.  The other families prefill **exact-length**: one
program per distinct prompt length, since padding would corrupt SSM
recurrent state.  ``prefill_recompiles`` counts the programs.

With ``page_size`` > 0 the KV cache is **paged** (DESIGN.md §8): device
leaves become page pools, each slot holds a page table, and a host-side
:class:`~repro.serve.paged_cache.PagedCacheManager` shares prompt-prefix
pages across requests by hash chain — a prompt whose leading pages are
resident skips prefill for them (its chunks start at the first
non-shared token).  Decode gathers each slot's pages into the contiguous
view the decode step already understands, then scatters the one new KV
row back to its pool page.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.engines import BatcherStats
from repro.kernels.decode_attention.quant import absmax_quantize
from repro.models.params import init_params, is_spec
from repro.serve import steps as steps_lib
from repro.serve.paged_cache import (
    PagedCacheManager,
    PagePoolExhausted,
    pages_for_budget,
)
from repro.sharding import ShardingRules, use_rules

PyTree = Any

#: model families whose caches are pure attention KV (batch x seq leaves)
#: and whose prefill can start after a shared prefix
_PAGEABLE_FAMILIES = ("dense", "moe")

#: rows of the chunk prefill program (capped at the batcher's ``max_len``)
PREFILL_WIDTH = 128


#: rows of a float32 tile on the TPU
_F32_TILE_ROWS = 8


def _gather_view(pool: jax.Array, tables: jax.Array, ax: int) -> jax.Array:
    """Gather one pool leaf's pages, ``tables`` (B, pages_per_slot), into
    the contiguous view with slots at ``ax`` and positions after them.

    Where the leaf's heads fill a float32 tile the pages are gathered
    page-major and moved into place.  Where they do not (a 4-KV-head model),
    the pages are taken in place and the view is pinned row-major: left to
    choose, the TPU compiler tiles such a view over (layers, head_dim), and
    the decode scan's in-place update of one layer then rewrites every tile
    of the stacked view (a step ran ~4x slower), and the page-major route
    keeps a page-major copy of the pool and of the view besides (2.6 GB of
    temporaries in the expert cell's step)."""
    b = tables.shape[0]
    if pool.shape[-2] % _F32_TILE_ROWS == 0:
        p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
        g = p[tables]  # (B, nP, ps, ...)
        g = g.reshape((b, -1) + p.shape[2:])
        return jnp.moveaxis(g, (0, 1), (ax, ax + 1))
    g = jnp.take(pool, tables, axis=ax)  # (..., B, nP, ps, ...)
    g = g.reshape(pool.shape[:ax] + (b, -1) + pool.shape[ax + 2 :])
    return _row_major(g)


def _row_major(v: jax.Array) -> jax.Array:
    """Pin a view whose heads do not fill a float32 tile to its row-major
    layout (:func:`_gather_view`); one whose heads do keeps its layout."""
    if v.shape[-2] % _F32_TILE_ROWS == 0:
        return v
    return with_layout_constraint(v, Layout(tuple(range(v.ndim))))


def _scatter_rows(
    pool: jax.Array,
    view: jax.Array,
    ax: int,
    positions: jax.Array,
    write_pages: jax.Array,
    write_offsets: jax.Array,
) -> jax.Array:
    """Write each slot's row at ``positions`` of the view back to its pool
    page, page-major or in place as :func:`_gather_view` gathered it."""
    b = positions.shape[0]
    if pool.shape[-2] % _F32_TILE_ROWS == 0:
        v = jnp.moveaxis(view, (ax, ax + 1), (0, 1))
        rows = v[jnp.arange(b), positions]  # (B, ...) the new KV rows
        p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
        p = p.at[write_pages, write_offsets].set(rows.astype(p.dtype))
        return jnp.moveaxis(p, (0, 1), (ax, ax + 1))
    at = (slice(None),) * ax
    rows = view[at + (jnp.arange(b), positions)]  # (..., B, ...)
    return pool.at[at + (write_pages, write_offsets)].set(rows.astype(pool.dtype))


def _chunk_prefill_supported(cfg: ModelConfig) -> bool:
    return cfg.family in _PAGEABLE_FAMILIES and not getattr(cfg, "use_mla", False)


@dataclasses.dataclass
class Request:
    request_id: int
    prompt_tokens: list[int]
    max_new_tokens: int = 32
    extras: dict | None = None  # e.g. {"frames": ...} for enc-dec archs
    #: ``time.monotonic()`` stamps: first queue entry (``submit``; a
    #: preempted request keeps it), admission into a slot, and the moment
    #: its first token reached the host
    queued_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: list[int]
    prompt_len: int
    finished_reason: str  # "eos" | "length" | "truncated"
    #: the request's stamps, and when it finished
    queued_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0

    @property
    def latency_s(self) -> float:
        """Queue entry to finish."""
        return self.finished_at - self.queued_at


def batch_axis_tree(cache_specs: PyTree) -> PyTree:
    """Index of the logical ``batch`` axis for every cache leaf."""
    return jax.tree.map(
        lambda s: s.axes.index("batch"), cache_specs, is_leaf=is_spec
    )


def paged_pool_specs(
    cache_specs: PyTree, n_pages: int, page_size: int
) -> PyTree:
    """Rewrite per-slot cache specs into page-pool specs: the ``batch``
    axis becomes the pool's page axis and ``cache_seq`` shrinks to one
    page.  Requires ``cache_seq`` directly after ``batch`` on every leaf
    (true for all attention KV caches) so a page is a contiguous block."""

    def to_pool(spec):
        if "cache_seq" not in spec.axes:
            raise ValueError(
                f"cache leaf {spec.axes} has no cache_seq axis — paged KV "
                f"does not support recurrent-state caches"
            )
        b_ax = spec.axes.index("batch")
        s_ax = spec.axes.index("cache_seq")
        if s_ax != b_ax + 1:
            raise ValueError(
                f"cache leaf {spec.axes}: cache_seq must follow batch"
            )
        shape = list(spec.shape)
        shape[b_ax] = n_pages
        shape[s_ax] = page_size
        axes = tuple("kv_pages" if a == "batch" else a for a in spec.axes)
        return dataclasses.replace(spec, shape=tuple(shape), axes=axes)

    return jax.tree.map(to_pool, cache_specs, is_leaf=is_spec)


def _pool_rest_shape(spec) -> tuple[int, ...]:
    """Leaf dims other than (pages, page row), in normalized order — what
    the moveaxis helpers see as the trailing ``...`` of ``(P, ps, ...)``."""
    ax = spec.axes.index("kv_pages" if "kv_pages" in spec.axes else "batch")
    return tuple(
        d for i, d in enumerate(spec.shape) if i not in (ax, ax + 1)
    )


def paged_scale_specs(pool_specs: PyTree) -> PyTree:
    """Per-page quantization-scale specs for an int8 pool: one f32 scale
    per (page, *rest[:-1]) group — the trailing axis (head_dim) and the
    page-row axis are reduced away by the absmax.  Stored pre-normalized
    as ``(P, ...)`` so the movement helpers index them without moveaxis.
    Init is "ones", matching the all-zero-group convention of
    ``absmax_quantize`` (zero bytes at scale 1.0 dequantize to exact 0)."""

    def to_scale(spec):
        ax = spec.axes.index("kv_pages")
        rest = _pool_rest_shape(spec)
        rest_axes = tuple(
            a for i, a in enumerate(spec.axes) if i not in (ax, ax + 1)
        )
        return dataclasses.replace(
            spec,
            shape=(spec.shape[ax],) + rest[:-1],
            axes=("kv_pages",) + rest_axes[:-1],
            dtype=jnp.float32,
            init="ones",
        )

    return jax.tree.map(to_scale, pool_specs, is_leaf=is_spec)


def paged_page_bytes(
    cache_specs: PyTree, page_size: int, kv_cache_dtype: str
) -> int:
    """HBM bytes one pool page costs across every cache leaf, including
    the f32 scale buffer in int8 mode (the spec-tree counterpart of
    ``paged_cache.kv_page_bytes``, exact for any leaf layout)."""
    total = 0
    for spec in jax.tree.leaves(cache_specs, is_leaf=is_spec):
        rest = _pool_rest_shape(spec)
        elems = page_size * int(np.prod(rest)) if rest else page_size
        if kv_cache_dtype == "int8":
            total += elems  # one byte per element
            total += int(np.prod(rest[:-1])) * 4 if rest else 4  # f32 scales
        else:
            total += elems * jnp.dtype(spec.dtype).itemsize
    return total


class ContinuousBatcher:
    """Slot-multiplexed decode loop around jitted prefill/decode steps."""

    def __init__(
        self,
        model: Any,
        cfg: ModelConfig,
        params: PyTree,
        *,
        n_slots: int = 8,
        max_len: int = 512,
        eos_id: int = 1,
        temperature: float = 0.0,
        admission: Callable[[int], float] | None = None,
        cache_dtype: Any = jnp.float32,
        max_prefills_per_step: int = 0,
        device: Any = None,
        rules: ShardingRules | None = None,
        page_size: int = 0,
        prefix_cache: bool = True,
        page_pool: int = 0,
        kv_cache_dtype: str = "bf16",
        page_pool_bytes: int = 0,
    ):
        self.model, self.cfg, self.params = model, cfg, params
        self.n_slots, self.max_len, self.eos_id = n_slots, max_len, eos_id
        self.temperature = temperature
        self.admission = admission
        #: 0 = unlimited; otherwise at most this many prompts are prefilled
        #: per step() — prefill/decode disaggregation: a long-prompt backlog
        #: waits for a prefill slot instead of stalling every decode step
        #: behind a wall of back-to-back prefills
        self.max_prefills_per_step = max_prefills_per_step
        #: single-device placement (one replica per host device) or, for a
        #: multi-device replica, logical-axis rules over its mesh — the two
        #: are mutually exclusive
        self.device = device
        self.rules = rules
        if rules is not None and cfg.n_experts:
            # under sharding rules the expert layer is the capacity dispatch,
            # which drops tokens by chunk size: serving keeps one expert path
            raise ValueError(
                f"{cfg.name}: an expert model is served one device per "
                f"replica, not on a multi-device replica mesh"
            )
        self.prefix = cfg.n_vision_tokens if cfg.family == "vlm" else 0
        #: 0 = contiguous per-slot cache; > 0 = paged pool with this page size
        self.page_size = page_size
        #: "bf16" = full-precision pool pages (the pre-quantization path);
        #: "int8" = absmax block-quantized pages + per-(page, head) scales,
        #: dequantized at gather time (DESIGN.md §10)
        if kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bf16' or 'int8', got "
                f"{kv_cache_dtype!r}"
            )
        if kv_cache_dtype == "int8" and not page_size:
            raise ValueError(
                "kv_cache_dtype='int8' requires a paged cache (page_size > 0)"
            )
        self.kv_cache_dtype = kv_cache_dtype
        self.quantized = kv_cache_dtype == "int8"
        self.scales: PyTree | None = None

        cache_specs = model.cache_specs(n_slots, max_len, cache_dtype)
        self._batch_axes = batch_axis_tree(cache_specs)
        if page_size:
            if not _chunk_prefill_supported(cfg):
                raise ValueError(
                    f"paged KV cache supports GQA attention families "
                    f"{_PAGEABLE_FAMILIES}, not {cfg.family}"
                    + (" with MLA" if getattr(cfg, "use_mla", False) else "")
                )
            if max_len % page_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of page_size "
                    f"{page_size}"
                )
            if rules is not None:
                raise ValueError(
                    "paged KV cache does not compose with sharding rules yet"
                )
            self.pages_per_slot = max_len // page_size
            #: default pool is worst case: every slot full + one defensive
            #: CoW per slot — it never exhausts.  ``page_pool`` pins it
            #: smaller (must still cover the longest single request, or
            #: that request thrashes preempt/recompute forever); decode
            #: pressure then triggers preemption instead of death.  One
            #: extra trailing page absorbs decode writes from inactive
            #: slots (their stale positions must scatter *somewhere* valid)
            self._page_bytes = paged_page_bytes(
                cache_specs, page_size, kv_cache_dtype
            )
            if page_pool_bytes:
                if page_pool:
                    raise ValueError(
                        "page_pool and page_pool_bytes are mutually exclusive"
                    )
                #: byte-budgeted pool: same HBM budget admits ~2x pages at
                #: int8 — this is where quantization buys capacity
                n_pool = pages_for_budget(page_pool_bytes, self._page_bytes)
            else:
                n_pool = page_pool or (n_slots * self.pages_per_slot + n_slots)
            self._trash_page = n_pool
            self.manager = PagedCacheManager(
                n_pool, page_size, prefix_cache=prefix_cache,
                page_bytes=self._page_bytes,
            )
            pool_specs = paged_pool_specs(cache_specs, n_pool + 1, page_size)
            if self.quantized:
                pool_specs = jax.tree.map(
                    lambda s: dataclasses.replace(
                        s, dtype=jnp.int8, init="zeros"
                    ),
                    pool_specs, is_leaf=is_spec,
                )
                self.cache = init_params(jax.random.key(0), pool_specs)
                self.scales = init_params(
                    jax.random.key(0), paged_scale_specs(pool_specs)
                )
            else:
                self.cache = init_params(jax.random.key(0), pool_specs)
        else:
            self.cache = init_params(jax.random.key(0), cache_specs)
        if rules is not None:
            self.params = jax.device_put(
                self.params, rules.param_shardings(model.param_specs())
            )
            self.cache = jax.device_put(
                self.cache, rules.param_shardings(cache_specs)
            )
        elif device is not None:
            self.params = jax.device_put(self.params, device)
            self.cache = jax.device_put(self.cache, device)
            if self.scales is not None:
                self.scales = jax.device_put(self.scales, device)
        row_specs = model.cache_specs(1, max_len, cache_dtype)
        self._row_specs = row_specs

        self._decode_fn = steps_lib.make_decode_fn(model, cfg)
        self._decode = jax.jit(self._decode_fn)
        #: GQA text models prefill through one fixed-width chunk program
        #: (paged or not); the other families run one exact-length program
        #: per prompt length
        self._chunked = _chunk_prefill_supported(cfg)
        if self._chunked:
            self.prefill_width = min(PREFILL_WIDTH, max_len)
            self._prefill_chunk = jax.jit(model.prefill_chunk)
        else:
            self._prefill = jax.jit(
                lambda params, batch, cache: model.prefill(params, batch, cache)
            )
        if page_size:
            if self.quantized:
                self._paged_decode_q = jax.jit(self._paged_decode_q_impl)
                self._read_prefix_q = jax.jit(self._read_prefix_q_impl)
                self._write_pages_q = jax.jit(
                    self._write_pages_q_impl, static_argnums=(4,)
                )
                self._copy_page_q = jax.jit(self._copy_page_q_impl)
            else:
                # an expert model's weights leave no room for a second pool:
                # its step updates the pool in place
                self._paged_decode = jax.jit(
                    self._paged_decode_impl,
                    donate_argnums=(2,) if cfg.n_experts else (),
                )
                self._read_prefix = jax.jit(self._read_prefix_impl)
                self._write_pages = jax.jit(
                    self._write_pages_impl, static_argnums=(3,)
                )
                self._copy_page = jax.jit(self._copy_page_impl)
        self._insert = jax.jit(self._insert_impl)

        # slot state (host side)
        self.slot_free = [True] * n_slots
        self.slot_req: list[Request | None] = [None] * n_slots
        self.slot_tokens: list[list[int]] = [[] for _ in range(n_slots)]
        self.slot_pos = np.zeros((n_slots,), np.int32)  # next position to write
        self.cur_tokens = np.zeros((n_slots, 1), np.int32)
        self.queue: list[Request] = []
        self.completions: list[Completion] = []
        self.steps_run = 0
        self.key = jax.random.key(0)
        #: occupancy/throughput counters for the persistent streaming mode
        #: (surfaced through the InferenceService into session accounting)
        self.stats = BatcherStats(n_slots=n_slots)
        if page_size:
            self.stats.kv_bytes_per_token = self._page_bytes // page_size
            self.stats.pool_pages = self.manager.n_pages
        #: prefill programs already compiled: the chunk width, or the prompt
        #: lengths of exact-length prefill
        self._seen_prefill_shapes: set = set()
        #: deterministic chaos hook: called with ``steps_run`` at the top
        #: of every step(); may raise (replica_crash), sleep (slow_step),
        #: or return a kind string — "page_pressure" forces a preemption,
        #: "hang" skips this decode step (see ServingFaultSchedule.as_hook)
        self.fault_hook: Callable[[int], str | None] | None = None

    # -- cache row insertion ---------------------------------------------------

    def _insert_impl(self, cache: PyTree, row: PyTree, slot: jax.Array) -> PyTree:
        return jax.tree.map(
            lambda full, r, ax: jax.lax.dynamic_update_slice_in_dim(
                full, r.astype(full.dtype), slot, axis=ax
            ),
            cache,
            row,
            self._batch_axes,
        )

    # -- paged cache movement ----------------------------------------------------
    #
    # Every helper normalizes a leaf to (pages, page_size, ...) /
    # (batch, seq, ...) with moveaxis and restores the leaf layout on the
    # way out, so one implementation serves every cache-leaf layout.

    def _read_prefix_impl(
        self, row: PyTree, pools: PyTree, shared_ids: jax.Array
    ) -> PyTree:
        """Gather shared prefix pages into positions [0, n*ps) of a B=1 row."""

        def read(r, pool, ax):
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            pref = p[shared_ids].reshape((-1,) + p.shape[2:])
            rr = jnp.moveaxis(r, (ax, ax + 1), (0, 1))
            rr = rr.at[0, : pref.shape[0]].set(pref.astype(rr.dtype))
            return jnp.moveaxis(rr, (0, 1), (ax, ax + 1))

        return jax.tree.map(read, row, pools, self._batch_axes)

    def _write_pages_impl(
        self, pools: PyTree, row: PyTree, fresh_ids: jax.Array, start_page: int
    ) -> PyTree:
        """Scatter row positions [start_page*ps, (start_page+n)*ps) into
        the pool pages that the prefill just produced."""
        ps = self.page_size
        n = fresh_ids.shape[0]

        def write(pool, r, ax):
            rr = jnp.moveaxis(r, (ax, ax + 1), (0, 1))
            chunk = rr[0, start_page * ps : (start_page + n) * ps]
            chunk = chunk.reshape((n, ps) + rr.shape[2:])
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            p = p.at[fresh_ids].set(chunk.astype(p.dtype))
            return jnp.moveaxis(p, (0, 1), (ax, ax + 1))

        return jax.tree.map(write, pools, row, self._batch_axes)

    def _copy_page_impl(
        self, pools: PyTree, src: jax.Array, dst: jax.Array
    ) -> PyTree:
        def cp(pool, ax):
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            p = p.at[dst].set(p[src])
            return jnp.moveaxis(p, (0, 1), (ax, ax + 1))

        return jax.tree.map(cp, pools, self._batch_axes)

    def _paged_decode_impl(
        self,
        params: PyTree,
        tokens: jax.Array,
        pools: PyTree,
        tables: jax.Array,       # (B, pages_per_slot) int32
        positions: jax.Array,    # (B,)
        write_pages: jax.Array,  # (B,) pool page receiving each slot's new KV
        write_offsets: jax.Array,  # (B,) row within that page
        valid: jax.Array | None = None,  # (B,) active slots (expert models)
    ) -> tuple:
        """Gather page tables into the contiguous (B, max_len) view the
        decode step understands, run it, scatter the one new KV row per
        slot back to its pool page.  Inactive slots' write targets point
        at the trash page, so stale positions never corrupt live pages.
        Returns the logits and the pools, and an expert model's step
        counters after them (a dense step returns what it always has)."""
        view = jax.tree.map(
            lambda pool, ax: _gather_view(pool, tables, ax),
            pools, self._batch_axes,
        )
        logits, view, counts = self._decode_fn(
            params, tokens, view, positions, valid
        )
        view = jax.tree.map(_row_major, view)
        pools = jax.tree.map(
            lambda pool, leaf, ax: _scatter_rows(
                pool, leaf, ax, positions, write_pages, write_offsets
            ),
            pools, view, self._batch_axes,
        )
        # a dense step returns its two results, as callers that wrap it expect
        return (logits, pools, counts) if counts else (logits, pools)

    # -- quantized paged movement (kv_cache_dtype == "int8") ---------------------
    #
    # Same normalized (pages, page_size, ...) layout as above, but pool
    # leaves hold int8 bytes and the separate ``self.scales`` tree holds
    # one f32 absmax scale per (page, *rest[:-1]) group — the page-row and
    # head_dim axes are the reduced ones.  Dequantization happens at
    # gather time; every write re-quantizes from full-precision values
    # with stale rows masked to zero, so stored bytes are a pure function
    # of the valid token history (the fixed-dtype determinism contract).

    @staticmethod
    def _expand_scale(s: jax.Array) -> jax.Array:
        """(n, *rest[:-1]) scale -> broadcastable over (n, ps, *rest)."""
        return jnp.expand_dims(s, (1, s.ndim + 1))

    def _map_pool_scale(self, fn, pools, scales, extra=None):
        """Map ``fn(pool, scale, extra, ax) -> (pool', scale')`` over the
        cache trees, unzipping the per-leaf pairs back into two trees."""
        p_leaves, tdef = jax.tree.flatten(pools)
        s_leaves = tdef.flatten_up_to(scales)
        a_leaves = tdef.flatten_up_to(self._batch_axes)
        e_leaves = (
            tdef.flatten_up_to(extra) if extra is not None
            else [None] * len(p_leaves)
        )
        pairs = [
            fn(p, s, e, a)
            for p, s, e, a in zip(p_leaves, s_leaves, e_leaves, a_leaves)
        ]
        return (
            tdef.unflatten([p for p, _ in pairs]),
            tdef.unflatten([s for _, s in pairs]),
        )

    def _read_prefix_q_impl(
        self, row: PyTree, pools: PyTree, scales: PyTree, shared_ids: jax.Array
    ) -> PyTree:
        """Quantized twin of ``_read_prefix_impl``: dequantize the shared
        prefix pages while gathering them into the full-precision row."""

        def read(r, pool, sc, ax):
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            deq = p[shared_ids].astype(jnp.float32) * self._expand_scale(
                sc[shared_ids]
            )
            pref = deq.reshape((-1,) + p.shape[2:])
            rr = jnp.moveaxis(r, (ax, ax + 1), (0, 1))
            rr = rr.at[0, : pref.shape[0]].set(pref.astype(rr.dtype))
            return jnp.moveaxis(rr, (0, 1), (ax, ax + 1))

        return jax.tree.map(read, row, pools, scales, self._batch_axes)

    def _write_pages_q_impl(
        self,
        pools: PyTree,
        scales: PyTree,
        row: PyTree,
        fresh_ids: jax.Array,
        start_page: int,
        n_valid: jax.Array,
    ) -> tuple[PyTree, PyTree]:
        """Quantized twin of ``_write_pages_impl``: quantize the prefill's
        fresh pages on write.  Rows past ``n_valid`` (the prompt's tail
        inside its final, partially filled page) are masked out of both
        the absmax and the stored bytes, so stale prefill-buffer content
        never reaches the pool."""
        ps = self.page_size
        n = fresh_ids.shape[0]

        def write(pool, sc, r, ax):
            rr = jnp.moveaxis(r, (ax, ax + 1), (0, 1))
            chunk = rr[0, start_page * ps : (start_page + n) * ps]
            chunk = chunk.reshape((n, ps) + rr.shape[2:])
            mask = (jnp.arange(n * ps) < n_valid).reshape(
                (n, ps) + (1,) * (chunk.ndim - 2)
            )
            q, s = absmax_quantize(chunk, (1, chunk.ndim - 1), mask=mask)
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            p = p.at[fresh_ids].set(q)
            return (
                jnp.moveaxis(p, (0, 1), (ax, ax + 1)),
                sc.at[fresh_ids].set(s),
            )

        return self._map_pool_scale(write, pools, scales, extra=row)

    def _copy_page_q_impl(
        self, pools: PyTree, scales: PyTree, src: jax.Array, dst: jax.Array
    ) -> tuple[PyTree, PyTree]:
        """CoW for quantized pages: bytes and scales copy verbatim — the
        copy is bit-identical to its source, never a requantization."""

        def cp(pool, sc, _e, ax):
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            p = p.at[dst].set(p[src])
            return (
                jnp.moveaxis(p, (0, 1), (ax, ax + 1)),
                sc.at[dst].set(sc[src]),
            )

        return self._map_pool_scale(cp, pools, scales)

    def _paged_decode_q_impl(
        self,
        params: PyTree,
        tokens: jax.Array,
        pools: PyTree,
        scales: PyTree,
        tables: jax.Array,
        positions: jax.Array,
        write_pages: jax.Array,
        write_offsets: jax.Array,
        valid: jax.Array | None = None,
    ) -> tuple:
        """Quantized twin of ``_paged_decode_impl``: dequantize at gather,
        decode on the full-precision view, then re-quantize each slot's
        *whole* write page from the updated view (valid rows only — the
        new token and everything before it in that page).  The page scale
        tracks its absmax as tokens land, so earlier rows re-round at most
        once per scale increase: bounded, deterministic drift that the
        end-to-end token-match gate bounds."""
        b = tokens.shape[0]
        ps = self.page_size

        def gather(pool, sc, ax):
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            scg = sc[tables]                        # (B, nP, *rest[:-1])
            g = p[tables].astype(jnp.float32) * jnp.expand_dims(
                scg, (2, scg.ndim + 1)
            )                                       # (B, nP, ps, ...)
            g = g.reshape((b, -1) + p.shape[2:])
            return jnp.moveaxis(g, (0, 1), (ax, ax + 1))

        view = jax.tree.map(gather, pools, scales, self._batch_axes)
        logits, view, counts = self._decode_fn(
            params, tokens, view, positions, valid
        )

        page_start = positions - write_offsets
        rows = page_start[:, None] + jnp.arange(ps)[None, :]       # (B, ps)
        rows = jnp.clip(rows, 0, self.max_len - 1)
        valid = jnp.arange(ps)[None, :] <= write_offsets[:, None]  # (B, ps)

        def scatter(pool, sc, leaf, ax):
            v = jnp.moveaxis(leaf, (ax, ax + 1), (0, 1))  # (B, S, ...)
            pages = v[jnp.arange(b)[:, None], rows]       # (B, ps, ...)
            mask = valid.reshape((b, ps) + (1,) * (pages.ndim - 2))
            q, s = absmax_quantize(pages, (1, pages.ndim - 1), mask=mask)
            p = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
            p = p.at[write_pages].set(q)
            return (
                jnp.moveaxis(p, (0, 1), (ax, ax + 1)),
                sc.at[write_pages].set(s),
            )

        pools, scales = self._map_pool_scale(
            scatter, pools, scales, extra=view
        )
        out = (logits, pools, scales)
        return (*out, counts) if counts else out

    # -- public API --------------------------------------------------------------

    def submit(self, req: Request) -> None:
        req.queued_at = time.monotonic()
        self.queue.append(req)

    @property
    def slots_busy(self) -> int:
        """Number of decode slots currently occupied."""
        return sum(1 for f in self.slot_free if not f)

    def drain_completions(self) -> list[Completion]:
        """Pop (and return) completions accumulated so far — the streaming
        counterpart to reading ``self.completions`` after
        :meth:`run_to_completion`."""
        out = self.completions
        self.completions = []
        return out

    def _admit(self, req: Request) -> None:
        if self.admission is not None:
            est = len(req.prompt_tokens) + req.max_new_tokens
            self.admission(est)  # blocks until budget available

    def _compute_ctx(self):
        """Placement context for jitted prefill/decode: activation-sharding
        rules on a multi-device replica mesh, default-device pinning for a
        single-device replica, no-op otherwise."""
        if self.rules is not None:
            return use_rules(self.rules)
        if self.device is not None:
            return jax.default_device(self.device)
        return contextlib.nullcontext()

    def _count_prefill_program(self, shape: Any) -> None:
        if shape not in self._seen_prefill_shapes:
            self._seen_prefill_shapes.add(shape)
            self.stats.prefill_recompiles += 1

    def _prefill_chunks(
        self, row: PyTree, ptoks: list[int], start: int
    ) -> tuple[jax.Array, PyTree, list[dict]]:
        """Prefill prompt positions ``[start, len(ptoks))`` into ``row``
        chunk by chunk; return the last position's logits, the row and each
        chunk's expert counters (still on the device)."""
        n, w = len(ptoks), self.prefill_width
        if n > self.max_len:
            raise ValueError(f"prompt of {n} tokens exceeds max_len {self.max_len}")
        self._count_prefill_program(w)
        counts = []
        with obs.span("sched.prefill", tokens=n - start):
            for c in range(start, n, w):
                chunk = np.zeros((1, w), np.int32)
                part = ptoks[c : c + w]
                chunk[0, : len(part)] = part
                logits, row, chunk_counts = self._prefill_chunk(
                    self.params, jnp.asarray(chunk), row, np.int32(c),
                    np.int32(n),
                )
                counts.append(chunk_counts)
        return logits, row, counts

    def _first_token(self, logits: jax.Array) -> int:
        """Bring the prefill's greedy token to the host (waits for the
        admission's device work)."""
        with obs.span("sched.first_token"):
            return int(
                jax.device_get(
                    steps_lib.greedy_sample(logits, self.cfg.vocab_size)
                )[0]
            )

    def _count_prefill(self, counts: list[dict]) -> None:
        """Add an admission's expert counters, computed by the time its
        first token reached the host (none for a dense model)."""
        for c in jax.device_get([c for c in counts if c]):
            self.stats.add_expert_counts(c, "prefill")

    def _contiguous_prefill(self, slot: int, req: Request) -> int:
        ptoks = req.prompt_tokens
        with self._compute_ctx():
            with obs.span("sched.row_init"):
                row_cache = init_params(jax.random.key(1), self._row_specs)
            counts: list[dict] = []
            if self._chunked:
                logits, row_cache, counts = self._prefill_chunks(
                    row_cache, ptoks, 0
                )
            else:
                self._count_prefill_program(len(ptoks))
                batch = {"tokens": jnp.asarray(np.asarray(ptoks, np.int32)[None])}
                if req.extras:
                    batch.update(
                        {k: jnp.asarray(v)[None] for k, v in req.extras.items()}
                    )
                with obs.span("sched.prefill", tokens=len(ptoks)):
                    logits, row_cache = self._prefill(self.params, batch, row_cache)
            self.cache = self._insert(self.cache, row_cache, slot)
            first_tok = self._first_token(logits)
        self._count_prefill(counts)
        return first_tok

    def _paged_prefill(self, slot: int, req: Request) -> int:
        """Acquire pages (reusing any resident shared prefix), prefill only
        the suffix, scatter the fresh pages back into the pool, and index
        the prompt's full pages for future sharers."""
        ptoks = req.prompt_tokens
        match = self.manager.acquire(slot, ptoks)
        start = match.n_shared_tokens
        self.stats.prefix_pages_hit += match.n_shared_pages
        self.stats.prefix_tokens_saved += start
        with self._compute_ctx():
            with obs.span("sched.row_init"):
                row = init_params(jax.random.key(1), self._row_specs)
            if match.n_shared_pages:
                with obs.span("sched.prefix_read", pages=match.n_shared_pages):
                    shared = jnp.asarray(
                        match.page_ids[: match.n_shared_pages], jnp.int32
                    )
                    if self.quantized:
                        row = self._read_prefix_q(
                            row, self.cache, self.scales, shared
                        )
                    else:
                        row = self._read_prefix(row, self.cache, shared)
            logits, row, counts = self._prefill_chunks(row, ptoks, start)
            n_fresh = len(match.page_ids) - match.n_shared_pages
            with obs.span("sched.page_write", pages=n_fresh):
                fresh = jnp.asarray(
                    match.page_ids[match.n_shared_pages :], jnp.int32
                )
                if self.quantized:
                    self.cache, self.scales = self._write_pages_q(
                        self.cache, self.scales, row, fresh,
                        match.n_shared_pages, len(ptoks) - start,
                    )
                else:
                    self.cache = self._write_pages(
                        self.cache, row, fresh, match.n_shared_pages
                    )
            first_tok = self._first_token(logits)
        self._count_prefill(counts)
        self.manager.register(slot, ptoks)
        return first_tok

    def _page_gate(self) -> bool:
        """Low-watermark admission gate: admit the queue head only if the
        pool covers its worst-case prompt-page need while keeping one page
        per busy slot in reserve for decode growth — prefills defer under
        pressure instead of overcommitting pages a decode will then have
        to preempt for.  A prompt larger than the whole pool is admitted
        anyway so ``acquire`` raises a clear error instead of the request
        deferring forever."""
        need = -(-len(self.queue[0].prompt_tokens) // self.page_size)
        if need >= self.manager.n_pages:
            return True
        reserve = sum(1 for f in self.slot_free if not f)
        avail = self.manager.pages_free + self.manager.pages_cached
        return avail >= need + reserve

    def _refill(self) -> None:
        admitted = 0
        for slot in range(self.n_slots):
            if not self.slot_free[slot] or not self.queue:
                continue
            if (
                self.max_prefills_per_step
                and admitted >= self.max_prefills_per_step
            ):
                # each still-queued request that a free slot could have
                # taken this step is deferred exactly once per step it
                # actually waits (not once per queue neighbour)
                free_left = sum(
                    1 for s in range(slot, self.n_slots) if self.slot_free[s]
                )
                self.stats.prefills_deferred += min(len(self.queue), free_left)
                break
            if self.page_size and not self._page_gate():
                free_left = sum(
                    1 for s in range(slot, self.n_slots) if self.slot_free[s]
                )
                self.stats.prefills_deferred += min(len(self.queue), free_left)
                break
            req = self.queue.pop(0)
            self._admit(req)
            req.admitted_at = time.monotonic()
            ptoks = req.prompt_tokens
            self.stats.admissions += 1
            with obs.span("sched.admit", rid=req.request_id):
                if self.page_size:
                    first_tok = self._paged_prefill(slot, req)
                else:
                    first_tok = self._contiguous_prefill(slot, req)
            req.first_token_at = time.monotonic()
            self.stats.queue_wait_s += req.admitted_at - req.queued_at
            self.stats.first_token_s += req.first_token_at - req.admitted_at
            admitted += 1

            self.slot_free[slot] = False
            self.slot_req[slot] = req
            self.slot_tokens[slot] = [first_tok]
            self.slot_pos[slot] = self.prefix + len(ptoks)
            self.cur_tokens[slot, 0] = first_tok

    def _finish(self, slot: int, reason: str) -> None:
        req = self.slot_req[slot]
        assert req is not None
        self.completions.append(
            Completion(
                request_id=req.request_id,
                tokens=list(self.slot_tokens[slot]),
                prompt_len=len(req.prompt_tokens),
                finished_reason=reason,
                queued_at=req.queued_at,
                admitted_at=req.admitted_at,
                first_token_at=req.first_token_at,
                finished_at=time.monotonic(),
            )
        )
        self._release_slot(slot)
        self.stats.completions += 1

    def _release_slot(self, slot: int) -> None:
        """Free a slot and its pages without emitting a completion."""
        self.slot_free[slot] = True
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        if self.page_size:
            self.manager.release(slot)

    def _preempt(self, slot: int) -> None:
        """Evict a decoding slot under pool pressure: release its pages
        and requeue its request (same request id, queue front) for a full
        recompute.  Greedy prefill+decode are bitwise reproducible, so the
        preempted request's final output is byte-identical to an
        unpreempted run — preemption costs work, never correctness."""
        req = self.slot_req[slot]
        assert req is not None
        self.stats.preemptions += 1
        self.stats.preempted_tokens += len(self.slot_tokens[slot])
        self.queue.insert(0, req)
        self._release_slot(slot)

    def _preempt_victim(self) -> bool:
        """Pick and preempt the cheapest-to-recompute victim: fewest
        decoded tokens, slot-index tie-break."""
        active = [s for s in range(self.n_slots) if not self.slot_free[s]]
        if not active:
            return False
        victim = min(active, key=lambda s: (len(self.slot_tokens[s]), s))
        self._preempt(victim)
        return True

    def cancel(self, request_id: int) -> bool:
        """Abandon a request without a completion: dequeue it, or free its
        slot and release its pages (the service cancels the losing leg of
        a hedged request this way).  Returns True if found."""
        for i, req in enumerate(self.queue):
            if req.request_id == request_id:
                del self.queue[i]
                return True
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is not None and req.request_id == request_id:
                self._release_slot(slot)
                return True
        return False

    def _reap(self) -> None:
        """Finish every slot whose latest sample terminated it."""
        for slot in range(self.n_slots):
            if self.slot_free[slot]:
                continue
            toks = self.slot_tokens[slot]
            req = self.slot_req[slot]
            assert req is not None
            if toks and toks[-1] == self.eos_id:
                self._finish(slot, "eos")
            elif len(toks) >= req.max_new_tokens:
                self._finish(slot, "length")

    def _paged_step_tables(
        self, active: list[int]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-step page tables and write targets; extends/copy-on-writes
        the page holding each active slot's next position."""
        tables = np.zeros((self.n_slots, self.pages_per_slot), np.int32)
        write_pages = np.full((self.n_slots,), self._trash_page, np.int32)
        write_offsets = np.zeros((self.n_slots,), np.int32)
        for slot in active:
            pos = int(self.slot_pos[slot])
            if pos < self.max_len:
                pw = self.manager.ensure_position(slot, pos)
                if pw.cow_src is not None:
                    # defensive: unreachable while sharing stops short of
                    # the final prompt token (see paged_cache docstring)
                    if self.quantized:
                        self.cache, self.scales = self._copy_page_q(
                            self.cache, self.scales, pw.cow_src, pw.page_id
                        )
                    else:
                        self.cache = self._copy_page(
                            self.cache, pw.cow_src, pw.page_id
                        )
                    self.stats.cow_copies += 1
                write_pages[slot] = pw.page_id
                write_offsets[slot] = pw.offset
            table = self.manager.table(slot)
            tables[slot, : len(table)] = table
        return tables, write_pages, write_offsets

    def step(self) -> int:
        """One scheduler iteration; returns number of active slots stepped."""
        with obs.span("sched.step"):
            return self._step()

    def _step(self) -> int:
        if self.fault_hook is not None:
            kind = self.fault_hook(self.steps_run)
            if kind == "page_pressure":
                self._preempt_victim()
            elif kind == "hang":
                return 0  # no admissions, no decode, no progress
        # finish-check *before* refill so a slot freed by the previous
        # iteration's sample is refillable in this very step, then check
        # again for fresh slots whose first token already terminated them
        with obs.span("sched.reap"):
            self._reap()
        with obs.span("sched.refill"):
            self._refill()
        with obs.span("sched.reap"):
            self._reap()
        active = [s for s in range(self.n_slots) if not self.slot_free[s]]
        if not active:
            return 0

        with self._compute_ctx():
            if self.page_size:
                # decode-time pool pressure preempts the cheapest victim
                # and retries instead of killing the replica (DESIGN.md §9);
                # ensure_position is idempotent, so rebuilding the tables
                # after a preemption released pages is safe
                with obs.span("sched.decode.tables"):
                    while True:
                        try:
                            tables, wpages, woffs = self._paged_step_tables(
                                active
                            )
                            break
                        except PagePoolExhausted:
                            self._preempt_victim()
                            active = [
                                s for s in range(self.n_slots)
                                if not self.slot_free[s]
                            ]
                            if not active:
                                return 0
            self.stats.steps += 1
            self.stats.active_slot_steps += len(active)
            self.stats.tokens_generated += len(active)
            with obs.span("sched.decode.dispatch", slots=len(active)):
                tokens = jnp.asarray(self.cur_tokens)
                positions = jnp.asarray(self.slot_pos)
                # an expert layer routes only the slots that decode; a dense
                # step takes no mask and compiles as it always has
                valid = ()
                if self.cfg.n_experts:
                    valid = (jnp.asarray(~np.asarray(self.slot_free)),)
                if self.page_size and self.quantized:
                    logits, self.cache, self.scales, *counts = self._paged_decode_q(
                        self.params, tokens, self.cache, self.scales,
                        jnp.asarray(tables), positions,
                        jnp.asarray(wpages), jnp.asarray(woffs), *valid,
                    )
                elif self.page_size:
                    logits, self.cache, *counts = self._paged_decode(
                        self.params, tokens, self.cache, jnp.asarray(tables),
                        positions, jnp.asarray(wpages), jnp.asarray(woffs),
                        *valid,
                    )
                else:
                    logits, self.cache, *counts = self._decode(
                        self.params, tokens, self.cache, positions, *valid
                    )
                counts = counts[0] if counts else {}
                if self.temperature > 0:
                    self.key, sub = jax.random.split(self.key)
                    nxt = steps_lib.temperature_sample(
                        logits, self.cfg.vocab_size, self.temperature, sub
                    )
                else:
                    nxt = steps_lib.greedy_sample(logits, self.cfg.vocab_size)
            with obs.span("sched.decode.sync"):
                nxt, counts = jax.device_get((nxt, counts))
                nxt = np.asarray(nxt)
            if counts:
                self.stats.add_expert_counts(counts, "decode")

        with obs.span("sched.decode.commit"):
            for slot in active:
                self.slot_tokens[slot].append(int(nxt[slot]))
                self.slot_pos[slot] += 1
                self.cur_tokens[slot, 0] = int(nxt[slot])
        self.steps_run += 1
        return len(active)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Completion]:
        for _ in range(max_steps):
            busy = any(not f for f in self.slot_free)
            if not busy and not self.queue:
                break
            self.step()
        # flush slots the loop left behind: finished-but-unreported ones
        # emit normally; a slot still mid-generation at max_steps
        # exhaustion emits a "truncated" completion rather than silently
        # dropping the request
        self._reap()
        for slot in range(self.n_slots):
            if not self.slot_free[slot]:
                self._finish(slot, "truncated")
        return self.completions
