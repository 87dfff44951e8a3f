"""Paged KV cache manager (ISSUE 8): hash-chain prefix matching,
refcounted sharing, LRU eviction of cached pages, copy-on-write
divergence, and no-leak invariants under churn — plus (ISSUE 10) the
byte-accounting layer for quantized pools: bytes-per-page formulae,
byte-budget sizing, partition invariants, and int8 round-trip bounds."""

import random

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.serve.paged_cache import (
    PagedCacheManager,
    kv_page_bytes,
    page_hash_chain,
    pages_for_budget,
)


def _mgr(n_pages=32, page_size=4, **kw):
    return PagedCacheManager(n_pages, page_size, **kw)


# -- hash chain ------------------------------------------------------------------


def test_hash_chain_one_digest_per_full_page():
    assert page_hash_chain([1, 2, 3], 4) == []
    assert len(page_hash_chain(list(range(8)), 4)) == 2
    assert len(page_hash_chain(list(range(9)), 4)) == 2


def test_hash_chain_commits_to_whole_prefix():
    a = page_hash_chain([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = page_hash_chain([1, 2, 3, 4, 5, 6, 7, 8], 4)
    assert a == b
    # same second page, different first page -> different second digest
    c = page_hash_chain([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert a[0] != c[0] and a[1] != c[1]
    # token-boundary ambiguity does not collide: [12,3] vs [1,23]
    assert page_hash_chain([12, 3], 2) != page_hash_chain([1, 23], 2)


# -- acquire / release -----------------------------------------------------------


def test_acquire_allocates_ceil_pages_and_release_frees():
    m = _mgr()
    match = m.acquire("a", list(range(10)))  # 10 tokens / 4 -> 3 pages
    assert len(match.page_ids) == 3
    assert match.n_shared_pages == 0
    assert m.pages_active == 3
    m.release("a")
    m.check_no_leaks()


def test_prefix_reuse_after_release_hits_cached_pages():
    m = _mgr()
    toks = list(range(10))
    m.acquire("a", toks)
    m.register("a", toks)  # indexes the 2 full pages
    m.release("a")
    assert m.pages_cached == 2 and m.pages_free == 30
    match = m.acquire("b", toks)
    assert match.n_shared_pages == 2
    assert match.n_shared_tokens == 8
    assert m.stats.prefix_tokens_saved == 8
    m.release("b")
    m.check_no_leaks()


def test_final_token_page_never_shared():
    """A prompt that is an exact multiple of the page size still prefills
    its last page: sharing stops at (len-1)//ps pages."""
    m = _mgr()
    toks = list(range(8))  # exactly 2 pages
    m.acquire("a", toks)
    m.register("a", toks)
    m.release("a")
    match = m.acquire("b", toks)
    assert match.n_shared_pages == 1  # not 2: last page stays private
    assert len(match.page_ids) == 2
    m.release("b")


def test_divergent_prefix_shares_only_matching_pages():
    m = _mgr()
    base = list(range(12))
    m.acquire("a", base)
    m.register("a", base)
    fork = base[:8] + [99, 98, 97, 96]  # diverges at page 2
    match = m.acquire("b", fork)
    assert match.n_shared_pages == 2
    shared_ids = match.page_ids[:2]
    assert [m.refcount(p) for p in shared_ids] == [2, 2]
    m.release("a")
    assert [m.refcount(p) for p in shared_ids] == [1, 1]
    m.release("b")
    m.check_no_leaks()


def test_concurrent_sharers_refcount():
    m = _mgr()
    toks = list(range(16))
    m.acquire("a", toks)
    m.register("a", toks)
    owners = [f"o{i}" for i in range(5)]
    for o in owners:
        assert m.acquire(o, toks).n_shared_pages == 3
    first = m.table("a")[0]
    assert m.refcount(first) == 6
    for o in owners + ["a"]:
        m.release(o)
    m.check_no_leaks()


def test_duplicate_registration_keeps_first_mapping():
    """Two identical prompts prefilled concurrently (neither registered
    when the other acquired): second register is a no-op and both release
    cleanly."""
    m = _mgr()
    toks = list(range(10))
    m.acquire("a", toks)
    m.acquire("b", toks)  # nothing indexed yet -> no sharing
    assert m.pages_active == 6
    assert m.register("a", toks) == 2
    assert m.register("b", toks) == 0  # first registration wins
    m.release("a")
    m.release("b")
    # a's indexed pages parked in the prefix cache, b's freed outright
    assert m.pages_cached == 2
    m.check_no_leaks()


def test_acquire_rejects_double_owner_and_empty_prompt():
    m = _mgr()
    m.acquire("a", [1, 2, 3])
    with pytest.raises(ValueError):
        m.acquire("a", [4, 5])
    with pytest.raises(ValueError):
        m.acquire("b", [])


# -- eviction / exhaustion -------------------------------------------------------


def test_lru_eviction_of_cached_pages_under_pressure():
    m = _mgr(n_pages=8, page_size=4)
    for i in range(3):  # park 2 indexed pages per round, LRU order
        toks = [i * 100 + t for t in range(9)]
        m.acquire(f"o{i}", toks)
        m.register(f"o{i}", toks)
        m.release(f"o{i}")
    assert m.pages_cached + m.pages_free == 8
    # a 8-page prompt must evict cached pages to fit
    m.acquire("big", list(range(1000, 1029)))
    assert m.stats.evictions > 0
    # oldest chain (o0) evicted first: re-acquiring it finds nothing
    m.release("big")
    assert m.acquire("probe", [0, 1, 2, 3, 4]).n_shared_pages == 0
    m.release("probe")
    m.check_no_leaks()


def test_pool_exhaustion_by_active_pages_raises():
    m = _mgr(n_pages=4, page_size=4)
    m.acquire("a", list(range(16)))  # all 4 pages active
    with pytest.raises(RuntimeError, match="exhausted"):
        m.acquire("b", [1, 2, 3])
    m.release("a")
    m.check_no_leaks()


def test_matched_pages_survive_allocation_pressure_in_same_acquire():
    """The fresh-page allocation of an acquire must not LRU-evict the
    pages its own prefix walk just matched."""
    m = _mgr(n_pages=4, page_size=2)
    toks = [1, 2, 3, 4, 5]
    m.acquire("a", toks)
    m.register("a", toks)
    m.release("a")  # 2 cached + ... pool: 3 pages used, 1 free
    match = m.acquire("b", toks)  # needs 1 fresh page beyond the 2 shared
    assert match.n_shared_pages == 2
    assert len(set(match.page_ids)) == 3
    m.release("b")
    m.check_no_leaks()


# -- ensure_position / copy-on-write --------------------------------------------


def test_ensure_position_extends_table():
    m = _mgr(page_size=4)
    m.acquire("a", [1, 2, 3])
    pw = m.ensure_position("a", 3)  # same page, private -> in place
    assert not pw.allocated and pw.cow_src is None and pw.offset == 3
    pw = m.ensure_position("a", 4)  # next page
    assert pw.allocated and pw.page_index == 1 and pw.offset == 0
    with pytest.raises(ValueError):
        m.ensure_position("a", 12)  # non-contiguous
    m.release("a")
    m.check_no_leaks()


def test_ensure_position_cow_on_shared_page():
    m = _mgr(page_size=4)
    toks = list(range(12))
    m.acquire("a", toks)
    m.register("a", toks)
    m.acquire("b", toks)  # shares pages 0-1
    shared = m.table("b")[0]
    pw = m.ensure_position("b", 1)  # write inside a shared page
    assert pw.cow_src == shared
    assert pw.page_id != shared
    assert m.table("b")[0] == pw.page_id
    assert m.refcount(shared) == 1  # only "a" holds it now
    assert m.stats.cow_copies == 1
    m.release("a")
    m.release("b")
    m.check_no_leaks()


def test_ensure_position_cow_on_indexed_private_page():
    """Even with refcount 1, an *indexed* page is copy-on-write: writing
    in place would leave a stale hash in the prefix index."""
    m = _mgr(page_size=4)
    toks = list(range(8))
    m.acquire("a", toks)
    m.register("a", toks)
    indexed = m.table("a")[0]
    pw = m.ensure_position("a", 2)
    assert pw.cow_src == indexed
    # the old page parks in the prefix cache, still matchable
    assert m.pages_cached == 1
    m.release("a")
    m.check_no_leaks()


# -- churn stress ---------------------------------------------------------------


# -- byte accounting (quantized pools) -------------------------------------------


def test_kv_page_bytes_formula():
    """bf16 pages cost 2 bytes/elem; int8 pages cost 1 byte/elem plus one
    f32 scale per (layer, kv head, K/V) — under 1% overhead at 16x64."""
    bf16 = kv_page_bytes(16, 8, 64, 4)
    int8 = kv_page_bytes(16, 8, 64, 4, "int8")
    elems = 2 * 4 * 8 * 16 * 64  # K+V x layers x heads x page x head_dim
    assert bf16 == elems * 2 == 131072
    assert int8 == elems + 2 * 4 * 8 * 4 == 65792
    assert bf16 / int8 >= 1.8  # the capacity lever the benchmark gates on
    with pytest.raises(ValueError):
        kv_page_bytes(16, 8, 64, 4, "fp8")


def test_pages_for_budget():
    pb = kv_page_bytes(16, 8, 64, 4)
    assert pages_for_budget(10 * pb, pb) == 10
    assert pages_for_budget(10 * pb + pb - 1, pb) == 10  # floor, never round up
    with pytest.raises(ValueError):
        pages_for_budget(pb - 1, pb)  # budget below a single page


def test_byte_partition_tracks_page_partition():
    """With ``page_bytes`` set, the byte view is page counts scaled: the
    free/cached/active partition holds in bytes at every transition and
    check_no_leaks enforces it."""
    m = _mgr(n_pages=8, page_size=4, page_bytes=100)
    assert m.pool_bytes == 800
    assert m.kv_bytes_per_token == 25
    m.acquire("a", list(range(10)))  # 3 pages active
    m.register("a", list(range(10)))
    assert (m.bytes_active, m.bytes_cached, m.bytes_free) == (300, 0, 500)
    m.release("a")  # 2 full pages park in the prefix cache
    assert (m.bytes_active, m.bytes_cached, m.bytes_free) == (0, 200, 600)
    assert m.bytes_free + m.bytes_cached + m.bytes_active == m.pool_bytes
    m.check_no_leaks()


def test_launch_cells_int8_cache_meta_matches_pool_formula():
    """The analytical serve cells charge int8 caches the same per-page
    f32 scale overhead as the byte-budgeted serving pool: a GQA decode
    cell's ``cache_bytes`` meta equals pages x kv_page_bytes exactly."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config
    from repro.launch.cells import build_cell

    devs = np.array(jax.devices() * 16)[:16]
    mesh = jax.sharding.Mesh(devs.reshape(4, 4), ("data", "model"))
    cfg = get_config("qwen3-4b")
    scfg = SHAPES["decode_32k"]
    assert scfg.seq_len % 16 == 0
    cell = build_cell("qwen3-4b", "decode_32k", mesh, cache_dtype=jnp.int8)
    pages = scfg.global_batch * (scfg.seq_len // 16)
    assert cell.meta["cache_bytes"] == pages * kv_page_bytes(
        16, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers, "int8"
    )
    # and the bf16 cell sees the ~2x capacity lever the pool advertises
    bf16 = build_cell("qwen3-4b", "decode_32k", mesh)
    assert bf16.meta["cache_bytes"] / cell.meta["cache_bytes"] >= 1.8


# -- int8 round-trip bounds (hypothesis + deterministic counterparts) ------------


def _round_trip_check(vals):
    """Shared property body: |dequant - x| <= scale/2 per group, zero
    groups get scale 1.0 exactly."""
    import jax.numpy as jnp

    from repro.kernels.decode_attention import absmax_dequantize, absmax_quantize

    x = np.asarray(vals, np.float32).reshape(1, -1)
    q, s = absmax_quantize(jnp.asarray(x), (1,))
    back = np.asarray(absmax_dequantize(q, s, (1,)))
    scale = float(np.asarray(s)[0])
    absmax = float(np.abs(x).max())
    if absmax == 0.0:
        assert scale == 1.0
        assert (back == 0.0).all()
    else:
        assert scale == pytest.approx(absmax / 127.0, rel=1e-6)
        # bound: half a quantization step, plus f32 rounding headroom
        assert np.abs(back - x).max() <= scale / 2 + 1e-6 * absmax


@given(
    st.lists(
        st.floats(-1e6, 1e6, width=32, allow_nan=False, allow_infinity=False),
        min_size=1,
        max_size=64,
    )
)
@settings(max_examples=50, deadline=None)
def test_quant_round_trip_error_bounded_property(vals):
    """Property: for arbitrary f32 content (zeros, denormals, outliers)
    the absmax round-trip error never exceeds half a quantization step
    of its own group."""
    _round_trip_check(vals)


def test_quant_round_trip_error_bounded_examples():
    """Deterministic counterpart: all-zero group, a single-outlier group
    (one huge head crushing resolution elsewhere is bounded by *its own*
    group's scale), and a plain random group."""
    rng = np.random.RandomState(0)
    _round_trip_check([0.0] * 16)
    _round_trip_check([1e6] + [1e-3] * 15)
    _round_trip_check(list(rng.randn(64)))


def test_quant_masked_rows_excluded_from_scale_and_bytes():
    """The write-path mask keeps stale rows out of the absmax AND the
    stored bytes — quantized content is a pure function of valid
    history, the determinism the serving stack's resume relies on."""
    import jax.numpy as jnp

    from repro.kernels.decode_attention import absmax_quantize

    x = np.zeros((1, 4), np.float32)
    x[0, :2] = [1.0, -2.0]
    stale = x.copy()
    stale[0, 2:] = 1e6  # garbage beyond the valid prefix
    mask = np.asarray([[True, True, False, False]])
    q1, s1 = absmax_quantize(jnp.asarray(x), (1,), mask=jnp.asarray(mask))
    q2, s2 = absmax_quantize(jnp.asarray(stale), (1,), mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert float(np.asarray(s1)[0]) == pytest.approx(2.0 / 127.0)
    assert (np.asarray(q1)[0, 2:] == 0).all()  # masked rows store zero bytes


# -- churn stress ---------------------------------------------------------------


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=15, deadline=None)
def test_byte_partition_invariant_under_churn_property(seed):
    """Property: under arbitrary acquire/register/extend/release churn on
    a byte-accounted (quantized-geometry) pool, free+cached+active bytes
    always partition the pool budget exactly."""
    _churn(random.Random(seed), page_bytes=65792)


def _churn(rnd, page_bytes=0):
    m = _mgr(n_pages=32, page_size=4, page_bytes=page_bytes)
    live: dict[int, list[int]] = {}
    headers = [[h * 1000 + t for t in range(12)] for h in range(3)]
    for i in range(120):
        roll = rnd.random()
        if live and (roll < 0.35 or len(live) >= 8):
            owner = rnd.choice(list(live))
            m.release(owner)
            del live[owner]
        elif live and roll < 0.55:
            owner = rnd.choice(list(live))
            m.ensure_position(owner, len(live[owner]))
            live[owner].append(i)
        else:
            toks = rnd.choice(headers) + [i, i + 1]
            try:
                m.acquire(i, toks)
            except RuntimeError:
                continue  # pool exhausted under churn: fine, keep going
            m.register(i, toks)
            live[i] = toks
        assert m.pages_free + m.pages_cached + m.pages_active == 32
        assert (
            m.bytes_free + m.bytes_cached + m.bytes_active == m.pool_bytes
        )
    for owner in list(live):
        m.release(owner)
    m.check_no_leaks()


def test_byte_partition_invariant_under_churn_examples():
    """Deterministic counterpart of the churn property."""
    for seed in (0, 7):
        _churn(random.Random(seed), page_bytes=65792)


def test_no_leaks_under_interleaved_shared_prefix_churn():
    rnd = random.Random(0)
    m = _mgr(n_pages=64, page_size=4)
    headers = [[h * 1000 + t for t in range(12)] for h in range(3)]
    live: dict[int, list[int]] = {}
    for i in range(200):
        if live and (rnd.random() < 0.45 or len(live) >= 10):
            owner = rnd.choice(list(live))
            m.release(owner)
            del live[owner]
        else:
            toks = rnd.choice(headers) + [i, i + 1]
            m.acquire(i, toks)
            m.register(i, toks)
            live[i] = toks
        assert m.pages_free + m.pages_cached + m.pages_active == 64
    for owner in list(live):
        m.release(owner)
    m.check_no_leaks()
    assert m.stats.prefix_tokens_saved > 0


@pytest.mark.parametrize("heads", [4, 8])
@pytest.mark.parametrize("ax", [0, 1])
def test_paged_view_gathers_pages_and_scatters_new_rows(heads, ax):
    """The paged decode's view is each slot's pages in table order, and its
    new rows land on their pages: page-major where the heads fill a float32
    tile (8), in place where they do not (4)."""
    import jax.numpy as jnp

    from repro.serve.scheduler import _gather_view, _scatter_rows

    rng = np.random.default_rng(heads + ax)
    n_pages, ps, dh = 7, 4, 3
    lead = (2,) * ax  # a layer axis before the pages
    pool = rng.standard_normal(lead + (n_pages, ps, heads, dh)).astype(np.float32)
    tables = np.array([[3, 0, 5], [6, 2, 1]], np.int32)
    view = np.asarray(_gather_view(jnp.asarray(pool), jnp.asarray(tables), ax))
    want = np.stack([
        np.concatenate([np.take(pool, p, axis=ax) for p in t], axis=ax)
        for t in tables
    ], axis=ax)
    np.testing.assert_array_equal(view, want)

    positions = np.array([5, 10], np.int32)
    pages = tables[np.arange(2), positions // ps]
    offsets = positions % ps
    new = view + rng.standard_normal(view.shape).astype(np.float32)
    got = np.asarray(_scatter_rows(
        jnp.asarray(pool), jnp.asarray(new), ax, jnp.asarray(positions),
        jnp.asarray(pages), jnp.asarray(offsets),
    ))
    at = (slice(None),) * ax
    want = pool.copy()
    for i in range(2):
        want[at + (pages[i], offsets[i])] = new[at + (i, positions[i])]
    np.testing.assert_array_equal(got, want)
