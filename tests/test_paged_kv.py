"""Paged KV cache conformance: paged-vs-contiguous bit-identity across
every Table-I KV format (packed fp4 included, at odd lengths crossing
page boundaries), allocator reuse/eviction invariants, and the paged
decode attention path vs the contiguous one.

The load-bearing claim: paging is *pure relayout*.  A page pool + block
table must hold codes and scales bit-identical to the contiguous cache
it replaces, whether rows arrive token-by-token (`paged_write_token`,
the decode path) or as a prefill scatter (`write_prefill_rows`), and the
attention consuming them (`dpa_paged_decode_attn`) must reproduce the
contiguous `dpa_decode_attn` bit-for-bit when the gathered view matches
the contiguous context length.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import kvcache as KV

# (fmt, packed): every KV format the policy table exposes
KV_FORMATS = [("fp16", False), ("bf16", False), ("fp8_e4m3", False),
              ("fp4_e2m1", False), ("fp4_e2m1", True)]
PS = 8                       # page size: small, so lengths cross pages
# odd lengths: mid-page tail, single partial page, >2 pages + 1 row
LENGTHS = [13, 5, 17]


def _fmt_id(p):
    return f"{p[0]}{'_packed' if p[1] else ''}"


def _raw_kv(seed, B, S, n_kv=2, hd=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(ks[0], (B, S, n_kv, hd))
    v = jax.random.normal(ks[1], (B, S, n_kv, hd))
    return k, v


def _alloc_tables(lengths, max_pages, capacity):
    alloc = KV.PageAllocator(capacity)
    table = np.full((len(lengths), max_pages), KV.SCRATCH_PAGE, np.int32)
    pages = []
    for b, L in enumerate(lengths):
        ids = alloc.alloc(-(-L // PS))
        pages.append(ids)
        table[b, :len(ids)] = ids
    return alloc, table, pages


def _assert_rows_equal(view, ref, lengths):
    for b, L in enumerate(lengths):
        for key in KV.QUANT_KEYS:
            got, want = np.asarray(view[key][b, :L]), np.asarray(ref[key][b, :L])
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), (key, b)


# -----------------------------------------------------------------------------
# bit-identity: token writes and prefill scatter vs the contiguous cache
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("fmt,packed", KV_FORMATS, ids=map(_fmt_id, KV_FORMATS))
def test_paged_token_writes_bit_identical(fmt, packed):
    """Token-by-token paged writes == contiguous update_kv_cache, for
    mixed lengths whose partial tails land mid-page."""
    B, n_kv, hd, max_pages = len(LENGTHS), 2, 16, 3
    k, v = _raw_kv(0, B, max_pages * PS, n_kv, hd)
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, max_pages * PS, n_kv, hd, fmt=fmt, packed=packed),
        k, v, 0, fmt=fmt, packed=packed)
    _, table, _ = _alloc_tables(LENGTHS, max_pages, capacity=16)
    cache = dict(KV.init_paged_kv_cache(16, PS, n_kv, hd, fmt=fmt,
                                        packed=packed),
                 block_table=jnp.asarray(table))
    for t in range(max(LENGTHS)):
        live = np.array([t < L for L in LENGTHS])
        # idle rows write position 0 of their (scratch) table row — the
        # engine's fixed-shape step; live data must be untouched by it
        tbl = np.where(live[:, None], table, KV.SCRATCH_PAGE).astype(np.int32)
        step = dict(cache, block_table=jnp.asarray(tbl))
        step = KV.paged_write_token(step, k[:, t:t + 1], v[:, t:t + 1],
                                    jnp.asarray(np.where(live, t, 0)),
                                    fmt=fmt, packed=packed)
        cache = dict(step, block_table=jnp.asarray(table))
    _assert_rows_equal(KV.gather_paged_kv(cache), ref, LENGTHS)


@pytest.mark.parametrize("fmt,packed", KV_FORMATS, ids=map(_fmt_id, KV_FORMATS))
def test_multi_token_write_equals_stepped_writes(fmt, packed):
    """`paged_write_tokens` over an S_new window == S_new sequential
    `paged_write_token` calls, bit for bit — rows quantize independently
    (per-row absmax over head_dim), so the speculative draft/verify
    window writes exactly what stepped decode would have written, even
    when the window straddles a page boundary."""
    B, n_kv, hd, max_pages, s_new = len(LENGTHS), 2, 16, 4, 5
    starts = [L - 2 for L in LENGTHS]           # windows cross boundaries
    k, v = _raw_kv(4, B, s_new, n_kv, hd)
    _, table, _ = _alloc_tables([L + s_new for L in LENGTHS], max_pages,
                                capacity=16)
    base = dict(KV.init_paged_kv_cache(16, PS, n_kv, hd, fmt=fmt,
                                       packed=packed),
                block_table=jnp.asarray(table))
    multi = KV.paged_write_tokens(base, k, v, jnp.asarray(starts, jnp.int32),
                                  fmt=fmt, packed=packed)
    stepped = base
    for t in range(s_new):
        stepped = KV.paged_write_token(
            stepped, k[:, t:t + 1], v[:, t:t + 1],
            jnp.asarray([s + t for s in starts], jnp.int32),
            fmt=fmt, packed=packed)
    for key in KV.QUANT_KEYS:
        assert np.array_equal(np.asarray(multi[key]),
                              np.asarray(stepped[key])), key


@pytest.mark.parametrize("fmt,packed", KV_FORMATS, ids=map(_fmt_id, KV_FORMATS))
def test_prefill_scatter_bit_identical(fmt, packed):
    """write_prefill_rows (whole pages + partial tail) == the contiguous
    staging rows it copies."""
    B, n_kv, hd, max_pages = len(LENGTHS), 2, 16, 3
    k, v = _raw_kv(1, B, max_pages * PS, n_kv, hd)
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, max_pages * PS, n_kv, hd, fmt=fmt, packed=packed),
        k, v, 0, fmt=fmt, packed=packed)
    _, table, pages = _alloc_tables(LENGTHS, max_pages, capacity=16)
    cache = dict(KV.init_paged_kv_cache(16, PS, n_kv, hd, fmt=fmt,
                                        packed=packed),
                 block_table=jnp.asarray(table))
    for b, L in enumerate(LENGTHS):
        rows = {key: ref[key][b] for key in KV.QUANT_KEYS}
        cache = KV.write_prefill_rows(cache, rows, pages[b], L)
    _assert_rows_equal(KV.gather_paged_kv(cache), ref, LENGTHS)


def test_write_prefill_rows_rejects_short_page_list():
    cache = KV.init_paged_kv_cache(4, PS, 2, 16, fmt="fp16")
    rows = {key: jnp.zeros((2 * PS,) + cache[key].shape[2:],
                           cache[key].dtype) for key in KV.QUANT_KEYS}
    with pytest.raises(ValueError, match="pages"):
        KV.write_prefill_rows(cache, rows, [1], PS + 1)


def test_gather_view_shape_and_scratch_tail():
    """The gathered view is (B, max_pages*page, ...) and tail slots past a
    request's pages read the scratch page (zeros here) — maskable, never
    out of bounds."""
    n_kv, hd = 2, 16
    cache = dict(KV.init_paged_kv_cache(8, PS, n_kv, hd, fmt="fp8_e4m3"),
                 block_table=jnp.asarray([[1, KV.SCRATCH_PAGE]], np.int32))
    k, v = _raw_kv(2, 1, PS, n_kv, hd)
    rows = KV.quantize_kv(k[0], fmt="fp8_e4m3")
    cache = KV.write_prefill_rows(
        cache, {"k_codes": rows[0], "k_scale": rows[1],
                "v_codes": rows[0], "v_scale": rows[1]}, [1], PS)
    view = KV.gather_paged_kv(cache)
    assert view["k_codes"].shape == (1, 2 * PS, n_kv, hd)
    assert np.all(np.asarray(view["k_scale"][0, PS:]) == 0.0)


# -----------------------------------------------------------------------------
# paged decode attention vs the contiguous decode path
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("pol_name", ["attn_fp16_dpa", "kv4_attn8_packed"])
def test_paged_decode_attn_matches_contiguous(pol_name):
    """dpa_paged_decode_attn == dpa_decode_attn bit-for-bit when the
    gathered view length equals the contiguous S_ctx (same shapes, same
    reductions), at per-request positions."""
    from repro.core import get_policy
    from repro.models.decode_attn import dpa_decode_attn, dpa_paged_decode_attn
    pol = get_policy(pol_name)
    B, H, n_kv, hd, n_pg = 3, 4, 2, 16, 4
    S = n_pg * PS
    k, v = _raw_kv(3, B, S, n_kv, hd)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, 1, H, hd))
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, S, n_kv, hd, fmt=pol.fmt_kv,
                         packed=pol.kv_packed),
        k, v, 0, fmt=pol.fmt_kv, packed=pol.kv_packed)
    cache = KV.paged_from_contiguous(ref, [S] * B, page_size=PS)
    positions = jnp.asarray([5, S - 1, 12], jnp.int32)
    got = dpa_paged_decode_attn(q, cache, positions, fmt=pol.fmt_attn,
                                fmt_kv=pol.fmt_kv, kv_packed=pol.kv_packed,
                                scale=hd ** -0.5)
    for b in range(B):
        want = dpa_decode_attn(q[b:b + 1],
                               {key: ref[key][b:b + 1]
                                for key in KV.QUANT_KEYS},
                               int(positions[b]), fmt=pol.fmt_attn,
                               fmt_kv=pol.fmt_kv, kv_packed=pol.kv_packed,
                               scale=hd ** -0.5)
        assert np.array_equal(np.asarray(got[b]), np.asarray(want[0])), b


# -----------------------------------------------------------------------------
# allocator invariants
# -----------------------------------------------------------------------------

def test_allocator_reserves_scratch_and_exhausts():
    a = KV.PageAllocator(5)
    assert a.n_free == 4                       # page 0 reserved
    got = a.alloc(4)
    assert KV.SCRATCH_PAGE not in got and len(set(got)) == 4
    assert not a.can_alloc(1)
    with pytest.raises(MemoryError):
        a.alloc(1)


def test_allocator_free_list_reuse():
    """Eviction returns pages for reuse (LIFO: the hottest pages first)."""
    a = KV.PageAllocator(8)
    first = a.alloc(3)
    a.free(first)
    assert a.in_use == 0 and a.n_free == 7
    again = a.alloc(3)
    assert again == first[::-1]                # LIFO reuse order
    assert a.peak_in_use == 3                  # peak survives the evict


def test_allocator_rejects_double_and_scratch_free():
    a = KV.PageAllocator(4)
    pages = a.alloc(2)
    a.free(pages[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free(pages[:1])
    with pytest.raises(ValueError, match="scratch"):
        a.free([KV.SCRATCH_PAGE])
    with pytest.raises(ValueError):
        KV.PageAllocator(1)


def test_allocator_utilization():
    a = KV.PageAllocator(11)
    a.alloc(5)
    assert a.utilization() == 0.5
    assert a.peak_in_use == 5


def test_allocator_refcount_lifecycle():
    """free() is a decref: a shared page survives every free but the
    last, then returns to the free list exactly once."""
    a = KV.PageAllocator(8)
    (p,) = a.alloc(1)
    assert a.refcount(p) == 1 and not a.is_shared(p)
    a.incref([p])
    a.incref([p])
    assert a.refcount(p) == 3 and a.is_shared(p)
    a.free([p])
    a.free([p])
    assert a.in_use == 1                       # still held once
    assert a.refcount(p) == 1
    a.free([p])
    assert a.in_use == 0 and a.refcount(p) == 0
    with pytest.raises(ValueError, match="double free"):
        a.free([p])
    with pytest.raises(ValueError, match="not in use"):
        a.incref([p])


def test_allocator_shared_page_never_rehanded_out():
    """While any holder remains, a shared page never reappears from
    alloc() — the prefix cache's never-freed-while-referenced contract."""
    a = KV.PageAllocator(6)
    (p,) = a.alloc(1)
    a.incref([p])                              # second holder
    a.free([p])                                # first holder exits
    assert p not in a.alloc(4)                 # the whole rest of the pool
    with pytest.raises(MemoryError):
        a.alloc(1)


def test_allocator_rollback_refuses_shared_pages():
    """Speculative rollback (free to_reserved=True) may only reclaim
    exclusively-owned pages; a shared prefix page inside the rollback
    set is an accounting bug and must raise, not silently corrupt."""
    a = KV.PageAllocator(8)
    a.reserve(2)
    pages = a.alloc(2, reserved=True)
    a.incref(pages[:1])
    with pytest.raises(ValueError, match="shared"):
        a.free(pages[:1], to_reserved=True)
    a.free(pages[1:], to_reserved=True)        # exclusive page: fine
    assert a.reserved == 1


def test_paged_from_contiguous_empty_and_single():
    """Empty workloads are legal: an all-scratch table over a minimal
    pool, not a max() crash; a single request round-trips exactly."""
    ref = KV.init_kv_cache(0, 2 * PS, 2, 16, fmt="fp8_e4m3")
    cache = KV.paged_from_contiguous(ref, [], page_size=PS)
    assert cache["block_table"].shape[0] == 0
    assert cache["block_table"].shape[1] >= 1
    k, v = _raw_kv(5, 1, 2 * PS, 2, 16)
    one = KV.update_kv_cache(KV.init_kv_cache(1, 2 * PS, 2, 16,
                                              fmt="fp8_e4m3"),
                             k, v, 0, fmt="fp8_e4m3")
    paged = KV.paged_from_contiguous(one, [2 * PS], page_size=PS)
    _assert_rows_equal(KV.gather_paged_kv(paged), one, [2 * PS])


@pytest.mark.parametrize("fmt,packed", [("fp8_e4m3", False),
                                        ("fp4_e2m1", True)],
                         ids=["fp8", "fp4_packed"])
def test_prefill_scatter_start_skips_prefix_pages(fmt, packed):
    """write_prefill_rows(start=m) leaves every row before m untouched —
    full prefix pages are never written (shared-page safety) and a CoW
    page keeps its copied head rows — while rows from m on land
    bit-identical to a start=0 scatter."""
    n_kv, hd, L, start = 2, 16, 2 * PS + 3, PS + 5   # mid-page divergence
    k, v = _raw_kv(6, 1, 3 * PS, n_kv, hd)
    ref = KV.update_kv_cache(
        KV.init_kv_cache(1, 3 * PS, n_kv, hd, fmt=fmt, packed=packed),
        k, v, 0, fmt=fmt, packed=packed)
    rows = {key: ref[key][0] for key in KV.QUANT_KEYS}
    _, table, pages = _alloc_tables([L], 3, capacity=8)
    base = dict(KV.init_paged_kv_cache(8, PS, n_kv, hd, fmt=fmt,
                                       packed=packed),
                block_table=jnp.asarray(table))
    # poison the pool so "untouched" is observable
    poisoned = {key: jnp.ones_like(base[key]) for key in KV.QUANT_KEYS}
    part = KV.write_prefill_rows(dict(base, **poisoned), rows, pages[0], L,
                                 start=start)
    full = KV.write_prefill_rows(base, rows, pages[0], L)
    pids = pages[0]
    for key in KV.QUANT_KEYS:
        got = np.asarray(part[key])
        # page 0 entirely before `start`: still poison
        assert np.all(got[pids[0]] == 1), key
        # page 1 rows before the in-page offset: still poison
        assert np.all(got[pids[1], :start - PS] == 1), key
        # everything from `start` up to `length` matches the full scatter
        want = np.asarray(full[key])
        assert np.array_equal(got[pids[1], start - PS:],
                              want[pids[1], start - PS:]), key
        assert np.array_equal(got[pids[2], :L - 2 * PS],
                              want[pids[2], :L - 2 * PS]), key
    with pytest.raises(ValueError, match="start"):
        KV.write_prefill_rows(base, rows, pages[0], L, start=L + 1)


def _traced_scatter_case(fmt, packed):
    """-> (jit'd scatter, staging rows (S_max, ...), poisoned pool, the
    request's pages) for the traced-scatter tests: S_max = 3 pages, the
    page list one real page longer than the rows need (a decode page the
    scatter must not touch), ids out of allocation order."""
    n_kv, hd, s_max = 2, 16, 3 * PS
    k, v = _raw_kv(7, 1, s_max, n_kv, hd)
    ref = KV.update_kv_cache(
        KV.init_kv_cache(1, s_max, n_kv, hd, fmt=fmt, packed=packed),
        k, v, 0, fmt=fmt, packed=packed)
    rows = {key: ref[key][0] for key in KV.QUANT_KEYS}
    pool = KV.init_paged_kv_cache(8, PS, n_kv, hd, fmt=fmt,
                                  packed=packed)
    # poison the pool so "untouched" is observable
    poison = {key: jnp.ones_like(pool[key]) for key in KV.QUANT_KEYS}
    pages = [5, 2, 7, 3]

    def scatter(*args):        # a function of its own: a jit cache of its own
        return KV.scatter_prefill_rows(*args)

    return jax.jit(scatter), rows, poison, pages


def _expected_pool(poison, rows, pages, length, start):
    want = {key: np.array(poison[key]) for key in KV.QUANT_KEYS}
    for key in KV.QUANT_KEYS:
        src = np.asarray(rows[key])
        for r in range(start, length):
            want[key][pages[r // PS], r % PS] = src[r]
    return want


def _assert_pool_bits(got, want):
    for key in KV.QUANT_KEYS:
        g = np.asarray(got[key])
        assert g.dtype == want[key].dtype, key
        assert np.array_equal(g.view(np.uint8), want[key].view(np.uint8)), key


@pytest.mark.parametrize("fmt,packed", KV_FORMATS, ids=map(_fmt_id, KV_FORMATS))
def test_traced_scatter_bit_identical_every_residue(fmt, packed):
    """scatter_prefill_rows under one jit writes rows [0, length) into
    the request's pages bit-identical to the contiguous cache, for every
    length residue mod the page size and for length == S_max; every
    other row of the pool — past `length`, on the unused decode page,
    on pages the request does not own — keeps its old contents, and
    the traced lengths never recompile."""
    fn, rows, poison, pages = _traced_scatter_case(fmt, packed)
    s_max = rows["k_codes"].shape[0]
    ids = jnp.asarray(pages, jnp.int32)
    for length in list(range(1, PS + 1)) + [2 * PS + 3, s_max]:
        got = fn(poison, rows, ids, jnp.int32(length), jnp.int32(0))
        _assert_pool_bits(got, _expected_pool(poison, rows, pages, length, 0))
    assert fn._cache_size() == 1


@pytest.mark.parametrize("fmt,packed", KV_FORMATS, ids=map(_fmt_id, KV_FORMATS))
def test_traced_scatter_start_and_padding_untouched(fmt, packed):
    """scatter_prefill_rows(start > 0) never writes a row before `start`
    (full prefix pages and a CoW page's head rows keep their contents)
    nor a row at or after `length`, and page ids padded past the
    request's own (here a real page, so a stray write would show) are
    never written — while rows [start, length) land bit-identical."""
    fn, rows, poison, pages = _traced_scatter_case(fmt, packed)
    s_max = rows["k_codes"].shape[0]
    own = pages[:3]
    ids = jnp.asarray(own + [6], jnp.int32)            # padded with page 6
    cases = [(PS + 5, 2 * PS + 3),      # mid-page divergence and tail
             (PS, 2 * PS),              # page-aligned start and end
             (2 * PS + 1, s_max),       # last page only, to S_max
             (PS + 2, PS + 2)]          # start == length: nothing written
    for start, length in cases:
        got = fn(poison, rows, ids, jnp.int32(length), jnp.int32(start))
        _assert_pool_bits(got, _expected_pool(poison, rows, own, length,
                                              start))
    assert fn._cache_size() == 1
    with pytest.raises(ValueError, match="page ids"):
        fn(poison, rows, ids[:2], jnp.int32(PS), jnp.int32(0))


# -----------------------------------------------------------------------------
# byte accounting: live tokens, not B x S_max
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("fmt,packed", [("fp8_e4m3", False),
                                        ("fp4_e2m1", True)],
                         ids=["fp8", "fp4_packed"])
def test_paged_bytes_scale_with_live_tokens(fmt, packed):
    n_kv, hd, B, s_max = 2, 64, 8, 256
    live, pages_used = 300, -(-300 // PS)
    nb = KV.paged_kv_cache_nbytes(live, pages_used, PS, n_kv, hd,
                                  fmt=fmt, packed=packed)
    static = KV.kv_cache_nbytes(B, s_max, n_kv, hd, fmt=fmt, packed=packed)
    assert nb["live"] <= nb["paged"]           # page-granularity overhead
    assert nb["paged"] < static["total"]       # << the B x S_max layout
    # live bytes are exactly per-row bytes x live rows
    per_row = KV.kv_cache_nbytes(1, 1, n_kv, hd, fmt=fmt,
                                 packed=packed)["total"]
    assert nb["live"] == per_row * live
