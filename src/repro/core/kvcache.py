"""Quantized KV-cache layer: format-width storage for decode attention.

Serving cost on long contexts is dominated by streaming the KV cache every
decode step; the paper's format-width I/O contract applies directly — a
cache held at operand width moves 2x/4x (fp16/fp8) or ~8x (packed fp4,
two E2M1 codes per byte via `core.packing`) fewer bytes than the seed f32
cache.  This module owns the storage layout; the *compute* contract (DPA
f32 accumulation for QK^T/PV over the dequantized-in-prologue operands)
lives in `kernels.flash_attention` / `models.decode_attn`.

Contiguous layout — one entry per (batch, position, kv-head) row of
head_dim values:

  k_codes / v_codes : (B, S, KV, hd)  native narrow dtype (fp16/bf16/fp8),
                      or uint8 E2M1 codes for fp4 — (B, S, KV, hd // 2)
                      packed bytes when `packed` (low nibble = even index).
  k_scale / v_scale : (B, S, KV, 1) f32 per-row absmax scales — the
                      software exponent path; dequant = widen(codes) * scale.

Paged layout — the serving-engine variant.  A static (B, S_max) cache is
the software analogue of FPnew-style lane replication: memory sized for
the longest request, replicated per batch slot.  The paged cache removes
it the same way TransDot removes idle mantissa lanes — storage is a pool
of fixed-size pages shared by every live request, and a per-request block
table maps its token timeline onto pages, so cache memory scales with
*live tokens*, not B x S_max:

  k_codes / v_codes : (P, page, KV, wc) page pool (same code dtype/width
                      rules as the contiguous layout)
  k_scale / v_scale : (P, page, KV, 1) f32 per-row scales
  block table       : (B, max_pages) i32, row b listing the pages that
                      hold request b's tokens in timeline order; token t
                      lives at (table[b, t // page], t % page).

Page 0 is a scratch page (see `PageAllocator`): idle batch slots point
their whole table row at it so a fixed-shape decode step can harmlessly
write there, and no live request ever references it.

Both layouts share one quantization recipe — exactly
`core.quantize.quant_rows_grid` over the head_dim axis — so a cache
round-trip is bit-identical to the fake-quant the attention reference
applies to raw K/V, and a paged cache holds bit-identical codes/scales to
the contiguous cache it replaces (paging is pure relayout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .formats import get_format
from .packing import operand_nbytes, pack_fp4, unpack_fp4
from .quantize import decode_fp4, encode_fp4, jnp_dtype, quant_rows_grid

QUANT_KEYS = ("k_codes", "k_scale", "v_codes", "v_scale")


def is_quantized(cache) -> bool:
    """True for the quantized layout ({k,v}_codes/{k,v}_scale pytree)."""
    return isinstance(cache, dict) and "k_codes" in cache


def _codes_dtype(fmt):
    fmt = get_format(fmt)
    return jnp.uint8 if fmt.name == "fp4_e2m1" else jnp_dtype(fmt)


def _codes_width(hd: int, fmt, packed: bool) -> int:
    fmt = get_format(fmt)
    if fmt.name == "fp4_e2m1" and packed:
        if hd % 2:
            raise ValueError(f"packed fp4 KV needs an even head_dim, got {hd}")
        return hd // 2
    return hd


def quantize_kv(x, *, fmt, packed: bool = False):
    """(..., hd) raw K or V -> (codes, scale) in the cache layout.

    Per-row absmax over the trailing head_dim axis; codes are the format's
    storage representation (native dtype, or E2M1 nibbles — packed two per
    byte along hd when `packed`).  Built ON `quant_rows_grid` — not a
    re-implementation — so the cache recipe cannot drift from the one the
    attention kernels/oracles use: re-encoding exact grid values is a
    bit-exact round trip."""
    fmt = get_format(fmt)
    grid, scale = quant_rows_grid(x, fmt)
    if fmt.name == "fp4_e2m1":
        codes = encode_fp4(grid)
        if packed:
            codes = pack_fp4(codes)
    else:
        codes = grid.astype(jnp_dtype(fmt))
    return codes, scale


def dequantize_kv(codes, scale, *, fmt, packed: bool = False):
    """Cache rows -> f32 values: widen(codes) * scale (dequant-in-prologue
    semantics; identical to `quant_rows_grid(x)[0] * scale` of the raw
    tensor, so the cached path reproduces the fake-quant path bit-for-bit)."""
    fmt = get_format(fmt)
    if fmt.name == "fp4_e2m1":
        c = unpack_fp4(codes) if packed else codes
        grid = decode_fp4(c)
    else:
        grid = codes.astype(jnp.float32)
    return grid * scale


def init_kv_cache(batch: int, s_ctx: int, n_kv: int, hd: int, *, fmt,
                  packed: bool = False):
    """Zeroed quantized cache pytree for a full-context decode cache."""
    wc = _codes_width(hd, fmt, packed)
    codes = jnp.zeros((batch, s_ctx, n_kv, wc), _codes_dtype(fmt))
    scale = jnp.zeros((batch, s_ctx, n_kv, 1), jnp.float32)
    return {"k_codes": codes, "k_scale": scale,
            "v_codes": codes, "v_scale": scale}


def update_kv_cache(cache, k_new, v_new, offset, *, fmt,
                    packed: bool = False):
    """Quantize k/v (B, S_new, KV, hd) and write them at `offset` along the
    sequence axis.  Returns the new cache pytree."""
    kc, ks = quantize_kv(k_new, fmt=fmt, packed=packed)
    vc, vs = quantize_kv(v_new, fmt=fmt, packed=packed)
    z = jnp.zeros((), jnp.int32)
    off = jnp.asarray(offset, jnp.int32)
    at = (z, off, z, z)
    return {
        "k_codes": jax.lax.dynamic_update_slice(cache["k_codes"], kc, at),
        "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks, at),
        "v_codes": jax.lax.dynamic_update_slice(cache["v_codes"], vc, at),
        "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs, at),
    }


def dequantize_cache(cache, *, fmt, packed: bool = False):
    """-> (k, v) f32 (B, S, KV, hd) — the prologue widening, as one op."""
    k = dequantize_kv(cache["k_codes"], cache["k_scale"], fmt=fmt,
                      packed=packed)
    v = dequantize_kv(cache["v_codes"], cache["v_scale"], fmt=fmt,
                      packed=packed)
    return k, v


def kv_cache_nbytes(batch: int, s_ctx: int, n_kv: int, hd: int, *, fmt,
                    packed: bool = False) -> dict:
    """Bytes one layer's K+V cache moves through the interface per full
    sweep (codes + f32 scales), vs the seed f32 cache, and the reduction.

    This is the decode-attention bandwidth story: every generated token
    streams the whole cache, so the reduction here is the per-token HBM
    saving (≈8x for packed fp4 at hd=128, ≈7x at hd=64 — the scale row
    amortizes over head_dim)."""
    n_rows = batch * s_ctx * n_kv
    code_b = operand_nbytes(n_rows * hd, fmt, packed=packed)
    total = 2 * (code_b + 4 * n_rows)          # K and V, codes + scales
    f32 = 2 * 4 * n_rows * hd
    return {"total": total, "f32_total": f32,
            "reduction_vs_f32": f32 / total}


# -----------------------------------------------------------------------------
# paged layout: page pool + block table (the continuous-batching cache)
# -----------------------------------------------------------------------------

SCRATCH_PAGE = 0


def is_paged(cache) -> bool:
    """True for the paged layout (page pool + "block_table" pytree)."""
    return isinstance(cache, dict) and "block_table" in cache


def init_paged_kv_cache(n_pages: int, page_size: int, n_kv: int, hd: int,
                        *, fmt, packed: bool = False):
    """Zeroed page pool: {k,v}_codes (P, page, KV, wc) + f32 scales.

    The pool carries no block table — tables are per-request routing state
    owned by the scheduler (`launch.engine`); `make_block_table` builds the
    (B, max_pages) leaf the decode step consumes alongside the pool."""
    wc = _codes_width(hd, fmt, packed)
    codes = jnp.zeros((n_pages, page_size, n_kv, wc), _codes_dtype(fmt))
    scale = jnp.zeros((n_pages, page_size, n_kv, 1), jnp.float32)
    return {"k_codes": codes, "k_scale": scale,
            "v_codes": codes, "v_scale": scale}


def make_block_table(n_slots: int, max_pages: int):
    """All-scratch (B, max_pages) i32 table — every slot starts idle."""
    return jnp.full((n_slots, max_pages), SCRATCH_PAGE, jnp.int32)


def paged_write_tokens(cache, k_new, v_new, positions, *, fmt,
                       packed: bool = False):
    """Quantize a run of S_new tokens per batch slot into its pages.

    k_new/v_new: (B, S_new, KV, hd); positions: (B,) i32 absolute index
    of each request's *first* new token (token i of row b lands at
    timeline position ``positions[b] + i``, i.e. at
    (table[b, p // page], p % page)).  S_new == 1 is the decode step;
    S_new > 1 is the speculative draft/verify window, whose query rows
    quantize independently per row (absmax over head_dim), so a
    multi-token write is bit-identical to S_new single-token writes.
    Idle slots carry an all-scratch table row, so their writes hit the
    scratch page and never touch live data.  Returns the cache pytree
    with updated pools (block_table passes through unchanged)."""
    ps = cache["k_codes"].shape[1]
    table = cache["block_table"]
    s_new = k_new.shape[1]
    pos = jnp.asarray(positions, jnp.int32)[:, None] \
        + jnp.arange(s_new, dtype=jnp.int32)[None]          # (B, S_new)
    page = jnp.take_along_axis(table, pos // ps, axis=1)    # (B, S_new)
    slot = pos % ps
    kc, ks = quantize_kv(k_new, fmt=fmt, packed=packed)
    vc, vs = quantize_kv(v_new, fmt=fmt, packed=packed)
    out = dict(cache)
    for key, new in (("k_codes", kc), ("k_scale", ks),
                     ("v_codes", vc), ("v_scale", vs)):
        out[key] = cache[key].at[page, slot].set(new)
    return out


def paged_write_token(cache, k_new, v_new, positions, *, fmt,
                      packed: bool = False):
    """Quantize one token per batch slot into its page (the decode step;
    see `paged_write_tokens` for the multi-token contract)."""
    return paged_write_tokens(cache, k_new, v_new, positions, fmt=fmt,
                              packed=packed)


def gather_paged_kv(cache):
    """Page pool + block table -> contiguous-layout view.

    Returns a {k,v}_codes/{k,v}_scale pytree shaped (B, max_pages * page,
    KV, ...) — request b's timeline re-materialized in order, exactly the
    contiguous layout `dequantize_cache` (and thus the whole DPA decode
    path) consumes.  This is the jnp gather fallback of the block-table
    read; rows past a request's live length come from whatever pages its
    table names (scratch for idle tail entries) and must be masked by
    position, as `models.decode_attn.dpa_paged_decode_attn` does.  Pure
    relayout: gathered codes/scales are bit-identical to the pool's."""
    table = cache["block_table"]
    B, n_pg = table.shape
    out = {}
    for key in QUANT_KEYS:
        pool = cache[key]                       # (P, page, KV, w)
        ps = pool.shape[1]
        g = pool[table]                         # (B, n_pg, page, KV, w)
        out[key] = g.reshape((B, n_pg * ps) + pool.shape[2:])
    return out


def scatter_prefill_rows(pools, rows, page_ids, length, start):
    """Traced scatter of one request's prefill rows [`start`, `length`)
    into its pages — the relayout `write_prefill_rows` wraps, written to
    run inside a jit with fixed shapes (one trace for every length).

    pools: the QUANT_KEYS pool leaves (P, page, KV, ...); rows: leaves
    (S, KV, ...) (one request, batch dim stripped); page_ids: (n,) i32
    array of the request's distinct pages in timeline order, padded past
    its own to cover all S rows; length, start: i32 scalars.  Row r
    lands at (page_ids[r // page], r % page) when start <= r < length.
    Every other row — before `start` (a shared or copy-on-write prefix,
    whose pages may be read-only) or at or after `length` — is sent to a
    page beyond the pool and dropped, so padded page ids are never
    written.  Pure relayout: the pages receive codes/scales
    bit-identical to the rows.  Donate the pools to the enclosing jit
    and the write is in place.  Returns the updated QUANT_KEYS pools."""
    n_pool, ps = pools["k_codes"].shape[:2]
    s = rows["k_codes"].shape[0]
    if page_ids.shape[0] * ps < s:
        raise ValueError(f"{page_ids.shape[0]} page ids cannot hold {s} "
                         f"rows of {ps}")
    r = jnp.arange(s, dtype=jnp.int32)
    page = r // ps
    live = (r >= start) & (r < length)
    # a dropped row's page lies past the pool, one per timeline page, so
    # the (page, slot) pairs stay unique and the scatter need not order
    pid = jnp.where(live, page_ids[page], n_pool + page)
    slot = r % ps
    return {key: pools[key].at[pid, slot].set(rows[key], mode="drop",
                                              unique_indices=True)
            for key in QUANT_KEYS}


def write_prefill_rows(cache, rows, page_ids, length: int, *,
                       start: int = 0):
    """Scatter a prefill's rows [`start`, `length`) into pages: the host
    wrapper over `scatter_prefill_rows`.

    rows: contiguous-layout pytree with leaves (S, KV, ...) (one request,
    batch dim already stripped); page_ids: host list of the request's
    pages in timeline order; length: host int, number of live rows;
    start: host int, first row to write (rows before it — a shared or
    copy-on-write prefix the engine matched from the prefix cache — are
    already in their pages and MUST NOT be rewritten: pages below the
    start row may be read-only shared pages).  Pure relayout, so the
    pages hold codes/scales bit-identical to the staging cache's.
    Returns the cache with updated pools."""
    ps = cache["k_codes"].shape[1]
    n_need = -(-length // ps) if length else 0
    if n_need > len(page_ids):
        raise ValueError(f"{length} rows need {n_need} pages, "
                         f"got {len(page_ids)}")
    if not 0 <= start <= length:
        raise ValueError(f"start ({start}) outside [0, {length}]")
    ids = np.full(-(-rows["k_codes"].shape[0] // ps), SCRATCH_PAGE, np.int32)
    n = min(len(page_ids), ids.shape[0])
    ids[:n] = page_ids[:n]
    out = dict(cache)
    out.update(scatter_prefill_rows(
        {key: cache[key] for key in QUANT_KEYS}, rows, jnp.asarray(ids),
        jnp.int32(length), jnp.int32(start)))
    return out


def paged_from_contiguous(ref, lengths, *, page_size: int,
                          n_pages: int = None):
    """Relayout a contiguous quantized cache into a fresh paged one.

    ref: contiguous pytree with leaves (B, S, KV, ...); lengths: host
    ints, request b's live rows (its first `lengths[b]` positions of
    `ref` scatter into freshly allocated pages).  Returns the paged
    cache pytree with the block table installed.  Pure relayout — pages
    hold codes/scales bit-identical to `ref` — which makes this the
    standard paged-vs-contiguous fixture for tests and benchmarks."""
    B = ref["k_codes"].shape[0]
    n_need = [max(1, -(-int(n) // page_size)) for n in lengths]
    if n_pages is None:
        n_pages = sum(n_need) + 2
    alloc = PageAllocator(n_pages)
    # empty workloads are legal (an engine draining to idle): the table
    # is a valid all-scratch (B, 1) — never max() of an empty sequence
    table = np.full((B, max(n_need, default=1)), SCRATCH_PAGE, np.int32)
    cache = {key: jnp.zeros((n_pages, page_size) + ref[key].shape[2:],
                            ref[key].dtype) for key in QUANT_KEYS}
    for b, n in enumerate(lengths):
        ids = alloc.alloc(n_need[b])
        table[b, :len(ids)] = ids
        rows = {key: ref[key][b] for key in QUANT_KEYS}
        cache = write_prefill_rows(cache, rows, ids, int(n))
    cache["block_table"] = jnp.asarray(table)
    return cache


def paged_kv_cache_nbytes(live_tokens: int, pages_in_use: int,
                          page_size: int, n_kv: int, hd: int, *, fmt,
                          packed: bool = False) -> dict:
    """Byte accounting for a paged cache vs the static (B, S_max) layouts.

    `live` counts exactly the rows live requests occupy (the engine
    report's honest number); `paged` counts whole pages in use (live
    rounded up by page granularity — the allocator's footprint).  Compare
    against `kv_cache_nbytes(B, S_max, ...)` for the static-batch
    baselines the engine replaces."""
    def row_bytes(n_rows):
        return 2 * (operand_nbytes(n_rows * hd, fmt, packed=packed)
                    + 4 * n_rows)               # K and V, codes + scales
    return {"live": row_bytes(live_tokens * n_kv),
            "paged": row_bytes(pages_in_use * page_size * n_kv)}


class PageAllocator:
    """Free-list page allocator for the paged KV cache.

    Page 0 is reserved as the scratch page idle decode slots write to, so
    `capacity` pages yield `capacity - 1` allocatable ones.  Freed pages
    return to the free list and are reused LIFO (hot pages stay cache-
    warm).  Tracks in-use count and the peak for utilization reporting.

    Reservations (the speculative-decoding commit/rollback protocol):
    a request may `reserve(n)` pages without popping them — reserved
    pages stay on the free list but are excluded from `can_alloc`, so no
    other request can claim them (the engine's no-OOM-mid-decode
    invariant survives lazy committing).  `alloc(n, reserved=True)`
    *commits* pages out of the caller's reservation as its timeline
    grows; `free(pages, to_reserved=True)` rolls committed pages back
    into the reservation (the KV-rollback path: pages holding only
    rejected draft tokens return without becoming grabbable by anyone
    else); `unreserve(n)` releases the unused remainder at finish.
    Invariant: ``reserved <= n_free`` always — every reserved page is
    physically on the free list until committed.

    Reference counts (the prefix-sharing protocol): `alloc` hands a page
    out with refcount 1; `incref` adds holders (a prefix-cache entry, a
    request matching a cached prefix).  `free` is a *decref* — the page
    only returns to the free list when its last holder releases it, so a
    shared page can never be freed or re-handed-out while any request's
    block table still points at it.  Shared pages (refcount > 1) are
    read-only by convention: a diverging request must copy-on-write into
    a private page (the engine's `_cow_copy`).  Rollback
    (`to_reserved=True`) refuses shared pages outright — only a page the
    caller exclusively owns can fold back into its reservation."""

    def __init__(self, capacity: int):
        if capacity < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.capacity = capacity
        self._free = list(range(capacity - 1, 0, -1))   # pop() -> page 1 first
        self._used = set()
        self._refs = {}                                 # page -> holder count
        self.reserved = 0
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._used)

    @property
    def n_available(self) -> int:
        """Free pages not spoken for by a reservation."""
        return self.n_free - self.reserved

    def can_alloc(self, n: int) -> bool:
        return n <= self.n_available

    def reserve(self, n: int) -> None:
        """Earmark `n` free pages without popping them off the free list."""
        if n > self.n_available:
            raise MemoryError(f"reserve({n}): only {self.n_available} "
                              "pages available")
        self.reserved += n

    def unreserve(self, n: int) -> None:
        """Release `n` reserved-but-uncommitted pages back to the pool."""
        if n > self.reserved:
            raise ValueError(f"unreserve({n}) exceeds reserved "
                             f"({self.reserved})")
        self.reserved -= n

    def alloc(self, n: int, *, reserved: bool = False) -> list:
        """Pop `n` pages off the free list (raises if short — callers gate
        admission on `can_alloc`, so running out mid-flight is a bug).
        With `reserved`, the pages commit out of the caller's reservation
        (which must cover them)."""
        if reserved:
            if n > self.reserved:
                raise ValueError(f"alloc({n}, reserved=True) exceeds "
                                 f"reserved ({self.reserved})")
            self.reserved -= n
        elif not self.can_alloc(n):
            raise MemoryError(f"alloc({n}): only {self.n_available} pages "
                              "available")
        pages = [self._free.pop() for _ in range(n)]
        self._used.update(pages)
        for p in pages:
            self._refs[p] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pages

    def incref(self, pages) -> None:
        """Add one holder to each in-use page (prefix sharing: a cache
        entry or a prefix-hit request pointing its table at the page).
        Referencing a page nobody holds is a bug, not a no-op."""
        for p in pages:
            if p not in self._used:
                raise ValueError(f"incref of page {p} that is not in use")
            self._refs[p] += 1

    def refcount(self, page) -> int:
        """Current holder count (0 for free pages and the scratch page)."""
        return self._refs.get(page, 0)

    def is_shared(self, page) -> bool:
        """True when more than one holder references the page (read-only
        by the copy-on-write convention)."""
        return self.refcount(page) > 1

    def free(self, pages, *, to_reserved: bool = False) -> None:
        """Drop one holder per page (decref); a page returns to the free
        list only when its last holder releases it.  With `to_reserved`,
        the page folds back into the caller's reservation (rollback) —
        refused for shared pages, which the caller does not own alone."""
        for p in pages:
            if p == SCRATCH_PAGE:
                raise ValueError("page 0 is the reserved scratch page")
            if p not in self._used:
                raise ValueError(f"double free of page {p}")
            if to_reserved and self._refs[p] > 1:
                raise ValueError(
                    f"page {p} is shared ({self._refs[p]} holders); a "
                    "rollback may only reclaim exclusively-owned pages")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._used.remove(p)
                self._free.append(p)
        if to_reserved:
            self.reserved += len(pages)

    def utilization(self) -> float:
        """Fraction of allocatable pages currently in use."""
        return self.in_use / (self.capacity - 1)
