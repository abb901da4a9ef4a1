"""Paged KV slot memory: break the ``[n_slots, max_seq]`` rectangle.

The dense cache (models/transformer.py ``init_cache``) preallocates
``[L, n_slots, K, max_seq, hd]`` — every resident row pays ``max_seq`` HBM
whether it holds 200 tokens or 100k, and slot count (hence concurrency) is
pinned by the worst case. This module replaces the rectangle with a
page-granular layout behind the ``kv_pages=1`` engine knob:

  - **page pool** ``[L, n_pages+1, K, page_size, hd]`` — physical page 0 is a
    reserved all-zeros *sink*: unreserved page-table entries point at it, so
    a read of a row's unwritten tail gathers zeros that every attention
    length mask already excludes. Gated/dead writes are routed to the
    out-of-bounds index ``n_pages+1`` with scatter ``mode="drop"`` so the
    sink stays zero forever.
  - **page table** ``[L, n_slots, max_pages]`` int32 — per-row physical page
    chains, broadcast over the leading layer axis so the table scans with
    the pool through the transformer's ``lax.scan`` (every cache leaf needs
    leading L). The table is *host-authored*: device programs treat it as a
    read-only input and pass it through unchanged; only admission/restore/
    release rewrite it (one tiny ``device_put`` per admission, never per
    token).
  - the int8 (``kv_quant``) representation stores the pool as
    ``(int8 [L,P,K,ps,hd], f32 scale [L,P,K,ps])`` — the same per-token
    symmetric quantization as the dense cache, at page granularity.

Reads materialize a dense per-layer window (``page_read``: gather the
``ceil(hist/ps)`` pages per row, reshape, slice to ``hist``), so decode
attention — including the native-int8 dot and the Pallas flash-decode
kernel — runs UNCHANGED on the gathered window; bytes streamed per step are
the same page-rounded ``hist`` window the dense path reads. What changes is
*capacity*: rows allocate pages only as they grow, so thousands of short
streams share a chip that the rectangle would cap at ``n_slots``.

Prefix reuse becomes page **aliasing** with copy-on-write: a tier-0 hit
installs page *references* (host-side refcount bump + table rewrite, zero
KV bytes moved); only a partially-filled boundary page is eagerly copied
on device (``paged_copy_page``, one program) before the new row appends
into it. :class:`PageAllocator` is the host-side bookkeeper — refcounts,
free list, per-row chains, and an LRU of retained (released-but-reusable)
chains.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax import lax
from jax.tree_util import register_pytree_node_class

from quorum_tpu.ops.attention import quantize_rows


@register_pytree_node_class
class PagedKV:
    """One side (K or V) of a paged KV cache: ``(pool, table)``.

    ``pool`` is ``[L, P, K, ps, hd]`` (or the ``(int8, f32 scale)`` tuple),
    ``table`` is ``[L, S, max_pages]`` int32; stacked-members engines carry
    a leading ``M`` on both. Registered as a pytree so the pair rides
    ``lax.scan`` carries (per-layer unstacking rebuilds a per-layer
    ``PagedKV``), member ``vmap``, jit donation, and ``jax.tree.map``
    transparently — exactly like the dense cache's ``(q8, scale)`` tuple.
    """

    __slots__ = ("pool", "table")

    def __init__(self, pool, table):
        self.pool = pool
        self.table = table

    def tree_flatten(self):
        return (self.pool, self.table), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def is_q8(self) -> bool:
        return isinstance(self.pool, tuple)

    @property
    def page_size(self) -> int:
        return (self.pool[0] if self.is_q8 else self.pool).shape[-2]

    def __repr__(self):  # pragma: no cover - debugging aid
        v = self.pool[0] if self.is_q8 else self.pool
        return (f"PagedKV(pool={v.shape}{' q8' if self.is_q8 else ''}, "
                f"table={getattr(self.table, 'shape', None)})")


def kv_is_paged(cache) -> bool:
    """True when a cache side is the paged ``(pool, table)`` representation."""
    return isinstance(cache, PagedKV)


def validate_page_config(max_seq: int, page_size: int) -> None:
    """Reject page sizes the layout cannot represent: the table maps every
    position p to page ``p // page_size``, so ``page_size`` must be a
    power of two (offsets are cheap masks, and every engine bucket unit —
    prefill chunks, history buckets — is pow2) and divide ``max_seq``."""
    if page_size < 1 or (page_size & (page_size - 1)) != 0:
        raise ValueError(
            f"kv_page_size={page_size} must be a power of two (page offsets "
            "must align with the engine's pow2 chunk/history buckets)")
    if max_seq % page_size != 0:
        raise ValueError(
            f"kv_page_size={page_size} must divide max_seq={max_seq} "
            "(the page table maps every position to exactly one page)")


def init_paged_cache(spec, batch: int, n_pages: int, page_size: int,
                     dtype=None, kv_quant: str | None = None,
                     members: int | None = None):
    """Zero page pool + sink-pointing tables: ``(PagedKV_k, PagedKV_v)``.

    ``n_pages`` counts *allocatable* pages; the pool's physical axis is
    ``n_pages + 1`` with index 0 the reserved zero sink. K and V get
    separate table arrays with identical content (sharing one buffer would
    double-donate it through the jitted decode programs)."""
    validate_page_config(spec.max_seq, page_size)
    dt = jnp.dtype(dtype or spec.dtype)
    mp = spec.max_seq // page_size
    lead = (() if members is None else (members,)) + (spec.n_layers,)
    pool_shape = lead + (n_pages + 1, spec.n_kv_heads, page_size,
                         spec.head_dim)

    def side():
        if kv_quant == "int8":
            pool = (jnp.zeros(pool_shape, jnp.int8),
                    jnp.zeros(pool_shape[:-1], jnp.float32))
        else:
            pool = jnp.zeros(pool_shape, dt)
        return PagedKV(pool, jnp.zeros(lead + (batch, mp), jnp.int32))

    return side(), side()


# ---- pure device helpers ----------------------------------------------------
#
# All take a PER-LAYER PagedKV (pool [P, K, ps, hd], table [S, max_pages]) —
# the shape the transformer's scan body sees — except the wire-chunk ops at
# the bottom, which take the full stack. Writes never touch the table.


def _pool_parts(pool):
    return pool if isinstance(pool, tuple) else (pool, None)


def _quantize(x):
    q8, s = quantize_rows(x, axis=-1)
    return q8, s[..., 0]


def _dequant(q8, scale, dtype):
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _assemble(g, hist: int):
    """[S, hp, K, ps(, hd)] gathered pages → dense [S, K, hist(, hd)]."""
    if g.ndim == 5:
        s, hp, k, ps, hd = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(s, k, hp * ps, hd)[:, :, :hist]
    s, hp, k, ps = g.shape
    return g.transpose(0, 2, 1, 3).reshape(s, k, hp * ps)[:, :, :hist]


def page_read(pkv: PagedKV, hist: int):
    """Materialize every row's first ``hist`` positions as a dense window.

    Returns ``[S, K, hist, hd]`` (or the ``(q8, scale)`` pair of dense
    windows for int8 pools — decode keeps contracting natively in int8).
    ``hist`` is static (the engine's pow2 history bucket); the gather reads
    ``ceil(hist/ps)`` pages per row, so bytes match the dense path's
    bounded read up to page rounding. Unreserved table entries gather the
    zero sink — masked by every attention length mask."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    hp = min(-(-hist // ps), pkv.table.shape[-1])
    phys = pkv.table[:, :hp]                              # [S, hp]
    if scales is not None:
        return _assemble(vals[phys], hist), _assemble(scales[phys], hist)
    return _assemble(vals[phys], hist)


def page_read_row(pkv: PagedKV, slot, hist: int, dtype):
    """One row's ``[1, K, hist, hd]`` history window (chunked-prefill read);
    int8 pools dequantize the bounded window (cold path, same as dense)."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    hp = min(-(-hist // ps), mp)
    row_tab = lax.dynamic_slice(pkv.table, (slot, 0), (1, mp))[0]
    phys = row_tab[:hp]                                   # [hp]

    def asm(p):
        g = jnp.moveaxis(p[phys], 0, 1)                   # [K, hp, ps(, hd)]
        g = g.reshape((g.shape[0], hp * ps) + g.shape[3:])
        return g[:, :hist][None]

    if scales is not None:
        return _dequant(asm(vals), asm(scales), dtype)
    return asm(vals)


def page_write_step(pkv: PagedKV, value, lengths, allow, max_seq: int):
    """Decode-step write: ``value [S, K, 1, hd]`` at each row's ``lengths``.

    Masked-out rows (and positions past ``max_seq``) route to the
    out-of-bounds index with ``mode="drop"`` — the paged equivalent of the
    dense path's write-old-value-back, with the same no-op semantics."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    drop = vals.shape[0]
    page_idx = jnp.clip(lengths // ps, 0, mp - 1)
    phys = jnp.take_along_axis(pkv.table, page_idx[:, None], axis=1)[:, 0]
    phys = jnp.where(allow & (lengths < max_seq), phys, drop)
    off = lengths % ps

    def scat(p, new):  # new [S, K(, hd)] → scatter dims move to the front
        return p.at[phys, :, off].set(new, mode="drop")

    if scales is not None:
        q8, s = _quantize(value)
        pool = (scat(vals, q8[:, :, 0, :]),
                scat(scales, s[:, :, 0].astype(scales.dtype)))
    else:
        pool = scat(vals, value[:, :, 0, :].astype(vals.dtype))
    return PagedKV(pool, pkv.table)


def page_write_seg(pkv: PagedKV, value, slot, offset, write_gate,
                   max_seq: int):
    """Chunked-prefill segment write: ``value [1, K, T, hd]`` at absolute
    positions ``offset..offset+T`` of row ``slot``."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    drop = vals.shape[0]
    t = value.shape[2]
    pos = offset + jnp.arange(t)
    row_tab = lax.dynamic_slice(pkv.table, (slot, 0), (1, mp))[0]
    phys = row_tab[jnp.clip(pos // ps, 0, mp - 1)]
    ok = pos < max_seq
    if write_gate is not None:
        ok = ok & write_gate
    phys = jnp.where(ok, phys, drop)
    off = pos % ps

    def scat(p, new):  # new [T, K(, hd)]
        return p.at[phys, :, off].set(new, mode="drop")

    if scales is not None:
        q8, s = _quantize(value)
        pool = (scat(vals, q8[0].transpose(1, 0, 2)),
                scat(scales, s[0].transpose(1, 0).astype(scales.dtype)))
    else:
        pool = scat(vals, value[0].transpose(1, 0, 2).astype(vals.dtype))
    return PagedKV(pool, pkv.table)


def page_write_prefill(pkv: PagedKV, value, cache_row, write_gate,
                       max_seq: int):
    """Whole-prompt write: ``value [B, K, T, hd]`` at positions ``0..T`` of
    rows ``cache_row..cache_row+B-1`` (B = 1 in slot-mode admission)."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    drop = vals.shape[0]
    b, _, t, _ = value.shape
    pos = jnp.arange(t)
    row_tabs = lax.dynamic_slice(pkv.table, (cache_row, 0), (b, mp))
    phys = row_tabs[:, jnp.clip(pos // ps, 0, mp - 1)]    # [B, T]
    ok = jnp.broadcast_to(pos < max_seq, (b, t))
    if write_gate is not None:
        ok = ok & write_gate
    phys = jnp.where(ok, phys, drop)
    off = jnp.broadcast_to(pos % ps, (b, t))

    def scat(p, new):  # new [B, T, K(, hd)]
        return p.at[phys, :, off].set(new, mode="drop")

    if scales is not None:
        q8, s = _quantize(value)
        pool = (scat(vals, q8.transpose(0, 2, 1, 3)),
                scat(scales, s.transpose(0, 2, 1).astype(scales.dtype)))
    else:
        pool = scat(vals, value.transpose(0, 2, 1, 3).astype(vals.dtype))
    return PagedKV(pool, pkv.table)


# ---- wire-chunk ops (full stack) -------------------------------------------
#
# kv_transfer's wire format is layout-free: [L, K, n, hd] values (scale leaf
# [L, K, n]), flat row = member * n_slots + slot for stacked engines. These
# two ops are the paged arms of slice_rows/write_rows — prefix-store export,
# snapshot/restore, and disagg/zero-drain handoff all ride them unchanged.


def _split_row(row, stacked: bool, n_slots):
    if stacked:
        return row // n_slots, row % n_slots
    return None, row


def paged_slice_rows(pkv: PagedKV, row, start, n: int, *,
                     stacked: bool = False, n_slots: int | None = None):
    """Gather positions ``[start, start+n)`` of flat row ``row`` into the
    dense wire chunk ``[L, K, n, hd]`` (+ ``[L, K, n]`` scale for q8).

    ``n`` is static; the gather covers a static ``ceil(n/ps)+1`` page
    window starting at the traced page ``start // ps`` (the +1 absorbs the
    start offset within the first page), then slices the exact ``n``."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    ncov = min(-(-n // ps) + 1, mp)
    member, slot = _split_row(row, stacked, n_slots)
    table0 = pkv.table[0, 0] if stacked else pkv.table[0]  # [S, mp]
    row_tab = lax.dynamic_slice(table0, (slot, 0), (1, mp))[0]
    row_tab = jnp.concatenate(
        [row_tab, jnp.zeros((ncov,), row_tab.dtype)])      # sink-padded tail
    p0 = start // ps
    pages = lax.dynamic_slice(row_tab, (p0,), (ncov,))     # [ncov]

    def gath(p):
        if stacked:
            p = lax.dynamic_index_in_dim(p, member, 0, keepdims=False)
        g = p[:, pages]                                    # [L, ncov, K, ps(, hd)]
        if g.ndim == 5:
            ell, nc, k, ps_, hd = g.shape
            g = g.transpose(0, 2, 1, 3, 4).reshape(ell, k, nc * ps_, hd)
        else:
            ell, nc, k, ps_ = g.shape
            g = g.transpose(0, 2, 1, 3).reshape(ell, k, nc * ps_)
        return lax.dynamic_slice_in_dim(g, start - p0 * ps, n, axis=2)

    if scales is not None:
        return gath(vals), gath(scales)
    return gath(vals)


def paged_write_rows(pkv: PagedKV, chunk, row, start, *,
                     stacked: bool = False, n_slots: int | None = None):
    """Scatter a dense wire chunk ``[L, K, n, hd]`` (+ scale) into positions
    ``[start, start+n)`` of flat row ``row`` — the paged arm of restore /
    handoff installs. Pages must already be reserved in the row's table
    (admission pre-reserves the full span); positions past ``max_seq``
    drop."""
    vals, scales = _pool_parts(pkv.pool)
    ps = vals.shape[-2]
    mp = pkv.table.shape[-1]
    drop = vals.shape[-4]                                  # the P axis size
    cvals, cscales = chunk if isinstance(chunk, tuple) else (chunk, None)
    n = cvals.shape[2]
    member, slot = _split_row(row, stacked, n_slots)
    table0 = pkv.table[0, 0] if stacked else pkv.table[0]
    row_tab = lax.dynamic_slice(table0, (slot, 0), (1, mp))[0]
    pos = start + jnp.arange(n)
    phys = row_tab[jnp.clip(pos // ps, 0, mp - 1)]
    phys = jnp.where(pos < mp * ps, phys, drop)
    off = pos % ps

    if stacked:
        def scat(p, new):  # p [M, L, P, K, ps(, hd)], new [n, L, K(, hd)]
            return p.at[member, :, phys, :, off].set(new, mode="drop")
    else:
        def scat(p, new):  # p [L, P, K, ps(, hd)]
            return p.at[:, phys, :, off].set(new, mode="drop")

    if scales is not None:
        pool = (scat(vals, cvals.transpose(2, 0, 1, 3).astype(vals.dtype)),
                scat(scales, cscales.transpose(2, 0, 1).astype(scales.dtype)))
    else:
        pool = scat(vals, cvals.transpose(2, 0, 1, 3).astype(vals.dtype))
    return PagedKV(pool, pkv.table)


def paged_copy_page(pkv: PagedKV, dst, src, *, stacked: bool = False):
    """Copy physical page ``src`` → ``dst`` across all layers (and members):
    the copy-on-write program behind prefix aliasing. One tiny on-device
    copy per partially-filled boundary page; full pages alias by reference
    and never run this."""
    ax = 2 if stacked else 1
    ix = (slice(None),) * ax

    def cp(p):
        return p.at[ix + (dst,)].set(p[ix + (src,)])

    return PagedKV(jax.tree.map(cp, pkv.pool), pkv.table)


# ---- host-side bookkeeping --------------------------------------------------


class PageAllocator:
    """Refcounted page bookkeeping — the host half of the paged layout.

    The device never sees this object; the engine consults it at admission
    (reserve a row's full page span up front — the table never changes
    mid-decode, so pool exhaustion can shed at admission but can never OOM
    a running stream), at release (retain the row's chain for prefix
    reuse, LRU-ordered), and on tier-0 hits (alias full pages by refcount,
    copy-on-write the partial boundary page). Page ids are ``1..n_pages``;
    physical page 0 is the zero sink and is never handed out.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1:
            raise ValueError(f"kv_pool_pages={n_pages} must be >= 1")
        validate_page_config(max(page_size, n_pages * page_size), page_size)
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.refs = [0] * (self.n_pages + 1)
        # pop() hands out low ids first — keeps tiny tests deterministic
        self._free = list(range(self.n_pages, 0, -1))
        self.chains: dict[int, list[int]] = {}
        self.retained: "OrderedDict[int, list[int]]" = OrderedDict()

    # -- capacity ------------------------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        """Pages covering ``n_tokens`` positions."""
        return max(0, -(-int(n_tokens) // self.page_size))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        return self.n_pages - len(self._free)

    # -- refcounting ---------------------------------------------------------

    def _incref(self, pages):
        for p in pages:
            self.refs[p] += 1

    def _decref(self, pages):
        for p in pages:
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
            elif self.refs[p] < 0:  # pragma: no cover - invariant guard
                raise AssertionError(f"page {p} refcount underflow")

    def is_shared(self, page: int) -> bool:
        return self.refs[page] > 1

    # -- allocation / chains -------------------------------------------------

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` fresh pages (ref 1 each), or None if the free list is
        short — the caller reclaims retained chains and retries, or sheds."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._incref(pages)
        return pages

    def free(self, pages: list[int]) -> None:
        """Drop one reference from each of ``pages`` (freeing those that hit
        zero) — the public decref for a caller unwinding a partially built
        chain (e.g. a COW boundary page replaced before assignment)."""
        self._decref(pages)

    def assign(self, row: int, pages: list[int]) -> None:
        """Install ``pages`` as live row ``row``'s chain (refs already held)."""
        if row in self.chains:  # pragma: no cover - invariant guard
            raise AssertionError(f"row {row} already has a live chain")
        self.chains[row] = list(pages)

    def extend(self, row: int, pages: list[int]) -> None:
        """Append ``pages`` (refs already held) to live row ``row``'s chain —
        a co-tenant on a stacked engine growing the slot group's shared
        span. Appending never disturbs existing entries, so in-flight
        programs reading the old table stay correct."""
        self.chains[row].extend(pages)

    def chain(self, row: int) -> list[int] | None:
        return self.chains.get(row)

    def release(self, row: int, keep_tokens: int = 0) -> None:
        """Row finished: retain the pages covering ``keep_tokens`` as a
        reusable chain (MRU end of the LRU), free the tail. ``keep_tokens=0``
        frees everything."""
        chain = self.chains.pop(row, None)
        if chain is None:
            return
        keep = min(self.pages_for(keep_tokens), len(chain))
        if chain[keep:]:
            self._decref(chain[keep:])
        old = self.retained.pop(row, None)
        if old is not None:
            self._decref(old)
        if keep:
            self.retained[row] = chain[:keep]

    def adopt(self, row: int) -> list[int] | None:
        """Same-slot tier-0 reuse: take the row's retained chain back
        (refs transfer to the live chain — no copy, no refcount change)."""
        return self.retained.pop(row, None)

    def retained_chain(self, row: int) -> list[int] | None:
        return self.retained.get(row)

    def retained_tokens_capacity(self, row: int) -> int:
        chain = self.retained.get(row)
        return 0 if chain is None else len(chain) * self.page_size

    def touch(self, row: int) -> None:
        """LRU refresh: a row whose retained chain just served as a donor
        is hot — keep it away from the eviction end."""
        if row in self.retained:
            self.retained.move_to_end(row)

    def share(self, pages: list[int]) -> list[int]:
        """Alias ``pages`` into another chain by reference (refcount bump)."""
        self._incref(pages)
        return list(pages)

    def drop_retained(self, row: int) -> bool:
        chain = self.retained.pop(row, None)
        if chain is None:
            return False
        self._decref(chain)
        return True

    def reclaimable_pages(self, protect=()) -> int:
        """Pages that would return to the free list if every retained chain
        outside ``protect`` were evicted. Only sole-reference pages count —
        evicting a retained entry whose pages are still aliased by a live
        chain frees nothing — and no page appears in two retained chains,
        so the sum is exact."""
        n = 0
        for row, chain in self.retained.items():
            if row in protect:
                continue
            n += sum(1 for p in chain if self.refs[p] == 1)
        return n

    def evict_lru(self, protect=()) -> int | None:
        """Free the least-recently-retained chain not in ``protect``;
        returns the evicted row (or None when nothing is evictable).
        Pages still aliased by live chains stay allocated — only their
        retained reference drops."""
        for row in list(self.retained):
            if row in protect:
                continue
            self._decref(self.retained.pop(row))
            return row
        return None

    def reset(self) -> None:
        """Forget everything (engine cache reset / containment zero)."""
        self.refs = [0] * (self.n_pages + 1)
        self._free = list(range(self.n_pages, 0, -1))
        self.chains.clear()
        self.retained.clear()
