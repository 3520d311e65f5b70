"""Paged, optionally int8-quantised KV cache: the page-allocator subsystem
behind ``ServeEngine``'s continuous batching.

The fixed-stripe cache gives every slot a ``max_len`` stripe, so one long
request pins memory that many short ones could use.  This module splits KV
storage into fixed-size **pages** in a flat device arena and hands them out
from a device-resident free-list, vLLM-style:

- :class:`PagingSpec` — the static geometry: page size (tokens), pool
  capacity (pages per layer) and the per-slot page-table width.
- :class:`PagePool` — the allocator state: a ``(slots, max_pages)`` int32
  page table (−1 = unmapped) and an ``(n_pages,)`` bool free mask.
  :func:`reserve` / :func:`release` are pure fixed-shape array programs in
  the ``PendingBuffer`` cumsum-ranked idiom, so the serving ``scan_ticks``
  loop allocates at admission and frees at eviction **on device** — the
  one-host-sync-per-chunk contract survives paging.
- **Page stores** — per-layer arenas of ``n_pages`` pages, the row axis
  second-to-last (K/V pages are head-major ``(n_pages, Hkv, page_size,
  D)``; see :func:`store_init`).  With ``int8=True`` rows are packed to
  int8 on write with a per-row (per-token) scale and unpacked on read;
  the quantisation core is the rowwise vectorisation of
  :func:`repro.optim.compress._quant_one` (absmax/127 + ε), shared via
  :func:`repro.optim.compress.rowwise_quant`.
  Per-row scales (rather than one scale per page) keep incremental
  single-token writes exact: a page never needs requantising when a new
  row's absmax exceeds the old page maximum.

Two reservation disciplines share the allocator.  Under
``reserve='worstcase'`` a request pins ``ceil(max_len / page_size)`` pages
at admission and releases them at eviction — allocation is a single
fixed-shape :func:`reserve` per tick, no mid-stream growth.  Under the
default ``reserve='asyougo'`` admission reserves only the pages the
*prompt* needs and a generating stream grows page-by-page in-scan via
:func:`extend` when its position crosses a page boundary; on pool
exhaustion the engine preempts a victim stream (youngest first),
:func:`release`-ing its pages and requeueing it for a recompute swap —
vLLM-style packing at the cost of a mid-stream out-of-pages path.

Reads materialise the logical contiguous ``(B, cap, *feat)`` view by
gathering pages through the table (the jnp fallback); on TPU the Pallas
flash kernel walks the page table directly from SMEM
(:func:`repro.kernels.ops.paged_flash_attention`) with no gather.

Besides the growable KV rows, the pool also backs **pinned runs**: a
read-only per-request page run (encoder outputs for whisper/paligemma
serving) reserved in full at admission via :func:`reserve_run` into a
caller-owned run table and held unchanged — never extended, never
quantised — until :func:`release_run` frees it at eviction/preemption.
Runs draw from the *same* free-list as KV reservations, so one ledger
(``pages_in_use``) accounts for both and the admission predicate can
price a request as ``kv_pages + run_pages``.

This module must stay import-light: ``models/`` imports it lazily at call
time, so it must never import ``repro.models`` or ``repro.serving.engine``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..optim import compress

PAGE_TABLE_KEY = "page_table"


@dataclasses.dataclass(frozen=True)
class PagingSpec:
    """Static paged-cache geometry (baked into compiled programs).

    ``n_pages`` is the pool capacity *per layer arena*: every paged layer
    owns an arena of ``n_pages`` pages, but all layers share one page
    table and one free-list because a slot holds the same number of
    tokens in every layer.
    """

    page_size: int  # tokens per page
    n_pages: int    # pool capacity (pages per layer arena)
    max_pages: int  # per-slot page-table width = ceil(max_len / page_size)
    int8: bool = False

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {self.n_pages}")
        if self.max_pages < 1:
            raise ValueError(f"max_pages must be >= 1, got {self.max_pages}")

    @property
    def cap(self) -> int:
        """Logical per-slot capacity of the gathered view, in tokens."""
        return self.max_pages * self.page_size

    @classmethod
    def build(cls, max_len: int, *, page_size: int, slots: int,
              n_pages: Optional[int] = None, int8: bool = False,
              ) -> "PagingSpec":
        """Geometry for an engine: table width covers ``max_len``; the
        default budget (``n_pages=None``) matches the fixed-stripe
        capacity ``slots * max_pages`` — same memory, paged semantics.
        Pass a smaller budget to oversubscribe slots against memory."""
        max_pages = -(-int(max_len) // int(page_size))
        if n_pages is None:
            n_pages = slots * max_pages
        return cls(int(page_size), int(n_pages), int(max_pages), bool(int8))

    def pages_for(self, kv_budget):
        """Worst-case page count for a request's total KV budget.

        Works on python ints and traced int arrays alike."""
        return (kv_budget + self.page_size - 1) // self.page_size


class PagePool(NamedTuple):
    """Device-resident page-allocator state.

    ``table[s, j]`` is the physical page backing logical rows
    ``[j*page_size, (j+1)*page_size)`` of slot ``s``; −1 = unmapped.
    ``free[p]`` marks page ``p`` allocatable.
    """

    table: jax.Array  # (slots, max_pages) int32; -1 = unmapped
    free: jax.Array   # (n_pages,) bool


def make_pool(spec: PagingSpec, slots: int) -> PagePool:
    return PagePool(
        table=jnp.full((slots, spec.max_pages), -1, jnp.int32),
        free=jnp.ones((spec.n_pages,), bool),
    )


def free_page_count(pool: PagePool) -> jax.Array:
    return jnp.sum(pool.free.astype(jnp.int32))


def pages_in_use(pool: PagePool) -> jax.Array:
    return pool.free.shape[0] - free_page_count(pool)


def _handout(free: jax.Array, need: jax.Array, mask: jax.Array,
             held: jax.Array, width: int):
    """Cumsum-rank free-page handout for a ``(slots, width)`` table.

    Free pages get ranks 0..F−1 in page order and slot ``s`` with
    exclusive-prefix demand ``offs[s]`` receives the pages ranked
    ``offs[s] .. offs[s]+need[s]`` into table entries
    ``held[s] .. held[s]+need[s]-1`` (the ``PendingBuffer`` admission
    idiom).  Returns ``(want, page, taken)``: the entry mask, the page id
    per entry, and the free-list bits consumed.  Shared core of
    :func:`reserve`, :func:`extend` and :func:`reserve_run`.
    """
    n_pages = free.shape[0]
    need = jnp.where(mask, need, 0).astype(jnp.int32)
    held = held.astype(jnp.int32)
    offs = jnp.cumsum(need) - need  # exclusive prefix per slot
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    want = mask[:, None] & (j >= held[:, None]) & (
        j < (held + need)[:, None])                     # (slots, width)
    target_rank = offs[:, None] + (j - held[:, None])    # rank per entry
    # invert rank -> page id: free pages are ranked in page order
    rank = jnp.cumsum(free.astype(jnp.int32)) - 1        # (n_pages,)
    rank_to_page = jnp.full((n_pages,), -1, jnp.int32).at[
        jnp.where(free, rank, n_pages)
    ].set(jnp.arange(n_pages, dtype=jnp.int32), mode="drop")
    page = rank_to_page[jnp.clip(target_rank, 0, n_pages - 1)]
    taken = jnp.zeros((n_pages,), bool).at[
        jnp.where(want, page, n_pages)
    ].set(True, mode="drop")
    return want, page, taken


def _free_rows(free: jax.Array, table: jax.Array, mask: jax.Array):
    """Return masked slots' mapped pages to ``free`` and the invalidated
    (−1) table.  Shared core of :func:`release` and :func:`release_run`."""
    n_pages = free.shape[0]
    owned = mask[:, None] & (table >= 0)
    freed = jnp.zeros((n_pages,), bool).at[
        jnp.where(owned, table, n_pages)
    ].set(True, mode="drop")
    return free | freed, jnp.where(mask[:, None], -1, table)


def reserve(pool: PagePool, need: jax.Array, mask: jax.Array) -> PagePool:
    """Allocate ``need[s]`` pages to each masked slot, in slot order.

    The free-list is drained by cumsum rank (:func:`_handout`).  Masked
    slots overwrite their whole table row (tail entries −1), so reserve
    doubles as the row reset at admission.

    Contract: the caller guarantees the masked demand fits
    (``sum(need * mask) <= free_page_count``) — both the fused admission
    predicate and the eager admission loop check before reserving.
    Fixed-shape and traceable inside ``lax.scan``/``while_loop``.
    """
    mp = pool.table.shape[1]
    held = jnp.zeros(mask.shape, jnp.int32)
    want, page, taken = _handout(pool.free, need, mask, held, mp)
    new_rows = jnp.where(want, page, -1)
    table = jnp.where(mask[:, None], new_rows, pool.table)
    return PagePool(table, pool.free & ~taken)


def extend(pool: PagePool, need: jax.Array, mask: jax.Array,
           held: jax.Array) -> PagePool:
    """Append ``need[s]`` pages to each masked slot, preserving its rows.

    The reserve-as-you-go growth primitive: where :func:`reserve`
    overwrites a slot's whole table row (admission-time reset), ``extend``
    fills only entries ``held[s] .. held[s]+need[s]-1`` — the pages a
    running stream acquires when its cursor crosses a page boundary —
    and leaves the already-mapped prefix untouched.

    Contract: the caller guarantees the masked demand fits the free-list
    and ``held + need <= max_pages`` (positions never exceed the
    per-request budget, which :meth:`PagingSpec.build` sizes the table
    for).  Fixed-shape and traceable inside ``lax.while_loop``.
    """
    mp = pool.table.shape[1]
    want, page, taken = _handout(pool.free, need, mask, held, mp)
    table = jnp.where(want, page, pool.table)
    return PagePool(table, pool.free & ~taken)


def release(pool: PagePool, mask: jax.Array) -> PagePool:
    """Return all pages of masked slots to the free-list and invalidate
    their page-table rows (−1), so a stale table copy can never route a
    write into a page that has been handed to another slot."""
    free, table = _free_rows(pool.free, pool.table, mask)
    return PagePool(table, free)


# ---------------------------------------------------------------------------
# Pinned runs: read-only per-request page runs (encoder outputs)
# ---------------------------------------------------------------------------


def reserve_run(pool: PagePool, run_table: jax.Array, need: jax.Array,
                mask: jax.Array) -> Tuple[PagePool, jax.Array]:
    """Reserve a pinned page run for each masked slot from the shared
    free-list, into the caller-owned ``run_table`` ``(slots, run_pages)``.

    A run is reserved in full at admission (``need[s]`` pages, typically
    the constant ``ceil(enc_tokens / page_size)``), never extended, and
    held until :func:`release_run` — the encoder-output lifecycle.
    Masked slots overwrite their whole run row (tail entries −1).  The
    KV ``pool.table`` is untouched; only the free-list advances, so KV
    reservations and runs share one ledger.

    Contract: the caller's admission predicate prices the run together
    with the KV demand (``sum((kv_need + run_need) * mask) <= free``).
    Fixed-shape and traceable inside ``lax.while_loop``.
    """
    width = run_table.shape[1]
    held = jnp.zeros(mask.shape, jnp.int32)
    want, page, taken = _handout(pool.free, need, mask, held, width)
    new_rows = jnp.where(want, page, -1)
    table = jnp.where(mask[:, None], new_rows, run_table)
    return PagePool(pool.table, pool.free & ~taken), table


def release_run(pool: PagePool, run_table: jax.Array, mask: jax.Array,
                ) -> Tuple[PagePool, jax.Array]:
    """Return masked slots' pinned-run pages to the shared free-list and
    invalidate their run-table rows (−1).  The KV table is untouched —
    callers release KV rows and runs independently (a preempted stream
    drops both; a worst-case KV reservation without an encoder keeps
    ``run_table`` all-(−1) and this is a no-op)."""
    free, table = _free_rows(pool.free, run_table, mask)
    return PagePool(pool.table, free), table


# ---------------------------------------------------------------------------
# Page stores: per-layer arenas with pack-on-write / unpack-on-read
# ---------------------------------------------------------------------------


def store_init(spec: PagingSpec, feat_shape: Tuple[int, ...], dtype,
               ) -> Dict[str, jax.Array]:
    """One paged arena plus, for int8 stores, the per-row dequantisation
    ``scale (n_pages, page_size)``.

    The row axis sits second-to-last: ``pages (n_pages, *feat[:-1],
    page_size, feat[-1])``.  For K/V that is head-major ``(n_pages, Hkv,
    page_size, D)``, so one head of one page is a ``(page_size, D)`` tile —
    the block the paged Pallas kernel reads in place.  One-axis features
    (MLA latents, encoder runs) keep ``(n_pages, page_size, F)``.
    """
    feat = tuple(feat_shape)
    shape = (spec.n_pages,) + feat[:-1] + (spec.page_size, feat[-1])
    if spec.int8:
        return {
            "pages": jnp.zeros(shape, jnp.int8),
            "scale": jnp.zeros((spec.n_pages, spec.page_size), jnp.float32),
        }
    return {"pages": jnp.zeros(shape, dtype)}


def spec_from(cache: Dict[str, Any]) -> PagingSpec:
    """Recover the static geometry from a paged layer cache's shapes."""
    for key in ("k", "ckv"):
        store = cache.get(key)
        if isinstance(store, dict) and "pages" in store:
            pages = store["pages"]
            return PagingSpec(
                page_size=pages.shape[-2], n_pages=pages.shape[0],
                max_pages=cache[PAGE_TABLE_KEY].shape[-1],
                int8=pages.dtype == jnp.int8)
    raise ValueError("not a paged cache: no 'k'/'ckv' page store found")


def write_rows(store: Dict[str, jax.Array], table: jax.Array,
               spec: PagingSpec, lens: jax.Array, vals: jax.Array,
               valid: jax.Array) -> Dict[str, jax.Array]:
    """Scatter ``vals[b, j]`` at logical row ``lens[b] + j`` of slot ``b``
    through the page table.  ``valid`` (B, S) masks ragged tails and
    paused slots; rows routed through unmapped (−1) table entries or past
    the logical capacity are **dropped** (``mode='drop'``) rather than
    clipped, so an inactive slot can never corrupt a page that has been
    re-allocated to a neighbour.  Int8 stores pack each row with its own
    absmax scale on the way in.
    """
    b, s = vals.shape[:2]
    ps = spec.page_size
    logical = lens[:, None] + jnp.arange(s, dtype=lens.dtype)[None, :]
    pidx = jnp.clip(logical // ps, 0, spec.max_pages - 1)
    page = jnp.take_along_axis(table, pidx, axis=1)  # (B, S)
    ok = valid & (page >= 0) & (logical >= 0) & (logical < spec.cap)
    page = jnp.where(ok, page, spec.n_pages).reshape(-1)  # OOB -> dropped
    slot = (logical % ps).reshape(-1)
    pages = store["pages"]
    # (page, :, ..., slot, :): the row axis is second-to-last
    # (store_init); the advanced indices select one (*feat) row each
    at = (page,) + (slice(None),) * (pages.ndim - 3) + (slot, slice(None))
    if spec.int8:
        q, scale = compress.rowwise_quant(vals, vals.ndim - 2)
        return {
            "pages": pages.at[at].set(
                q.reshape((b * s,) + q.shape[2:]), mode="drop"),
            "scale": store["scale"].at[page, slot].set(
                scale.reshape(-1), mode="drop"),
        }
    return {"pages": pages.at[at].set(
        vals.astype(pages.dtype).reshape((b * s,) + vals.shape[2:]),
        mode="drop")}


def read_rows(store: Dict[str, jax.Array], table: jax.Array,
              spec: PagingSpec, dtype) -> jax.Array:
    """Gather the logical contiguous ``(B, cap, *feat)`` view of each
    slot's pages (the jnp page-walk; the Pallas kernel is the no-gather
    TPU route).  Rows behind unmapped entries alias page 0 and must be
    masked downstream by ``kv_len`` — exactly the stale-row contract the
    contiguous cache already relies on.  Int8 stores unpack with their
    per-row scales."""
    page = jnp.clip(table, 0, spec.n_pages - 1)      # (B, max_pages)
    # (B, mp, *feat[:-1], ps, feat[-1]) -> (B, mp, ps, *feat)
    view = jnp.moveaxis(store["pages"][page], -2, 2)
    if spec.int8:
        view = compress.rowwise_dequant(view, store["scale"][page], dtype)
    else:
        view = view.astype(dtype)
    b = table.shape[0]
    return view.reshape((b, spec.cap) + view.shape[3:])


def set_page_table(caches: Any, table: jax.Array) -> Any:
    """Alias the pool's page table into every paged layer cache.

    Layer caches each carry a (stacked) copy of the table so the cache
    pytree stays self-contained through ``forward_hidden``'s per-layer
    scan; this re-points those copies after reserve/release.  Leaves are
    broadcast views of one array — no materialised per-layer copies.
    """
    from ..utils import named_tree_map

    def fix(path, x):
        if path.split("/")[-1] != PAGE_TABLE_KEY:
            return x
        if x.ndim == table.ndim + 1:  # layer-stacked (L, slots, max_pages)
            return jnp.broadcast_to(table[None], x.shape)
        return table

    return named_tree_map(fix, caches)


def cache_bytes(caches: Any) -> Tuple[int, int]:
    """(total cache bytes, bytes in page arenas + scales) for a cache tree."""
    total = paged = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = leaf.size * leaf.dtype.itemsize
        total += n
        if keys and keys[-1] in ("pages", "scale"):
            paged += n
    return total, paged
