"""Pallas TPU kernel: flash attention (online softmax), GQA/causal/SWA.

Block-tiled attention for the 32k prefill shapes: q/k/v stream through VMEM
in (Bq, D)/(Bk, D) tiles; softmax statistics (m, l) and the output
accumulator live in VMEM scratch across the kv-block axis (TPU grids are
sequential over the minor axis).  Causal and sliding-window blocks that are
fully masked are skipped with ``pl.when`` — the static-skip that halves
causal FLOPs vs a masked dense computation.

Grid: (B, Hq, Sq/Bq, Sk/Bk).  GQA: the kv block index maps query head
h -> kv head h // (Hq/Hkv) in the BlockSpec index map (no HBM repeat).

Layout: every block is one head's ``(rows, D)`` tile of a head-major
``(B, H, S, D)`` operand, so the last two block dimensions are (rows, D)
as Mosaic's tiling requires.  The public wrappers take the model's
``(B, S, H, D)`` and transpose q/k/v; the paged kernel reads the page
arena in place, because ``serving/paging.py`` stores pages head-major
``(n_pages, Hkv, page_size, D)``.

Two entry modes share the kernel body:

- aligned prefill (``q_offset=None``): queries and keys index the same
  sequence; the causal/SWA block skip is static.
- **cached block prefill** (``q_offset``/``kv_len`` given): per-batch
  ``(B,)`` scalars in SMEM place each sample's query block at its own
  offset into a KV cache and bound the valid cache rows — the serving
  engine's multi-token prompt ingestion, where every slot sits at a
  different cache cursor.  The block skip becomes a per-sample predicate
  (kv blocks beyond ``kv_len`` or entirely in the causal future of the
  block are skipped at run time).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc, m_acc, l_acc,
    *, scale: float, n_kv_blocks: int, bq: int, bk: int,
    causal: bool, window: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q_start = qi * bq
    k_start = ki * bk
    relevant = True
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window > 0:
        relevant = jnp.logical_and(relevant, k_start + bk - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_acc[...], jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_acc[...] - m_new)
        l_acc[...] = l_acc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_acc[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _out():
        o_ref[0, 0] = (
            acc[...] / jnp.maximum(l_acc[...], 1e-30)
        ).astype(o_ref.dtype)


def _flash_cached_kernel(
    qo_ref, kl_ref, q_ref, k_ref, v_ref, o_ref, acc, m_acc, l_acc,
    *, scale: float, n_kv_blocks: int, bq: int, bk: int,
    causal: bool, window: int,
):
    """Cached-block variant: per-sample q offset / kv length from SMEM.

    Queries sit at absolute positions ``qo + qi*bq + i`` against cache
    rows (absolute positions ``ki*bk + j``); rows at or beyond ``kl`` are
    stale and masked.  KV blocks entirely beyond the query block's last
    position, the kv length, or the sliding window are skipped whole —
    the run-time analogue of the static causal skip.
    """
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_off = qo_ref[bi]
    kv_len = kl_ref[bi]

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q_start = q_off + qi * bq
    k_start = ki * bk
    relevant = k_start < kv_len
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)
    if window > 0:
        relevant = jnp.logical_and(relevant, k_start + bk - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_acc[...], jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_acc[...] - m_new)
        l_acc[...] = l_acc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_acc[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _out():
        o_ref[0, 0] = (
            acc[...] / jnp.maximum(l_acc[...], 1e-30)
        ).astype(o_ref.dtype)


def _flash_paged_kernel(
    pt_ref, qo_ref, kl_ref, q_ref, k_ref, v_ref, o_ref, acc, m_acc, l_acc,
    *, scale: float, n_kv_blocks: int, bq: int, ps: int, causal: bool,
):
    """Paged variant: the kv-block axis walks the per-slot page table.

    Scalar-prefetched SMEM rows (page table, q offset, kv length) steer the
    kv BlockSpec: kv block ``ki`` of sample ``bi`` streams physical page
    ``page_table[bi, ki]`` from the flat arena — no gather materialises the
    logical view.  Unmapped entries (−1) clamp to page 0 in the index map
    and are skipped whole by the run-time predicate, as are blocks beyond
    the kv length or entirely in the causal future.
    """
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_off = qo_ref[bi]
    kv_len = kl_ref[bi]
    page = pt_ref[bi, ki]

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q_start = q_off + qi * bq
    k_start = ki * ps  # logical position of the page's first row
    relevant = jnp.logical_and(k_start < kv_len, page >= 0)
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, ps)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, ps), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, ps), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_acc[...], jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_acc[...] - m_new)
        l_acc[...] = l_acc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_acc[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _out():
        o_ref[0, 0] = (
            acc[...] / jnp.maximum(l_acc[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_paged_pallas(
    q: jax.Array,        # (B, Sq, Hq, D)
    k_pages: jax.Array,  # (n_pages, Hkv, page_size, D) head-major arena
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32; -1 = unmapped
    *,
    q_offset: jax.Array,    # (B,) int32 cache rows before this block
    kv_len: jax.Array,      # (B,) int32 valid rows incl. this block
    causal: bool = True,
    block_q: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over a paged KV cache (``serving/paging.py``).

    The kv block size **is** the page size: grid axis 3 runs over page-table
    columns and the scalar-prefetched table routes each block to its
    physical page, so the kernel reads the arena in place.
    """
    b, sq, hq, d = q.shape
    n_pages, hkv, ps, _ = k_pages.shape
    group = hq // hkv
    mp = page_table.shape[1]
    bq = min(block_q, sq)
    assert sq % bq == 0
    grid = (b, hq, sq // bq, mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, h, qi, ki, pt, qo, kl: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, h, qi, ki, pt, qo, kl:
                         (jnp.maximum(pt[bi, ki], 0), h // group, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, h, qi, ki, pt, qo, kl:
                         (jnp.maximum(pt[bi, ki], 0), h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, h, qi, ki, pt, qo, kl:
                               (bi, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_paged_kernel,
            scale=1.0 / math.sqrt(d),
            n_kv_blocks=mp,
            bq=bq, ps=ps, causal=causal,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
        name="paged_flash_attention",
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32),
      kv_len.astype(jnp.int32), _heads_major(q), k_pages, v_pages)
    return _heads_major(out)


def _heads_major(x: jax.Array) -> jax.Array:
    """(B, S, H, D) <-> (B, H, S, D)."""
    return jnp.swapaxes(x, 1, 2)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 256,
    block_k: int = 512,
    q_offset: jax.Array = None,  # (B,) int32 per-sample query offsets
    kv_len: jax.Array = None,    # (B,) int32 valid cache rows per sample
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    grid = (b, hq, sq // bq, sk // bk)
    qo_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bi, h, qi, ki: (bi, h // group, ki, 0))
    common = dict(
        grid=grid,
        out_specs=qo_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    qkv = tuple(_heads_major(x) for x in (q, k, v))

    if q_offset is None and kv_len is None:
        out = pl.pallas_call(
            functools.partial(
                _flash_kernel,
                scale=1.0 / math.sqrt(d),
                n_kv_blocks=sk // bk,
                bq=bq, bk=bk, causal=causal, window=window,
            ),
            in_specs=[qo_spec, kv_spec, kv_spec],
            name="flash_attention",
            **common,
        )(*qkv)
        return _heads_major(out)

    # cached block-prefill mode: per-sample offsets/lengths ride in SMEM
    q_offset = (jnp.zeros((b,), jnp.int32) if q_offset is None
                else q_offset.astype(jnp.int32))
    kv_len = (jnp.full((b,), sk, jnp.int32) if kv_len is None
              else kv_len.astype(jnp.int32))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(
            _flash_cached_kernel,
            scale=1.0 / math.sqrt(d),
            n_kv_blocks=sk // bk,
            bq=bq, bk=bk, causal=causal, window=window,
        ),
        in_specs=[smem, smem, qo_spec, kv_spec, kv_spec],
        name="cached_flash_attention",
        **common,
    )(q_offset, kv_len, *qkv)
    return _heads_major(out)
