"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the kernels execute (and are
validated) on CPU; on TPU backends the compiled Mosaic path is used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .fisher import fisher_pallas
from .flash_attention import flash_attention_paged_pallas, flash_attention_pallas
from .grad_quant import grad_quant_pallas
from .ssd_scan import ssd_scan_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_d", "block_c", "interpret"))
def fisher(a, g, *, mask=None, block_d: int = 512, block_c: int = 256,
           interpret=None):
    """Fused Eq. 2 reduction; ``mask`` is an optional (N,) validity vector.

    With a mask, padded rows are zeroed before the kernel and the
    normaliser is rescaled from the padded batch to the valid count
    (mask-weighted normalisation) — the result matches the unpadded
    oracle exactly, so bucket-padded probes score like unpadded ones.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if mask is not None:
        m = mask.astype(jnp.float32)
        a = a * m[:, None, None].astype(a.dtype)
        out = fisher_pallas(a, g, block_d=block_d, block_c=block_c,
                            interpret=interpret)
        # kernel bakes 1/(2·N_pad); rescale to 1/(2·n_valid)
        return out * (a.shape[0] / jnp.maximum(jnp.sum(m), 1.0))
    return fisher_pallas(a, g, block_d=block_d, block_c=block_c,
                         interpret=interpret)


def _divisor_block(dim: int, pref: int) -> int:
    """Largest block <= pref that tiles ``dim`` exactly (0 if none)."""
    if dim <= pref:
        return dim
    b = pref
    while b >= 8:
        if dim % b == 0:
            return b
        b //= 2
    return 0


def fisher_auto(a, g, *, mask=None, block_d: int = 512, block_c: int = 256):
    """Fisher reduction with automatic kernel/oracle dispatch.

    Routes (N, D, C) activation/gradient pairs through the fused Pallas
    kernel whenever block sizes tiling (D, C) exist — interpret mode
    off-TPU — and falls back to the jnp oracle for non-tileable shapes.
    On the compiled Mosaic path the blocks must additionally be
    lane-aligned (sublane multiple of 8, lane multiple of 128); unaligned
    shapes use the oracle rather than failing at lowering time.  This is
    the production entry point for the materialised-(a, g) probe;
    ``fisher`` stays the explicit-block escape hatch.

    ``mask`` is an optional (N,) per-row validity vector for bucket-padded
    batches: masked rows contribute zero and the 1/(2N) normaliser uses
    the valid count, so scores match the unpadded oracle.
    """
    if a.ndim != 3 or a.shape != g.shape:
        raise ValueError(f"expected matching (N, D, C) operands, got "
                         f"{a.shape} vs {g.shape}")
    _, d, c = a.shape
    bd, bc = _divisor_block(d, block_d), _divisor_block(c, block_c)
    if not bd or not bc:
        return _fisher_oracle(a, g, mask)
    if not _default_interpret() and (bd % 8 or bc % 128):
        return _fisher_oracle(a, g, mask)
    return fisher(a, g, mask=mask, block_d=bd, block_c=bc)


@jax.jit
def _fisher_oracle(a, g, mask=None):
    from .ref import fisher_ref

    if mask is None:
        return fisher_ref(a, g)
    # same zero-rows-then-rescale route as the kernel path: one reference
    # implementation of the Eq. 2 math
    m = mask.astype(jnp.float32)
    return fisher_ref(a * m[:, None, None].astype(a.dtype), g) * (
        a.shape[0] / jnp.maximum(jnp.sum(m), 1.0))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=0, block_q=256,
                    block_k=512, q_offset=None, kv_len=None, interpret=None):
    """Flash attention; ``q_offset``/``kv_len`` are optional per-sample
    (B,) vectors for cached block prefill: sample i's queries sit at
    absolute positions ``q_offset[i] + j`` against cache rows, and rows at
    or beyond ``kv_len[i]`` are stale and masked (see
    ``flash_attention_pallas``)."""
    interpret = _default_interpret() if interpret is None else interpret
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        q_offset=q_offset, kv_len=kv_len, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "interpret"),
)
def paged_flash_attention(q, k_pages, v_pages, page_table, *, q_offset,
                          kv_len, causal=True, block_q=256, interpret=None):
    """Flash attention over a paged KV cache: the kv-block axis walks the
    per-slot ``page_table`` (scalar-prefetched into SMEM), streaming pages
    straight from the flat ``(n_pages, page_size, Hkv, D)`` arena — no
    gather materialises the logical view (see
    ``flash_attention_paged_pallas``)."""
    interpret = _default_interpret() if interpret is None else interpret
    return flash_attention_paged_pallas(
        q, k_pages, v_pages, page_table,
        q_offset=q_offset, kv_len=kv_len,
        causal=causal, block_q=block_q, interpret=interpret,
    )


def fisher_tapgrads(g, n, mask=None, *, block_c: int = 256):
    """Eq. 2 channel scores from *tap gradients* via the fused kernel.

    The probe's tap gradient ``g[l, b, c]`` already equals Eq. 2's inner
    sum ``u_{b,(l,c)}``, so the per-channel score is ``Δ = Σ_b u² / (2n)``.
    This routes that reduction through the Pallas fisher kernel by viewing
    the stacked layers as one channel axis — a ``(B, 1, L·C)`` problem with
    a ones-valued activation operand.  The channel axis is zero-padded to
    a lane-aligned block (a multiple of 128), so every width takes the
    kernel; padded channels score zero and are sliced off.  ``mask`` is an
    optional (B,) validity vector (bucket-padded episodes); ``n`` the
    valid-sample normaliser.

    g: (L, B, C) -> (L, C) float32.
    """
    l, b, c = g.shape
    lc = l * c
    flat = jnp.moveaxis(g, 0, 1).reshape(b, 1, lc)
    bc = min(block_c, -(-lc // 128) * 128)
    flat = jnp.pad(flat, ((0, 0), (0, 0), (0, -lc % bc)))
    out = fisher(jnp.ones_like(flat), flat, mask=mask, block_d=1,
                 block_c=bc)[:lc]
    # the kernel normalises by the (masked) batch count; rescale to 1/(2n)
    valid = jnp.float32(b) if mask is None else jnp.sum(
        mask.astype(jnp.float32))
    return (out * (valid / n)).reshape(l, c)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bmat, cmat, *, chunk=256, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return ssd_scan_pallas(x, dt, a, bmat, cmat, chunk=chunk,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def grad_quant(g, err, *, block=1024, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return grad_quant_pallas(g, err, block=block, interpret=interpret)
