"""Pallas TPU kernel: fused Mamba2 SSD chunk scan.

One grid step processes one (batch, head, chunk) cell: the intra-chunk
quadratic part (three MXU matmuls over (Q,Q)/(Q,P)/(Q,N) tiles) fused with
the inter-chunk state recurrence, whose (P, N) state lives in VMEM scratch
across the chunk axis (TPU grids execute the minor axis sequentially).
This is the TPU-native shape of the SSD algorithm: HBM traffic is one read
of x/dt/B/C and one write of y per token — no (B,S,H,Q) intermediates.

Grid: (B, H, S/Q), chunk innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_out_ref, state,
    *, n_chunks: int, q: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    a = a_ref[0, 0].astype(jnp.float32)  # ()
    bmat = b_ref[0].astype(jnp.float32)  # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)  # (Q, N)

    dta = dt * a  # (Q,) negative
    cum = jnp.cumsum(dta)  # (Q,)
    # intra-chunk decay L[i, j] = exp(cum_i - cum_j) for j <= i
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(jj <= ii, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    w = scores * l_mat
    xdt = x * dt[:, None]  # (Q, P)
    y_intra = jax.lax.dot_general(
        w, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # inter-chunk: y_inter = (C ⊙ exp(cum)) @ state^T   (state: (P, N))
    c_dec = cmat * jnp.exp(cum)[:, None]
    y_inter = jax.lax.dot_general(
        c_dec, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- S * exp(cum_end) + xdt^T @ (B ⊙ decay_to_end)
    decay_end = jnp.exp(cum[-1] - cum)  # (Q,)
    b_dec = bmat * decay_end[:, None]
    local = jax.lax.dot_general(
        xdt, b_dec, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state[...] = state[...] * jnp.exp(cum[-1]) + local

    @pl.when(ci == n_chunks - 1)
    def _flush():
        st_out_ref[0, 0] = state[...].astype(st_out_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    a: jax.Array,  # (H,)
    bmat: jax.Array,  # (B, S, N)
    cmat: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Returns (y: (B,S,H,P), final_state: (B,H,P,N))."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    assert s % q == 0
    n_chunks = s // q
    grid = (b, h, n_chunks)
    a2 = a.reshape(h, 1)

    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, q, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, 1), lambda bi, hi, ci: (hi, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_chunk_scan",
    )(x, dt, a2, bmat, cmat)
    return y, st
