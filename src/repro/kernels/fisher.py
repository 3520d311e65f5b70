"""Pallas TPU kernel: fused Fisher-information reduction (paper Eq. 2).

Computes, per channel o:
    Δ_o = 1/(2N) Σ_n ( Σ_d a_{nd,o} · g_{nd,o} )²
from materialised activations/gradients — the compute core of TinyTrain's
online selection step (the 20–35 s "Fisher Calculation" phase of Tables
9/10).  The fusion avoids materialising the (N, C) intermediate ``u`` in
HBM: each grid step streams one (n, d-tile, c-tile) block through VMEM,
accumulates u in a VMEM scratch, and squares/accumulates into the output on
the last d-tile.

Grid: (C/Bc, N, D/Bd) — d innermost so the u-accumulator carries across the
minor axis; TPU grids execute sequentially, so scratch carries are safe.
Default blocks are (512, 256) = 512 KiB/operand f32 — well inside the
~16 MiB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fisher_kernel(a_ref, g_ref, out_ref, u_acc, *, n_d_tiles: int, inv_2n: float):
    ni = pl.program_id(1)
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init_u():
        u_acc[...] = jnp.zeros_like(u_acc)

    a = a_ref[0].astype(jnp.float32)  # (Bd, Bc)
    g = g_ref[0].astype(jnp.float32)
    u_acc[...] += jnp.sum(a * g, axis=0, keepdims=True)  # (1, Bc)

    @pl.when(di == n_d_tiles - 1)
    def _flush():
        u = u_acc[...]

        @pl.when(ni == 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += u * u * inv_2n


def fisher_pallas(
    a: jax.Array,  # (N, D, C)
    g: jax.Array,  # (N, D, C)
    *,
    block_d: int = 512,
    block_c: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Δ_o per channel, fused.  Returns (C,) float32."""
    n, d, c = a.shape
    block_d = min(block_d, d)
    block_c = min(block_c, c)
    assert d % block_d == 0 and c % block_c == 0, (d, c, block_d, block_c)
    n_d_tiles = d // block_d
    grid = (c // block_c, n, n_d_tiles)

    out = pl.pallas_call(
        functools.partial(
            _fisher_kernel, n_d_tiles=n_d_tiles, inv_2n=1.0 / (2.0 * n)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_d, block_c), lambda ci, ni, di: (ni, di, ci)),
            pl.BlockSpec((1, block_d, block_c), lambda ci, ni, di: (ni, di, ci)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda ci, ni, di: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
        name="fisher_channel_scores",
    )(a, g)
    return out[0]
