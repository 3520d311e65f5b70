"""Pallas TPU kernels: int8 error-feedback gradient pack/unpack.

Two tiled kernels: (1) global abs-max reduction, (2) quantise + residual.
Used to shrink TinyTrain's delta-gradient DP all-reduce payload (DESIGN.md
§6); the XLA path in ``repro/optim/compress.py`` is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _absmax_kernel(g_ref, err_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = jnp.max(jnp.abs(g_ref[...].astype(jnp.float32) + err_ref[...]))
    out_ref[0, 0] = jnp.maximum(out_ref[0, 0], m)


def _quant_kernel(g_ref, err_ref, scale_ref, q_ref, new_err_ref):
    g = g_ref[...].astype(jnp.float32) + err_ref[...]
    inv = 1.0 / scale_ref[0, 0]
    qf = jnp.clip(jnp.round(g * inv), -127.0, 127.0)
    q_ref[...] = qf.astype(jnp.int8)
    new_err_ref[...] = g - qf * scale_ref[0, 0]


def grad_quant_pallas(
    g: jax.Array,  # any shape; flattened to (R, 128k) tiles
    err: jax.Array,
    *,
    block: int = 1024,
    interpret: bool = False,
):
    """Returns (q int8, scale f32 scalar, new_err f32), matching ref.py."""
    shape = g.shape
    flat = g.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
        err_f = jnp.pad(err.reshape(-1), (0, pad))
    else:
        err_f = err.reshape(-1)
    rows = flat.shape[0] // block
    g2 = flat.reshape(rows, block)
    e2 = err_f.reshape(rows, block)

    absmax = pl.pallas_call(
        _absmax_kernel,
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
        name="grad_absmax",
    )(g2, e2)
    scale = absmax / 127.0 + 1e-12

    q, new_err = pl.pallas_call(
        _quant_kernel,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block), jnp.int8),
            jax.ShapeDtypeStruct((rows, block), jnp.float32),
        ],
        interpret=interpret,
        name="grad_quant_int8",
    )(g2, e2, scale)

    q = q.reshape(-1)[:n].reshape(shape)
    new_err = new_err.reshape(-1)[:n].reshape(shape)
    return q, scale[0, 0], new_err
