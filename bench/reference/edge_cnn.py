"""Plain float32 reference of the inverted-residual edge CNN (MobileNetV2)
and of TinyTrain's online stage on it (paper Algorithm 1): the Fisher
probe (Eq. 2), the ProtoNet cosine loss (Eq. 1) and the sparse fine-tune
with Adam.

It imports nothing of the program.  Architecture from the configuration
file's block table; layer order and the residual rule follow MobileNetV2's
inverted-residual block (expand 1x1, depthwise kxk, project 1x1; a residual
where stride is 1 and the width is kept).

Sparse deltas are held as full-size tensors whose unselected output
channels stay zero: the gradient is masked to the selected channels, so
Adam never moves the others, and ``W + ΔW`` is what the program's thin
delta convolution adds before the activation.  One compiled program then
serves every policy.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _width(ch: float, mult: float, div: int = 8) -> int:
    return max(div, int(ch * mult + div / 2) // div * div)


def layers(conf: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Flat conv layer list from the configuration's block table."""
    w = conf["width_mult"]
    c_prev = _width(conf["stem_channels"], w)
    out = [dict(kind="conv", c_in=3, c_out=c_prev, k=3, stride=2, relu=True,
                residual_with=-1)]
    for t, c, n, s, k in conf["blocks"]:
        c_out = _width(c, w)
        for i in range(n):
            stride = s if i == 0 else 1
            c_mid = c_prev * t
            start = len(out)
            res = start if (stride == 1 and c_prev == c_out) else -1
            if t != 1:
                out.append(dict(kind="conv", c_in=c_prev, c_out=c_mid, k=1,
                                stride=1, relu=True, residual_with=-1))
            out.append(dict(kind="dw", c_in=c_mid, c_out=c_mid, k=k,
                            stride=stride, relu=True, residual_with=-1))
            out.append(dict(kind="conv", c_in=c_mid, c_out=c_out, k=1,
                            stride=1, relu=False, residual_with=res))
            c_prev = c_out
    if conf["head_channels"]:
        out.append(dict(kind="conv", c_in=c_prev,
                        c_out=_width(conf["head_channels"], w), k=1, stride=1,
                        relu=True, residual_with=-1))
    return out


def weight_shape(layer: Dict[str, Any]) -> Tuple[int, ...]:
    cin = 1 if layer["kind"] == "dw" else layer["c_in"]
    return (layer["k"], layer["k"], cin, layer["c_out"])


def _conv(x, w, layer):
    pad = (layer["k"] - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, (layer["stride"], layer["stride"]), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=layer["c_in"] if layer["kind"] == "dw" else 1)


def fp8(x, axes):
    """Float8 (e4m3) values of ``x``, scaled by its largest magnitude over
    ``axes`` (the control's operands); the gradient passes straight
    through, as in float8 training."""
    s = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0)
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + jax.lax.stop_gradient(q - x)


def features(ls, params, images, deltas=None, taps=None, quant=False):
    """Mean-pooled features (N, C).  ``deltas``: per-layer full-size
    tensors added to the weights; ``taps``: per-layer (N, C) scales on
    each layer's activated output (the Fisher probe's taps); ``quant``:
    each convolution's input (per tensor) and weight (per output channel)
    rounded to float8."""
    x = images
    saved = {}
    for i, (layer, p) in enumerate(zip(ls, params)):
        saved[i] = x
        w = p["w"] if deltas is None else p["w"] + deltas[i]
        if quant:
            y = _conv(fp8(x, (0, 1, 2, 3)), fp8(w, (0, 1, 2)), layer) + p["b"]
        else:
            y = _conv(x, w, layer) + p["b"]
        if layer["relu"]:
            y = jnp.clip(y, 0.0, 6.0)
        if taps is not None:
            y = y * taps[i][:, None, None, :]
        if layer["residual_with"] >= 0:
            y = y + saved[layer["residual_with"]]
        x = y
    return jnp.mean(x, axis=(1, 2))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def proto_loss(fs, ys, fq, yq, max_way: int, temperature: float):
    """Cross-entropy of query rows against cosine support prototypes;
    rows labelled -1 are padding and count nowhere."""
    onehot = jax.nn.one_hot(ys, max_way, dtype=jnp.float32)
    counts = jnp.sum(onehot, axis=0)
    protos = (onehot.T @ fs.astype(jnp.float32)) / jnp.maximum(
        counts[:, None], 1.0)
    sim = _unit(fq.astype(jnp.float32)) @ _unit(protos).T
    logits = jnp.where((counts > 0)[None, :], temperature * sim, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(yq, 0)[:, None], 1)[:, 0]
    mask = (yq >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_task_reference(conf, iters: int, lr: float, max_way: int,
                        temperature: float, dtype=jnp.float32,
                        quant: bool = False):
    """``f(params, sx, sy, qx, qy, masks, n_support)`` for one task ->
    (fisher per layer (C,), losses (iters,), the norm of each layer's
    first gradient, deltas after the last step).

    ``masks``: per-layer (C,) 1.0 on the selected output channels.  The
    probe's taps scale the same row index in the support and the
    pseudo-query pass, as one tap array threads through both.  Computed
    in ``dtype``, with float8 convolution operands where ``quant`` (the
    controls), Adam in float32.
    """
    ls = layers(conf)

    def loss_fn(params, deltas, taps, sx, sy, qx, qy):
        fs = features(ls, params, sx, deltas, taps, quant)
        fq = features(ls, params, qx, deltas, taps, quant)
        return proto_loss(fs, sy, fq, qy, max_way, temperature)

    def f(params, sx, sy, qx, qy, masks, n_support):
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        sx, qx = sx.astype(dtype), qx.astype(dtype)
        n = sx.shape[0]
        taps = [jnp.ones((n, l["c_out"]), dtype) for l in ls]
        g = jax.grad(loss_fn, argnums=2)(params, None, taps, sx, sy, qx, qy)
        valid = (sy >= 0).astype(jnp.float32)[:, None]
        fisher = [jnp.sum(jnp.square(t.astype(jnp.float32)) * valid, axis=0)
                  / (2.0 * n_support) for t in g]

        zeros = [jnp.zeros(weight_shape(l), jnp.float32) for l in ls]

        def step(carry, t):
            d, m, v = carry
            loss, gr = jax.value_and_grad(
                lambda dd: loss_fn(params, [x.astype(dtype) for x in dd],
                                   None, sx, sy, qx, qy))(d)
            gr = [gi.astype(jnp.float32) * mk for gi, mk in zip(gr, masks)]
            m = [0.9 * a + 0.1 * b for a, b in zip(m, gr)]
            v = [0.999 * a + 0.001 * b * b for a, b in zip(v, gr)]
            c1 = 1.0 - 0.9 ** t
            c2 = 1.0 - 0.999 ** t
            d = [x - lr * (a / c1) / (jnp.sqrt(b / c2) + 1e-8)
                 for x, a, b in zip(d, m, v)]
            norms = [jnp.sqrt(jnp.sum(gi * gi)) for gi in gr]
            return (d, m, v), (loss.astype(jnp.float32), norms)

        ts = jnp.arange(1, iters + 1, dtype=jnp.float32)
        (d, _, _), (losses, norms) = jax.lax.scan(
            step, (zeros, zeros, zeros), ts)
        first = [n[0] for n in norms]
        return fisher, losses, first, d

    return f


def channel_masks(conf, units: Dict[int, np.ndarray]) -> List[np.ndarray]:
    """Per-layer (C,) float masks from {layer: selected channels}."""
    out = []
    for i, l in enumerate(layers(conf)):
        m = np.zeros((l["c_out"],), np.float32)
        if i in units:
            m[np.asarray(units[i])] = 1.0
        out.append(m)
    return out
