"""The edge-CNN family: the configuration file as the program runs it, and
seeded weights made on the device."""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

import common
from reference import edge_cnn as ref


def build_weights(conf: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """Per-layer ``{"w", "b"}`` in the program's layout, float32, from the
    seed in one jitted call.  Weights ~ N(0, 2/fan_in) before a ReLU6 and
    N(0, 1/fan_in) before a residual add (He), so features still tell
    images apart after 52 layers; biases ~ N(0, 0.02^2) so the bias path
    does work."""
    ls = ref.layers(conf)

    def make(key):
        keys = jax.random.split(key, 2 * len(ls))
        out = []
        for i, l in enumerate(ls):
            shape = ref.weight_shape(l)
            fan_in = shape[0] * shape[1] * shape[2]
            w = jax.random.normal(keys[2 * i], shape, jnp.float32)
            b = jax.random.normal(keys[2 * i + 1], (l["c_out"],),
                                  jnp.float32)
            gain = 2.0 if l["relu"] else 1.0
            out.append({"w": w * math.sqrt(gain / fan_in), "b": 0.02 * b})
        return out

    return jax.jit(make)(common.seed_key(seed))


def program_config(conf: Dict[str, Any]):
    """The program's ``CnnConfig`` built from the file; checked layer by
    layer against the reference's reading of the same file."""
    from repro.models import edge_cnn

    cfg = edge_cnn.build_ir_net(
        conf["name"], [tuple(b) for b in conf["blocks"]], conf["width_mult"],
        conf["stem_channels"], conf["head_channels"], conf["in_res"])
    for spec, l in zip(cfg.layers, ref.layers(conf), strict=True):
        got = (spec.kind, spec.c_in, spec.c_out, spec.k, spec.stride,
               spec.relu, spec.residual_with)
        want = tuple(l[k] for k in ("kind", "c_in", "c_out", "k", "stride",
                                    "relu", "residual_with"))
        if got != want:
            raise ValueError(f"program layer {got} differs from {want}")
    return cfg


def backbone(conf: Dict[str, Any], traffic: Dict[str, Any]):
    from repro.core.backbones import cnn_backbone

    return cnn_backbone(program_config(conf),
                        batch_size=traffic["cost_batch"])
