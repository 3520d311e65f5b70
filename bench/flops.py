"""Operations and bytes that the algorithm needs, from shapes alone.

These count required work, whatever implements it: padding rows, per-slot
weight copies and recomputation count for nothing.  A multiply-add is two
operations.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping

from reference import edge_cnn as cnn_ref


# ---------------------------------------------------------------------------
# edge CNN
# ---------------------------------------------------------------------------


def cnn_layer_macs(conf: Mapping[str, Any]) -> List[int]:
    """Multiply-adds of each conv layer for one image at ``in_res``
    ('SAME' padding: a stride-2 layer maps r to ceil(r / 2))."""
    res = conf["in_res"]
    out = []
    for l in cnn_ref.layers(conf):
        if l["stride"] == 2:
            res = (res + 1) // 2
        cin = 1 if l["kind"] == "dw" else l["c_in"]
        out.append(l["k"] * l["k"] * cin * l["c_out"] * res * res)
    return out


def cnn_adapt_flops(conf: Mapping[str, Any], rows: int, iters: int,
                    units: Mapping[int, int]) -> float:
    """Algorithm 1 on one task of ``rows`` real support rows (and as many
    pseudo-query rows): the probe (forward, and input gradients down to the
    first layer's output) and ``iters`` sparse steps (forward, input
    gradients down to the lowest selected layer, weight gradients of the
    selected channels).  ``units``: {layer: selected channels}."""
    macs = cnn_layer_macs(conf)
    ls = cnn_ref.layers(conf)
    images = 2 * rows
    fwd = sum(macs)
    probe = fwd + sum(macs[1:])
    if units:
        lo = min(units)
        dx = sum(macs[lo + 1:])
        dw = sum(macs[i] * k / ls[i]["c_out"] for i, k in units.items())
    else:
        dx = dw = 0
    step = fwd + dx + dw
    return 2.0 * images * (probe + iters * step)
