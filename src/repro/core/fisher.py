"""Fisher information on activations (paper Eq. 2) via tap gradients.

The paper computes, per activation channel o:
    Δ_o = 1/(2N) Σ_n ( Σ_d a_{nd} g_{nd} )²
where g = ∂L/∂a and d ranges over the channel's feature positions.

Implementation trick (memory-optimal, exact): multiply each tapped
activation by a ones-valued per-(sample, channel) scale c.  Then
∂L/∂c_{n,o} = Σ_d a_{nd} g_{nd} — precisely Eq. 2's inner sum — so a single
``grad(loss, taps)`` pass yields every u_{n,o} with O(B·C) extra memory
instead of storing full activation gradients (O(B·S·C)).  The direct
(a, g) reduction is also provided as a fused Pallas kernel
(``repro/kernels/fisher.py``) for engines that already materialise both.

The probe runs **once per target task** (Algorithm 1 lines 1-2).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backbones import Backbone


def fisher_probe(
    backbone: Backbone,
    params: Any,
    loss_fn: Callable[..., jax.Array],
    batch: Dict[str, jax.Array],
    n_samples: int,
) -> Tuple[np.ndarray, Dict, float]:
    """Compute per-unit Fisher potentials P and per-channel Δ_o.

    loss_fn(params, batch, taps=...) -> scalar.  Returns
    (potentials aligned with backbone.unit_costs, {(layer, kind): Δ_o},
    wall_seconds) — the wall time is reported in the latency-breakdown
    benchmark (paper Tables 9/10's "Fisher Calculation" column).

    ``n_samples`` is the count of *valid* (non-padded) support samples used
    for Eq. 2's 1/(2N); taps are sized to the padded forward batch.
    """
    batch_pad = next(
        v.shape[0] for v in jax.tree_util.tree_leaves(batch)
    )
    taps = backbone.make_taps(batch_pad)

    t0 = time.perf_counter()
    if backbone.fisher_reduce is not None:
        chans = jax.device_get(probe_fn(backbone, loss_fn)(
            params, batch, taps, jnp.float32(n_samples)))
        potentials = potentials_from_chans(backbone.unit_costs, chans)
    else:
        g = jax.grad(lambda t: loss_fn(params, batch, taps=t))(taps)
        g = jax.tree_util.tree_map(lambda x: np.asarray(x), g)
        potentials, chans = backbone.fisher_from_grads(g, n_samples)
    dt = time.perf_counter() - t0
    return potentials, chans, dt


def probe_fn(backbone: Backbone, loss_fn: Callable[..., jax.Array]):
    """Jitted probe ``pf(params, batch, taps, n) -> {(layer, kind): Δ_o}``:
    tap gradients and the backbone's device-side Eq. 2 reduction in one
    program, so only the O(L·C) scores reach the host (on TPU the LM
    reduction is the Pallas fisher kernel)."""

    def pf(params, batch, taps, n):
        g = jax.grad(lambda t: loss_fn(params, batch, taps=t))(taps)
        return backbone.fisher_reduce(g, n)

    return jax.jit(pf)


def fisher_from_activations(a: jax.Array, g: jax.Array,
                            mask: Optional[jax.Array] = None) -> jax.Array:
    """Direct Eq. 2 from materialised activations/gradients.

    a, g: (N, D, C) -> Δ: (C,).  Routed through the fused Pallas kernel
    (``repro.kernels.ops.fisher``, interpret mode off-TPU); shapes that no
    block size tiles fall back to the jnp oracle.  ``mask`` is an optional
    (N,) validity vector for bucket-padded batches: padded rows contribute
    exactly zero and the normaliser is the valid count.
    """
    from ..kernels import ops

    return ops.fisher_auto(a, g, mask=mask)


def potentials_from_chans(unit_costs, chans: Dict) -> np.ndarray:
    """Per-unit Fisher potential P = Σ_o Δ_o, aligned with ``unit_costs``."""
    return np.array(
        [np.asarray(chans[(c.layer, c.kind)], np.float64).sum()
         for c in unit_costs],
        np.float64,
    )
