"""Algorithm 1: the TinyTrain online stage, end to end.

Given a meta-trained backbone, a target task's support set and the device
budgets: (1) one gradient probe on the support set; (2) Fisher potential per
unit; (3) multi-objective scores; (4) budgeted layer selection + top-K
channel selection; (5) sparse fine-tuning of the selected deltas.

The online stage is device-resident: the probe reduces Eq. 2 on the
accelerator and ships only per-channel scores, and the fine-tune loop runs
as one ``lax.scan`` dispatch that transfers the whole loss trajectory once
at the end — a fused ``adapt_task`` performs exactly two blocking host
transfers (probe scores + final losses).  ``fused=False`` keeps the eager
one-dispatch-per-iteration loop as a debugging escape hatch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..optim import Optimizer
from .backbones import Backbone
from .criterion import Budget
from .fisher import fisher_probe, potentials_from_chans
from .policy import SparseUpdatePolicy
from .protonet import episode_accuracy, episode_loss
from .selection import select_policy
from .sparse import make_episode_sparse_scan, make_episode_sparse_step


# Blocking host-transfer telemetry.  Every device->host fetch on the adapt
# path goes through _fetch()/_fetch_scalar(), so tests and benchmarks can
# assert the fused path's two-transfer contract instead of trusting it.  The
# count is the process recorder's ``host_syncs`` counter.


def host_sync_count() -> int:
    """Blocking device->host transfer events since the last reset."""
    return telemetry.counter("host_syncs")


def reset_host_sync_count() -> None:
    telemetry.reset_counter("host_syncs")


def _fetch(tree: Any) -> Any:
    """Materialise a pytree on the host: one blocking transfer event.
    Waits for the whole tree, then copies each leaf."""
    tree = jax.block_until_ready(tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    telemetry.count("host_syncs")
    telemetry.count("arrays_fetched", len(leaves))
    return jax.tree_util.tree_unflatten(treedef,
                                        [np.asarray(x) for x in leaves])


def _fetch_scalar(x: Any) -> float:
    telemetry.count("host_syncs")
    telemetry.count("arrays_fetched")
    return float(x)


def _fetch_local(tree: Any) -> Any:
    """Collective-free fetch: materialise only the *addressable* shards.

    On a multi-host mesh, ``np.asarray`` of a task-sharded global array is
    a cross-host gather.  This fetch instead reads each leaf's addressable
    shards — every host pulls only its own rows of the task axis — and
    reassembles them in task order; replicated leaves (probe taps, loss
    scalars broadcast over hosts) dedupe to a single shard read.  Counts
    as one blocking transfer event, same contract as :func:`_fetch`, and
    likewise waits for the whole tree before it copies.
    """
    tree = jax.block_until_ready(tree)
    telemetry.count("host_syncs")
    telemetry.count("arrays_fetched", len(jax.tree_util.tree_leaves(tree)))

    def pull(x):
        shards = getattr(x, "addressable_shards", None)
        if shards is None:
            return np.asarray(x)
        by_slice = {}
        for sh in shards:
            key = tuple((s.start or 0, s.stop) for s in sh.index)
            if key not in by_slice:
                by_slice[key] = np.asarray(sh.data)
        rows = [by_slice[k] for k in sorted(by_slice)]
        return rows[0] if len(rows) == 1 else np.concatenate(rows, axis=0)

    return jax.tree_util.tree_map(pull, tree)


@dataclasses.dataclass
class AdaptResult:
    deltas: Any
    policy: SparseUpdatePolicy
    fisher_seconds: float
    train_seconds: float
    losses: list
    # blocking device->host transfer events attributable to this task; a
    # fleet adaptation amortises its per-group fetches, so this is a float
    host_transfers: float = 0.0
    # fine-tune steps skipped by the non-finite guard (carry passthrough)
    skipped_steps: int = 0

    @property
    def steps_per_sec(self) -> float:
        n = len(self.losses or ())
        return n / self.train_seconds if self.train_seconds > 0 else 0.0


def _probe_and_select(
    backbone: Backbone,
    params: Any,
    support: Dict[str, jax.Array],
    pseudo_query: Dict[str, jax.Array],
    budget: Budget,
    *,
    max_way: int,
    criterion: str,
    shard_channels: int,
    step_cache,
) -> Tuple[SparseUpdatePolicy, float, int]:
    """Algorithm 1 lines 1-4: Fisher probe → budgeted policy.

    Returns (policy, fisher_seconds, host_transfers)."""
    n = int(np.sum(np.asarray(support["episode_labels"]) >= 0))

    if step_cache is not None and backbone.fisher_reduce is not None:
        # steady-state path: probe + on-device Eq. 2 reduction, one fetch
        batch_pad = next(
            v.shape[0] for v in jax.tree_util.tree_leaves(support))
        taps = backbone.make_taps(batch_pad)
        t0 = time.perf_counter()
        chans_dev = step_cache.probe_fisher()(
            params, support, pseudo_query, taps, jnp.float32(n))
        chans = _fetch(chans_dev)
        potentials = potentials_from_chans(backbone.unit_costs, chans)
        fisher_dt = time.perf_counter() - t0
        transfers = 1
    elif step_cache is not None:
        batch_pad = next(
            v.shape[0] for v in jax.tree_util.tree_leaves(support))
        taps = backbone.make_taps(batch_pad)
        t0 = time.perf_counter()
        g = step_cache.probe_grad()(params, support, pseudo_query, taps)
        g = _fetch(g)
        potentials, chans = backbone.fisher_from_grads(g, n)
        fisher_dt = time.perf_counter() - t0
        transfers = 1
    else:
        def probe_loss(p, batch, taps=None):
            return episode_loss(
                backbone.features, p, support, pseudo_query, max_way,
                taps=taps)

        potentials, chans, fisher_dt = fisher_probe(
            backbone, params, probe_loss, support, n
        )
        telemetry.count("host_syncs")
        transfers = 1
    policy = select_policy(
        backbone.unit_costs, potentials, chans, budget,
        criterion=criterion, shard_channels=shard_channels,
    )
    return policy, fisher_dt, transfers


def adapt_task(
    backbone: Backbone,
    params: Any,
    support: Dict[str, jax.Array],
    pseudo_query: Dict[str, jax.Array],
    budget: Budget,
    optimizer: Optimizer,
    *,
    iters: int = 40,
    max_way: int = 16,
    criterion: str = "tinytrain",
    shard_channels: int = 1,
    policy_override: Optional[SparseUpdatePolicy] = None,
    step_cache=None,  # EpisodeStepCache: reuse compiles across tasks
    fused: bool = True,
    nan_loss_steps: Tuple[int, ...] = (),
) -> AdaptResult:
    """Run Algorithm 1 for one target task.

    ``pseudo_query`` is the augmented support set used for backprop (Hu et
    al. 2022 procedure, Appendix C).  ``policy_override`` lets ablations
    inject static policies (random/L2 channels, ES policies, ...).

    ``fused=True`` (default) runs the fine-tune loop as a single scanned
    dispatch; ``fused=False`` keeps the eager per-iteration loop for
    debugging and loss-trajectory inspection mid-run.

    Non-finite steps (diverged loss/grads) are skipped in-graph — the
    delta/optimizer carry passes through — and counted in
    ``AdaptResult.skipped_steps``.  ``nan_loss_steps`` injects NaN losses
    at the listed step indices (the fault harness for that guard).
    """
    transfers = 0
    if policy_override is None:
        policy, fisher_dt, transfers = _probe_and_select(
            backbone, params, support, pseudo_query, budget,
            max_way=max_way, criterion=criterion,
            shard_channels=shard_channels, step_cache=step_cache)
    else:
        policy = policy_override
        fisher_dt = 0.0

    deltas = backbone.init_deltas(policy)
    opt_state = optimizer.init(deltas)

    t0 = time.perf_counter()
    losses: list = []
    skipped = 0
    if iters <= 0:
        pass
    elif fused and step_cache is not None:
        run = step_cache.scan_steps(policy, iters, nan_loss_steps)
        ci = step_cache.chan_idx_arrays(policy)
        deltas, opt_state, loss_arr, skip_arr = run(
            params, deltas, opt_state, support, pseudo_query, ci)
        loss_h, skip_h = _fetch((loss_arr, skip_arr))
        losses = [float(x) for x in loss_h]
        skipped = int(np.sum(skip_h))
        transfers += 1
    elif fused:
        run = make_episode_sparse_scan(
            backbone.features, policy, optimizer, max_way, iters,
            nan_steps=nan_loss_steps)
        deltas, opt_state, loss_arr, skip_arr = run(
            params, deltas, opt_state, support, pseudo_query)
        loss_h, skip_h = _fetch((loss_arr, skip_arr))
        losses = [float(x) for x in loss_h]
        skipped = int(np.sum(skip_h))
        transfers += 1
    else:
        # eager escape hatch: the compiled step applies the same in-graph
        # guard and reports NaN for a skipped step; injection restores the
        # pre-step carry host-side (the step itself stays fault-free)
        if step_cache is not None:
            step = step_cache.step(policy)
            ci = step_cache.chan_idx_arrays(policy)
            args = (support, pseudo_query, ci)
        else:
            step = make_episode_sparse_step(
                backbone.features, policy, optimizer, max_way)
            args = (support, pseudo_query)
        inject = frozenset(int(s) for s in nan_loss_steps)
        for t in range(iters):
            if t in inject:
                # the step donates its carries: keep live copies to restore
                prev = jax.tree_util.tree_map(jnp.copy, (deltas, opt_state))
            deltas, opt_state, loss = step(params, deltas, opt_state, *args)
            if t in inject:
                deltas, opt_state = prev
                losses.append(float("nan"))
                skipped += 1
            else:
                val = _fetch_scalar(loss)
                losses.append(val)
                skipped += int(not np.isfinite(val))
        transfers += iters - len([t for t in inject if t < iters])
    train_dt = time.perf_counter() - t0
    return AdaptResult(deltas, policy, fisher_dt, train_dt, losses,
                       host_transfers=transfers, skipped_steps=skipped)


def evaluate_task(
    backbone: Backbone,
    params: Any,
    deltas: Any,
    policy: Optional[SparseUpdatePolicy],
    support: Dict[str, jax.Array],
    query: Dict[str, jax.Array],
    max_way: int = 16,
) -> float:
    kw = {"deltas": deltas, "plan": policy} if policy is not None else {}
    acc = episode_accuracy(
        backbone.features, params, support, query, max_way, **kw
    )
    return float(acc)
