"""Sparse fine-tuning step builder (Algorithm 1 lines 5-6).

The policy is static, so the step function closes over it and is re-jitted
once per target task — matching the paper's "selection runs only once per
target dataset".  Gradients are taken **only w.r.t. the delta parameters**;
base weights are constants to autodiff, which is what yields the backward
memory/compute savings (no dW for frozen layers; no backprop below the
horizon; optimizer state only for deltas).

Every step builder carries the non-finite guard: a step whose loss or
gradients diverge is skipped (carry passthrough) instead of poisoning the
remaining iterations — the scan loops report per-step ``skipped`` flags,
the eager steps report the loss as NaN.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..optim import Optimizer, apply_updates
from ..utils import tree_size
from .backbones import Backbone
from .policy import SparseUpdatePolicy


def _finite_step(loss, grads):
    """Scalar bool: the step's loss *and* every gradient leaf are finite.

    The non-finite guard for the fine-tune loops: a diverged step (fp16
    overflow, log(0) on a degenerate episode, injected fault) must not
    poison the delta/optimizer carry, so callers apply the update through
    :func:`_guard_carry` and the bad step becomes a no-op."""
    ok = jnp.all(jnp.isfinite(loss))
    for g in jax.tree_util.tree_leaves(grads):
        ok = ok & jnp.all(jnp.isfinite(g))
    return ok


def _guard_carry(ok, new, old):
    """Select ``new`` when ``ok`` else keep ``old`` (carry passthrough)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new, old)


def make_sparse_train_step(
    loss_fn: Callable[..., jax.Array],
    policy: SparseUpdatePolicy,
    optimizer: Optimizer,
    *,
    donate: bool = True,
):
    """loss_fn(params, batch, deltas=..., plan=...) -> scalar.

    Returns step(params, deltas, opt_state, batch) -> (deltas, opt_state,
    loss).  Params are never updated — they stay the frozen meta-trained
    weights; deltas carry the task adaptation.  A non-finite step (loss or
    any gradient leaf) leaves deltas/opt_state untouched and reports the
    loss as NaN so the host can count the skip.
    """

    def step(params, deltas, opt_state, batch):
        def f(d):
            return loss_fn(params, batch, deltas=d, plan=policy)

        loss, grads = jax.value_and_grad(f)(deltas)
        ok = _finite_step(loss, grads)
        updates, new_st = optimizer.update(grads, opt_state, deltas)
        deltas = _guard_carry(ok, apply_updates(deltas, updates), deltas)
        opt_state = _guard_carry(ok, new_st, opt_state)
        return deltas, opt_state, jnp.where(ok, loss, jnp.nan)

    donate_argnums = (1, 2) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_episode_sparse_step(
    feature_fn: Callable[..., jax.Array],
    policy: SparseUpdatePolicy,
    optimizer: Optimizer,
    max_way: int,
):
    """Sparse fine-tune step for the ProtoNet meta-testing procedure."""
    from .protonet import episode_loss

    def step(params, deltas, opt_state, support, query):
        def f(d):
            return episode_loss(
                feature_fn, params, support, query, max_way,
                deltas=d, plan=policy,
            )

        loss, grads = jax.value_and_grad(f)(deltas)
        ok = _finite_step(loss, grads)
        updates, new_st = optimizer.update(grads, opt_state, deltas)
        deltas = _guard_carry(ok, apply_updates(deltas, updates), deltas)
        opt_state = _guard_carry(ok, new_st, opt_state)
        return deltas, opt_state, jnp.where(ok, loss, jnp.nan)

    return jax.jit(step, donate_argnums=(1, 2))


def scan_train_loop(
    loss_fn: Callable[..., jax.Array],
    optimizer: Optimizer,
    iters: int,
    *,
    nan_steps: Tuple[int, ...] = (),
):
    """Fuse a (value_and_grad -> update -> apply) loop into one ``lax.scan``.

    ``loss_fn(x, *ctx) -> scalar`` where ``x`` is the trained pytree and
    ``ctx`` is static context (frozen params, batches, channel indices).
    Returns run(x, opt_state, *ctx) -> (x, opt_state, losses, skipped)
    with losses and skipped shaped (iters,) — the single-dispatch core
    shared by the sparse, full-train and TinyTL fused loops (jit/donation
    is the caller's job).

    Non-finite guard: a step whose loss or any gradient leaf is non-finite
    is skipped — the (deltas, opt_state) carry passes through unchanged
    and ``skipped[t]`` is True — so one diverged iteration cannot poison
    the rest of the scanned loop.  ``nan_steps`` is the fault-injection
    hook (``FaultConfig.nan_loss_steps``): the listed step indices get
    their loss forced to NaN at trace time, driving the guard path under
    test without touching the real numerics.
    """
    nan_steps = tuple(int(s) for s in nan_steps)

    def run(x, opt_state, *ctx):
        def body(carry, inject):
            x, st = carry
            loss, grads = jax.value_and_grad(
                lambda xx: loss_fn(xx, *ctx))(x)
            if inject is not None:
                loss = jnp.where(inject, jnp.nan, loss)
            ok = _finite_step(loss, grads)
            updates, new_st = optimizer.update(grads, st, x)
            x = _guard_carry(ok, apply_updates(x, updates), x)
            st = _guard_carry(ok, new_st, st)
            return (x, st), (loss, ~ok)

        xs = None
        if nan_steps:
            xs = jnp.zeros((iters,), bool).at[
                jnp.asarray(nan_steps, jnp.int32)].set(True, mode="drop")
        (x, opt_state), (losses, skipped) = jax.lax.scan(
            body, (x, opt_state), xs, length=iters)
        return x, opt_state, losses, skipped

    return run


def make_episode_sparse_scan(
    feature_fn: Callable[..., jax.Array],
    policy: SparseUpdatePolicy,
    optimizer: Optimizer,
    max_way: int,
    iters: int,
    *,
    nan_steps: Tuple[int, ...] = (),
):
    """Whole fine-tune loop as one compiled ``lax.scan`` call.

    Returns run(params, deltas, opt_state, support, query) -> (deltas,
    opt_state, losses, skipped) with losses/skipped shaped (iters,) — a
    single dispatch and a single host transfer instead of one per
    iteration, non-finite steps skipped via carry passthrough.
    """
    from .protonet import episode_loss

    loop = scan_train_loop(
        lambda d, params, support, query: episode_loss(
            feature_fn, params, support, query, max_way,
            deltas=d, plan=policy),
        optimizer, iters, nan_steps=nan_steps)

    def run(params, deltas, opt_state, support, query):
        return loop(deltas, opt_state, params, support, query)

    return jax.jit(run, donate_argnums=(1, 2))


@dataclasses.dataclass(frozen=True)
class ScoreLayout:
    """Where each unit's Fisher scores sit in the fleet probe's packed array.

    Unit ``keys[i]``'s per-task scores, shaped ``shapes[i]``, fill columns
    ``offsets[i]:offsets[i + 1]`` of one ``(T, sum C)`` array, so a probe
    group's scores reach the host in one copy instead of one per unit.
    """
    keys: Tuple[Any, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[int, ...]

    @classmethod
    def of(cls, scores: Dict) -> "ScoreLayout":
        """The layout of task-stacked scores ``{key: (T, *shape)}``, from
        their shapes alone (arrays, tracers or shape structs).  All leaves
        must share one dtype: packing casts nothing."""
        keys = tuple(sorted(scores))
        dtypes = {jnp.dtype(scores[k].dtype) for k in keys}
        if len(dtypes) != 1:
            raise TypeError(
                f"Fisher scores mix dtypes {sorted(map(str, dtypes))}; "
                "packing them would cast")
        shapes = tuple(tuple(scores[k].shape[1:]) for k in keys)
        offsets = [0]
        for s in shapes:
            offsets.append(offsets[-1] + math.prod(s))
        return cls(keys, shapes, tuple(offsets))

    def pack(self, scores: Dict) -> jax.Array:
        """Inside the program: every unit's scores side by side along the
        channel axis, ``(T, sum C)``; the task axis stays leading."""
        t = scores[self.keys[0]].shape[0]
        return jnp.concatenate(
            [scores[k].reshape(t, -1) for k in self.keys], axis=1)

    def unpack(self, packed: np.ndarray) -> Dict:
        """On the host: ``{key: (T, *shape)}`` numpy views into ``packed``
        (no copies), the dict the unpacked probe would have returned."""
        t, width = packed.shape
        if width != self.offsets[-1]:
            raise ValueError(f"packed scores have {width} columns; the "
                             f"layout has {self.offsets[-1]}")
        return {k: packed[:, lo:hi].reshape((t,) + s)
                for k, s, lo, hi in zip(self.keys, self.shapes,
                                        self.offsets, self.offsets[1:])}


class EpisodeStepCache:
    """Adaptation-engine jit cache: one compile per policy *structure*.

    Channel indices are passed as traced arrays, so two tasks whose policies
    select the same (layers, kinds, K) but different channels share one
    compiled step — the common case when adapting to many user tasks.
    """

    def __init__(self, backbone: Backbone, optimizer: Optimizer, max_way: int):
        self.backbone = backbone
        self.optimizer = optimizer
        self.max_way = max_way
        self._steps: Dict = {}
        self._scans: Dict = {}
        self._vscans: Dict = {}
        self._evals: Dict = {}
        self._block_scores: Dict = {}
        self._probe = None
        self._probe_fisher = None
        self._probe_fisher_batch = None
        self._probe_layout = None

    def probe_grad(self):
        """Jitted Fisher-probe gradient, compiled once per backbone (episodes
        pass their batches as arguments — no per-task retrace)."""
        from .protonet import episode_loss

        if self._probe is None:
            feature_fn = self.backbone.features
            max_way = self.max_way

            def f(params, support, query, taps):
                return episode_loss(feature_fn, params, support, query,
                                    max_way, taps=taps)

            self._probe = jax.jit(jax.grad(f, argnums=3))
        return self._probe

    def _probe_fisher_fn(self):
        """Tap-grad + device-side Eq. 2 reduction, fused in one trace.

        pf(params, support, query, taps, n) -> {(layer, kind): Δ_o} — only
        the O(L·C) channel scores ever cross to the host, not the full
        (L, B, C) tap-gradient tree.  ``n`` is the valid-sample count,
        traced so episodes with different shot counts share the compile.

        The per-example validity mask (support labels >= 0) is threaded
        into the reduction, so bucket-padded episodes score exactly like
        their unpadded originals: padded rows contribute zero and the
        1/(2N) normaliser is the valid count, not the padded batch.
        """
        import inspect

        from .protonet import episode_loss

        feature_fn = self.backbone.features
        max_way = self.max_way
        reduce = self.backbone.fisher_reduce
        # external Backbones may still implement the pre-mask two-arg
        # reduction; only thread the validity mask when it is accepted
        try:
            takes_mask = len(inspect.signature(reduce).parameters) >= 3
        except (TypeError, ValueError):
            takes_mask = True

        def f(params, support, query, taps):
            return episode_loss(feature_fn, params, support, query,
                                max_way, taps=taps)

        def pf(params, support, query, taps, n):
            g = jax.grad(f, argnums=3)(params, support, query, taps)
            if not takes_mask:
                return reduce(g, n)
            mask = (support["episode_labels"] >= 0).astype(jnp.float32)
            return reduce(g, n, mask)

        return pf

    def probe_fisher(self):
        """Jitted single-task probe → per-channel Fisher scores."""
        if self._probe_fisher is None:
            self._probe_fisher = jax.jit(self._probe_fisher_fn())
        return self._probe_fisher

    def probe_fisher_batch(self):
        """Vmapped probe: one dispatch scores a whole fleet of tasks.

        pfb(params, supports, queries) with task-stacked leading axes on
        supports/queries; params are broadcast.  The program builds the
        rest of the per-task probe's arguments itself: the taps for the
        stacked row count (constants, broadcast) and each task's valid
        count ``n``, its support labels >= 0 (``Task.n_support``; padded
        and repeated rows count as their task does).

        It returns one ``(T, sum C)`` array: every unit's per-channel
        scores packed along the channel axis inside the program, so the
        host copies a group's scores once.  :meth:`probe_layout` says
        which columns are whose.
        """
        if self._probe_fisher_batch is None:
            batched = jax.vmap(self._probe_fisher_fn(),
                               in_axes=(None, 0, 0, None, 0))
            backbone = self.backbone

            # named for the device trace: its program is jit_fleet_probe
            def fleet_probe(params, supports, queries):
                labels = supports["episode_labels"]
                taps = backbone.make_taps(labels.shape[1])
                ns = jnp.sum(labels >= 0, axis=1).astype(jnp.float32)
                scores = batched(params, supports, queries, taps, ns)
                # the reduction's output shapes are static: kept while
                # the program traces, the same for every group shape
                self._probe_layout = ScoreLayout.of(scores)
                return self._probe_layout.pack(scores)

            self._probe_fisher_batch = jax.jit(fleet_probe)
        return self._probe_fisher_batch

    def probe_layout(self) -> ScoreLayout:
        """The column layout of :meth:`probe_fisher_batch`'s output, found
        from the reduction's output shapes when the program first traced
        (so after its first call)."""
        if self._probe_layout is None:
            raise RuntimeError("the fleet probe has not traced yet")
        return self._probe_layout

    @staticmethod
    def _key(policy: SparseUpdatePolicy):
        return (policy.horizon,
                tuple((u.layer, u.kind, u.n_channels) for u in policy.units))

    def fleet_scan_compiles(self) -> int:
        """Total compiled fleet-scan programs (every (bucket shape, task
        count, policy structure, iters, mode) variant XLA actually built —
        the quantity the O(#buckets x #structures) contract bounds)."""
        return sum(f._cache_size() for f in self._vscans.values())

    @staticmethod
    def chan_idx_arrays(policy: SparseUpdatePolicy):
        return {
            lid: {k: jnp.asarray(v) for k, v in kinds.items()}
            for lid, kinds in policy.channel_idx.items()
        }

    def step(self, policy: SparseUpdatePolicy):
        from .protonet import episode_loss

        key = self._key(policy)
        if key not in self._steps:
            feature_fn = self.backbone.features
            optimizer = self.optimizer
            max_way = self.max_way

            def step(params, deltas, opt_state, support, query, chan_idx):
                def f(d):
                    return episode_loss(
                        feature_fn, params, support, query, max_way,
                        deltas=d, plan=policy, chan_idx=chan_idx,
                    )

                loss, grads = jax.value_and_grad(f)(deltas)
                ok = _finite_step(loss, grads)
                updates, new_st = optimizer.update(grads, opt_state, deltas)
                deltas = _guard_carry(
                    ok, apply_updates(deltas, updates), deltas)
                opt_state = _guard_carry(ok, new_st, opt_state)
                return deltas, opt_state, jnp.where(ok, loss, jnp.nan)

            self._steps[key] = jax.jit(step, donate_argnums=(1, 2))
        return self._steps[key]

    def _scan_run_fn(self, policy: SparseUpdatePolicy, iters: int,
                     nan_steps: Tuple[int, ...] = ()):
        from .protonet import episode_loss

        feature_fn = self.backbone.features
        max_way = self.max_way
        loop = scan_train_loop(
            lambda d, params, support, query, chan_idx: episode_loss(
                feature_fn, params, support, query, max_way,
                deltas=d, plan=policy, chan_idx=chan_idx),
            self.optimizer, iters, nan_steps=nan_steps)

        def run(params, deltas, opt_state, support, query, chan_idx):
            return loop(deltas, opt_state, params, support, query, chan_idx)

        return run

    def scan_steps(self, policy: SparseUpdatePolicy, iters: int,
                   nan_steps: Tuple[int, ...] = ()):
        """The whole fine-tune loop as one compiled call (keyed on policy
        structure + iters, carries donated).

        run(params, deltas, opt_state, support, query, chan_idx) ->
        (deltas, opt_state, losses, skipped) with losses/skipped shaped
        (iters,): one dispatch and one loss transfer per adapt() instead
        of ``iters``.  ``nan_steps`` (fault injection) is part of the
        compile key — production callers pass none and share the clean
        program.
        """
        nan_steps = tuple(int(s) for s in nan_steps)
        key = (self._key(policy), int(iters), nan_steps)
        if key not in self._scans:
            self._scans[key] = jax.jit(
                self._scan_run_fn(policy, int(iters), nan_steps),
                donate_argnums=(1, 2))
        return self._scans[key]

    def vmap_scan_steps(self, policy: SparseUpdatePolicy, iters: int,
                        mode: Optional[str] = None):
        """Fleet variant of :meth:`scan_steps`: support/query/chan_idx carry
        a leading task axis, params broadcast, and the zero-initialised
        delta/optimizer carries are created *inside* the compiled call —
        run(params, supports, queries, chan_idxs) -> (deltas, opt_state,
        losses, skipped), everything task-stacked.  N same-structure tasks
        fine-tune in a single dispatch with no per-task host-side init.

        ``mode``: ``"vmap"`` batches the task axis through every op (the
        accelerator path — batched matmuls/convs fill the hardware);
        ``"map"`` runs tasks as a sequential on-device loop in the same
        single dispatch — on CPU, XLA lowers batched-*weight* convs (the
        per-task delta kernels) poorly, so the loop is faster there;
        ``"shard"`` splits the task axis across the data axes of the mesh
        published via ``dist.context`` (``fleet_mesh``) with ``shard_map``
        — params replicate, episodes/deltas/opt-state shard — and runs the
        backend-appropriate single-device path (vmap/map) on each shard,
        so one host drives every local device in one dispatch.  Default:
        shard when a fleet mesh is published, else vmap on tpu/gpu, map
        on cpu.

        Episodes may be bucket-padded: padded rows carry label -1, which
        the episode loss masks out, so the batched loss/gradients are
        identical to the unpadded per-task computation.
        """
        from ..dist import context as dist_context

        mesh = dist_context.get("fleet_mesh")
        if mode is None:
            if mesh is not None:
                mode = "shard"
            else:
                mode = ("vmap" if jax.default_backend() in ("tpu", "gpu")
                        else "map")
        key = (self._key(policy), int(iters), mode,
               mesh if mode == "shard" else None)
        if key not in self._vscans:
            run = self._scan_run_fn(policy, int(iters))
            init_deltas = self.backbone.init_deltas
            optimizer = self.optimizer

            def run_from_zero(params, support, query, chan_idx):
                d = init_deltas(policy)
                st = optimizer.init(d)
                return run(params, d, st, support, query, chan_idx)

            def map_fleet(params, support, query, chan_idx):
                return jax.lax.map(
                    lambda args: run_from_zero(params, *args),
                    (support, query, chan_idx))

            vmap_fleet = jax.vmap(run_from_zero, in_axes=(None, 0, 0, 0))

            if mode == "vmap":
                fleet = vmap_fleet
            elif mode == "map":
                fleet = map_fleet
            else:
                from ..dist.sharding import _dp_axes

                local = (vmap_fleet
                         if jax.default_backend() in ("tpu", "gpu")
                         else map_fleet)
                dp, _ = _dp_axes(mesh)  # FleetShardingRules's convention
                if not dp:
                    # pure-'model' mesh: no data axis to split tasks over
                    # (FleetShardingRules replicates too) — run locally
                    fleet = local
                else:
                    from jax.sharding import PartitionSpec as P

                    ts = P(dp if len(dp) > 1 else dp[0])  # task-axis prefix
                    # callers pad the stacked task axis to a multiple of
                    # the data size (FleetShardingRules.padded_count), so
                    # every shard sees an equal local slice
                    fleet = jax.shard_map(
                        local, mesh=mesh, in_specs=(P(), ts, ts, ts),
                        out_specs=ts, check_vma=False)

            # named for the device trace: its program is jit_fleet_finetune
            def fleet_finetune(params, support, query, chan_idx):
                return fleet(params, support, query, chan_idx)

            self._vscans[key] = jax.jit(fleet_finetune)
        return self._vscans[key]

    def block_score(self, block: int = 32):
        """Compiled LM token-batch scorer on the serving *block* path.

        score(params, tokens (N, S) int32) -> per-sequence mean next-token
        NLL (N,) float32, computed by folding the batch through
        ``models.transformer.prefill_block`` in S/block chunks against
        decode caches — the exact sequence-mode path the serving engine
        uses for prompt ingestion, so adaptation-side token-batch scoring
        (support-set perplexity, candidate ranking) exercises the deployed
        cache math instead of looping positions or re-deriving a separate
        forward.  One compiled dispatch per call; cached per block size
        (jit re-specialises per batch shape as usual).

        Sliding-window archs score through their rolling cache, matching
        what a served request would see.
        """
        if self.backbone.kind != "lm":
            raise ValueError(
                "block_score is for LM token-batch workloads; "
                f"backbone kind is {self.backbone.kind!r}")
        key = int(block)
        if key < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if key not in self._block_scores:
            from ..models import transformer as T

            cfg = self.backbone.cfg

            def score(params, tokens):
                n, s = tokens.shape
                if s < 2:
                    raise ValueError(
                        f"need at least 2 tokens to score next-token NLL, "
                        f"got sequences of length {s}")
                blk = min(key, s)
                nb = -(-s // blk)  # ragged tail rides a validity mask,
                pad = nb * blk - s  # exactly like serving prompt tails
                caches = T.init_caches(cfg, n, s)
                tb = jnp.moveaxis(
                    jnp.pad(tokens, ((0, 0), (0, pad))).reshape(n, nb, blk),
                    1, 0)
                vb = (jnp.arange(nb * blk) < s).reshape(nb, 1, blk)
                vb = jnp.broadcast_to(vb, (nb, n, blk))

                def body(carry, xs):
                    caches, pos = carry
                    toks, vld = xs
                    logits, caches = T.prefill_block(
                        cfg, params, toks, caches, pos, vld)
                    return (caches, pos + jnp.sum(vld[0].astype(pos.dtype))
                            ), logits

                (_, _), ls = jax.lax.scan(
                    body, (caches, jnp.zeros((n,), jnp.int32)), (tb, vb))
                logits = jnp.moveaxis(ls, 0, 1).reshape(n, nb * blk, -1)
                lg = logits[:, :s - 1].astype(jnp.float32)
                logz = jax.nn.logsumexp(lg, axis=-1)
                gold = jnp.take_along_axis(
                    lg, tokens[:, 1:, None], axis=-1)[..., 0]
                return jnp.mean(logz - gold, axis=-1)

            self._block_scores[key] = jax.jit(score)
        return self._block_scores[key]

    def evaluate(self, policy: Optional[SparseUpdatePolicy]):
        from .protonet import episode_accuracy

        key = self._key(policy) if policy is not None else None
        if key not in self._evals:
            feature_fn = self.backbone.features
            max_way = self.max_way

            if policy is None:
                def ev(params, deltas, support, query, chan_idx):
                    return episode_accuracy(
                        feature_fn, params, support, query, max_way)
            else:
                def ev(params, deltas, support, query, chan_idx):
                    return episode_accuracy(
                        feature_fn, params, support, query, max_way,
                        deltas=deltas, plan=policy, chan_idx=chan_idx)

            self._evals[key] = jax.jit(ev)
        return self._evals[key]


def deltas_param_count(deltas: Any) -> int:
    return tree_size(deltas)


def sparse_memory_report(
    backbone: Backbone,
    policy: SparseUpdatePolicy,
    deltas: Any,
    optimizer: Optimizer,
    param_bytes: int = 4,
) -> Dict[str, float]:
    """Backward-pass memory accounting in the paper's Table-2/7 format."""
    n = deltas_param_count(deltas)
    updated_weights = n * param_bytes
    opt_mem = n * param_bytes * optimizer.slots
    by_key = backbone.cost_by_key()
    act = sum(
        by_key[(u.layer, u.kind)].act_in_bytes for u in policy.units
    )
    return {
        "updated_weights_bytes": updated_weights,
        "optimizer_bytes": opt_mem,
        "activation_bytes": act,
        "total_bytes": updated_weights + opt_mem + act,
        "delta_params": n,
    }
