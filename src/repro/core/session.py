"""Session layer: the stateful objects behind the ``repro.api`` façade.

TinyTrain's contribution is a *pipeline* — Fisher probe → multi-objective
selection → sparse fine-tune → deploy (Algorithm 1) — but the low-level
``core/*`` functions leave every workload to hand-wire that chain.  This
module packages the pipeline behind three objects:

- :class:`DeviceProfile` — a named resource envelope (memory / compute /
  energy) that replaces raw :class:`~repro.core.criterion.Budget`
  construction, with presets for common edge targets.
- :class:`TinyTrainSession` — owns one backbone + frozen meta-trained
  params + the jit step cache, and amortises compiled steps across every
  ``adapt()`` / ``baseline()`` / ``evaluate()`` call.
- :class:`Adaptation` — the result object: accuracy, memory accounting and
  deployment (``fold_into``) without reaching into core internals.

``core/*`` stays the stable low-level layer; nothing here adds new math.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..optim import Optimizer, adam
from .adapt import (
    AdaptResult, adapt_task, _fetch, _fetch_local, _fetch_scalar,
)
from .backbones import Backbone
from .criterion import Budget
from .fisher import potentials_from_chans
from .policy import SparseUpdatePolicy, last_layer_policy
from .selection import select_policy, static_channel_policy
from .sparse import (
    EpisodeStepCache, deltas_param_count, sparse_memory_report,
)

__all__ = [
    "Adaptation", "DeviceProfile", "PROFILES", "Task", "TinyTrainSession",
    "criteria", "device_profile", "register_criterion", "register_profile",
    "JETSON_NANO", "RPI_ZERO", "STM32F746",
]


# ---------------------------------------------------------------------------
# Device profiles
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Resource envelope of a deployment target.

    The online stage consumes ``mem_kb`` (backward-pass memory: B1 updated
    weights + B2 optimizer state + B4 saved inputs) and ``compute_frac``
    (backward MACs as a fraction of a full backward pass).  ``flash_mb`` and
    ``peak_mw`` are informational (model storage / energy envelope) and feed
    reporting, not selection.
    """

    name: str
    mem_kb: float
    compute_frac: float
    channel_ratio: float = 0.5
    opt_slots: int = 2  # adam: m, v
    param_bytes: int = 4
    flash_mb: float = 0.0
    peak_mw: float = 0.0

    def budget(self) -> Budget:
        """Lower this profile to the Algorithm-1 budget inputs."""
        return Budget(
            mem_bytes=self.mem_kb * 1e3,
            compute_frac=self.compute_frac,
            channel_ratio=self.channel_ratio,
            opt_slots=self.opt_slots,
            param_bytes=self.param_bytes,
        )

    def scaled(self, mem: float = 1.0, compute: float = 1.0,
               name: Optional[str] = None) -> "DeviceProfile":
        """A derived profile with scaled envelopes (ablation sweeps)."""
        return dataclasses.replace(
            self,
            name=name or f"{self.name}*{mem:g}/{compute:g}",
            mem_kb=self.mem_kb * mem,
            compute_frac=min(1.0, self.compute_frac * compute),
        )


# Presets: paper-scale edge targets (Sec. 3.1 uses Pi Zero 2 / Jetson Nano;
# STM32-class MCUs are the MCUNet deployment point the cost model mirrors).
STM32F746 = DeviceProfile(
    name="stm32f746", mem_kb=320, compute_frac=0.25, channel_ratio=0.5,
    flash_mb=1.0, peak_mw=400.0)
RPI_ZERO = DeviceProfile(
    name="rpi-zero", mem_kb=1000, compute_frac=0.5, channel_ratio=0.75,
    flash_mb=512.0, peak_mw=1200.0)  # the paper's "around 1 MB" envelope
JETSON_NANO = DeviceProfile(
    name="jetson-nano", mem_kb=4096, compute_frac=0.8, channel_ratio=1.0,
    flash_mb=4096.0, peak_mw=10_000.0)

PROFILES: Dict[str, DeviceProfile] = {}


def register_profile(profile: DeviceProfile) -> DeviceProfile:
    # normalise the key exactly as device_profile() normalises lookups
    PROFILES[profile.name.lower().replace("_", "-")] = profile
    return profile


for _p in (STM32F746, RPI_ZERO, JETSON_NANO):
    register_profile(_p)


def device_profile(name: str) -> DeviceProfile:
    """Look up a registered profile (case/underscore tolerant)."""
    key = name.lower().replace("_", "-")
    try:
        return PROFILES[key]
    except KeyError:
        raise KeyError(
            f"unknown device profile {name!r}; known: {sorted(PROFILES)}"
        ) from None


def _as_budget(profile: Union[DeviceProfile, Budget, str]) -> Budget:
    if isinstance(profile, str):
        profile = device_profile(profile)
    if isinstance(profile, DeviceProfile):
        return profile.budget()
    if isinstance(profile, Budget):
        return profile
    raise TypeError(
        f"expected DeviceProfile, Budget or profile name, got {type(profile)}")


# ---------------------------------------------------------------------------
# Criteria registry: selection criterion + channel mode behind one string
# ---------------------------------------------------------------------------

# name -> (multi-objective score mode for layer selection, channel mode)
_CRITERIA: Dict[str, Tuple[str, str]] = {
    "tinytrain": ("tinytrain", "dynamic"),
    "fisher_only": ("fisher_only", "dynamic"),
    "fisher_mem": ("fisher_mem", "dynamic"),
    "fisher_compute": ("fisher_compute", "dynamic"),
    # Fig. 4 ablations: TinyTrain layer selection, static channel choice
    "random": ("tinytrain", "random"),
    "l2norm": ("tinytrain", "l2norm"),
}


def register_criterion(name: str, score_mode: str,
                       channel_mode: str = "dynamic") -> None:
    """Register a selection criterion usable as ``adapt(criterion=name)``."""
    _CRITERIA[name] = (score_mode, channel_mode)


def criteria() -> List[str]:
    return sorted(_CRITERIA)


def _resolve_criterion(name: str) -> Tuple[str, str]:
    try:
        return _CRITERIA[name]
    except KeyError:
        raise KeyError(
            f"unknown criterion {name!r}; known: {criteria()}") from None


# ---------------------------------------------------------------------------
# Task
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Task:
    """One target task: support/query episode plus the augmented
    pseudo-query set used for backprop (Hu et al. 2022, Appendix C)."""

    name: str
    support: Dict[str, jax.Array]
    query: Dict[str, jax.Array]
    pseudo_query: Dict[str, jax.Array]
    max_way: int

    @property
    def n_support(self) -> int:
        return int(np.sum(np.asarray(self.support["episode_labels"]) >= 0))

    @classmethod
    def from_episode(cls, ep, rng: np.random.Generator, max_way: int,
                     name: str = "") -> "Task":
        """Build a Task from a ``repro.data`` Episode (vision or LM)."""
        from ..data import (
            augment_encdec_support, augment_lm_support, augment_support,
        )

        if "images" in ep.support:
            augment = augment_support
        elif "frames" in ep.support or "image_embeds" in ep.support:
            augment = augment_encdec_support
        else:
            augment = augment_lm_support
        return cls(
            name=name or getattr(ep, "domain", "task"),
            support={k: jnp.asarray(v) for k, v in ep.support.items()},
            query={k: jnp.asarray(v) for k, v in ep.query.items()},
            pseudo_query={
                k: jnp.asarray(v) for k, v in augment(rng, ep.support).items()
            },
            max_way=max_way,
        )


def _stack_trees(trees: List[Any]) -> Any:
    """Stack a list of identically-shaped pytrees along a new task axis
    (traced inside :func:`fleet_stack_episodes`)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _tree_shape_key(tree: Any) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((l.shape, str(l.dtype)) for l in leaves))


def _episode_shape_key(sup: Any, pq: Any) -> Tuple:
    """Episodes are stackable iff their (support, pseudo-query) pytrees
    match exactly; with bucketing the key is computed on the *padded*
    episodes, so any way/shot mix inside one bucket shares it."""
    return (_tree_shape_key(sup), _tree_shape_key(pq))


def _group_indices(keys: List[Any]) -> Dict[Any, List[int]]:
    groups: Dict[Any, List[int]] = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    return groups


# counters of repro.telemetry an adapt_many call records on its root span
_FLEET_COUNTERS = ("host_syncs", "arrays_fetched", "prep_programs")


# Bucketed episode padding: heterogeneous way/shot traffic is padded up to
# a small set of canonical row counts (next power of two, floored) so a
# fleet of arbitrary episode sizes compiles O(#buckets) programs instead of
# O(#distinct shapes).  Padded rows carry label -1 — the episode loss, the
# accuracy mask and the Fisher reduction all treat them as invisible, so
# padding changes no result, only the compiled shape.
_MIN_BUCKET_ROWS = 8


def _bucket_rows(n: int, floor: int = _MIN_BUCKET_ROWS) -> int:
    """Canonical bucket size: next power of two >= n (>= floor)."""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


def _pad_episode_rows(ep: Dict[str, jax.Array], rows: int
                      ) -> Dict[str, jax.Array]:
    """Pad every episode leaf to ``rows`` along axis 0.

    ``episode_labels`` pads with -1 (the validity-mask sentinel shared by
    the episode loss, accuracy and Fisher reduction); data leaves pad with
    zeros.  A no-op when the episode already sits on the bucket boundary.
    """
    out: Dict[str, jax.Array] = {}
    for k, v in ep.items():
        n = int(v.shape[0])
        if n == rows:
            out[k] = v
            continue
        if n > rows:
            raise ValueError(
                f"episode leaf {k!r} has {n} rows > bucket {rows}")
        width = [(0, rows - n)] + [(0, 0)] * (v.ndim - 1)
        fill = -1 if k == "episode_labels" else 0
        out[k] = jnp.pad(v, width, constant_values=fill)
    return out


# The fleet's input preparation runs as compiled programs, one call each,
# never as one eager op per leaf and task.  Their compile keys hold shapes
# alone: the pad's the raw episode shape and the bucket rows, the stack's
# the task count and the bucket shape, so no key depends on the order of
# the tasks inside a group.  Each call counts once in the ``prep_programs``
# counter of :mod:`repro.telemetry`.


def fleet_pad_episode(support: Any, pseudo_query: Any, rows: int
                      ) -> Tuple[Any, Any]:
    """Both episode trees of a task padded to ``rows`` (a static row
    count)."""
    return (_pad_episode_rows(support, rows),
            _pad_episode_rows(pseudo_query, rows))


def fleet_stack_episodes(supports: List[Any], pseudo_queries: List[Any]
                         ) -> Tuple[Any, Any]:
    """A group's support and pseudo-query trees, each stacked along a new
    task axis."""
    return _stack_trees(supports), _stack_trees(pseudo_queries)


# named for the device trace: jit_fleet_pad_episode, jit_fleet_stack_episodes
_fleet_pad_episode = jax.jit(fleet_pad_episode, static_argnums=2)
_fleet_stack_episodes = jax.jit(fleet_stack_episodes)


def _bucket_episode(task: Task) -> Tuple[Any, Any]:
    """(support, pseudo_query) of a task, padded to one shared bucket.

    Both sets pad to the same row count because the Fisher taps are sized
    once per episode and threaded through both forward passes.  One
    compiled call pads both; a task already on its bucket is returned as
    it is, with no call.
    """
    trees = (task.support, task.pseudo_query)
    rows = [int(v.shape[0]) for v in jax.tree_util.tree_leaves(trees)]
    target = _bucket_rows(max(rows))
    if all(n == target for n in rows):
        return trees
    telemetry.count("prep_programs")
    return _fleet_pad_episode(task.support, task.pseudo_query, target)


# ---------------------------------------------------------------------------
# Adaptation result
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Adaptation:
    """Outcome of one adapt()/baseline() call.

    ``deltas`` is the method's trainable pack (channel deltas, TinyTL
    adapters, or a full fine-tuned parameter copy depending on ``method``);
    ``policy`` is set for sparse-update methods only.
    """

    method: str
    task: Task
    profile: Optional[DeviceProfile]
    budget: Optional[Budget]
    deltas: Any
    policy: Optional[SparseUpdatePolicy]
    fisher_seconds: float
    train_seconds: float
    losses: List[float]
    host_transfers: float
    _session: "TinyTrainSession" = dataclasses.field(repr=False)
    _eval: Callable[[Any, Any], float] = dataclasses.field(repr=False)
    # fine-tune steps skipped by the non-finite guard (loss/grad diverged
    # or fault-injected): the carry passed through unchanged on those
    skipped_steps: int = 0

    @property
    def steps_per_sec(self) -> float:
        """Fine-tune iterations per second (0 when nothing was trained)."""
        n = len(self.losses)
        return n / self.train_seconds if self.train_seconds > 0 and n else 0.0

    def accuracy(self, task: Optional[Task] = None) -> float:
        """Query-set accuracy on this task (or another Task's episode)."""
        t = task or self.task
        return float(self._eval(t.support, t.query))

    def delta_param_count(self) -> int:
        return deltas_param_count(self.deltas) if self.deltas is not None else 0

    def memory_report(self) -> Dict[str, float]:
        """Backward-pass memory accounting (paper Table-2/7 format).

        Uses the profile's ``param_bytes`` so the report is commensurate
        with the budget the policy was selected under.
        """
        if self.policy is None:
            raise ValueError(
                f"method {self.method!r} has no sparse-update policy; "
                "memory_report() applies to policy-based adaptations")
        pb = (self.profile.param_bytes if self.profile is not None
              else self.budget.param_bytes if self.budget is not None
              else 4)
        return sparse_memory_report(
            self._session.backbone, self.policy, self.deltas,
            self._session.optimizer, param_bytes=pb)

    def fold_into(self, target: Any) -> Any:
        """Fold channel deltas into serving weights: W ⊕ scatter(ΔW, idx).

        ``target`` is either a :class:`~repro.serving.engine.ServeEngine`
        (its params are replaced in place and the engine returned) or a raw
        parameter pytree (a folded copy is returned).  Adapted models then
        serve at exactly base cost.
        """
        if self.policy is None or self.deltas is None:
            raise ValueError(
                f"method {self.method!r} produced no delta pack to fold")
        bb = self._session.backbone
        if hasattr(target, "params") and hasattr(target, "cfg"):
            from ..serving.engine import fold_deltas

            target.params = fold_deltas(
                target.cfg, target.params, self.deltas, self.policy)
            return target
        if bb.kind == "lm":
            from ..serving.engine import fold_deltas

            return fold_deltas(bb.cfg, target, self.deltas, self.policy)
        from ..models.edge_cnn import cnn_fold_deltas

        return cnn_fold_deltas(bb.cfg, target, self.deltas, self.policy)

    def describe(self) -> str:
        pol = self.policy.describe() if self.policy is not None else "none"
        return (f"{self.method}: policy={pol} "
                f"fisher={self.fisher_seconds:.2f}s "
                f"train={self.train_seconds:.2f}s "
                f"steps_per_sec={self.steps_per_sec:.1f} "
                f"host_transfers={self.host_transfers:g} "
                f"skipped_steps={self.skipped_steps} "
                f"delta_params={self.delta_param_count()}")


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------


class TinyTrainSession:
    """One backbone + frozen params + jit cache, many tasks.

    The session compiles a sparse step once per policy *structure* and
    reuses it across every subsequent ``adapt()`` — the production
    adaptation-engine behaviour (one deployed model, many user tasks).
    """

    def __init__(
        self,
        backbone: Backbone,
        params: Any = None,
        *,
        optimizer: Optional[Optimizer] = None,
        lr: float = 3e-3,
        baseline_lr: float = 1e-3,
        max_way: int = 16,
        seed: int = 0,
    ):
        self.backbone = backbone
        self.params = (params if params is not None
                       else backbone.init(jax.random.PRNGKey(seed)))
        # delta packs start at zero -> slightly hotter lr than full tuning
        self.optimizer = optimizer or adam(lr)
        self.baseline_optimizer = adam(baseline_lr)
        self.max_way = max_way
        self.step_cache = EpisodeStepCache(backbone, self.optimizer, max_way)
        self._static_policies: Dict[str, SparseUpdatePolicy] = {}
        # ES baseline cache: one (proxy_task, policy) per budget/proxy/seed
        # combo; holding the task pins its id() for the key's lifetime.
        # Grows with distinct proxies — callers reuse one proxy per run.
        self._es_cache: Dict[Any, Tuple[Task, SparseUpdatePolicy]] = {}
        self._full_step = None
        self._full_scans: Dict[int, Any] = {}
        self._tinytl_steps: Dict[int, Any] = {}
        self._tinytl_scans: Dict[Tuple[int, int], Any] = {}
        # grouping summary of the most recent adapt_many() call
        self.last_fleet_report: Dict[str, Any] = {}

    # -- telemetry ---------------------------------------------------------

    def compiled_steps(self) -> int:
        """Number of distinct jitted sparse-step variants compiled so far
        (eager per-iteration steps, fused scan variants and fleet scans)."""
        return (len(self.step_cache._steps) + len(self.step_cache._scans)
                + len(self.step_cache._vscans))

    # -- core pipeline -----------------------------------------------------

    def adapt(
        self,
        task: Task,
        profile: Union[DeviceProfile, Budget, str],
        *,
        criterion: str = "tinytrain",
        iters: int = 40,
        shard_channels: int = 1,
        policy_override: Optional[SparseUpdatePolicy] = None,
        seed: int = 0,
        fused: bool = True,
        nan_loss_steps: Tuple[int, ...] = (),
    ) -> Adaptation:
        """Algorithm 1 on one task: probe → select → sparse fine-tune.

        ``fused=True`` (default) runs the fine-tune loop as one scanned
        dispatch; ``fused=False`` is the eager per-iteration escape hatch.
        ``nan_loss_steps`` fault-injects NaN losses at the listed step
        indices to drive the non-finite guard (skipped steps are counted
        in ``Adaptation.skipped_steps``).
        """
        self._check_task(task)
        if isinstance(profile, str):
            profile = device_profile(profile)
        budget = _as_budget(profile)
        prof = profile if isinstance(profile, DeviceProfile) else None
        kw = dict(iters=iters, max_way=self.max_way,
                  step_cache=self.step_cache, fused=fused,
                  nan_loss_steps=nan_loss_steps)

        if policy_override is not None:
            res = adapt_task(self.backbone, self.params, task.support,
                             task.pseudo_query, budget, self.optimizer,
                             policy_override=policy_override, **kw)
            method = f"override:{(policy_override.meta or {}).get('source', 'policy')}"
        else:
            mode, channel_mode = _resolve_criterion(criterion)
            if channel_mode == "dynamic":
                res = adapt_task(self.backbone, self.params, task.support,
                                 task.pseudo_query, budget, self.optimizer,
                                 criterion=mode,
                                 shard_channels=shard_channels, **kw)
            else:
                # probe + layer selection only, then a static channel pick
                # at the same layers/K (Fig. 4 ablations) — no wasted
                # fine-tune pass on the dynamic channels
                probe = adapt_task(
                    self.backbone, self.params, task.support,
                    task.pseudo_query, budget, self.optimizer,
                    criterion=mode, shard_channels=shard_channels,
                    iters=0, max_way=self.max_way,
                    step_cache=self.step_cache)
                l2 = (self.backbone.weight_l2(self.params)
                      if channel_mode == "l2norm" else None)
                pol = static_channel_policy(
                    probe.policy, self.backbone.unit_costs, channel_mode,
                    rng=np.random.default_rng(seed), weight_l2=l2)
                res = adapt_task(self.backbone, self.params, task.support,
                                 task.pseudo_query, budget, self.optimizer,
                                 policy_override=pol, **kw)
                res = dataclasses.replace(
                    res, fisher_seconds=probe.fisher_seconds,
                    host_transfers=probe.host_transfers + res.host_transfers)
            method = criterion
        return self._wrap(method, task, prof, res, budget=budget)

    def adapt_many(
        self,
        tasks: List[Task],
        profile: Union[DeviceProfile, Budget, str],
        *,
        criterion: str = "tinytrain",
        iters: int = 40,
        shard_channels: int = 1,
        policy_override: Optional[SparseUpdatePolicy] = None,
        bucket: bool = True,
        mesh: Optional[Any] = None,
        hosts: Optional[int] = None,
    ) -> List[Adaptation]:
        """Fleet adaptation: N user tasks in O(#buckets x #structures) calls.

        Probes every task in one vmapped dispatch per episode group,
        selects a policy per task, then groups tasks by policy *structure*
        and runs one vmap-of-scanned-steps call per group — support sets,
        pseudo-query sets and channel indices are stacked along a task
        axis while the frozen backbone params broadcast.  Returns one
        :class:`Adaptation` per task, in input order.

        ``bucket=True`` (default) pads each task's support/pseudo-query
        rows up to a canonical bucket size (next power of two), so
        heterogeneous way/shot traffic groups by *bucket* instead of exact
        shape: a 16-task mix with four (way, shot) combinations adapts in
        O(#buckets x #policy-structures) compiled calls rather than one
        per distinct shape.  Padded rows carry label -1 and contribute
        exactly zero to the loss, gradients and Fisher scores.
        ``bucket=False`` restores exact-shape grouping.

        ``mesh``: an optional ``jax.sharding.Mesh``; each group's stacked
        task axis is sharded across the mesh's data axes (every axis but
        'model', per :class:`repro.dist.FleetShardingRules`) with the
        frozen params replicated, so one host drives all local devices.
        Groups pad their task axis to a multiple of the data size by
        repeating the last task; the copies are sliced off before the
        fetch.  Without a mesh the single-device paths are unchanged.

        ``hosts``: multi-process-shaped ingestion (defaults to the
        ``fleet_hosts`` sharding-context key).  With ``hosts=H > 1`` each
        of H "processes" builds, pads and places only its own contiguous
        block of the task axis (global row ``p`` holds the episode of
        task ``min(p, n_real - 1)``, which reproduces the global
        repeat-last padding bit-for-bit), the global arrays are assembled
        shard-by-shard via ``FleetShardingRules.assemble_tasks`` without
        any host materialising the full stack, and results come back
        through a collective-free fetch that reads only addressable
        shards.  ``H`` must divide the mesh's data size; requires
        ``mesh``.  Exercised in one process over device groups in CI
        (``--xla_force_host_platform_device_count=8``, 2 hosts x 4
        devices) — on a real multi-process mesh each process runs the
        same code over its own episode shard.

        A summary of the grouping (buckets, policy structures, compiled
        scans, host syncs, prep programs) is recorded in
        ``self.last_fleet_report``.  Each group's inputs are prepared by
        compiled calls alone (``prep_programs``: a pad per task off its
        bucket, a stack per group, a transfer per channel-index leaf);
        the probe program makes its taps and valid counts itself, so the
        call's only host syncs are its group fetches.

        Each call is one ``adapt_many`` span of :mod:`repro.telemetry`,
        tiled by the spans of its phases: ``adapt_many.bucket``, then per
        episode group ``.probe.stack``, ``.probe.run`` (dispatch up to the
        device's completion), ``.probe.fetch`` (the host copy) and
        ``.select``, then per run group ``.finetune.stack``,
        ``.finetune.run``, ``.finetune.fetch`` and ``.finish``.  A task's
        ``fisher_seconds`` is its group's probe run and fetch, and its
        ``train_seconds`` its group's fine-tune run and fetch, each shared
        over the group's tasks.
        """
        if not tasks:
            return []
        with telemetry.span("adapt_many", tasks=len(tasks)) as root:
            before = {k: telemetry.counter(k) for k in _FLEET_COUNTERS}
            out, report = self._adapt_many(
                root, tasks, profile, criterion=criterion, iters=iters,
                shard_channels=shard_channels,
                policy_override=policy_override, bucket=bucket, mesh=mesh,
                hosts=hosts)
            counts = root.counts
            counts.update({k: telemetry.counter(k) - n
                           for k, n in before.items()})
        self.last_fleet_report = dict(
            tasks=counts["tasks"], groups=counts["finetune_groups"],
            probe_groups=counts["probe_groups"],
            host_syncs=counts["host_syncs"],
            prep_programs=counts["prep_programs"], **report)
        return out

    def _adapt_many(self, root, tasks, profile, *, criterion, iters,
                    shard_channels, policy_override, bucket, mesh, hosts
                    ) -> Tuple[List["Adaptation"], Dict[str, Any]]:
        """The body of :meth:`adapt_many` inside its root span ``root``:
        returns the adaptations and the grouping summary, and sets the
        root's ``probe_groups`` and ``finetune_groups`` counts."""
        for t in tasks:
            self._check_task(t)
        if isinstance(profile, str):
            profile = device_profile(profile)
        budget = _as_budget(profile)
        prof = profile if isinstance(profile, DeviceProfile) else None
        method = criterion

        from ..dist import context as dist_context

        rules = None
        params_run = self.params
        if mesh is not None:
            from ..dist.sharding import FleetShardingRules

            rules = FleetShardingRules(mesh)
            params_run = rules.place_replicated(self.params)

        if hosts is None:
            hosts = dist_context.get("fleet_hosts")
        hosts = 1 if hosts is None else int(hosts)
        if hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {hosts}")
        hosted = hosts > 1
        if hosted:
            if rules is None:
                raise ValueError(
                    "hosts > 1 requires mesh=; per-host ingestion shards "
                    "the task axis over the mesh's data axes")
            if rules.dp_size % hosts:
                raise ValueError(
                    f"hosts ({hosts}) must divide the mesh data size "
                    f"({rules.dp_size}) so device shards never straddle "
                    "host blocks")
        fetch = _fetch_local if hosted else _fetch

        # bucket (or pass through) every episode once; keys come from the
        # padded trees so one bucket serves any way/shot mix inside it
        with telemetry.span("adapt_many.bucket"):
            eps = [_bucket_episode(t) if bucket
                   else (t.support, t.pseudo_query) for t in tasks]
            keys = [_episode_shape_key(sup, pq) for sup, pq in eps]

        fisher_dt = [0.0] * len(tasks)
        transfers = [0.0] * len(tasks)  # per-task share of group fetches

        def rows_of(idxs, lo=0, hi=None):
            """Tasks of rows ``lo..hi`` of a group's task axis, padded to
            the mesh data size: row ``p`` holds task
            ``idxs[min(p, n_real - 1)]`` (the copies of the last task are
            sliced off before the fetch)."""
            n_real = len(idxs)
            if hi is None:
                hi = n_real if rules is None else rules.padded_count(n_real)
            return [idxs[min(p, n_real - 1)] for p in range(lo, hi)]

        def stack(rows):
            """The rows' episodes stacked in one compiled call."""
            telemetry.count("prep_programs")
            return _fleet_stack_episodes([eps[i][0] for i in rows],
                                         [eps[i][1] for i in rows])

        def chan_idx(rows):
            """The rows' channel indices, stacked on the host (numpy):
            each leaf is one transfer to the device."""
            pols = [policies[i] for i in rows]
            ci = {lid: {k: np.stack([p.channel_idx[lid][k] for p in pols])
                        for k in kinds}
                  for lid, kinds in pols[0].channel_idx.items()}
            telemetry.count("prep_programs",
                            len(jax.tree_util.tree_leaves(ci)))
            return ci

        # stacked episode pytrees keyed by task-index tuple, so the probe
        # and fine-tune loops ship each task's data to the device once
        stack_cache: Dict[Tuple[int, ...], Tuple[Any, Any]] = {}

        def stacked(idxs):
            key = tuple(idxs)
            if key not in stack_cache:
                trees = stack(rows_of(idxs))
                if rules is not None:
                    trees = tuple(rules.place_tasks(t) for t in trees)
                stack_cache[key] = trees
            return stack_cache[key]

        def host_ingest(idxs, with_chan_idx):
            """Per-host episode ingestion for one group.

            Each of the H hosts builds only its own contiguous block of
            the padded task axis (:func:`rows_of`), the same values the
            global padding produces, then the global arrays are assembled
            shard-by-shard, no host holding the full stack.  Returns
            placed (sup, pq) global arrays, and the channel indices when
            ``with_chan_idx``."""
            blocks = []
            for lo, hi in rules.host_blocks(len(rows_of(idxs)), hosts):
                rows = rows_of(idxs, lo, hi)
                blocks.append(stack(rows) + (
                    (chan_idx(rows),) if with_chan_idx else ()))
            return tuple(rules.assemble_tasks(list(b)) for b in zip(*blocks))

        root.counts["probe_groups"] = 0
        if policy_override is not None:
            policies = [policy_override] * len(tasks)
            method = (f"override:"
                      f"{(policy_override.meta or {}).get('source', 'policy')}")
        else:
            mode, channel_mode = _resolve_criterion(criterion)
            if channel_mode != "dynamic":
                raise ValueError(
                    f"criterion {criterion!r} uses a static channel mode "
                    f"({channel_mode}); adapt_many supports dynamic-channel "
                    "criteria (or pass policy_override=)")
            policies = [None] * len(tasks)
            if self.backbone.fisher_reduce is None:
                # external backbone without a device-side reduction: fall
                # back to the sequential probe path (still one policy per
                # task; only the probe batching is lost)
                from .adapt import _probe_and_select

                with telemetry.span("adapt_many.select"):
                    for i, t in enumerate(tasks):
                        policies[i], fisher_dt[i], tr = _probe_and_select(
                            self.backbone, self.params, t.support,
                            t.pseudo_query, budget, max_way=self.max_way,
                            criterion=mode, shard_channels=shard_channels,
                            step_cache=self.step_cache)
                        transfers[i] = float(tr)
            else:
                shape_groups = _group_indices(keys)
                root.counts["probe_groups"] = len(shape_groups)
                for idxs in shape_groups.values():
                    # the probe program makes its taps and each task's
                    # valid count itself: the host sends the episodes only
                    with telemetry.span("adapt_many.probe.stack"):
                        sup, pq = (host_ingest(idxs, False) if hosted
                                   else stacked(idxs))
                    with telemetry.span("adapt_many.probe.run") as run_sp:
                        packed = jax.block_until_ready(
                            self.step_cache.probe_fisher_batch()(
                                params_run, sup, pq))
                    # the group's scores arrive packed: one array, one copy
                    with telemetry.span(
                            "adapt_many.probe.fetch") as fetch_sp:
                        chans_all = self.step_cache.probe_layout().unpack(
                            fetch(packed))
                    dt = (run_sp.seconds + fetch_sp.seconds) / len(idxs)
                    with telemetry.span("adapt_many.select"):
                        for j, i in enumerate(idxs):
                            chans = {k: v[j] for k, v in chans_all.items()}
                            policies[i] = select_policy(
                                self.backbone.unit_costs,
                                potentials_from_chans(
                                    self.backbone.unit_costs, chans),
                                chans, budget, criterion=mode,
                                shard_channels=shard_channels)
                            fisher_dt[i] = dt
                            transfers[i] = 1.0 / len(idxs)

        # one vmapped scan per (bucket, policy structure) group
        out: List[Optional[Adaptation]] = [None] * len(tasks)
        run_groups = _group_indices(
            [(k, self.step_cache._key(p)) for k, p in zip(keys, policies)])
        root.counts["finetune_groups"] = len(run_groups)
        compiles_before = self.step_cache.fleet_scan_compiles()
        for idxs in run_groups.values():
            pol0 = policies[idxs[0]]
            n_real = len(idxs)
            padded = rules is not None and rules.padded_count(n_real) != n_real
            with telemetry.span("adapt_many.finetune.stack"):
                if hosted:
                    sup, pq, ci = host_ingest(idxs, True)
                else:
                    sup, pq = stacked(idxs)
                    ci = chan_idx(rows_of(idxs))
                    ci = (jax.device_put(ci) if rules is None
                          else rules.place_tasks(ci))
            # publish the fleet mesh so vmap_scan_steps picks the
            # shard_map path (task axis split across the mesh's data axes)
            with dist_context.sharding_context(fleet_mesh=mesh), \
                    telemetry.span("adapt_many.finetune.run") as run_sp:
                run = self.step_cache.vmap_scan_steps(pol0, iters)
                d_stack, _, loss_stack, skip_stack = jax.block_until_ready(
                    run(params_run, sup, pq, ci))
            with telemetry.span("adapt_many.finetune.fetch") as fetch_sp:
                if padded and not hosted:
                    d_stack = jax.tree_util.tree_map(
                        lambda x: x[:n_real], d_stack)
                    loss_stack = loss_stack[:n_real]
                    skip_stack = skip_stack[:n_real]
                # one barrier fetch per group (collective-free when hosted:
                # each host reads only its addressable shards); per-task
                # views are numpy slices
                d_host, losses, skips = fetch(
                    (d_stack, loss_stack, skip_stack))
                if padded and hosted:
                    d_host = jax.tree_util.tree_map(
                        lambda x: x[:n_real], d_host)
                    losses = losses[:n_real]
                    skips = skips[:n_real]
            dt = (run_sp.seconds + fetch_sp.seconds) / n_real
            with telemetry.span("adapt_many.finish"):
                for j, i in enumerate(idxs):
                    res = AdaptResult(
                        deltas=jax.tree_util.tree_map(
                            lambda x, _j=j: x[_j], d_host),
                        policy=policies[i], fisher_seconds=fisher_dt[i],
                        train_seconds=dt,
                        losses=[float(x) for x in losses[j]],
                        host_transfers=transfers[i] + 1.0 / n_real,
                        skipped_steps=int(np.sum(skips[j])))
                    out[i] = self._wrap(method, tasks[i], prof, res,
                                        budget=budget)
        report = {
            "bucketed": bucket,
            "buckets": len(set(keys)),
            "policy_structures": len({self.step_cache._key(p)
                                      for p in policies}),
            "scan_compiles": (self.step_cache.fleet_scan_compiles()
                              - compiles_before),
            "mesh_axes": dict(mesh.shape) if mesh is not None else None,
            "hosts": hosts,
            "ingestion": ("per-host" if hosted
                          else "global" if mesh is not None else "local"),
        }
        return out, report

    def evaluate(self, task: Task, adaptation: Optional[Adaptation] = None
                 ) -> float:
        """Query accuracy: zero-shot when ``adaptation`` is None."""
        self._check_task(task)
        if adaptation is not None:
            return adaptation.accuracy(task)
        ev = self.step_cache.evaluate(None)
        return float(ev(self.params, None, task.support, task.query, None))

    def score_stream(self, tokens: Any, *, block: int = 32,
                     params: Any = None) -> np.ndarray:
        """Per-sequence mean next-token NLL of a (N, S) token batch.

        Scored on the serving *block-prefill* path (the same cached
        sequence-mode forward the engine uses to ingest prompts —
        :meth:`EpisodeStepCache.block_score`), so adaptation-time
        token-batch scoring matches deployed behaviour exactly instead of
        re-deriving a separate forward or looping per position.  ``params``
        defaults to the session's frozen weights; pass a folded copy
        (:meth:`Adaptation.fold_into`) to score an adapted model.
        """
        fn = self.step_cache.block_score(block)
        return _fetch(fn(params if params is not None else self.params,
                         jnp.asarray(tokens, jnp.int32)))

    # -- baselines (paper Sec. 3.1 zoo) ------------------------------------

    def baseline(
        self,
        name: str,
        task: Task,
        profile: Union[DeviceProfile, Budget, str],
        *,
        iters: int = 40,
        proxy_task: Optional[Task] = None,
        seed: int = 0,
        fused: bool = True,
    ) -> Adaptation:
        """Run one on-device-training baseline on a task.

        ``name``: none | fulltrain | lastlayer | sparseupdate | tinytl |
        adapterdrop<pct> | any registered criterion (tinytrain, random, ...).
        """
        self._check_task(task)
        if isinstance(profile, str):
            profile = device_profile(profile)
        if name in _CRITERIA:
            return self.adapt(task, profile, criterion=name, iters=iters,
                              seed=seed, fused=fused)
        if name == "none":
            return self._wrap(
                "none", task,
                profile if isinstance(profile, DeviceProfile) else None,
                AdaptResult(None, None, 0.0, 0.0, []),
                budget=_as_budget(profile))
        if name == "lastlayer":
            pol = self._static_policies.setdefault(
                "lastlayer",
                last_layer_policy(self.backbone.unit_costs,
                                  len(self.backbone.unit_costs)))
            return dataclasses.replace(
                self.adapt(task, profile, policy_override=pol, iters=iters,
                           fused=fused),
                method="lastlayer")
        if name == "sparseupdate":
            pol = self._sparseupdate_policy(_as_budget(profile), proxy_task,
                                            seed)
            return dataclasses.replace(
                self.adapt(task, profile, policy_override=pol, iters=iters,
                           fused=fused),
                method="sparseupdate")
        if name == "fulltrain":
            return self._fulltrain(task, iters, fused=fused)
        if name.startswith("tinytl") or name.startswith("adapterdrop"):
            return self._tinytl(name, task, iters, seed, fused=fused)
        raise KeyError(
            f"unknown baseline {name!r}; known: none, fulltrain, lastlayer, "
            f"sparseupdate, tinytl, adapterdrop<pct>, {criteria()}")

    # -- internals ---------------------------------------------------------

    def _check_task(self, task: Task) -> None:
        if task.max_way > self.max_way:
            raise ValueError(
                f"task {task.name!r} has way {task.max_way} > session "
                f"max_way {self.max_way}")

    def _wrap(self, method: str, task: Task, profile, res: AdaptResult,
              budget: Optional[Budget] = None) -> Adaptation:
        ev = self.step_cache.evaluate(res.policy)
        if res.policy is not None:
            ci = self.step_cache.chan_idx_arrays(res.policy)
        else:
            ci = None

        def _eval(sup, qry, _ev=ev, _ci=ci, _d=res.deltas):
            return float(_ev(self.params, _d, sup, qry, _ci))

        return Adaptation(
            method=method, task=task, profile=profile, budget=budget,
            deltas=res.deltas, policy=res.policy,
            fisher_seconds=res.fisher_seconds,
            train_seconds=res.train_seconds,
            losses=list(res.losses) if res.losses is not None else [],
            host_transfers=res.host_transfers,
            _session=self, _eval=_eval,
            skipped_steps=res.skipped_steps)

    def _sparseupdate_policy(self, budget: Budget,
                             proxy_task: Optional[Task], seed: int
                             ) -> SparseUpdatePolicy:
        """Offline ES policy (Lin et al. 2022) from a *proxy* task."""
        if proxy_task is None:
            raise ValueError(
                "baseline('sparseupdate') needs proxy_task= — the offline "
                "evolutionary search runs on proxy data, never the target")
        key = (budget.mem_bytes, budget.compute_frac,
               budget.channel_ratio, budget.opt_slots, budget.param_bytes,
               id(proxy_task), seed)
        if key not in self._es_cache:
            from .baselines import evolutionary_search_policy
            from .fisher import fisher_probe
            from .protonet import episode_loss

            def probe_loss(p, b, taps=None):
                return episode_loss(
                    self.backbone.features, p, proxy_task.support,
                    proxy_task.pseudo_query, self.max_way, taps=taps)

            potentials, _, _ = fisher_probe(
                self.backbone, self.params, probe_loss, proxy_task.support,
                proxy_task.n_support)
            self._es_cache[key] = (proxy_task, evolutionary_search_policy(
                self.backbone.unit_costs, potentials, budget, iters=400,
                seed=seed))
        return self._es_cache[key][1]

    def _fulltrain(self, task: Task, iters: int,
                   fused: bool = True) -> Adaptation:
        from .baselines import make_full_episode_scan, make_full_episode_step

        # the step donates its params argument: train a private copy
        p = jax.tree_util.tree_map(jnp.copy, self.params)
        st = self.baseline_optimizer.init(p)
        t0 = time.perf_counter()
        if fused and iters > 0:
            if iters not in self._full_scans:
                self._full_scans[iters] = make_full_episode_scan(
                    self.backbone.features, self.baseline_optimizer,
                    self.max_way, iters)
            p, st, loss_arr, skip_arr = self._full_scans[iters](
                p, st, task.support, task.pseudo_query)
            loss_h, skip_h = _fetch((loss_arr, skip_arr))
            losses = [float(x) for x in loss_h]
            skipped = int(np.sum(skip_h))
        else:
            if self._full_step is None:
                self._full_step = make_full_episode_step(
                    self.backbone.features, self.baseline_optimizer,
                    self.max_way)
            losses = []
            for _ in range(iters):
                p, st, loss = self._full_step(p, st, task.support,
                                              task.pseudo_query)
                losses.append(_fetch_scalar(loss))
            skipped = sum(1 for x in losses if not np.isfinite(x))
        dt = time.perf_counter() - t0

        def _eval(sup, qry, _p=p):
            from .protonet import episode_accuracy

            return float(episode_accuracy(
                self.backbone.features, _p, sup, qry, self.max_way))

        return Adaptation(
            method="fulltrain", task=task, profile=None, budget=None,
            deltas=p, policy=None, fisher_seconds=0.0, train_seconds=dt,
            losses=losses, host_transfers=1 if (fused and iters > 0) else iters,
            _session=self, _eval=_eval, skipped_steps=skipped)

    def _tinytl(self, name: str, task: Task, iters: int, seed: int,
                fused: bool = True) -> Adaptation:
        from .baselines import (
            make_tinytl_episode_scan, make_tinytl_episode_step,
            tinytl_adapter_init, tinytl_features,
        )

        if self.backbone.kind != "cnn":
            raise ValueError("tinytl/adapterdrop baselines are CNN-only")
        dropped = 0
        if name.startswith("adapterdrop"):
            frac = int(name.replace("adapterdrop", "") or "50") / 100
            n_blocks = max(s.block for s in self.backbone.cfg.layers) + 1
            dropped = int(n_blocks * frac)
        adapters = tinytl_adapter_init(self.backbone.cfg,
                                       jax.random.PRNGKey(seed))
        st = self.baseline_optimizer.init(adapters)
        t0 = time.perf_counter()
        if fused and iters > 0:
            skey = (dropped, iters)
            if skey not in self._tinytl_scans:
                self._tinytl_scans[skey] = make_tinytl_episode_scan(
                    self.backbone.cfg, self.baseline_optimizer, self.max_way,
                    dropped, iters)
            adapters, st, loss_arr, skip_arr = self._tinytl_scans[skey](
                self.params, adapters, st, task.support, task.pseudo_query)
            loss_h, skip_h = _fetch((loss_arr, skip_arr))
            losses = [float(x) for x in loss_h]
            skipped = int(np.sum(skip_h))
        else:
            if dropped not in self._tinytl_steps:
                self._tinytl_steps[dropped] = make_tinytl_episode_step(
                    self.backbone.cfg, self.baseline_optimizer, self.max_way,
                    dropped)
            step = self._tinytl_steps[dropped]
            losses = []
            for _ in range(iters):
                adapters, st, loss = step(self.params, adapters, st,
                                          task.support, task.pseudo_query)
                losses.append(_fetch_scalar(loss))
            skipped = sum(1 for x in losses if not np.isfinite(x))
        dt = time.perf_counter() - t0

        cfg, params, mw = self.backbone.cfg, self.params, self.max_way

        def _eval(sup, qry, _a=adapters):
            from .protonet import episode_accuracy

            return float(episode_accuracy(
                lambda a, b: tinytl_features(cfg, params, a, b["images"],
                                             dropped_blocks=dropped),
                _a, sup, qry, mw))

        return Adaptation(
            method=name, task=task, profile=None, budget=None,
            deltas=adapters, policy=None, fisher_seconds=0.0,
            train_seconds=dt, losses=losses,
            host_transfers=1 if (fused and iters > 0) else iters,
            _session=self, _eval=_eval, skipped_steps=skipped)
