"""Fleet adaptation: batches of users' few-shot tasks through
``TinyTrainSession.adapt_many`` (Fisher probe, budgeted selection, sparse
fine-tune per task), replayed back to back for the window.

The mix file gives the fleet: a fixed list of (way, shot) pairs, so every
seed has the same task sizes, in an order and with contents drawn from
the seed.  Set-up builds the session on seeded weights and runs every
fleet of the pool once, which compiles every program the window uses.
The window replays the pool in order; ``adapt_tasks_per_s`` is the tasks
of the calls started in the window over the time from the window's start
to the end of its last call.

``correct``: once the window has closed and the program's state is freed,
a sample of the window's tasks (drawn from the seed, the largest task
always in it) goes through the plain reference at the same sizes, with
the program's own channel picks:

- ``pick_gap``: per selected layer, how far the reference's Fisher score
  (Eq. 2) of the best channel the program left out lies above that of the
  worst channel it took, over the layer's best score: the batched probe
  and the host's selection from it;
- ``loss_gap``: the program's loss at steps 1-3 against the reference's,
  relative;
- ``delta_gap``: per selected layer, the gap between the norms of the
  program's and the reference's deltas after the last step, over the
  larger of that layer's reference norm and the task's median layer norm.
  Layers whose first reference gradient is under a thousandth of the
  median layer's are left out (they move by rounding alone).
"""
from __future__ import annotations

import json
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import flops


# ---------------------------------------------------------------------------
# traffic: few-shot image tasks
# ---------------------------------------------------------------------------


def _images(rng: np.random.Generator, protos: np.ndarray, labels: np.ndarray,
            res: int, noise: float, shift: int) -> np.ndarray:
    """Class prototypes plus noise, each image rolled by a random shift."""
    x = protos[labels] + noise * rng.standard_normal(
        (len(labels), res, res, 3), dtype=np.float32)
    sh = rng.integers(-shift, shift + 1, size=(len(labels), 2))
    for i, (a, b) in enumerate(sh):
        x[i] = np.roll(x[i], (int(a), int(b)), axis=(0, 1))
    return x


def image_task(rng: np.random.Generator, way: int, shot: int,
               mix: Dict[str, Any], res: int):
    """One task: ``way`` smooth random class patterns, ``shot`` support
    images each, and a pseudo-query set made by flipping, shifting and
    adding noise to the support images (Hu et al. 2022)."""
    from repro import api

    coarse = rng.standard_normal((way, 6, 6, 3)).astype(np.float32)
    reps = -(-res // 6)
    protos = np.kron(coarse, np.ones((1, reps, reps, 1), np.float32))
    protos = protos[:, :res, :res]
    y = np.repeat(np.arange(way, dtype=np.int32), shot)
    sx = _images(rng, protos, y, res, mix["noise"], mix["shift"])
    qx = sx.copy()
    flip = rng.random(len(y)) < 0.5
    qx[flip] = qx[flip, :, ::-1]
    qx = _images(rng, qx, np.arange(len(y)), res, mix["aug_noise"],
                 mix["shift"])
    sup = {"images": jnp.asarray(sx), "episode_labels": jnp.asarray(y)}
    pq = {"images": jnp.asarray(qx), "episode_labels": jnp.asarray(y)}
    return api.Task(name=f"{way}x{shot}", support=sup, query=pq,
                    pseudo_query=pq, max_way=mix["max_way"])


def make_pool(seed: int, mix: Dict[str, Any], res: int) -> List[List[Any]]:
    """The fleets of one run, each task ``res`` px square."""
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(mix["pool_fleets"]):
        order = rng.permutation(len(mix["fleet"]))
        pool.append([image_task(rng, *mix["fleet"][i], mix, res)
                     for i in order])
    return pool


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(ctx) -> Dict[str, Any]:
    from repro import api

    mix, conf = ctx.traffic, ctx.conf
    params = ctx.family.build_weights(conf, ctx.seed)
    bb = ctx.family.backbone(conf, mix)
    session = api.TinyTrainSession(bb, params=params, lr=mix["lr"],
                                   max_way=mix["max_way"])
    kw = dict(iters=mix["iters"], criterion=mix["criterion"],
              bucket=mix["bucket"])
    profile = mix["profile"]
    with ctx.spans.span("generate"):
        pool = make_pool(ctx.seed, mix, conf["in_res"])
    for fleet in pool:
        session.adapt_many(fleet, profile, **kw)
    jax.effects_barrier()

    calls = []  # (fleet index, adaptations, start, end)
    with ctx.window() as t0:
        import time

        i = 0
        while time.perf_counter() - t0 < ctx.seconds:
            a = time.perf_counter()
            with ctx.spans.span("adapt_many"):
                out = session.adapt_many(pool[i % len(pool)], profile, **kw)
            calls.append((i % len(pool), out, a, time.perf_counter()))
            i += 1
    t_end = calls[-1][3]
    n_tasks = sum(len(c[1]) for c in calls)

    failed = sum(1 for c in calls for a in c[1]
                 if a.skipped_steps or not np.all(np.isfinite(a.losses)))
    counters = {
        "tasks": n_tasks,
        "calls": len(calls),
        "window_s": t_end - t0,
        "fisher_seconds": sum(a.fisher_seconds for c in calls for a in c[1]),
        "compiles_in_window": ctx.compiles.between(t0, t_end),
        "flops": sum(
            flops.cnn_adapt_flops(
                conf, a.task.n_support, mix["iters"],
                {u.layer: u.n_channels for u in a.policy.units})
            for c in calls for a in c[1]),
    }
    e2e = {"adapt_tasks_per_s": n_tasks / (t_end - t0)}

    ctx.read_memory()
    sample = _sample(ctx.seed, calls, mix["check_tasks"])
    del session, bb
    ctx.free()
    checks, correct = check(ctx, params, sample)
    return {"correct": correct, "attempted": n_tasks, "failed": failed,
            "e2e": e2e, "counters": counters, "checks": checks}


def _sample(seed: int, calls, k: int):
    """(task, adaptation) pairs drawn from the seed, with the task of the
    most support rows always among them."""
    flat = [a for c in calls for a in c[1]]
    rng = np.random.default_rng(seed + 1)
    biggest = max(range(len(flat)), key=lambda j: flat[j].task.n_support)
    rest = [j for j in range(len(flat)) if j != biggest]
    pick = [biggest] + list(rng.choice(rest, size=min(k - 1, len(rest)),
                                       replace=False))
    return [(flat[j].task, flat[j]) for j in pick]


def outcome(task, ad) -> Dict[str, Any]:
    """What the program made of one task: its channel picks, its losses
    and its deltas per selected layer."""
    return {"task": task,
            "units": {u.layer: np.asarray(u.channels)
                      for u in ad.policy.units},
            "losses": np.asarray(ad.losses, np.float64),
            "deltas": {u.layer: np.asarray(ad.deltas[f"L{u.layer}"]["conv"]
                                           ["w"], np.float64)
                       for u in ad.policy.units}}


def check(ctx, params, sample):
    """Reference readings of the sampled tasks; returns (checks, correct).
    A number with no limit yet is reported and counts as not correct."""
    ref = Reference(ctx.conf, ctx.traffic, params)
    readings = ref.compare([outcome(t, a) for t, a in sample])
    checks = [(name, readings[name], ctx.limits.get(name))
              for name in NUMBERS]
    return checks, all(lim is not None and v <= lim
                       for _, v, lim in checks)


NUMBERS = ("pick_gap", "loss_gap", "delta_gap")


class Reference:
    """The plain reference at the sampled tasks' own sizes, each padded to
    the mix's ``ref_rows`` with label -1 (which counts nowhere) so one
    compiled program serves every task, under ``highest`` matmul
    precision.  Float32 readings are kept per task and
    policy, as the program's outcome and the planted faults share them."""

    def __init__(self, conf, mix, params):
        self.conf, self.mix, self.params = conf, mix, params
        self.fns: Dict[Any, Any] = {}
        self.memo: Dict[Any, Any] = {}

    def fn(self, mode: str):
        from reference import edge_cnn as ref

        if mode not in self.fns:
            dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
            self.fns[mode] = jax.jit(ref.make_task_reference(
                self.conf, self.mix["iters"], self.mix["lr"],
                self.mix["max_way"], self.mix["temperature"], dtype,
                quant=mode == "fp8"))
        return self.fns[mode]

    def run(self, task, units, mode: str = "f32", pq_labels=None):
        from reference import edge_cnn as ref

        key = (id(task), tuple((l, tuple(np.asarray(c).tolist()))
                               for l, c in sorted(units.items())))
        if mode == "f32" and pq_labels is None and key in self.memo:
            return self.memo[key]
        rows = self.mix["ref_rows"]
        qy = (task.pseudo_query["episode_labels"] if pq_labels is None
              else pq_labels)
        masks = [jnp.asarray(m) for m in ref.channel_masks(self.conf, units)]
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(self.fn(mode)(
                self.params, _pad(task.support["images"], rows, 0),
                _pad(task.support["episode_labels"], rows, -1),
                _pad(task.pseudo_query["images"], rows, 0),
                _pad(qy, rows, -1), masks, jnp.float32(task.n_support)))
        if mode == "f32" and pq_labels is None:
            self.memo[key] = out
        return out

    def compare(self, outcomes) -> Dict[str, Any]:
        """The numbers of ``correct`` for a list of outcomes, and the
        loss gap of each of the first three steps apart."""
        pick = delta = 0.0
        by_step = np.zeros(3)
        for o in outcomes:
            units = o["units"]
            fisher, losses, first, d = self.run(o["task"], units)
            for layer, ch in units.items():
                fi = np.asarray(fisher[layer], np.float64)
                top = float(fi.max())
                left = np.delete(fi, ch)
                if top > 0 and left.size:
                    pick = max(pick, (float(left.max())
                                      - float(fi[ch].min())) / top)
            k = min(3, len(losses))
            lr = np.asarray(losses[:k], np.float64)
            by_step[:k] = np.maximum(by_step[:k], np.abs(o["losses"][:k] - lr)
                                     / np.abs(lr))
            gnorm = {l: float(np.linalg.norm(first[l])) for l in units}
            gmed = float(np.median(list(gnorm.values())))
            rnorm = {l: float(np.linalg.norm(d[l])) for l in units}
            rmed = float(np.median(list(rnorm.values())))
            for l in units:
                if gnorm[l] < 1e-3 * gmed:
                    continue
                pn = float(np.linalg.norm(o["deltas"][l]))
                delta = max(delta, abs(pn - rnorm[l]) / max(rnorm[l], rmed))
        loss = float(by_step.max())
        if not all(math.isfinite(v) for v in (pick, loss, delta)):
            pick = loss = delta = float("inf")
        return {"pick_gap": pick, "loss_gap": loss, "delta_gap": delta,
                "loss_gap_by_step": by_step.tolist()}

    def control(self, outcomes, mode: str) -> List[Dict[str, Any]]:
        """The control in the program's place: the reference in a lower
        precision (``fp8`` operands or all ``bf16``) picking its own top-K
        channels by its own Fisher scores in the program's layers."""
        out = []
        for o in outcomes:
            fisher = self.run(o["task"], o["units"], mode)[0]
            units = {l: np.sort(np.argsort(-np.asarray(fisher[l],
                                                       np.float64))[:len(ch)])
                     for l, ch in o["units"].items()}
            _, losses, _, d = self.run(o["task"], units, mode)
            out.append({"task": o["task"], "units": units,
                        "losses": np.asarray(losses, np.float64),
                        "deltas": {l: np.asarray(d[l], np.float64)
                                   for l in units}})
        return out

    def reversed_picks(self, outcomes) -> List[Dict[str, Any]]:
        """A planted fault in the program's place: in each selected layer
        the channels of the lowest reference Fisher scores are taken, as
        many as the program took, and fine-tuned."""
        out = []
        for o in outcomes:
            fisher = self.run(o["task"], o["units"])[0]
            units = {l: np.sort(np.argsort(np.asarray(fisher[l],
                                                      np.float64))[:len(ch)])
                     for l, ch in o["units"].items()}
            _, losses, _, d = self.run(o["task"], units)
            out.append({"task": o["task"], "units": units,
                        "losses": np.asarray(losses, np.float64),
                        "deltas": {l: np.asarray(d[l], np.float64)
                                   for l in units}})
        return out

    def half_batch(self, outcomes) -> List[Dict[str, Any]]:
        """A planted fault in the program's place: every second
        pseudo-query row left out, the loss a mean over the rest."""
        out = []
        for o in outcomes:
            y = jnp.asarray(o["task"].pseudo_query["episode_labels"])
            y = jnp.where(jnp.arange(y.shape[0]) % 2 == 1, -1, y)
            _, losses, _, d = self.run(o["task"], o["units"], pq_labels=y)
            out.append(dict(o, losses=np.asarray(losses, np.float64),
                            deltas={l: np.asarray(d[l], np.float64)
                                    for l in o["units"]}))
        return out


def _pad(x, rows: int, fill):
    x = jnp.asarray(x)
    width = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=fill)


def calibrate(ctx, seeds: List[int], faults: int = 3
               ) -> List[Dict[str, Any]]:
    """Readings for setting the limits, at the cell's own sizes, one
    adapt_many call of each seed's fleet: the program's, and on the first
    ``faults`` seeds the control's (the reference with float8 operands in
    the program's place) and those of planted faults (half of the batch
    left out; the lowest-scored channels taken)."""
    from repro import api

    mix, conf = ctx.traffic, ctx.conf
    session = ref = None
    rows = []
    for seed in seeds:
        params = ctx.family.build_weights(conf, seed)
        if session is None:
            session = api.TinyTrainSession(ctx.family.backbone(conf, mix),
                                           params=params, lr=mix["lr"],
                                           max_way=mix["max_way"])
            ref = Reference(conf, mix, params)
        # one session and one reference for every seed, so each program
        # compiles once; only the weights change
        session.params = ref.params = params
        ref.memo.clear()
        fleet = make_pool(seed, mix, conf["in_res"])[0]
        out = session.adapt_many(fleet, mix["profile"], iters=mix["iters"],
                                 criterion=mix["criterion"],
                                 bucket=mix["bucket"])
        sample = _sample(seed, [(0, out, 0.0, 0.0)], mix["check_tasks"])
        prog = [outcome(t, a) for t, a in sample]
        row = {"seed": seed, "program": ref.compare(prog),
               "structures": session.last_fleet_report["policy_structures"]}
        if len(rows) < faults:
            row["control_fp8"] = ref.compare(ref.control(prog, "fp8"))
            row["half_batch"] = ref.compare(ref.half_batch(prog))
            row["reversed_picks"] = ref.compare(ref.reversed_picks(prog))
        rows.append(row)
        print(json.dumps(rows[-1]), flush=True)
    return rows
