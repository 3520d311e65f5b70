"""Adaptation-engine throughput: eager loop vs scan-fused vs vmapped fleet.

Measures steady-state (post-compile) tasks/sec and steps/sec for the three
online-stage execution paths:

- ``eager``: one jitted dispatch + one blocking ``float(loss)`` sync per
  fine-tune iteration (the pre-fusion behaviour, kept as ``fused=False``);
- ``fused``: the whole loop as one ``lax.scan`` dispatch, losses
  transferred once at the end;
- ``fleet``: ``TinyTrainSession.adapt_many`` — every same-structure task
  stacked and run through one vmap-of-scanned-steps call.

All paths run the same policy structure so the comparison isolates
dispatch/sync overhead, which is exactly what device residency removes.

Two heterogeneous-fleet sections measure the bucketed-padding and
mesh-sharding work: ``fleet_het_exact`` vs ``fleet_het_bucketed`` stream
fresh random way/shot mixes through ``adapt_many`` with exact-shape vs
bucketed grouping (novel shapes keep arriving, so compile cost is part of
the measured service rate — exactly what bucketing caps at O(#buckets)),
and ``fleet_het_sharded`` repeats the bucketed run on a data mesh over all
local devices when more than one is visible.

Results are appended to ``BENCH_adaptation.json`` (one record per run) so
CI accumulates a perf trajectory per PR.

    PYTHONPATH=src python -m benchmarks.adaptation_throughput --quick
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time
from typing import Dict, List

import jax
import numpy as np

from repro import api
from repro.core import adapt as adapt_mod
from repro.core.backbones import cnn_backbone
from repro.launch.mesh import make_mesh
from repro.models import edge_cnn as E

DEFAULT_OUT = "BENCH_adaptation.json"


def _backbone(arch: str, res: int, batch: int):
    if arch == "micro":
        # one IR block: per-step compute small enough that per-dispatch
        # overhead dominates — the quantity the fusion removes.  The full
        # run uses the real tiny-cnn demo backbone instead.
        cfg = E.build_ir_net("micro", [(1, 8, 1, 2, 3)], 1.0, 8, 0, res)
        return cnn_backbone(cfg, batch_size=batch)
    return api.backbone(arch, in_res=res, batch_size=batch)


def _timed(fn, reps: int):
    """Best wall-clock of ``reps`` steady-state passes (throttling-robust),
    plus the host-transfer count of the last pass and its results."""
    best, results = float("inf"), None
    for _ in range(reps):
        adapt_mod.reset_host_sync_count()
        t0 = time.perf_counter()
        results = fn()
        best = min(best, time.perf_counter() - t0)
    return best, adapt_mod.host_sync_count(), results


def run(
    *,
    arch: str = "micro",
    n_tasks: int = 8,
    iters: int = 40,
    fleet_tasks: int = 16,
    fleet_iters: int = 10,
    res: int = 12,
    max_way: int = 4,
    support_pad: int = 8,
    query_pad: int = 8,
    reps: int = 3,
    seed: int = 0,
) -> Dict[str, object]:
    bb = _backbone(arch, res, support_pad)
    session = api.TinyTrainSession(bb, max_way=max_way, seed=seed)
    rng = np.random.default_rng(seed)

    # cap episode sizes at the pads so every task shares one padded shape —
    # the same-structure fleet case the acceptance criteria measure
    def make_tasks(n):
        return [
            api.sample_task(rng, "stripes", res=res, max_way=max_way,
                            min_way=max(2, max_way // 2),
                            support_pad=support_pad, query_pad=query_pad,
                            max_support_total=support_pad,
                            max_support_per_class=max(1, support_pad // 2),
                            query_per_class=max(1, query_pad // max_way))
            for _ in range(n)
        ]

    tasks = make_tasks(n_tasks)

    # -- section 1: the fine-tune loop, eager vs scan-fused ----------------
    # one dynamic adapt picks the shared policy structure and reports the
    # probe cost; the loop paths then run policy_override so the comparison
    # isolates exactly what fusion removes (dispatch + per-iter syncs)
    probe_a = session.adapt(tasks[0], api.RPI_ZERO, iters=1)
    policy = probe_a.policy

    def eager_pass():
        return [session.adapt(t, api.RPI_ZERO, iters=iters,
                              policy_override=policy, fused=False)
                for t in tasks]

    def fused_pass():
        return [session.adapt(t, api.RPI_ZERO, iters=iters,
                              policy_override=policy)
                for t in tasks]

    paths: Dict[str, object] = {}
    for name, fn in (("eager", eager_pass), ("fused", fused_pass)):
        fn()  # warm-up: compiles out of the timed passes
        dt, syncs, results = _timed(fn, reps)
        paths[name] = {
            "iters": iters,
            "seconds_total": dt,
            "tasks_per_sec": n_tasks / dt,
            "steps_per_sec": n_tasks * iters / dt,
            "host_transfers_per_task": syncs / n_tasks,
            "final_loss_mean":
                float(np.mean([r.losses[-1] for r in results])),
        }

    # -- section 2: fleet (adapt_many) vs sequential adapt, full pipeline --
    # both sides run probe -> select -> fine-tune per task; the fleet path
    # batches the probe into one dispatch and the fine-tune into one
    # compiled call per policy structure
    ftasks = make_tasks(fleet_tasks)

    def sequential_pass():
        return [session.adapt(t, api.RPI_ZERO, iters=fleet_iters)
                for t in ftasks]

    def fleet_pass():
        return session.adapt_many(ftasks, api.RPI_ZERO, iters=fleet_iters)

    for name, fn in (("sequential", sequential_pass), ("fleet", fleet_pass)):
        fn()
        dt, syncs, results = _timed(fn, reps)
        paths[name] = {
            "iters": fleet_iters,
            "n_tasks": fleet_tasks,
            "seconds_total": dt,
            "tasks_per_sec": fleet_tasks / dt,
            "steps_per_sec": fleet_tasks * fleet_iters / dt,
            "host_transfers_per_task": syncs / fleet_tasks,
            "final_loss_mean":
                float(np.mean([r.losses[-1] for r in results])),
        }

    fisher = {"probe_seconds_single": probe_a.fisher_seconds}
    # batched probe: N tasks scored in one dispatch + one fetch
    session.adapt_many(ftasks, api.RPI_ZERO, iters=0)  # warm-up
    t0 = time.perf_counter()
    session.adapt_many(ftasks, api.RPI_ZERO, iters=0)
    fisher["probe_seconds_batched_per_task"] = \
        (time.perf_counter() - t0) / fleet_tasks

    # -- section 3: heterogeneous fleet — bucketed vs shape-exact grouping -
    # real traffic varies (way, shot) per user, so the exact-shape path
    # keeps meeting novel episode shapes and compiling new scan programs;
    # bucketed padding absorbs the same stream with O(#buckets) programs.
    # Each pass streams a FRESH random mix (novel shapes), so compile cost
    # is part of the measured service rate — the quantity bucketing caps.
    combos = [(2, 2), (3, 3), (min(4, max_way), 3), (2, 7)]

    def het_mix(seed_):
        r = np.random.default_rng(seed_)
        out = []
        for i in range(fleet_tasks):
            way, shots = combos[i % len(combos)]
            # jitter shots so successive mixes hit genuinely new shapes
            shots = shots + int(r.integers(0, 3)) * (seed_ % 3 + 1)
            out.append(api.sample_task(
                r, "stripes", res=res, max_way=max_way, min_way=way,
                support_pad=None, query_pad=None,
                max_support_total=way * shots, max_support_per_class=shots,
                query_per_class=2))
        return out

    het_reps = max(2, reps)
    mixes = [het_mix(1000 + i) for i in range(het_reps)]
    het = {"combos": len(combos), "mixes": het_reps,
           "tasks_per_mix": fleet_tasks}
    for name, bucketed in (("fleet_het_exact", False),
                           ("fleet_het_bucketed", True)):
        hsession = api.TinyTrainSession(bb, max_way=max_way, seed=seed)
        adapt_mod.reset_host_sync_count()
        t0 = time.perf_counter()
        results = []
        for mix in mixes:
            results.extend(hsession.adapt_many(
                mix, api.RPI_ZERO, iters=fleet_iters, bucket=bucketed))
        dt = time.perf_counter() - t0
        n_total = het_reps * fleet_tasks
        paths[name] = {
            "iters": fleet_iters,
            "n_tasks": n_total,
            "seconds_total": dt,
            "tasks_per_sec": n_total / dt,
            "steps_per_sec": n_total * fleet_iters / dt,
            "host_transfers_per_task": adapt_mod.host_sync_count() / n_total,
            "scan_compiles": hsession.step_cache.fleet_scan_compiles(),
            "buckets_last_mix": hsession.last_fleet_report["buckets"],
            "final_loss_mean":
                float(np.mean([r.losses[-1] for r in results])),
        }

    # -- section 4: bucketed heterogeneous fleet on a local data mesh ------
    if jax.device_count() > 1:
        mesh = make_mesh((jax.device_count(),), ("data",))
        msession = api.TinyTrainSession(bb, max_way=max_way, seed=seed)
        msession.adapt_many(mixes[0], api.RPI_ZERO, iters=fleet_iters,
                            mesh=mesh)  # warm-up
        t0 = time.perf_counter()
        results = []
        for mix in mixes:
            results.extend(msession.adapt_many(
                mix, api.RPI_ZERO, iters=fleet_iters, mesh=mesh))
        dt = time.perf_counter() - t0
        n_total = het_reps * fleet_tasks
        paths["fleet_het_sharded"] = {
            "iters": fleet_iters,
            "n_tasks": n_total,
            "devices": jax.device_count(),
            "seconds_total": dt,
            "tasks_per_sec": n_total / dt,
            "steps_per_sec": n_total * fleet_iters / dt,
            "final_loss_mean":
                float(np.mean([r.losses[-1] for r in results])),
        }

        # -- section 4b: per-host episode ingestion (hosts=2 over the same
        # mesh) — each simulated host builds only its local shard of the
        # task axis and results come back collective-free from addressable
        # shards; losses must match the global-ingestion mesh run exactly
        if jax.device_count() % 2 == 0:
            hsess = api.TinyTrainSession(bb, max_way=max_way, seed=seed)
            hsess.adapt_many(mixes[0], api.RPI_ZERO, iters=fleet_iters,
                             mesh=mesh, hosts=2)  # warm-up
            t0 = time.perf_counter()
            hresults = []
            for mix in mixes:
                hresults.extend(hsess.adapt_many(
                    mix, api.RPI_ZERO, iters=fleet_iters, mesh=mesh,
                    hosts=2))
            dt = time.perf_counter() - t0
            assert hsess.last_fleet_report["ingestion"] == "per-host"
            for hr, mr in zip(hresults, results):
                assert hr.losses == mr.losses, (
                    "per-host ingestion diverged from global mesh run")
            paths["fleet_het_perhost"] = {
                "iters": fleet_iters,
                "n_tasks": n_total,
                "devices": jax.device_count(),
                "hosts": 2,
                "ingestion": "per-host",
                "seconds_total": dt,
                "tasks_per_sec": n_total / dt,
                "steps_per_sec": n_total * fleet_iters / dt,
                "final_loss_mean":
                    float(np.mean([r.losses[-1] for r in hresults])),
            }

    record = {
        "bench": "adaptation_throughput",
        "backend": jax.default_backend(),
        "host": platform.node(),
        "devices": jax.device_count(),
        "config": {"n_tasks": n_tasks, "iters": iters,
                   "fleet_tasks": fleet_tasks, "fleet_iters": fleet_iters,
                   "res": res, "support_pad": support_pad, "backbone": arch},
        "paths": paths,
        "fisher": fisher,
        "heterogeneous": het,
        "speedup": {
            "fused_vs_eager":
                paths["fused"]["tasks_per_sec"]
                / paths["eager"]["tasks_per_sec"],
            "fleet_vs_sequential":
                paths["fleet"]["tasks_per_sec"]
                / paths["sequential"]["tasks_per_sec"],
            "het_bucketed_vs_exact":
                paths["fleet_het_bucketed"]["tasks_per_sec"]
                / paths["fleet_het_exact"]["tasks_per_sec"],
        },
    }
    return record


def run_encdec(
    *,
    archs: List[str] = ("whisper-base", "paligemma-3b"),
    n_tasks: int = 4,
    iters: int = 4,
    seq: int = 16,
    max_way: int = 3,
    pad: int = 8,
    seed: int = 0,
) -> Dict[str, object]:
    """Conditioned-decoder adaptation coverage: whisper/paligemma episodes.

    Episodes carry per-class encoder conditioning (log-mel frames / SigLIP
    patch embeddings) through the same ``build_inputs`` path serving uses;
    the fleet pass measures ``adapt_many`` tasks/sec over them."""
    from repro import configs

    paths: Dict[str, object] = {}
    for arch in archs:
        cfg = configs.get_reduced(arch)
        bb = api.backbone("lm", cfg=cfg, batch_size=2, seq=seq)
        session = api.TinyTrainSession(bb, max_way=max_way, seed=seed)
        rng = np.random.default_rng(seed)
        tasks = [api.sample_encdec_task(
                     rng, cfg, seq=seq, max_way=max_way, shots=2,
                     query_per_class=2, support_pad=pad, query_pad=pad)
                 for _ in range(n_tasks)]
        session.adapt_many(tasks, api.JETSON_NANO, iters=iters)  # warm-up
        adapt_mod.reset_host_sync_count()
        t0 = time.perf_counter()
        results = session.adapt_many(tasks, api.JETSON_NANO, iters=iters)
        dt = time.perf_counter() - t0
        paths[arch] = {
            "feat_key": "frames" if cfg.is_encoder_decoder
            else "image_embeds",
            "n_tasks": n_tasks,
            "iters": iters,
            "seconds_total": dt,
            "tasks_per_sec": n_tasks / dt,
            "host_transfers_per_task":
                adapt_mod.host_sync_count() / n_tasks,
            "accuracy_mean": float(np.mean([r.accuracy() for r in results])),
            "units_mean": float(np.mean(
                [len(r.policy.units) for r in results])),
        }
    return {
        "bench": "adaptation_throughput_encdec",
        "backend": jax.default_backend(),
        "host": platform.node(),
        "config": {"n_tasks": n_tasks, "iters": iters, "seq": seq,
                   "max_way": max_way, "pad": pad},
        "paths": paths,
    }


def write_record(record: Dict[str, object], out_path: str) -> None:
    """Append the run to the bench trajectory file (a JSON list)."""
    history: List[Dict[str, object]] = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev = json.load(f)
            history = prev if isinstance(prev, list) else [prev]
        except (json.JSONDecodeError, OSError):
            history = []
    history.append(record)
    with open(out_path, "w") as f:
        json.dump(history, f, indent=2)


def main(quick: bool = True, out_path: str = DEFAULT_OUT) -> List[str]:
    kw = (dict(arch="micro", n_tasks=8, iters=40, fleet_tasks=16,
               fleet_iters=10, res=12, max_way=4, support_pad=8,
               query_pad=8)
          if quick else
          dict(arch="tiny-cnn", n_tasks=8, iters=40, fleet_tasks=16,
               fleet_iters=20, res=48, max_way=8, support_pad=64,
               query_pad=80))
    record = run(**kw)
    write_record(record, out_path)

    out = ["path,iters,tasks_per_sec,steps_per_sec,host_transfers_per_task"]
    for name, p in record["paths"].items():
        # the sharded/per-host mesh paths fetch through shard-aware
        # helpers outside the per-task transfer counter
        ht = p.get("host_transfers_per_task")
        out.append(f"{name},{p['iters']},{p['tasks_per_sec']:.2f},"
                   f"{p['steps_per_sec']:.1f},"
                   f"{'-' if ht is None else format(ht, '.1f')}")
    sp = record["speedup"]
    out.append(f"speedup,fused_vs_eager={sp['fused_vs_eager']:.2f}x,"
               f"fleet_vs_sequential={sp['fleet_vs_sequential']:.2f}x,"
               f"het_bucketed_vs_exact={sp['het_bucketed_vs_exact']:.2f}x,"
               f"-> {out_path}")
    return out


def main_encdec(quick: bool = True, out_path: str = DEFAULT_OUT) -> List[str]:
    kw = (dict(n_tasks=4, iters=4, seq=16, max_way=3, pad=8)
          if quick else
          dict(n_tasks=8, iters=10, seq=32, max_way=4, pad=16))
    record = run_encdec(**kw)
    write_record(record, out_path)
    out = ["arch,feat_key,tasks_per_sec,accuracy_mean,units_mean"]
    for arch, p in record["paths"].items():
        out.append(f"{arch},{p['feat_key']},{p['tasks_per_sec']:.2f},"
                   f"{p['accuracy_mean']:.2f},{p['units_mean']:.1f}")
    out.append(f"-> {out_path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CPU-scale shapes (CI smoke mode)")
    ap.add_argument("--encdec", action="store_true",
                    help="conditioned-decoder (whisper/paligemma) coverage")
    ap.add_argument("--out", type=str, default=DEFAULT_OUT)
    args = ap.parse_args()
    entry = main_encdec if args.encdec else main
    for line in entry(quick=args.quick, out_path=args.out):
        print(line)
