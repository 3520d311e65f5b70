"""Bring-up smoke of the main path on one TPU chip.

Runs, in this one process, the three things a user of the repo does,
through the same entry points and at published widths:

1. adapt — TinyTrain's Algorithm 1 on the paper's edge CNN
   (``mcunet`` at its registered 84 px input, 40 iterations), then a mixed
   way/shot fleet through ``TinyTrainSession.adapt_many``;
2. train — ``repro.launch.train`` at ``--arch qwen2-1.5b --preset full
   --mode tinytrain`` (bf16) for a few steps: Fisher probe, sparse steps,
   checkpoint;
3. serve — a paged, personalised ``ServeEngine`` at qwen2-1.5b width on
   the policy and deltas from phase 2, then the attention and Fisher
   kernels against their ``jax.numpy`` forms at the engine's shapes.

Each phase prints one JSON line (compile seconds, step or tick time, peak
device bytes); any failed check raises and the script exits non-zero.  The
last line is the device record, printed only when every phase passed::

    python chip_smoke.py                 # one chip, the default
    python chip_smoke.py --chips 4       # cross-chip parity only

``--chips 4`` runs only the paths that span chips, each against its
one-device twin: ``adapt_many(mesh=...)``, a four-replica ``FleetRouter``
and the sharded sparse train step.  The script refuses to report a result
without a TPU; ``--preset smoke`` runs the phases at toy sizes for a
rehearsal on the CPU (Pallas in interpret mode) and then exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

LM = "qwen2-1.5b"
_COMPILE = {"seconds": 0.0}


def _on_event(event: str, duration: float, **_) -> None:
    # backend compile, persistent-cache retrieval included
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += duration


class Phase:
    """Times one phase and prints its JSON line on exit."""

    def __init__(self, name: str):
        self.name = name
        self.out = {}

    def __enter__(self):
        self.c0 = _COMPILE["seconds"]
        self.t0 = time.perf_counter()
        return self.out

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        stats = jax.devices()[0].memory_stats() or {}
        line = {"phase": self.name,
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": _COMPILE["seconds"] - self.c0,
                **self.out,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        print(json.dumps(line), flush=True)
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _shapes(tree):
    """Abstract twin of a call's arguments, for re-lowering it."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def _assert_kernel(text: str, what: str, on_tpu: bool) -> None:
    if on_tpu:
        check("tpu_custom_call" in text,
              f"{what}: no Mosaic kernel (tpu_custom_call) in compiled HLO")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _cnn_tasks(rng, res, n):
    """n unpadded tasks of mixed way and shot across the CDFSL domains."""
    combos = [(2, 3), (3, 5), (5, 1), (4, 8)]
    domains = ["glyphs", "stripes", "rings", "checkers"]
    tasks = []
    for i in range(n):
        way, shot = combos[i % len(combos)]
        tasks.append(api.sample_task(
            rng, domains[i % len(domains)], res=res, max_way=8,
            min_way=way, support_pad=None, query_pad=None,
            max_support_total=way * shot, max_support_per_class=shot,
            query_per_class=4))
    return tasks


def _check_adaptations(adaptations, what):
    """Finite losses, no skipped step, and query accuracy above chance
    (for a fleet: its mean against the mean chance).  Returns the
    accuracies."""
    accs, chance = [], []
    for a in adaptations:
        check(np.all(np.isfinite(a.losses)), f"{what}: non-finite losses")
        check(a.skipped_steps == 0,
              f"{what}: {a.skipped_steps} non-finite steps skipped")
        accs.append(a.accuracy())
        chance.append(1.0 / (1 + int(np.max(np.asarray(
            a.task.support["episode_labels"])))))
    check(np.mean(accs) > np.mean(chance),
          f"{what}: query accuracy {accs} not above chance {chance}")
    return accs


def phase_adapt(args, rng):
    res = 32 if args.preset == "smoke" else 84
    iters = 10 if args.preset == "smoke" else 40
    with Phase("adapt") as out:
        bb = api.backbone("mcunet", in_res=res)
        session = api.TinyTrainSession(bb, max_way=8, seed=args.seed)
        task = api.sample_task(rng, "glyphs", res=res, max_way=8,
                               support_pad=64, query_pad=96)
        t0 = time.perf_counter()
        one = session.adapt(task, api.JETSON_NANO, iters=iters)
        first_s = time.perf_counter() - t0
        _check_adaptations([one], "adapt")
        # same policy structure again: cached programs, no compile
        t0 = time.perf_counter()
        warm = session.adapt(task, api.JETSON_NANO, iters=iters)
        warm_s = time.perf_counter() - t0
        tasks = _cnn_tasks(rng, res, 8)
        session.adapt_many(tasks, api.JETSON_NANO, iters=iters)  # compiles
        t0 = time.perf_counter()
        fleet = session.adapt_many(tasks, api.JETSON_NANO, iters=iters)
        fleet_s = time.perf_counter() - t0
        fleet_acc = _check_adaptations(fleet, "adapt_many")
        out.update(in_res=res, iters=iters, policy=one.policy.describe(),
                   accuracy=one.accuracy(), fleet_accuracy=fleet_acc,
                   adapt_first_s=first_s,
                   adapt_warm_s=warm_s,
                   step_ms=1e3 * warm.train_seconds / iters,
                   fleet_tasks=len(tasks), fleet_warm_s=fleet_s,
                   fleet_groups=session.last_fleet_report["groups"])


def phase_train(args, on_tpu):
    from repro.core.fisher import probe_fn
    from repro.launch import train

    # the trainer resumes from a checkpoint it finds: start clean
    out_dir = os.path.join(ROOT, "results", "chip_smoke")
    shutil.rmtree(out_dir, ignore_errors=True)

    steps, batch, seq = (6, 4, 64) if args.preset == "smoke" else (6, 4, 256)
    with Phase("train") as out:
        res = train.main([
            "--arch", LM, "--preset", args.preset, "--mode", "tinytrain",
            "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--lr", "1e-2", "--seed", str(args.seed),
            "--ckpt-dir", os.path.join(out_dir, "ckpt"),
            "--ckpt-every", str(steps)])
        losses = res["losses"]
        check(len(losses) == steps and np.all(np.isfinite(losses)),
              f"train: losses {losses}")
        check(losses[-1] < losses[0],
              f"train: last loss {losses[-1]} not below first {losses[0]}")
        check(res["policy"].n_units > 0, "train: policy selected no units")
        # the LM probe must run the Pallas fisher kernel, not an oracle
        bb = api.backbone(LM, preset=args.preset, batch_size=batch, seq=seq)
        toks = jnp.zeros((batch, seq), jnp.int32)
        probe_args = (res["params"], {"tokens": toks, "labels": toks},
                      bb.make_taps(batch), jnp.float32(batch))
        text = probe_fn(bb, lambda p, b, taps=None: bb.loss(p, b, taps=taps)
                        ).lower(*_shapes(probe_args)).compile().as_text()
        _assert_kernel(text, "LM Fisher probe", on_tpu)
        out.update(arch=res["cfg"].name, dtype=res["cfg"].dtype,
                   steps=steps, batch=batch, seq=seq,
                   policy=res["policy"].describe(),
                   loss_first=losses[0], loss_last=losses[-1],
                   first_step_s=res["step_seconds"][0],
                   step_ms=1e3 * float(np.median(res["step_seconds"][1:])))
    return dict(res, batch=batch)


def _record_scan_args(eng):
    """Wrap the engine's compiled chunk program to keep its argument
    shapes, so the exact serve program can be re-lowered for its HLO."""
    fn = eng.scan_ticks(eng.chunk)
    seen = {}

    def recorder(*a):
        seen.setdefault("args", _shapes(a))
        return fn(*a)

    eng._scan_cache[eng.chunk] = recorder
    return fn, seen


def phase_serve(args, rng, trained, on_tpu):
    from repro.serving.engine import DeltaSet

    cfg, params, policy = trained["cfg"], trained["params"], trained["policy"]
    deltas = trained["train_state"][0]
    lo, hi, max_new = (8, 48, 8) if args.preset == "smoke" else (64, 512, 32)
    slots, max_len = 4, -(-(hi + max_new + 1) // 128) * 128
    with Phase("serve") as out:
        eng = api.ServeEngine(cfg, params, slots=slots, max_len=max_len,
                              kv_paging=True, personalise=policy)
        fn, seen = _record_scan_args(eng)
        ds = DeltaSet.from_policy(policy, deltas)

        def requests(n, sizes):
            return [api.Request(
                uid=i, prompt=rng.integers(0, cfg.vocab, size=int(s)
                                           ).astype(np.int32),
                max_new=max_new, delta_set=ds if i % 2 == 0 else None)
                for i, s in zip(range(n), sizes)]

        t0 = time.perf_counter()
        eng.run(requests(1, [lo]))  # compiles the chunk program
        warm_s = time.perf_counter() - t0
        reqs = requests(8, rng.integers(lo, hi + 1, size=8))
        t0 = time.perf_counter()
        eng.run(reqs)
        run_s = time.perf_counter() - t0
        rep = eng.last_run_report
        outcomes = [r.outcome for r in reqs]
        check(all(o == "done" for o in outcomes),
              f"serve: outcomes {outcomes}")
        check(all(len(r.out) == max_new for r in reqs),
              "serve: a request ended short of max_new")
        text = fn.lower(*seen["args"]).compile().as_text()
        _assert_kernel(text, "serve chunk program (block tick)", on_tpu)
        errs = _kernel_checks(eng, cfg, trained["batch"])
        ticks = rep.get("ticks_dispatched", rep.get("ticks"))
        out.update(arch=cfg.name, slots=slots, max_len=max_len,
                   page_size=eng.spec.page_size, n_pages=eng.spec.n_pages,
                   prefill_block=eng.prefill_block,
                   prompt_tokens=int(sum(len(r.prompt) for r in reqs)),
                   new_tokens=int(sum(len(r.out) for r in reqs)),
                   first_run_s=warm_s, run_s=run_s, ticks=ticks,
                   host_syncs=rep.get("host_syncs"),
                   tick_ms=1e3 * run_s / max(int(ticks), 1),
                   kernel_max_abs_err=errs)


def _kernel_checks(eng, cfg, n):
    """Kernels against their jnp forms at the engine's and the probe's
    (``n`` samples) shapes, on this device.  Returns the max abs error
    per check."""
    from repro.kernels import ops
    from repro.models.layers import dot_attention
    from repro.serving import paging as PG

    spec, slots = eng.spec, eng.n_slots
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    errs = {}

    def near(name, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        errs[name] = err
        check(err <= tol * max(1.0, float(np.max(np.abs(want)))),
              f"kernel {name}: max abs error {err} above {tol}")

    def rnd(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    kp = rnd((spec.n_pages, hkv, spec.page_size, d))
    vp = rnd((spec.n_pages, hkv, spec.page_size, d))
    perm = jax.random.permutation(next(keys), spec.n_pages)
    perm = perm[:slots * spec.max_pages].reshape(slots, spec.max_pages)
    cap = spec.cap
    for sq in (1, eng.prefill_block):
        q = rnd((slots, sq, hq, d))
        kv_len = jnp.asarray(np.linspace(sq, cap, slots).astype(np.int32))
        q_off = kv_len - sq
        # pages past each slot's length unmapped (-1), as the engine leaves
        col = jnp.arange(spec.max_pages)[None, :] * spec.page_size
        table = jnp.where(col < kv_len[:, None], perm, -1).astype(jnp.int32)
        kc = PG.read_rows({"pages": kp}, table, spec, dt)  # (slots, cap, ..)
        vc = PG.read_rows({"pages": vp}, table, spec, dt)
        want = dot_attention(q.astype(jnp.float32), kc.astype(jnp.float32),
                             vc.astype(jnp.float32), causal=True,
                             q_offset=q_off, kv_len=kv_len)
        got = ops.paged_flash_attention(q, kp, vp, table, q_offset=q_off,
                                        kv_len=kv_len, block_q=sq)
        near(f"paged_flash_sq{sq}", got, want, 2e-2)
        got = ops.flash_attention(q, kc, vc, causal=True, block_q=sq,
                                  block_k=ops._divisor_block(cap, 512),
                                  q_offset=q_off, kv_len=kv_len)
        near(f"cached_flash_sq{sq}", got, want, 2e-2)
    # Fisher: the probe's (L, B, C) tap gradients, MLP and attention
    for name, c in (("mlp", cfg.d_ff), ("attn", cfg.n_heads)):
        g = jax.random.normal(next(keys), (cfg.n_layers, n, c), jnp.float32)
        want = jnp.sum(g * g, axis=1) / (2.0 * n)
        got = ops.fisher_tapgrads(g, jnp.float32(n))
        near(f"fisher_tapgrads_{name}", got, want, 1e-4)
    return errs


# ---------------------------------------------------------------------------
# four chips: cross-chip paths against their one-device twins
# ---------------------------------------------------------------------------


def phase_mesh_adapt(args, rng):
    from repro.launch.mesh import make_mesh

    res = 32 if args.preset == "smoke" else 84
    with Phase("mesh_adapt") as out:
        bb = api.backbone("mcunet", in_res=res)
        session = api.TinyTrainSession(bb, max_way=8, seed=args.seed)
        tasks = _cnn_tasks(rng, res, 8)
        mesh = make_mesh((4,), ("data",))
        sharded = session.adapt_many(tasks, api.JETSON_NANO, iters=10,
                                     mesh=mesh)
        check(session.last_fleet_report["mesh_axes"] == {"data": 4},
              "mesh_adapt: fleet did not run on the 4-way mesh")
        single = session.adapt_many(tasks, api.JETSON_NANO, iters=10)
        worst = 0.0
        for m, s in zip(sharded, single):
            check(m.policy.units == s.policy.units,
                  "mesh_adapt: policies differ from one device")
            np.testing.assert_allclose(m.losses, s.losses, rtol=1e-2,
                                       atol=1e-3)
            worst = max(worst, float(np.max(np.abs(
                np.asarray(m.losses) - np.asarray(s.losses)))))
        _check_adaptations(sharded, "mesh_adapt")
        out.update(tasks=len(tasks), max_abs_loss_diff=worst)


def phase_fleet(args, rng):
    from repro import configs
    from repro.models import transformer as T

    preset = "smoke" if args.preset == "smoke" else "100m"
    cfg = configs.preset_config(LM, preset)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    kw = dict(slots=4, max_len=160, kv_paging=True)
    prompts = [rng.integers(0, cfg.vocab, size=int(s)).astype(np.int32)
               for s in rng.integers(8, 96, size=12)]

    def reqs():
        return [api.Request(uid=i, prompt=p, max_new=16)
                for i, p in enumerate(prompts)]

    with Phase("fleet") as out:
        router = api.FleetRouter(cfg, params, replicas=4, **kw)
        homes = {next(iter(jax.tree_util.tree_leaves(e.params)[0].devices()))
                 for e in router.engines}
        check(len(homes) == 4,
              f"fleet: replica params on {len(homes)} devices, not 4")
        a = reqs()
        router.run(a)
        b = reqs()
        api.ServeEngine(cfg, params, **kw).run(b)
        check(all(r.outcome == "done" for r in a + b), "fleet: not all done")
        check([r.out for r in a] == [r.out for r in b],
              "fleet: greedy streams differ from one engine")
        out.update(arch=cfg.name, replicas=4, requests=len(a),
                   devices=sorted(str(d) for d in homes))


def phase_sharded_step(args):
    from repro import configs
    from repro.core.sparse import make_sparse_train_step
    from repro.dist.sharding import ShardingRules
    from repro.launch.mesh import make_debug_mesh
    from repro.models import transformer as T
    from repro.optim import adam

    preset = "smoke" if args.preset == "smoke" else "100m"
    cfg = configs.preset_config(LM, preset)
    bb = api.backbone(LM, preset=preset, batch_size=8, seq=64)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    toks = jax.random.randint(jax.random.PRNGKey(args.seed + 1), (8, 64), 0,
                              cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    with Phase("sharded_step") as out:
        policy, _ = api.plan_sparse_update(
            bb, params, batch,
            api.DeviceProfile(name="t", mem_kb=64e3, compute_frac=0.9),
            n_samples=8)
        check(policy.n_units > 0, "sharded_step: policy selected no units")
        opt = adam(1e-2)
        step = make_sparse_train_step(bb.loss, policy, opt, donate=False)

        def run(p, d, b):
            st = opt.init(d)
            losses = []
            for _ in range(3):
                d, st, loss = step(p, d, st, b)
                losses.append(float(loss))
            return losses

        one = run(params, bb.init_deltas(policy), batch)
        mesh = make_debug_mesh(4, model=2)
        rules = ShardingRules(cfg, mesh)
        d0 = bb.init_deltas(policy)
        with mesh:
            four = run(jax.device_put(params, rules.params(params)),
                       jax.device_put(d0, rules.deltas(d0)),
                       jax.device_put(batch, rules.batch(batch)))
        check(np.all(np.isfinite(four)), f"sharded_step: losses {four}")
        np.testing.assert_allclose(four, one, rtol=1e-2, atol=1e-3)
        out.update(arch=cfg.name, mesh=dict(mesh.shape), losses_1=one,
                   losses_4=four)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--preset", default="full", choices=["full", "smoke"],
                    help="full: published widths (needs a TPU); smoke: toy "
                         "sizes for a rehearsal off the chip")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.preset != "smoke":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[chip_smoke] compile cache {cache}: {entries} entries at start",
          flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    rng = np.random.default_rng(args.seed)

    if args.chips == 4:
        phase_mesh_adapt(args, rng)
        phase_fleet(args, rng)
        phase_sharded_step(args)
    else:
        phase_adapt(args, rng)
        trained = phase_train(args, on_tpu)
        phase_serve(args, rng, trained, on_tpu)

    if not on_tpu:
        print("chip_smoke: rehearsal passed on "
              f"{dev.platform}; no TPU, so no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
