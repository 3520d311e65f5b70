"""End-to-end training driver.

Runs TinyTrain sparse fine-tuning (or FullTrain) of any registered arch on
the synthetic token pipeline, with fault-tolerant checkpointing.  On the CPU
container use ``--preset smoke`` / ``--preset 100m``; on a real pod the same
driver runs the full configs with the production mesh.

The device envelope comes from the façade: pick a preset with ``--device
rpi-zero`` or override it ad hoc with ``--mem-budget-mb``/``--compute-frac``.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --preset smoke --steps 50 --mode tinytrain
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import api, configs
from ..core.baselines import make_full_train_step
from ..core.sparse import make_sparse_train_step
from ..data import TokenLoader
from ..models import transformer as T
from ..optim import adam, warmup_cosine
from ..runtime import Trainer, TrainerConfig
from ..utils import enable_compile_cache
from .mesh import make_debug_mesh, make_production_mesh

# kept for older callers; the canonical resolver lives in repro.configs
preset_config = configs.preset_config


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return what
    the run produced: ``cfg``, ``params``, ``policy`` (None for
    ``--mode full``), the final ``train_state``, ``losses`` and per-step
    wall ``step_seconds`` (the first includes compilation)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="tinytrain", choices=["tinytrain", "full"])
    ap.add_argument("--device", default=None,
                    help="device profile preset (e.g. rpi-zero, jetson-nano)")
    ap.add_argument("--mem-budget-mb", type=float, default=64.0)
    ap.add_argument("--compute-frac", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the token stream")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.preset_config(args.arch, args.preset)
    mesh = (make_production_mesh() if args.production_mesh
            else make_debug_mesh(len(jax.devices())))
    print(f"[train] arch={cfg.name} mode={args.mode} mesh={dict(mesh.shape)}")

    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"[train] params: {n_params/1e6:.1f}M")

    loader = TokenLoader(cfg.vocab, global_batch=args.batch, seq=args.seq,
                         seed=args.seed)
    lr = warmup_cosine(args.lr, args.steps, warmup_steps=max(1, args.steps // 20))
    opt = adam(lr)
    bb = api.backbone(args.arch, preset=args.preset,
                      batch_size=args.batch, seq=args.seq)

    if args.device:
        if args.mem_budget_mb != 64.0 or args.compute_frac != 0.5:
            print("[train] WARNING: --device overrides "
                  "--mem-budget-mb/--compute-frac")
        profile = api.device_profile(args.device)
    else:
        profile = api.DeviceProfile(name="cli",
                                    mem_kb=args.mem_budget_mb * 1e3,
                                    compute_frac=args.compute_frac)

    policy = None
    with mesh:
        if args.mode == "full":
            step = make_full_train_step(
                lambda p, b: T.lm_loss(cfg, p, b), opt)

            def step_fn(ts, batch):
                p, ost = ts
                b = {k: jnp.asarray(v) for k, v in batch.items()}
                p, ost, loss = step(p, ost, b)
                return (p, ost), loss

            init_state = (params, opt.init(params))
        else:
            # TinyTrain Algorithm 1: probe once, select, then sparse steps
            probe = {k: jnp.asarray(v) for k, v in loader.next().items()}
            t0 = time.perf_counter()
            policy, fisher_dt = api.plan_sparse_update(
                bb, params, probe, profile, n_samples=args.batch)
            print(f"[train] device={profile.name} fisher {fisher_dt:.1f}s "
                  f"(total selection {time.perf_counter()-t0:.1f}s)")
            print(f"[train] policy: {policy.describe()}")
            deltas = bb.init_deltas(policy)
            step = make_sparse_train_step(bb.loss, policy, opt, donate=False)

            def step_fn(ts, batch):
                d, ost = ts
                b = {k: jnp.asarray(v) for k, v in batch.items()}
                d, ost, loss = step(params, d, ost, b)
                return (d, ost), loss

            init_state = (deltas, opt.init(deltas))

        tc = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                           ckpt_dir=args.ckpt_dir)
        trainer = Trainer(tc, step_fn, loader)
        t0 = time.perf_counter()
        state = trainer.run(init_state)
        dt = time.perf_counter() - t0
    # a resumed run whose checkpoint already covers --steps executes zero
    # new steps and records no losses
    final = (f"final loss {trainer.losses[-1]:.4f}" if trainer.losses
             else "no new steps (checkpoint already at --steps)")
    print(f"[train] done: {state.step} steps in {dt:.1f}s "
          f"({dt/max(state.step,1)*1e3:.0f} ms/step), {final}")
    return {"cfg": cfg, "params": params, "policy": policy,
            "train_state": state.train_state, "losses": trainer.losses,
            "step_seconds": trainer.step_seconds}


if __name__ == "__main__":
    main()
