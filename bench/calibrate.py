"""Readings from which a cell's limits on ``correct`` are set: for each
seed, the program's numbers, and on the first seeds the control's (the
plain reference computed in the next lower precision, in the program's
place) and those of planted faults, at the cell's own sizes on the chip.

    python bench/calibrate.py --workload adapt.mobilenetv2-0.35.fleet \
        --seeds 1,2,3,4 --faults 3

One JSON line per seed; the driver of the cell's mix says what each
reading is.  Run on the chip the cell asks for.
"""
from __future__ import annotations

import argparse
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                    "control and the planted faults")
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH_DIR)
    spec = common.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    common.enable_compile_cache(root)
    import run

    ns = types.SimpleNamespace(seed=0, seconds=0.0, trace=0)
    ctx = run.Context(root, spec, cell, ns, jax.devices()[:cell["chips"]])
    driver = common.load_module(os.path.join(
        BENCH_DIR, "drivers", ctx.traffic["driver"] + ".py"))
    driver.calibrate(ctx, [int(s) for s in args.seeds.split(",")],
                     args.faults)
    return 0


if __name__ == "__main__":
    sys.exit(main())
