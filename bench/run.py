"""Run one benchmark cell on the chip this process finds, and print its
result as the last line of standard output.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the configuration file it names (``bench/configs/<config>.json``), whose
  ``family`` selects ``bench/families/<family>.py`` (how the program is
  built from the file, and seeded weights) and ``bench/reference/`` (the
  plain float32 reference);
- the traffic mix ``bench/traffic/<traffic>.json``, whose ``driver``
  selects ``bench/drivers/<driver>.py`` (the generator and the window);
- the cell's limits on the numbers that decide ``correct``,
  ``bench/limits/<cell>.json``;
- one reader per metric, ``bench/metrics/<metric>.py``.

With ``--trace 0`` the line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from the bench's spans, the
program's counters and a profiler trace of the window.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import common  # noqa: E402


class Context:
    """What a driver gets: the cell's files, the seed, the spans and the
    window, and the hooks that record set-up and device memory."""

    def __init__(self, root: str, spec: Dict[str, Any], cell: Dict[str, Any],
                 args, devices):
        self.root = root
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.tracing = bool(args.trace)
        conf_entry = next(c for c in spec["configs"]
                          if c["name"] == cell["config"])
        self.conf = common.load_json(os.path.join(root, conf_entry["file"]))
        self.traffic = common.load_json(
            os.path.join(root, "bench", "traffic", cell["traffic"] + ".json"))
        self.limits = common.load_json(
            os.path.join(root, "bench", "limits", cell["name"] + ".json"))
        self.family = common.load_module(os.path.join(
            BENCH_DIR, "families", self.conf["family"] + ".py"))
        self.devices = devices
        self.spans = common.Spans(self.tracing)
        self.compiles = common.Compiles().install()
        self.setup_s: Optional[float] = None
        self.window_t: Optional[Tuple[float, float]] = None
        self.memory: Optional[Dict[str, Any]] = None
        self.trace_dir = os.path.join(root, "bench_out", "trace",
                                      cell["name"])

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    @contextlib.contextmanager
    def window(self):
        """The measured window: traced with ``--trace 1``."""
        import jax

        if self.setup_s is None:
            self.setup_done()
        if self.tracing:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
        t0 = time.perf_counter()
        try:
            with self.spans.span("window"):
                yield t0
        finally:
            t1 = time.perf_counter()
            if self.tracing:
                jax.profiler.stop_trace()
            self.window_t = (t0, t1)

    def read_memory(self) -> None:
        """Device record with the peak bytes, read before the reference
        runs (a process's peak never falls again)."""
        self.memory = common.device_record(self.devices)

    @staticmethod
    def free() -> None:
        gc.collect()


def _fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def _per_layer(spec, cell_name: str, reading: Dict[str, Any]
               ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in spec["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        reader = common.load_module(
            os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"))
        value = reader.read(reading)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None, *, root: Optional[str] = None,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    root = root or os.path.dirname(BENCH_DIR)
    spec_path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(spec_path):
        return _fail(f"no BENCHMARK.json in {root}")
    spec = common.load_json(spec_path)
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        return _fail(f"unknown workload {args.workload!r}")

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return _fail(f"no TPU (JAX sees {devices[0].platform}); no result")
    if len(devices) < cell["chips"]:
        return _fail(f"cell needs {cell['chips']} chips, JAX sees "
                     f"{len(devices)}")
    devices = devices[:cell["chips"]]
    common.enable_compile_cache(root)

    import peaks as peak_table

    ctx = Context(root, spec, cell, args, devices)
    peak = peak_table.peaks(devices[0].device_kind) if require_tpu else None
    driver = common.load_module(os.path.join(
        BENCH_DIR, "drivers", ctx.traffic["driver"] + ".py"))
    out = driver.run(ctx)

    device = dict(ctx.memory or common.device_record(devices))
    result: Dict[str, Any] = {
        "correct": bool(out["correct"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
    }
    if ctx.tracing:
        import xplane as tr

        path = tr.find_xplane(ctx.trace_dir)
        reduced = tr.reduce(tr.load_events(path)) if path else None
        if reduced is None or reduced["busy_s"] is None:
            return _fail("the trace holds no device operation", 4)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        reading = dict(out["counters"], trace=reduced, peaks=peak,
                       spans=ctx.spans, window_t=ctx.window_t,
                       compiles=ctx.compiles, conf=ctx.conf,
                       traffic=ctx.traffic)
        result["metrics"] = _per_layer(spec, cell["name"], reading)
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = ctx.setup_s if m["name"] == "setup_s" else out[
                "e2e"].get(m["name"])
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    common.emit(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
