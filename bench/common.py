"""Pieces every cell shares: spans, the compile counter, the compile cache,
seeds, the device record and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files of their own that ``run.py`` finds by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# the checkout root: bench/common.py -> ..
ROOT = os.path.dirname(BENCH_DIR)
SPAN_PREFIX = "bench:"


def load_module(path: str, name: Optional[str] = None):
    """Import a Python file by path (file names may hold dots)."""
    name = name or "bench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, whatever ``JAX_COMPILATION_CACHE_DIR`` says,
    so that two checkouts never share compiled programs.  Every program is
    cached, however short its compile, so a second run of a cell compiles
    nothing."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A JAX key from a seed of any size (the driver's exceed 32 bits)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class Compiles:
    """Backend compiles seen through ``jax.monitoring`` (a cache hit
    reports here too), each with the host time at which it ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: List[Tuple[float, float]] = []

    def install(self) -> "Compiles":
        import jax

        def on_event(event: str, duration: float, **_) -> None:
            if event == self.EVENT:
                self.events.append((time.perf_counter(), float(duration)))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


class Spans:
    """The bench's own host spans around its calls into the program.

    Each span is kept as (name, start, end) on the host clock; with
    tracing on it is also a ``TraceAnnotation`` named ``bench:<name>``,
    so the trace reduction can say what the host did in a device gap.
    """

    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))


def device_record(devices) -> Dict[str, Any]:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: Dict[str, Any], checks: List[Tuple[str, float, float]]
         ) -> None:
    """Print each compared number beside its limit (last on stderr) and
    the result line (last on stdout), the checks under the last key."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {name: {"value": _finite(value), "limit": limit}
                      for name, value, limit in checks}
    print(json.dumps(line, allow_nan=False), flush=True)


def _finite(x):
    """A reading that is no number (a control that crashed, a run with
    nothing to compare) is printed as null."""
    return x if x is not None and math.isfinite(x) else None
