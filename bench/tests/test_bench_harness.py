"""The harness end to end at toy sizes on the CPU: the shape of the result
line, cells found by name from data files, the refusal without a chip,
and ``correct`` coming out false under planted faults and for the
control."""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import common  # noqa: E402
import run  # noqa: E402

TOY_CNN = {"name": "toy-cnn", "family": "edge_cnn", "source": "test",
           "dtype": "float32", "in_res": 16, "width_mult": 1.0,
           "stem_channels": 8, "head_channels": 0,
           "blocks": [[1, 8, 1, 1, 3], [2, 16, 2, 2, 3]], "reduced": []}
TOY_FLEET = {"driver": "adapt_fleet", "fleet": [[2, 2], [3, 1], [2, 3],
                                                [4, 2]],
             "pool_fleets": 1, "noise": 0.5, "aug_noise": 0.1,
             "shift": 2, "max_way": 4, "profile": "rpi-zero",
             "criterion": "tinytrain", "iters": 3, "lr": 0.003,
             "temperature": 10.0, "bucket": True, "cost_batch": 8,
             "check_tasks": 2, "ref_rows": 16}
# limits at toy sizes, where the program runs float32 on the CPU and
# agrees with the reference to rounding
LIMITS = {"toy.adapt": {"pick_gap": 0.05, "loss_gap": 1e-3,
                        "delta_gap": 5e-3}}
CELL = "adapt.mobilenetv2-0.35.fleet"


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def toy_root(tmp_path):
    """A checkout holding only data: a toy configuration, mix and limits
    in temporary files, found by the names in its BENCHMARK.json."""
    spec = copy.deepcopy(common.load_json(os.path.join(ROOT,
                                                       "BENCHMARK.json")))
    spec["configs"] = [{"name": TOY_CNN["name"], "source": "test",
                        "reduced": [], "why": "toy",
                        "file": "bench/configs/toy-cnn.json"}]
    spec["workloads"] = [{"name": "toy.adapt", "config": "toy-cnn",
                          "traffic": "toy_fleet", "chips": 1, "why": "toy"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    _write(str(tmp_path / "BENCHMARK.json"), spec)
    _write(str(tmp_path / "bench" / "configs" / "toy-cnn.json"), TOY_CNN)
    _write(str(tmp_path / "bench" / "traffic" / "toy_fleet.json"), TOY_FLEET)
    for w, lim in LIMITS.items():
        _write(str(tmp_path / "bench" / "limits" / f"{w}.json"), lim)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield str(tmp_path)
    for k, v in saved.items():
        jax.config.update(k, v)


def run_cell(root, workload, capsys, seed=2**31 + 11, seconds=0.5):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out and out[-1].startswith("{")
                else None)


def test_toy_cell_prints_the_result_line(toy_root, capsys):
    rc, line = run_cell(toy_root, "toy.adapt", capsys)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"adapt_tasks_per_s", "setup_s"}
    assert line["metrics"]["adapt_tasks_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    assert set(line["checks"]) == set(LIMITS["toy.adapt"])
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


def test_a_new_mix_is_a_data_file(toy_root, capsys):
    """A mix added under a new name needs its file and an entry, nothing
    else."""
    mix = dict(TOY_FLEET, fleet=[[2, 1], [3, 2]], iters=2)
    _write(os.path.join(toy_root, "bench", "traffic", "toy_other.json"), mix)
    spec = common.load_json(os.path.join(toy_root, "BENCHMARK.json"))
    spec["workloads"].append({"name": "toy.other", "config": "toy-cnn",
                              "traffic": "toy_other", "chips": 1,
                              "why": "toy"})
    _write(os.path.join(toy_root, "BENCHMARK.json"), spec)
    _write(os.path.join(toy_root, "bench", "limits", "toy.other.json"),
           LIMITS["toy.adapt"])
    rc, line = run_cell(toy_root, "toy.other", capsys)
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] % 2 == 0


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_command_refuses_in_a_checkout_without_its_files(tmp_path, capsys):
    rc = run.main(["--workload", CELL, "--seed", "1",
                   "--seconds", "1"], root=str(tmp_path), require_tpu=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_adapt_step_that_keeps_its_state_is_not_correct(toy_root, capsys,
                                                        monkeypatch):
    from repro.core.sparse import EpisodeStepCache

    orig = EpisodeStepCache.vmap_scan_steps

    def frozen(self, policy, iters, mode=None):
        fn = orig(self, policy, iters, mode)

        def run_(params, s, q, c):
            d, st, losses, skipped = fn(params, s, q, c)
            same = jnp.broadcast_to(losses[:, :1], losses.shape)
            return (jax.tree_util.tree_map(jnp.zeros_like, d), st, same,
                    skipped)
        return run_

    monkeypatch.setattr(EpisodeStepCache, "vmap_scan_steps", frozen)
    rc, line = run_cell(toy_root, "toy.adapt", capsys)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["delta_gap"]["value"] >= 0.99


def test_adapt_half_batch_is_not_correct(toy_root, capsys, monkeypatch):
    from repro.core import session as S

    orig = S._bucket_episode

    def half(task):
        sup, pq = orig(task)
        y = pq["episode_labels"]
        y = jnp.where(jnp.arange(y.shape[0]) % 2 == 1, -1, y)
        return sup, dict(pq, episode_labels=y)

    monkeypatch.setattr(S, "_bucket_episode", half)
    rc, line = run_cell(toy_root, "toy.adapt", capsys)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["loss_gap"]["value"] > \
        LIMITS["toy.adapt"]["loss_gap"]


def test_adapt_reversed_picks_are_not_correct(toy_root, capsys,
                                             monkeypatch):
    """The host's selection planted wrong: in each selected layer the
    channels of the lowest Fisher scores are taken."""
    from repro.core import session as S

    orig = S.select_policy

    def lowest(costs, potentials, chans, budget, **kw):
        return orig(costs, potentials, {k: -np.asarray(v)
                                        for k, v in chans.items()},
                    budget, **kw)

    monkeypatch.setattr(S, "select_policy", lowest)
    rc, line = run_cell(toy_root, "toy.adapt", capsys)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["pick_gap"]["value"] > \
        LIMITS["toy.adapt"]["pick_gap"]


def _context(root, workload):
    spec = common.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    common.enable_compile_cache(root)
    return run.Context(root, spec, cell, types.SimpleNamespace(
        seed=0, seconds=0.5, trace=0), jax.devices()[:1])


def test_adapt_control_is_not_correct(toy_root):
    """The control and each planted fault fail the number that is theirs
    to catch; the program fails none."""
    ctx = _context(toy_root, "toy.adapt")
    drv = common.load_module(os.path.join(BENCH, "drivers", "adapt_fleet.py"))
    lim = LIMITS["toy.adapt"]
    rows = drv.calibrate(ctx, [3, 4, 2**32 + 3], faults=3)
    for row in rows:
        assert all(row["program"][k] <= lim[k] for k in lim)
        assert any(row["control_fp8"][k] > lim[k] for k in lim)
        assert row["half_batch"]["loss_gap"] > lim["loss_gap"]
        assert row["reversed_picks"]["pick_gap"] > lim["pick_gap"]
