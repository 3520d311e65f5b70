"""Mesh-sharded fleet adaptation: ``adapt_many(mesh=...)`` must match the
single-device path bit-for-tolerance on an 8-way CPU mesh, and a 16-task
heterogeneous fleet must stay inside the O(#buckets x #policy-structures)
compiled-scan contract.

The parity check needs 8 host-platform devices (``XLA_FLAGS=
--xla_force_host_platform_device_count=8``, as the CI mesh job sets); when
the current process has fewer devices it re-runs itself in a subprocess
with the flag so the test works everywhere.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import api
from repro.core.backbones import cnn_backbone
from repro.dist.sharding import FleetShardingRules
from repro.launch.mesh import make_mesh
from repro.models import edge_cnn as E

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _micro_session():
    cfg = E.build_ir_net("micro", [(1, 8, 1, 2, 3)], 1.0, 8, 0, 12)
    bb = cnn_backbone(cfg, batch_size=8)
    return api.TinyTrainSession(bb, max_way=4, seed=0)


def _het_tasks(rng, combos, n):
    """n unpadded tasks cycling through (way, shots) combos."""
    tasks = []
    for i in range(n):
        way, shots = combos[i % len(combos)]
        tasks.append(api.sample_task(
            rng, "stripes", res=12, max_way=4, min_way=way,
            support_pad=None, query_pad=None,
            max_support_total=way * shots, max_support_per_class=shots,
            query_per_class=2))
    return tasks


def _run_mesh_parity():
    """adapt_many on an 8-way data mesh == single-device adapt_many, and
    per-host ingestion (2 hosts x 4 devices) == the global mesh path
    bit-for-bit (local repeat-last padding reproduces the global padding
    exactly, so the compiled program sees identical inputs)."""
    session = _micro_session()
    rng = np.random.default_rng(0)
    tasks = _het_tasks(rng, [(2, 2), (3, 3), (4, 3), (2, 7)], 8)
    mesh = make_mesh((8,), ("data",))
    fleet_m = session.adapt_many(tasks, api.RPI_ZERO, iters=2, mesh=mesh)
    rep_m = dict(session.last_fleet_report)
    fleet_h = session.adapt_many(tasks, api.RPI_ZERO, iters=2, mesh=mesh,
                                 hosts=2)
    rep_h = dict(session.last_fleet_report)
    fleet_1 = session.adapt_many(tasks, api.RPI_ZERO, iters=2)
    assert rep_m["mesh_axes"] == {"data": 8}
    assert rep_m["ingestion"] == "global"
    assert rep_h["hosts"] == 2 and rep_h["ingestion"] == "per-host"
    for m, h, s in zip(fleet_m, fleet_h, fleet_1):
        assert m.policy.units == s.policy.units
        np.testing.assert_allclose(m.losses, s.losses, rtol=1e-4, atol=1e-5)
        assert abs(m.accuracy() - s.accuracy()) < 1e-5
        # hosted ingestion is exact vs the global mesh path
        assert h.policy.units == m.policy.units
        assert h.losses == m.losses
        for a, b in zip(jax.tree_util.tree_leaves(h.deltas),
                        jax.tree_util.tree_leaves(m.deltas)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_padded_group_parity():
    """Probe groups whose task count the 8-way mesh pads: the mesh path
    and per-host ingestion (2 hosts) drop the padded rows of each group's
    packed scores and pick every task's units and channels as the single
    device does, each probe group fetching one array."""
    from repro import telemetry
    from repro.core import session as S

    session = _micro_session()
    rng = np.random.default_rng(3)
    tasks = _het_tasks(rng, [(2, 2), (4, 3)], 6)
    mesh = make_mesh((8,), ("data",))
    rules = FleetShardingRules(mesh)
    keys = [S._episode_shape_key(*S._bucket_episode(t)) for t in tasks]
    groups = S._group_indices(keys)
    assert all(rules.padded_count(len(g)) != len(g)
               for g in groups.values())
    fleet_1 = session.adapt_many(tasks, api.RPI_ZERO, iters=2)
    for kw in (dict(mesh=mesh), dict(mesh=mesh, hosts=2)):
        fleet = session.adapt_many(tasks, api.RPI_ZERO, iters=2, **kw)
        root = [r for r in telemetry.RECORDER.records
                if r.name == "adapt_many"][-1]
        c = root.counts
        assert c["probe_groups"] == len(groups)
        assert c["host_syncs"] == c["probe_groups"] + c["finetune_groups"]
        run_groups = {(k, session.step_cache._key(a.policy)): a
                      for k, a in zip(keys, fleet)}
        assert c["finetune_groups"] == len(run_groups)
        # one packed array per probe group; deltas, losses and skips per
        # run group
        assert c["arrays_fetched"] == c["probe_groups"] + sum(
            len(jax.tree_util.tree_leaves(a.deltas)) + 2
            for a in run_groups.values())
        for a, s in zip(fleet, fleet_1):
            assert a.policy.units == s.policy.units
            for lid, kinds in s.policy.channel_idx.items():
                for k, idx in kinds.items():
                    np.testing.assert_array_equal(
                        a.policy.channel_idx[lid][k], idx)


def _in_8_devices(body):
    """Run ``body`` (a function of this module) with 8 host-platform
    devices: here when the process has them, else in a subprocess under
    the device-count flag."""
    if jax.device_count() >= 8:
        body()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(_REPO, "src") + os.pathsep + _REPO
        + os.pathsep + env.get("PYTHONPATH", ""))
    code = ("import tests.test_fleet_sharding as t; "
            f"t.{body.__name__}(); print('MESH_PARITY_OK')")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_PARITY_OK" in proc.stdout


class TestMeshParity:
    def test_adapt_many_mesh_matches_single_device(self):
        _in_8_devices(_run_mesh_parity)

    def test_padded_probe_groups_pick_single_device_policies(self):
        _in_8_devices(_run_padded_group_parity)


class TestCompileBudget:
    def test_16_task_heterogeneous_fleet_compile_bound(self):
        """A 16-task fleet with 4 distinct (way, shot) combinations adapts
        in <= #buckets x #policy-structures compiled scan programs — the
        bucketed-padding contract (exact-shape grouping would need one per
        distinct shape)."""
        session = _micro_session()
        rng = np.random.default_rng(1)
        tasks = _het_tasks(rng, [(2, 2), (3, 3), (4, 3), (2, 7)], 16)
        raw_shapes = {t.support["episode_labels"].shape[0] for t in tasks}
        assert len(raw_shapes) >= 4  # genuinely heterogeneous traffic
        before = session.step_cache.fleet_scan_compiles()
        session.adapt_many(tasks, api.RPI_ZERO, iters=2)
        rep = session.last_fleet_report
        compiles = session.step_cache.fleet_scan_compiles() - before
        bound = rep["buckets"] * rep["policy_structures"]
        assert compiles <= bound, (compiles, rep)
        assert rep["groups"] <= bound
        # bucketing actually coalesced shapes (not one bucket per shape)
        assert rep["buckets"] < len(raw_shapes)

    def test_exact_grouping_compiles_per_shape(self):
        """bucket=False restores exact-shape grouping: one group per
        distinct episode shape (the behaviour bucketing replaces)."""
        session = _micro_session()
        rng = np.random.default_rng(2)
        tasks = _het_tasks(rng, [(2, 2), (3, 3), (4, 3), (2, 7)], 8)
        raw_shapes = {t.support["episode_labels"].shape[0] for t in tasks}
        session.adapt_many(tasks, api.RPI_ZERO, iters=2, bucket=False)
        rep = session.last_fleet_report
        assert rep["buckets"] == len(raw_shapes)


class TestFleetShardingRules:
    def test_specs_without_devices(self):
        """Specs are plain tuples computable against a mesh-shaped fake."""

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}

        r = FleetShardingRules(FakeMesh())
        assert r.dp == ("data",) and r.dp_size == 4
        assert r.task_spec(3, 8) == ("data", None, None)
        assert r.task_spec(3, 6) == ()  # indivisible -> replicate
        assert r.task_spec(0, 8) == ()
        assert r.padded_count(6) == 8
        assert r.padded_count(8) == 8

    def test_pure_model_mesh_replicates(self):
        class FakeMesh:
            axis_names = ("model",)
            shape = {"model": 4}

        r = FleetShardingRules(FakeMesh())
        assert r.dp == () and r.dp_size == 1
        assert r.task_spec(2, 8) == ()
        assert r.padded_count(5) == 5
