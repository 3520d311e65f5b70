"""Fleet input preparation: ``adapt_many`` builds each group's device inputs
in a bounded number of compiled programs.

- The probe program makes its taps and each task's valid count itself, so
  the host reads no task's ``n_support`` (a blocking copy) and makes no
  taps; the call's only host syncs are its group fetches.
- ``prep_programs`` counts the compiled pads, stacks and channel-index
  transfers; their compile keys hold shapes alone, so a fleet replayed in
  another order builds nothing new.
- The fleet probe, fed only the stacked episodes, scores every task as the
  per-task probe does with explicit taps and ``Task.n_support``.
- The fleet probe packs a group's scores into one array, so the group's
  fetch copies one array, and every task picks the channels the
  sequential probe picks.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import api, configs, data, telemetry
from repro.core import adapt as adapt_mod
from repro.core import session as S
from repro.core.backbones import cnn_backbone, lm_backbone
from repro.models import edge_cnn as E
from repro.models.api import ArchConfig

from test_fleet_padding import _assert_trees_close


def _micro_cnn():
    return cnn_backbone(
        E.build_ir_net("micro", [(1, 8, 1, 2, 3)], 1.0, 8, 0, 12),
        batch_size=8)


def _micro_lm():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     vocab=64, n_heads=2, n_kv_heads=2, head_dim=16,
                     d_ff=64, dtype="float32").validate()
    return lm_backbone(cfg, tokens_per_batch=32, batch_size=2)


def _wide_lm():
    """An LM whose unit kinds differ in width (4 MLA heads, a 128-wide
    dense MLP, 8 MoE experts), so every offset of the packed scores
    counts."""
    return lm_backbone(configs.get_reduced("deepseek-v3-671b"),
                       tokens_per_batch=32, batch_size=2)


def _cnn_tasks(rng, combos, n):
    """n unpadded image tasks cycling through (way, shots) combos."""
    out = []
    for i in range(n):
        way, shots = combos[i % len(combos)]
        out.append(api.sample_task(
            rng, "stripes", res=12, max_way=4, min_way=way,
            support_pad=None, query_pad=None,
            max_support_total=way * shots, max_support_per_class=shots,
            query_per_class=2))
    return out


def _lm_tasks(rng, combos, n, vocab=64):
    """n unpadded token tasks cycling through (way, shots) combos."""
    out = []
    for i in range(n):
        way, shots = combos[i % len(combos)]
        ep = data.lm_episode(rng, vocab, 8, min_way=way, max_way=way,
                             shots=shots, query_per_class=2)
        out.append(api.Task.from_episode(ep, rng, 4, name="lm-task"))
    return out


class _UncountedTask(api.Task):
    """A task whose valid count may not be read on the fleet path."""

    @property
    def n_support(self):
        raise AssertionError("adapt_many read Task.n_support")


def _uncounted(task):
    return _UncountedTask(**{f.name: getattr(task, f.name)
                             for f in dataclasses.fields(task)})


MIX = [(2, 2), (3, 3), (4, 3), (2, 7)]


@pytest.fixture(scope="module")
def counted_session():
    """A session whose backbone counts its make_taps calls."""
    bb = _micro_cnn()
    calls = []

    def make_taps(n):
        calls.append(n)
        return bb.make_taps(n)

    sess = api.TinyTrainSession(
        dataclasses.replace(bb, make_taps=make_taps), max_way=4, seed=0)
    return sess, calls


def _expected_prep_programs(session, tasks, out):
    """(tasks padded) + (probe groups) + (run-group stacks the probe's
    stack cache does not hold) + (one channel-index transfer per leaf and
    run group)."""
    pads = 0
    probe_groups, run_groups = {}, {}
    for i, (t, a) in enumerate(zip(tasks, out)):
        rows = [int(v.shape[0]) for v in
                jax.tree_util.tree_leaves((t.support, t.pseudo_query))]
        bucket = S._bucket_rows(max(rows))
        pads += any(r != bucket for r in rows)
        probe_groups.setdefault(bucket, []).append(i)
        run_groups.setdefault(
            (bucket, session.step_cache._key(a.policy)), []).append(i)
    stacks = sum(1 for idxs in run_groups.values()
                 if idxs not in probe_groups.values())
    transfers = sum(len(out[idxs[0]].policy.units)
                    for idxs in run_groups.values())
    return pads + len(probe_groups) + stacks + transfers


class TestPrepMakesNoSyncsOrTaps:
    def test_no_n_support_reads_taps_or_extra_syncs(self, counted_session):
        session, calls = counted_session
        rng = np.random.default_rng(3)
        tasks = [_uncounted(t) for t in _cnn_tasks(rng, MIX, 8)]
        session.adapt_many(tasks, api.RPI_ZERO, iters=2)  # compile
        traced = len(calls)
        # taps are made only while a probe program is traced
        assert traced <= session.last_fleet_report["probe_groups"]
        syncs0 = adapt_mod.host_sync_count()
        out = session.adapt_many(tasks, api.RPI_ZERO, iters=2)
        assert len(out) == len(tasks)
        assert len(calls) == traced  # none on a warm call
        rep = session.last_fleet_report
        syncs = adapt_mod.host_sync_count() - syncs0
        assert syncs == rep["host_syncs"]
        assert syncs == rep["probe_groups"] + rep["groups"]

    def test_prep_programs_on_a_16_task_bucketed_mix(self, counted_session):
        session, _ = counted_session
        rng = np.random.default_rng(4)
        tasks = _cnn_tasks(rng, MIX, 16)
        out = session.adapt_many(tasks, api.RPI_ZERO, iters=2)
        rep = session.last_fleet_report
        root = [r for r in telemetry.RECORDER.records
                if r.name == "adapt_many"][-1]
        want = _expected_prep_programs(session, tasks, out)
        assert rep["prep_programs"] == root.counts["prep_programs"] == want
        # a bounded count: at most one pad per task, one stack per group
        # and one transfer per selected unit of each run group
        assert want <= len(tasks) + rep["probe_groups"] + rep["groups"] * (
            1 + max(len(a.policy.units) for a in out))


class TestCompileKeysIgnoreOrder:
    def test_permuted_replay_builds_no_program(self):
        session = api.TinyTrainSession(_micro_cnn(), max_way=4, seed=0)
        rng = np.random.default_rng(5)
        tasks = _cnn_tasks(rng, MIX, 12)
        session.adapt_many(tasks, api.RPI_ZERO, iters=2)

        def sizes():
            return (S._fleet_pad_episode._cache_size(),
                    S._fleet_stack_episodes._cache_size(),
                    session.step_cache.probe_fisher_batch()._cache_size(),
                    session.step_cache.fleet_scan_compiles())

        before = sizes()
        order = rng.permutation(len(tasks))
        session.adapt_many([tasks[i] for i in order], api.RPI_ZERO, iters=2)
        assert sizes() == before


def _probe_cases():
    rng = np.random.default_rng(6)
    return {
        "cnn": (_micro_cnn, _cnn_tasks(rng, MIX, 4),
                _cnn_tasks(rng, [(3, 2)], 3)),
        "lm": (_micro_lm, _lm_tasks(rng, [(2, 2), (3, 2), (2, 3)], 3),
               _lm_tasks(rng, [(2, 2)], 3)),
        "lm-widths": (_wide_lm, _lm_tasks(rng, [(2, 2), (3, 2), (2, 3)], 3,
                                          vocab=512),
                      _lm_tasks(rng, [(2, 2)], 3, vocab=512)),
    }


class TestFleetProbeMatchesExplicitTaps:
    @pytest.mark.parametrize("bucket", [True, False])
    @pytest.mark.parametrize("kind", ["cnn", "lm", "lm-widths"])
    def test_scores_match_per_task_probe(self, kind, bucket):
        """Each group's fleet probe (taps and valid counts made inside the
        program), unpacked through its layout, == the per-task probe given
        ``make_taps(batch_pad)`` and ``Task.n_support`` explicitly,
        bucketed and exact-shape."""
        make_bb, mixed, same = _probe_cases()[kind]
        session = api.TinyTrainSession(make_bb(), max_way=4, seed=0)
        bb, cache = session.backbone, session.step_cache
        tasks = mixed if bucket else same
        eps = [S._bucket_episode(t) if bucket
               else (t.support, t.pseudo_query) for t in tasks]
        groups = S._group_indices(
            [S._episode_shape_key(sup, pq) for sup, pq in eps])
        assert sum(len(g) > 1 for g in groups.values()) >= 1
        for idxs in groups.values():
            sup, pq = S._fleet_stack_episodes([eps[i][0] for i in idxs],
                                              [eps[i][1] for i in idxs])
            packed = cache.probe_fisher_batch()(session.params, sup, pq)
            layout = cache.probe_layout()
            assert packed.shape == (len(idxs), layout.offsets[-1])
            if kind == "lm-widths":
                assert len(set(layout.shapes)) == 3
            got = layout.unpack(np.asarray(packed))
            for j, i in enumerate(idxs):
                rows = int(eps[i][0]["episode_labels"].shape[0])
                want = cache.probe_fisher()(
                    session.params, eps[i][0], eps[i][1],
                    bb.make_taps(rows), np.float32(tasks[i].n_support))
                _assert_trees_close({k: v[j] for k, v in got.items()}, want)


class TestProbeGroupFetchesOneArray:
    def test_one_array_per_probe_group_and_sequential_picks(self):
        """A probe group's fetch copies one packed array: the root's
        ``arrays_fetched`` is the probe groups plus each run group's
        deltas, losses and skips; ``host_syncs`` stays one per group.  The
        unpacked scores pick, for every task, the units and channels of
        the sequential probe-and-select path."""
        session = api.TinyTrainSession(_micro_cnn(), max_way=4, seed=0)
        rng = np.random.default_rng(7)
        tasks = _cnn_tasks(rng, MIX, 8)
        syncs0 = adapt_mod.host_sync_count()
        out = session.adapt_many(tasks, api.RPI_ZERO, iters=2)
        syncs = adapt_mod.host_sync_count() - syncs0
        root = [r for r in telemetry.RECORDER.records
                if r.name == "adapt_many"][-1]
        c = root.counts
        run_groups = {}
        for t, a in zip(tasks, out):
            key = (S._episode_shape_key(*S._bucket_episode(t)),
                   session.step_cache._key(a.policy))
            run_groups.setdefault(key, a)
        assert c["finetune_groups"] == len(run_groups)
        finetune_leaves = sum(len(jax.tree_util.tree_leaves(a.deltas)) + 2
                              for a in run_groups.values())
        assert c["arrays_fetched"] == c["probe_groups"] + finetune_leaves
        assert syncs == c["host_syncs"] == (c["probe_groups"]
                                            + c["finetune_groups"])
        budget = S._as_budget(api.RPI_ZERO)
        for t, a in zip(tasks, out):
            want, _, _ = adapt_mod._probe_and_select(
                session.backbone, session.params, t.support, t.pseudo_query,
                budget, max_way=4, criterion="tinytrain", shard_channels=1,
                step_cache=session.step_cache)
            assert a.policy.units == want.units
            assert a.policy.channel_idx.keys() == want.channel_idx.keys()
            for lid, kinds in want.channel_idx.items():
                assert a.policy.channel_idx[lid].keys() == kinds.keys()
                for k, idx in kinds.items():
                    np.testing.assert_array_equal(
                        a.policy.channel_idx[lid][k], idx)
