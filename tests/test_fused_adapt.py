"""Device-resident adaptation engine: the scan-fused fine-tune loop must
match the eager per-iteration loop, fleet adaptation (``adapt_many``) must
match sequential ``adapt``, one scanned compile is shared across
same-structure tasks, and a fused adapt() performs at most two blocking
host transfers (probe scores + final losses)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs
from repro.core import adapt as adapt_mod
from repro.core import lm_backbone


def _assert_trees_close(a, b, rtol=1e-4, atol=1e-5):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.fixture(scope="module")
def cnn_session():
    bb = api.backbone("tiny-cnn", in_res=32, batch_size=64)
    return api.TinyTrainSession(bb, max_way=8, seed=0)


@pytest.fixture(scope="module")
def cnn_tasks():
    # episode sizes capped at the pads -> one padded shape for every task,
    # so the fleet tests exercise the single-group stacked path
    rng = np.random.default_rng(7)
    return [api.sample_task(rng, dom, res=32, max_way=8,
                            support_pad=64, query_pad=96,
                            max_support_total=64, max_support_per_class=16)
            for dom in ("glyphs", "stripes", "waves")]


@pytest.fixture(scope="module")
def lm_session():
    cfg = configs.get_reduced("qwen2-1.5b")
    bb = lm_backbone(cfg, tokens_per_batch=32 * 16, batch_size=32)
    return api.TinyTrainSession(bb, max_way=5, seed=0), cfg


class TestScanMatchesEager:
    def test_cnn(self, cnn_session, cnn_tasks):
        task = cnn_tasks[0]
        fused = cnn_session.adapt(task, api.RPI_ZERO, iters=6)
        eager = cnn_session.adapt(task, api.RPI_ZERO, iters=6, fused=False)
        # identical probe -> identical policy (structure and channels)
        assert fused.policy.units == eager.policy.units
        np.testing.assert_allclose(fused.losses, eager.losses,
                                   rtol=1e-4, atol=1e-5)
        _assert_trees_close(fused.deltas, eager.deltas)
        assert fused.accuracy() == pytest.approx(eager.accuracy(), abs=1e-6)

    def test_lm(self, lm_session):
        session, cfg = lm_session
        rng = np.random.default_rng(0)
        task = api.sample_lm_task(rng, cfg.vocab, seq=16, max_way=5,
                                  support_pad=32, query_pad=32)
        fused = session.adapt(task, api.JETSON_NANO, iters=4)
        eager = session.adapt(task, api.JETSON_NANO, iters=4, fused=False)
        assert fused.policy.units == eager.policy.units
        np.testing.assert_allclose(fused.losses, eager.losses,
                                   rtol=1e-4, atol=1e-4)
        _assert_trees_close(fused.deltas, eager.deltas,
                            rtol=2e-3, atol=2e-4)  # bf16-tolerant

    def test_fused_loss_trajectory_decreases(self, cnn_session, cnn_tasks):
        a = cnn_session.adapt(cnn_tasks[0], api.RPI_ZERO, iters=8)
        assert len(a.losses) == 8
        assert a.losses[-1] < a.losses[0]
        assert a.steps_per_sec > 0


class TestFleetAdaptation:
    def test_adapt_many_matches_sequential_cnn(self, cnn_session, cnn_tasks):
        fleet = cnn_session.adapt_many(cnn_tasks, api.RPI_ZERO, iters=4)
        seq = [cnn_session.adapt(t, api.RPI_ZERO, iters=4)
               for t in cnn_tasks]
        assert len(fleet) == len(cnn_tasks)
        for f, s in zip(fleet, seq):
            assert f.policy.units == s.policy.units
            np.testing.assert_allclose(f.losses, s.losses,
                                       rtol=1e-4, atol=1e-5)
            _assert_trees_close(f.deltas, s.deltas)
            assert f.accuracy() == pytest.approx(s.accuracy(), abs=1e-5)

    def test_adapt_many_matches_sequential_lm(self, lm_session):
        session, cfg = lm_session
        rng = np.random.default_rng(3)
        tasks = [api.sample_lm_task(rng, cfg.vocab, seq=16, max_way=5,
                                    support_pad=32, query_pad=32)
                 for _ in range(3)]
        fleet = session.adapt_many(tasks, api.JETSON_NANO, iters=3)
        seq = [session.adapt(t, api.JETSON_NANO, iters=3) for t in tasks]
        for f, s in zip(fleet, seq):
            assert f.policy.units == s.policy.units
            np.testing.assert_allclose(f.losses, s.losses,
                                       rtol=1e-4, atol=1e-4)

    def test_adapt_many_rejects_static_channel_modes(self, cnn_session,
                                                     cnn_tasks):
        with pytest.raises(ValueError, match="static channel mode"):
            cnn_session.adapt_many(cnn_tasks, api.RPI_ZERO,
                                   criterion="random", iters=2)

    def test_adapt_many_empty(self, cnn_session):
        assert cnn_session.adapt_many([], api.RPI_ZERO) == []


class TestCompileAndTransferBudget:
    def test_one_scan_compile_shared_across_tasks(self):
        """Same policy structure + iters -> exactly one scanned compile,
        reused by every subsequent task (and by the fleet path's vmap
        cache, counted separately)."""
        bb = api.backbone("tiny-cnn", in_res=32, batch_size=64)
        session = api.TinyTrainSession(bb, max_way=8, seed=0)
        rng = np.random.default_rng(11)
        t1, t2 = (api.sample_task(rng, "blobs", res=32, max_way=8,
                                  support_pad=64, query_pad=96)
                  for _ in range(2))
        a1 = session.adapt(t1, api.RPI_ZERO, iters=3)
        assert len(session.step_cache._scans) == 1
        session.adapt(t2, api.RPI_ZERO, iters=3,
                      policy_override=a1.policy)
        assert len(session.step_cache._scans) == 1
        assert session.compiled_steps() == 1
        # different iters is a different scanned program
        session.adapt(t2, api.RPI_ZERO, iters=2,
                      policy_override=a1.policy)
        assert len(session.step_cache._scans) == 2

    def test_fused_adapt_two_host_transfers(self, cnn_session, cnn_tasks):
        # warm-up so the timed-path compiles don't hide extra syncs
        cnn_session.adapt(cnn_tasks[1], api.RPI_ZERO, iters=3)
        adapt_mod.reset_host_sync_count()
        a = cnn_session.adapt(cnn_tasks[1], api.RPI_ZERO, iters=3)
        assert adapt_mod.host_sync_count() <= 2
        assert a.host_transfers == 2

    def test_eager_adapt_syncs_every_iteration(self, cnn_session, cnn_tasks):
        adapt_mod.reset_host_sync_count()
        a = cnn_session.adapt(cnn_tasks[1], api.RPI_ZERO, iters=3,
                              fused=False)
        assert adapt_mod.host_sync_count() == 1 + 3  # probe + per-iter
        assert a.host_transfers == 4


PHASES_PER_PROBE_GROUP = ("adapt_many.probe.stack", "adapt_many.probe.run",
                          "adapt_many.probe.fetch", "adapt_many.select")
PHASES_PER_RUN_GROUP = ("adapt_many.finetune.stack",
                        "adapt_many.finetune.run",
                        "adapt_many.finetune.fetch", "adapt_many.finish")


@pytest.fixture(scope="module")
def fleet_spans(cnn_session, cnn_tasks):
    """One warm adapt_many call's root span, its children and results,
    and the host-sync counter's increase over the call."""
    from repro import telemetry

    tasks = cnn_tasks + cnn_tasks[:1]
    cnn_session.adapt_many(tasks, api.RPI_ZERO, iters=3)  # compile
    syncs0 = adapt_mod.host_sync_count()
    out = cnn_session.adapt_many(tasks, api.RPI_ZERO, iters=3)
    syncs = adapt_mod.host_sync_count() - syncs0
    recs = list(telemetry.RECORDER.records)
    root = [r for r in recs if r.name == "adapt_many"][-1]
    kids = [r for r in recs if r.call_id == root.id and r is not root]
    return root, kids, out, syncs


class TestFleetSpans:
    def test_every_phase_is_spanned_per_group(self, fleet_spans,
                                              cnn_session):
        root, kids, out, _ = fleet_spans
        c = root.counts
        assert c["tasks"] == len(out) == 4
        assert c["probe_groups"] >= 1 and c["finetune_groups"] >= 1
        names = [k.name for k in kids]
        assert names.count("adapt_many.bucket") == 1
        for n in PHASES_PER_PROBE_GROUP:
            assert names.count(n) == c["probe_groups"], n
        for n in PHASES_PER_RUN_GROUP:
            assert names.count(n) == c["finetune_groups"], n
        assert len(kids) == 1 + 4 * (c["probe_groups"]
                                     + c["finetune_groups"])
        assert all(k.parent == root.id for k in kids)
        rep = cnn_session.last_fleet_report
        assert rep["groups"] == c["finetune_groups"]
        assert rep["probe_groups"] == c["probe_groups"]
        assert rep["tasks"] == c["tasks"]

    def test_children_tile_their_root(self, fleet_spans):
        from repro import telemetry

        root, kids, _, _ = fleet_spans
        for k in kids:
            assert root.start <= k.start <= k.end <= root.end
        order = sorted(kids, key=lambda k: k.start)
        for a, b in zip(order, order[1:]):
            assert a.end <= b.start  # phases run one after another
        assert telemetry.self_seconds(root, kids) >= 0

    def test_fisher_and_train_seconds_are_the_spans_shares(self,
                                                           fleet_spans):
        root, kids, out, _ = fleet_spans
        assert root.counts["probe_groups"] == 1  # one padded shape
        probe = sum(k.seconds for k in kids if k.name in (
            "adapt_many.probe.run", "adapt_many.probe.fetch"))
        for a in out:
            assert a.fisher_seconds == pytest.approx(probe / len(out),
                                                     rel=1e-12)
        train = sum(k.seconds for k in kids if k.name in (
            "adapt_many.finetune.run", "adapt_many.finetune.fetch"))
        assert sum(a.train_seconds for a in out) == pytest.approx(
            train, rel=1e-9)

    def test_root_counts_the_calls_host_syncs(self, fleet_spans):
        root, _, _, syncs = fleet_spans
        c = root.counts
        assert c["host_syncs"] == syncs
        # one probe fetch per episode group, one fetch per run group
        assert syncs == c["probe_groups"] + c["finetune_groups"]
        assert c["arrays_fetched"] >= 3 * c["finetune_groups"]
