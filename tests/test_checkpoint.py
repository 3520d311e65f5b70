"""Checkpoint manager: atomic round-trip, keep-N, corrupted-tmp cleanup,
elastic restore (different device topology via subprocess)."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointError, CheckpointManager


@pytest.fixture
def tmpdir(tmp_path):
    return str(tmp_path / "ckpt")


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32)}}


class TestCheckpoint:
    def test_roundtrip(self, tmpdir):
        mgr = CheckpointManager(tmpdir)
        t = _tree()
        mgr.save(10, t, extra={"cursor": 5})
        t2, extra = mgr.restore(10, jax.eval_shape(lambda: t))
        assert extra["cursor"] == 5
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(t2)):
            np.testing.assert_array_equal(np.array(a), np.array(b))

    def test_keep_n(self, tmpdir):
        mgr = CheckpointManager(tmpdir, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, _tree())
        assert mgr.all_steps() == [3, 4]

    def test_latest(self, tmpdir):
        mgr = CheckpointManager(tmpdir)
        assert mgr.restore_latest(_tree()) is None
        mgr.save(7, _tree())
        step, _, _ = mgr.restore_latest(_tree())
        assert step == 7

    def test_structure_mismatch_rejected(self, tmpdir):
        mgr = CheckpointManager(tmpdir)
        mgr.save(1, _tree())
        bad = {"a": jnp.zeros((4, 8))}  # missing leaf
        with pytest.raises(CheckpointError):
            mgr.restore(1, bad)

    def test_shape_mismatch_rejected(self, tmpdir):
        mgr = CheckpointManager(tmpdir)
        mgr.save(1, _tree())
        bad = {"a": jnp.zeros((4, 9)),
               "b": {"c": jnp.zeros((6,), jnp.int32)}}
        with pytest.raises(CheckpointError, match="shape"):
            mgr.restore(1, bad)

    def test_dtype_mismatch_rejected(self, tmpdir):
        """Restoring into a differently-typed target must not silently
        cast — a float32 checkpoint is not an int32 training state."""
        mgr = CheckpointManager(tmpdir)
        mgr.save(1, _tree())
        bad = {"a": jnp.zeros((4, 8)),
               "b": {"c": jnp.zeros((6,), jnp.float32)}}  # saved as int32
        with pytest.raises(CheckpointError, match="dtype"):
            mgr.restore(1, bad)

    def test_corrupt_npz_rejected(self, tmpdir):
        """A truncated/overwritten arrays.npz raises CheckpointError, not
        a zipfile traceback or silent garbage."""
        mgr = CheckpointManager(tmpdir)
        mgr.save(1, _tree())
        with open(os.path.join(tmpdir, "step_1", "arrays.npz"), "wb") as f:
            f.write(b"not a zip archive")
        with pytest.raises(CheckpointError):
            mgr.restore(1, jax.eval_shape(lambda: _tree()))

    def test_meta_array_disagreement_rejected(self, tmpdir):
        """tree.json is the integrity record: an arrays.npz swapped in
        from another run (leaf shapes/dtypes disagree with the metadata)
        is refused even when it happens to match the restore target."""
        mgr = CheckpointManager(tmpdir)
        mgr.save(1, _tree())
        meta_path = os.path.join(tmpdir, "step_1", "tree.json")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["shapes"]["leaf_0"] = [2, 16]  # claim a different saved shape
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(CheckpointError, match="tree.json"):
            mgr.restore(1, jax.eval_shape(lambda: _tree()))

    def test_tmp_dir_not_published(self, tmpdir):
        """A stale .tmp dir (crash mid-save) must not be listed as a step."""
        mgr = CheckpointManager(tmpdir)
        os.makedirs(os.path.join(tmpdir, ".tmp-step_99"))
        assert mgr.all_steps() == []
        mgr.save(1, _tree())
        assert mgr.all_steps() == [1]


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys
sys.path.insert(0, "{src}")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh

mesh = make_mesh(({n},), ("data",))
mgr = CheckpointManager("{ckpt}")
like = {{"w": jnp.zeros((8, 4))}}
sh = {{"w": NamedSharding(mesh, P("data", None))}}
if "{mode}" == "save":
    t = {{"w": jax.device_put(jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
                              sh["w"])}}
    mgr.save(1, t)
else:
    t, _ = mgr.restore(1, like, shardings=sh)
    assert t["w"].sharding.num_devices == {n}
    np.testing.assert_array_equal(np.asarray(t["w"]).ravel(), np.arange(32))
print("OK")
"""


def test_elastic_reshard(tmp_path):
    """Checkpoint written on a 4-device mesh restores onto a 2-device mesh."""
    ckpt = str(tmp_path / "elastic")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    for n, mode in ((4, "save"), (2, "load")):
        script = ELASTIC_SCRIPT.format(n=n, src=src, ckpt=ckpt, mode=mode)
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout
