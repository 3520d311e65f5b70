"""The benchmark's yardstick at toy sizes on the CPU: the trace reduction,
the operation counts, the peak table and each plain reference against the
program."""
from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import common  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import xplane  # noqa: E402
from reference import edge_cnn as cnn_ref  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start_ms, dur_ms):
    return xplane.Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def synthetic_trace():
    """10 ms window: a program from 1 to 4 ms holding a loop (1-3 ms)
    whose body holds one op (1.5-2.5 ms), another op 6-7 ms; the host is
    in ``adapt_many`` from 4 to 6 ms and in ``generate`` from 7 to 10."""
    return [
        ev(HOST, "python", "bench:window", 0, 10),
        ev(HOST, "python", "bench:adapt_many", 4, 2),
        ev(HOST, "python", "bench:generate", 7, 3),
        ev(DEV, "XLA Modules", "jit_run(123)", 1, 3),
        ev(DEV, "XLA Modules", "jit_pf(9)", 6, 1),
        ev(DEV, "XLA Ops", "%while.5 = (s32[]) while(...)", 1, 2),
        ev(DEV, "XLA Ops", "%fusion.7 = f32[8] fusion(...)", 1.5, 1),
        ev(DEV, "XLA Ops", "%copy.1 = f32[8] copy(...)", 3, 1),
        ev(DEV, "XLA Ops", "%dot.2 = f32[8] dot(...)", 6, 1),
        ev(DEV, "Steps", "1", 0, 10),
    ]


def test_trace_reduction_busy_idle_and_gaps():
    r = xplane.reduce(synthetic_trace())
    assert r["planes"] == 1
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(4e-3)  # 1-4 ms and 6-7 ms
    ops = dict(r["device_ops"])
    # the loop counts only its own time, its body op apart
    assert ops["jit_run/while.5"] == pytest.approx(1e-3)
    assert ops["jit_run/fusion.7"] == pytest.approx(1e-3)
    assert ops["jit_pf/dot.2"] == pytest.approx(1e-3)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["generate", pytest.approx(3e-3)]
    assert ["adapt_many", pytest.approx(2e-3)] in gaps
    assert ["none", pytest.approx(1e-3)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(6e-3)


def test_trace_reduction_without_device_has_no_busy_time():
    host_only = [e for e in synthetic_trace() if e.plane == HOST]
    assert xplane.reduce(host_only)["busy_s"] is None


def test_trace_names():
    assert xplane.short_name("%fusion.12 = f32[2] fusion(x)") == "fusion.12"
    assert xplane.module_name("jit_run_from_zero(2526339934)") == \
        "jit_run_from_zero"


TOY_CNN = {"name": "toy-cnn", "family": "edge_cnn", "in_res": 16,
           "width_mult": 1.0, "stem_channels": 8, "head_channels": 0,
           "blocks": [[1, 8, 1, 1, 3], [2, 16, 2, 2, 3]]}


def test_cnn_macs_and_adapt_flops_by_hand():
    ls = cnn_ref.layers(TOY_CNN)
    assert [(l["kind"], l["c_in"], l["c_out"], l["stride"]) for l in ls] == [
        ("conv", 3, 8, 2), ("dw", 8, 8, 1), ("conv", 8, 8, 1),
        ("conv", 8, 16, 1), ("dw", 16, 16, 2), ("conv", 16, 16, 1),
        ("conv", 16, 32, 1), ("dw", 32, 32, 1), ("conv", 32, 16, 1)]
    macs = flops.cnn_layer_macs(TOY_CNN)
    assert macs[0] == 3 * 3 * 3 * 8 * 8 * 8  # stride 2: 16 -> 8 px
    assert macs[4] == 3 * 3 * 1 * 16 * 4 * 4  # depthwise, 8 -> 4 px
    fwd = sum(macs)
    got = flops.cnn_adapt_flops(TOY_CNN, rows=5, iters=2, units={7: 16})
    step = fwd + macs[8] + macs[7] * 16 / 32
    want = 2.0 * 10 * ((fwd + sum(macs[1:])) + 2 * step)
    assert got == pytest.approx(want)


def _make_divisible(v: float, div: int = 8) -> int:
    """MobileNetV2's reference rounding of a cut width."""
    new = max(div, int(v + div / 2) // div * div)
    return new + div if new < 0.9 * v else new


def test_mobilenetv2_config_has_the_published_widths():
    """The configuration file is MobileNetV2's Table 2 at width 0.35, the
    last convolution uncut, and the program builds it layer for layer."""
    conf = common.load_json(os.path.join(BENCH, "configs",
                                         "mobilenetv2-0.35.json"))
    table = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
             (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
    assert conf["blocks"] == [[t, _make_divisible(c * 0.35), n, s, 3]
                              for t, c, n, s in table]
    assert conf["stem_channels"] == _make_divisible(32 * 0.35) == 16
    assert conf["head_channels"] == 1280 and conf["width_mult"] == 1.0
    assert conf["in_res"] == 128
    ls = cnn_ref.layers(conf)
    assert len(ls) == 52
    assert [l["c_out"] for l in ls[:4]] == [16, 16, 8, 48]
    # 20M multiply-adds with a 1001-way classifier on the 1280 features
    macs = sum(flops.cnn_layer_macs(conf))
    assert macs == 18_940_672
    assert 19.5e6 < macs + 1280 * 1001 < 20.5e6
    fam = common.load_module(os.path.join(BENCH, "families", "edge_cnn.py"))
    cfg = fam.program_config(conf)
    assert cfg.n_layers == 52 and cfg.feat_dim == 1280


def test_peaks_table_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.roofline_seconds(197e12, 0, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_seed_key_takes_seeds_over_32_bits():
    a = common.seed_key(2**31 + 5)
    b = common.seed_key(2**33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_mcunet_reference_matches_program_probe_and_steps():
    from repro import api
    from repro.core.policy import SelectedUnit, SparseUpdatePolicy

    fam = common.load_module(os.path.join(BENCH, "families", "edge_cnn.py"))
    params = fam.build_weights(TOY_CNN, 5)
    mix = {"cost_batch": 8}
    bb = fam.backbone(TOY_CNN, mix)
    sess = api.TinyTrainSession(bb, params=params, lr=3e-3, max_way=4)
    rng = np.random.default_rng(0)
    y = np.repeat(np.arange(3, dtype=np.int32), 2)
    sx = rng.normal(size=(6, 16, 16, 3)).astype(np.float32)
    qx = sx + 0.1 * rng.normal(size=sx.shape).astype(np.float32)
    sup = {"images": jnp.asarray(sx), "episode_labels": jnp.asarray(y)}
    pq = {"images": jnp.asarray(qx), "episode_labels": jnp.asarray(y)}
    # the program's probe: tap gradients and Eq. 2 per channel
    taps = bb.make_taps(6)
    got = sess.step_cache.probe_fisher()(params, sup, pq, taps,
                                         jnp.float32(6))
    units = {5: np.array([0, 3, 7]), 7: np.arange(0, 32, 2)}
    pol = SparseUpdatePolicy(horizon=5, units=tuple(
        SelectedUnit(l, "conv", tuple(int(c) for c in ch))
        for l, ch in units.items()))
    run = sess.step_cache.scan_steps(pol, 4)
    d0 = bb.init_deltas(pol)
    d, _, losses, _ = run(params, d0, sess.optimizer.init(d0), sup, pq,
                          sess.step_cache.chan_idx_arrays(pol))
    f = jax.jit(cnn_ref.make_task_reference(TOY_CNN, 4, 3e-3, 4, 10.0))
    masks = [jnp.asarray(m) for m in cnn_ref.channel_masks(TOY_CNN, units)]
    with jax.default_matmul_precision("highest"):
        fisher, ref_losses, _, ref_d = f(
            params, sup["images"], sup["episode_labels"], pq["images"],
            pq["episode_labels"], masks, jnp.float32(6))
    for i in range(len(fisher)):
        a, b = np.asarray(got[(i, "conv")]), np.asarray(fisher[i])
        assert np.max(np.abs(a - b)) <= 1e-4 * max(np.max(np.abs(b)), 1e-12)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(ref_losses),
                               rtol=1e-4)
    for l, ch in units.items():
        full = np.asarray(ref_d[l])
        np.testing.assert_allclose(np.asarray(d[f"L{l}"]["conv"]["w"]),
                                   full[..., ch], rtol=1e-3, atol=1e-7)
        assert not np.any(np.delete(full, ch, axis=-1))
