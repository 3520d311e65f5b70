"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, at qwen2-1.5b widths (Hq=12, Hkv=2, D=128, bf16, 16-row pages).

Nothing runs: the TPU compiler lowers each kernel for a topology that is
described, not attached, and each test asserts that the Mosaic kernel
(``tpu_custom_call``) is in the compiled program — a block layout Mosaic
refuses fails here, not on the chip.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HQ, HKV, D, PAGE, SLOTS, MAX_PAGES = 12, 2, 128, 16, 8, 40
L, C = 28, 8960  # qwen2-1.5b MLP taps: layers x d_ff channels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache here; keep them out of it.  The kernels pick
    # interpret mode from jax.default_backend(), which is the CPU here:
    # steer them to the Mosaic path, and drop traces made either way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_default_interpret", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=shardings)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [8, 1], ids=["batch8", "batch1"])
def test_fisher_tapgrads_compiles(one_chip, n):
    """The LM probe's (B, 1, L·C) tap-gradient view (block_d=1)."""
    text = _compile_text(
        functools.partial(ops.fisher_tapgrads, n=float(n), mask=None),
        one_chip, ((L, n, C), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq", [1, 8], ids=["decode", "block8"])
def test_cached_flash_compiles(one_chip, sq):
    cap = MAX_PAGES * PAGE

    def f(q, k, v, qo, kl):
        return ops.flash_attention(q, k, v, causal=True, block_q=sq,
                                   block_k=128, q_offset=qo, kv_len=kl)

    text = _compile_text(
        f, one_chip,
        ((SLOTS, sq, HQ, D), jnp.bfloat16),
        ((SLOTS, cap, HKV, D), jnp.bfloat16),
        ((SLOTS, cap, HKV, D), jnp.bfloat16),
        ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq", [1, 8], ids=["decode", "block8"])
def test_paged_flash_compiles(one_chip, sq):
    n_pages = SLOTS * MAX_PAGES

    def f(q, kp, vp, table, qo, kl):
        return ops.paged_flash_attention(q, kp, vp, table, q_offset=qo,
                                         kv_len=kl, block_q=sq)

    text = _compile_text(
        f, one_chip,
        ((SLOTS, sq, HQ, D), jnp.bfloat16),
        ((n_pages, HKV, PAGE, D), jnp.bfloat16),
        ((n_pages, HKV, PAGE, D), jnp.bfloat16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text
