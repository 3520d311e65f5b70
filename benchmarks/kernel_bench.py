"""Kernel microbenchmarks: Pallas (interpret) correctness sweeps + XLA-path
timings of the same ops (wall-clock is CPU; TPU perf comes from §Roofline).
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(f, *args, n: int = 5) -> float:
    jax.block_until_ready(f(*args))  # one warm-up call (compile + transfer)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def main(quick: bool = True) -> List[str]:
    out = ["kernel,shape,us_per_call,max_err_vs_oracle"]
    key = jax.random.PRNGKey(0)

    # fisher: time the Pallas op itself (interpret on CPU, Mosaic on TPU)
    # and the jnp oracle side by side
    n, d, c = (4, 512, 256) if quick else (16, 2048, 1024)
    a = jax.random.normal(key, (n, d, c))
    g = jax.random.normal(jax.random.PRNGKey(1), (n, d, c)) * 0.1
    want = ref.fisher_ref(a, g)
    bd, bc = min(512, d), min(256, c)
    got = ops.fisher(a, g, block_d=bd, block_c=bc)
    err = float(jnp.max(jnp.abs(got - want) / (jnp.abs(want) + 1e-6)))
    us = _time(lambda a, g: ops.fisher(a, g, block_d=bd, block_c=bc), a, g)
    out.append(f"fisher,({n}x{d}x{c}),{us:.0f},{err:.2e}")
    us = _time(jax.jit(ref.fisher_ref), a, g)
    out.append(f"fisher_xla_ref,({n}x{d}x{c}),{us:.0f},0.00e+00")

    # flash attention
    b, s, hq, hkv, hd = (1, 512, 4, 2, 64) if quick else (2, 2048, 8, 2, 128)
    q = jax.random.normal(key, (b, s, hq, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, s, hkv, hd))
    got = ops.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    kk, vv = jnp.repeat(k, hq // hkv, 2), jnp.repeat(v, hq // hkv, 2)
    want = ref.flash_attention_ref(q, kk, vv, causal=True)
    err = float(jnp.max(jnp.abs(got - want)))
    us = _time(jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True)), q, kk, vv)
    out.append(f"flash_attention,({b}x{s}x{hq}x{hd}),{us:.0f},{err:.2e}")

    # ssd scan
    b, s, h, p, nst = (1, 256, 2, 32, 16) if quick else (2, 1024, 8, 64, 64)
    x = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4), (b, s, h)))
    aa = -jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (h,)))
    bm = jax.random.normal(jax.random.PRNGKey(6), (b, s, nst)) * 0.5
    cm = jax.random.normal(jax.random.PRNGKey(7), (b, s, nst)) * 0.5
    y, _ = ops.ssd_scan(x, dt, aa, bm, cm, chunk=64)
    yr, _ = ref.ssd_scan_ref(x, dt, aa, bm, cm)
    err = float(jnp.max(jnp.abs(y - yr)))
    us = _time(jax.jit(lambda *a: ref.ssd_scan_ref(*a)[0]), x, dt, aa, bm, cm)
    out.append(f"ssd_scan,({b}x{s}x{h}x{p}x{nst}),{us:.0f},{err:.2e}")

    # paged cached flash: the page-table walk vs the contiguous cached
    # kernel on the serving hot paths — single-token decode (Sq=1) and
    # block prefill (Sq=8) — plus the int8 page-unpack overhead.  Pages
    # hold a permutation of the contiguous rows, so the two kernels see
    # identical logical caches and the error column is a correctness check.
    from repro.optim.compress import rowwise_quant
    from repro.serving import paging as PG
    b, hq, hkv, hd = (2, 4, 2, 64) if quick else (4, 8, 2, 128)
    ps, mp = (16, 16) if quick else (16, 64)
    spec = PG.PagingSpec(page_size=ps, n_pages=b * mp, max_pages=mp)
    cap = mp * ps
    k = jax.random.normal(jax.random.PRNGKey(8), (b, cap, hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(9), (b, cap, hkv, hd))
    perm = jax.random.permutation(jax.random.PRNGKey(10), b * mp)
    table = perm.reshape(b, mp).astype(jnp.int32)
    # head-major page arenas (n_pages, Hkv, page_size, D), as
    # PG.store_init lays them out
    kp = jnp.zeros((b * mp, hkv, ps, hd)).at[table.reshape(-1)].set(
        k.reshape(b * mp, ps, hkv, hd).swapaxes(1, 2))
    vp = jnp.zeros((b * mp, hkv, ps, hd)).at[table.reshape(-1)].set(
        v.reshape(b * mp, ps, hkv, hd).swapaxes(1, 2))
    kv_len = jnp.asarray([cap - 5, cap // 2] * (b // 2), jnp.int32)
    for sq, tag in ((1, "decode"), (8, "prefill8")):
        qo = kv_len - sq
        q = jax.random.normal(jax.random.PRNGKey(11), (b, sq, hq, hd))
        want = ops.flash_attention(q, k, v, causal=True, block_q=sq,
                                   block_k=ps, q_offset=qo, kv_len=kv_len)
        got = ops.paged_flash_attention(q, kp, vp, table, q_offset=qo,
                                        kv_len=kv_len, block_q=sq)
        err = float(jnp.max(jnp.abs(got - want)))
        us = _time(lambda q: ops.flash_attention(
            q, k, v, causal=True, block_q=sq, block_k=ps, q_offset=qo,
            kv_len=kv_len), q)
        out.append(f"cached_flash_contig_{tag},({b}x{sq}x{hq}x{hd}),"
                   f"{us:.0f},0.00e+00")
        us = _time(lambda q: ops.paged_flash_attention(
            q, kp, vp, table, q_offset=qo, kv_len=kv_len, block_q=sq), q)
        out.append(f"cached_flash_paged_{tag},({b}x{sq}x{hq}x{hd}),"
                   f"{us:.0f},{err:.2e}")

    # int8 page store: gather-only (fp pages) vs gather + rowwise dequant
    import dataclasses as _dc
    spec_i8 = _dc.replace(spec, int8=True)
    q8, sc = rowwise_quant(kp.swapaxes(1, 2), 2)  # one scale per token row
    q8 = q8.swapaxes(1, 2)
    read_fp = jax.jit(lambda t: PG.read_rows({"pages": kp}, t, spec,
                                             jnp.float32))
    read_i8 = jax.jit(lambda t: PG.read_rows(
        {"pages": q8, "scale": sc}, t, spec_i8, jnp.float32))
    err = float(jnp.max(jnp.abs(read_i8(table) - read_fp(table))))
    us = _time(read_fp, table)
    out.append(f"page_read_fp,({b}x{cap}x{hkv}x{hd}),{us:.0f},0.00e+00")
    us = _time(read_i8, table)
    out.append(f"page_read_int8_unpack,({b}x{cap}x{hkv}x{hd}),{us:.0f},{err:.2e}")

    # grad quant
    g1 = jax.random.normal(key, (4096,)) * 0.01
    e1 = jnp.zeros((4096,))
    q8, sc, ne = ops.grad_quant(g1, e1, block=1024)
    qr, sr, nr = ref.grad_quant_ref(g1, e1)
    err = float(jnp.max(jnp.abs(ne - nr)))
    us = _time(jax.jit(ref.grad_quant_ref), g1, e1)
    out.append(f"grad_quant,(4096),{us:.0f},{err:.2e}")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
