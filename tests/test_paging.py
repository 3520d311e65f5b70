"""Paged, int8-quantised KV cache (``repro/serving/paging.py``).

Covers the page-allocator subsystem end to end: free-list invariants under
random admit/evict/re-admit schedules (never double-allocates, never
leaks, freed rows invalidated), fp-page parity with the contiguous cache
across the full eager/fused serving matrix (every unit-kind family, block
and token prefill, folded deltas, greedy and seeded sampling), the int8
page store against a stated logit tolerance at unchanged sync budget, the
per-request ``max_len`` budget (admission reserves pages, eviction frees
them, head-of-line blocking under a tight page budget), the unified
prompt/budget validation (empty / exact-fit / oversize, both paths), the
paged Pallas flash kernel against the gather oracle, and
``memory_report`` accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs
from repro.core import adapt as adapt_mod
from repro.core import lm_backbone
from repro.core.policy import SelectedUnit, SparseUpdatePolicy
from repro.models import transformer as T
from repro.models.api import ArchConfig
from repro.serving import Request, ServeEngine, fold_deltas
from repro.serving import paging as PG


def tiny_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=32, vocab=64,
                n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                dtype="float32")
    base.update(kw)
    return ArchConfig(**base).validate()


# exercises every foldable unit kind: attn+mlp, attn+moe, mla, ssm, and the
# hybrid ssm+shared-attn family — the same matrix the fused-scan tests use
PARITY_ARCHS = ["qwen2-1.5b", "mixtral-8x7b", "deepseek-v3-671b",
                "mamba2-1.3b", "zamba2-1.2b"]


# ---------------------------------------------------------------------------
# PagePool free-list invariants (property test)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_pool_free_list_invariants(seed):
    """Random admit/evict/re-admit schedules: a page is never owned by two
    slots, pages-in-use always equals the sum of live reservations (no
    leak), freed slots' table rows are invalidated, and draining everything
    returns the pool to all-free."""
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(1, 6))
    max_pages = int(rng.integers(1, 5))
    n_pages = int(rng.integers(max_pages, slots * max_pages + 3))
    spec = PG.PagingSpec(page_size=int(rng.integers(1, 9)),
                         n_pages=n_pages, max_pages=max_pages)
    pool = PG.make_pool(spec, slots)
    held = {}  # slot -> page count it reserved

    for _ in range(30):
        free_now = int(PG.free_page_count(pool))
        idle = [s for s in range(slots) if s not in held]
        admit = idle and (not held or rng.random() < 0.6)
        if admit:
            s = int(rng.choice(idle))
            need = int(rng.integers(1, max_pages + 1))
            if need > free_now:
                continue  # head-of-line blocking: caller never over-asks
            mask = np.zeros(slots, bool)
            mask[s] = True
            nd = np.zeros(slots, np.int32)
            nd[s] = need
            pool = PG.reserve(pool, jnp.asarray(nd), jnp.asarray(mask))
            held[s] = need
        elif held:
            s = int(rng.choice(sorted(held)))
            mask = np.zeros(slots, bool)
            mask[s] = True
            pool = PG.release(pool, jnp.asarray(mask))
            del held[s]

        table = np.asarray(pool.table)
        free = np.asarray(pool.free)
        owned = table[table >= 0]
        # never double-allocated: each mapped page appears exactly once
        assert len(owned) == len(set(owned.tolist()))
        # mapped pages are not on the free-list; the ledger balances
        assert not free[owned].any()
        assert len(owned) == sum(held.values())
        assert int(PG.pages_in_use(pool)) == sum(held.values())
        for s in range(slots):
            row = table[s]
            if s in held:
                assert (row >= 0).sum() == held[s]
                # reservations are row-prefixes: tail entries invalid
                assert (row[:held[s]] >= 0).all() and (row[held[s]:] == -1).all()
            else:
                assert (row == -1).all()  # freed rows are invalidated

    pool = PG.release(pool, jnp.ones((slots,), bool))
    assert int(PG.free_page_count(pool)) == n_pages  # full drain: no leak


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_pool_invariants_with_asyougo_growth(seed):
    """The reserve-as-you-go cycle: random admit (prompt pages) / extend
    (growth) / release (preempt) schedules keep the same ledger invariants
    — no double-allocation, no leak, grown rows are contiguous prefixes,
    released rows invalidated — and the pool drains clean."""
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(1, 6))
    max_pages = int(rng.integers(2, 6))
    n_pages = int(rng.integers(max_pages, slots * max_pages + 3))
    spec = PG.PagingSpec(page_size=int(rng.integers(1, 9)),
                         n_pages=n_pages, max_pages=max_pages)
    pool = PG.make_pool(spec, slots)
    held = {}  # slot -> page count currently mapped

    for _ in range(40):
        free_now = int(PG.free_page_count(pool))
        idle = [s for s in range(slots) if s not in held]
        growable = [s for s in held if held[s] < max_pages]
        op = rng.random()
        if idle and (op < 0.4 or not held):
            # admission: reserve only the prompt's pages
            s = int(rng.choice(idle))
            need = int(rng.integers(1, max_pages + 1))
            if need > free_now:
                continue
            mask = np.zeros(slots, bool)
            mask[s] = True
            nd = np.zeros(slots, np.int32)
            nd[s] = need
            pool = PG.reserve(pool, jnp.asarray(nd), jnp.asarray(mask))
            held[s] = need
        elif growable and op < 0.75:
            # in-scan growth: possibly several slots cross a boundary in
            # the same tick (the fused path extends them in one call)
            grow = [s for s in growable
                    if rng.random() < 0.7][:max(free_now, 0)]
            if not grow:
                continue
            mask = np.zeros(slots, bool)
            nd = np.zeros(slots, np.int32)
            hd = np.zeros(slots, np.int32)
            for s in range(slots):
                hd[s] = held.get(s, 0)
            for s in grow:
                mask[s] = True
                nd[s] = 1
            pool = PG.extend(pool, jnp.asarray(nd), jnp.asarray(mask),
                             jnp.asarray(hd))
            for s in grow:
                held[s] += 1
        elif held:
            # preemption: victim releases everything it holds
            s = int(rng.choice(sorted(held)))
            mask = np.zeros(slots, bool)
            mask[s] = True
            pool = PG.release(pool, jnp.asarray(mask))
            del held[s]

        table = np.asarray(pool.table)
        free = np.asarray(pool.free)
        owned = table[table >= 0]
        assert len(owned) == len(set(owned.tolist()))  # no double-alloc
        assert not free[owned].any()
        assert len(owned) == sum(held.values())  # ledger balances: no leak
        assert int(PG.pages_in_use(pool)) == sum(held.values())
        for s in range(slots):
            row = table[s]
            h = held.get(s, 0)
            # mapped pages form a contiguous row prefix even after growth
            assert (row[:h] >= 0).all() and (row[h:] == -1).all()

    pool = PG.release(pool, jnp.ones((slots,), bool))
    assert int(PG.free_page_count(pool)) == n_pages


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_pool_invariants_with_pinned_runs(seed):
    """Pinned encoder runs share the KV pool's single free-list: random
    admit (KV reserve + full-run reserve), as-you-go growth, preempt and
    evict (both releases) schedules keep one balanced ledger — no page is
    ever owned by a KV table row and a run row at once, runs are reserved
    whole (a full row prefix, never grown), released runs' rows are
    invalidated, and a full drain returns every page."""
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(1, 5))
    max_pages = int(rng.integers(2, 5))
    enc_pages = int(rng.integers(1, 4))
    n_pages = int(rng.integers(max_pages + enc_pages,
                               slots * (max_pages + enc_pages) + 3))
    spec = PG.PagingSpec(page_size=int(rng.integers(1, 9)),
                         n_pages=n_pages, max_pages=max_pages)
    pool = PG.make_pool(spec, slots)
    run_table = jnp.full((slots, enc_pages), -1, jnp.int32)
    held = {}  # slot -> KV page count (every held slot also pins a run)

    for _ in range(40):
        free_now = int(PG.free_page_count(pool))
        idle = [s for s in range(slots) if s not in held]
        growable = [s for s in held if held[s] < max_pages]
        op = rng.random()
        if idle and (op < 0.4 or not held):
            # admission prices the KV demand plus the whole pinned run
            s = int(rng.choice(idle))
            need = int(rng.integers(1, max_pages + 1))
            if need + enc_pages > free_now:
                continue
            mask = np.zeros(slots, bool)
            mask[s] = True
            nd = np.zeros(slots, np.int32)
            nd[s] = need
            pool = PG.reserve(pool, jnp.asarray(nd), jnp.asarray(mask))
            pool, run_table = PG.reserve_run(
                pool, run_table,
                jnp.full((slots,), enc_pages, jnp.int32), jnp.asarray(mask))
            held[s] = need
        elif growable and op < 0.75:
            # KV growth only — runs never extend
            grow = [s for s in growable
                    if rng.random() < 0.7][:max(free_now, 0)]
            if not grow:
                continue
            mask = np.zeros(slots, bool)
            nd = np.zeros(slots, np.int32)
            hd = np.zeros(slots, np.int32)
            for s in range(slots):
                hd[s] = held.get(s, 0)
            for s in grow:
                mask[s] = True
                nd[s] = 1
            pool = PG.extend(pool, jnp.asarray(nd), jnp.asarray(mask),
                             jnp.asarray(hd))
            for s in grow:
                held[s] += 1
        elif held:
            # preemption / eviction: KV pages and the pinned run go back
            s = int(rng.choice(sorted(held)))
            mask = np.zeros(slots, bool)
            mask[s] = True
            pool = PG.release(pool, jnp.asarray(mask))
            pool, run_table = PG.release_run(pool, run_table,
                                             jnp.asarray(mask))
            del held[s]

        table = np.asarray(pool.table)
        free = np.asarray(pool.free)
        runs = np.asarray(run_table)
        kv_owned = table[table >= 0]
        run_owned = runs[runs >= 0]
        owned = np.concatenate([kv_owned, run_owned])
        # one free-list, one ledger: no page owned twice across both kinds
        assert len(owned) == len(set(owned.tolist()))
        assert not free[owned].any()
        assert len(kv_owned) == sum(held.values())
        assert len(run_owned) == len(held) * enc_pages
        assert int(np.asarray(pool.free).sum()) == (
            n_pages - sum(held.values()) - len(held) * enc_pages)
        for s in range(slots):
            if s in held:
                # runs are whole: reserved in full at admission
                assert (runs[s] >= 0).all()
            else:
                assert (runs[s] == -1).all()  # released rows invalidated

    pool = PG.release(pool, jnp.ones((slots,), bool))
    pool, run_table = PG.release_run(pool, run_table,
                                     jnp.ones((slots,), bool))
    assert int(PG.free_page_count(pool)) == n_pages  # full drain: no leak


# ---------------------------------------------------------------------------
# fp-page parity with the contiguous cache (the serving matrix)
# ---------------------------------------------------------------------------


def _streams(cfg, params, requests_fn, engine_kwargs, *, slots=2,
             max_len=24, chunk=8, **extra):
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len, chunk=chunk,
                      **engine_kwargs, **extra)
    reqs = requests_fn()
    eng.run(reqs)
    assert all(r.done for r in reqs)
    return [(r.out, r.truncated) for r in reqs], eng


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_fp_matches_contiguous_streams(arch):
    """fp pages, page size dividing max_len: token streams are identical
    to the contiguous cache on the eager path and the fused path at both
    prefill block sizes (1 and 8)."""
    cfg = configs.get_reduced(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 7)))
               .astype(np.int32) for _ in range(5)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]

    ref, _ = _streams(cfg, params, mk, dict(fused=False))
    for kw in (dict(fused=False), dict(fused=True, prefill_block=1),
               dict(fused=True, prefill_block=8)):
        got, eng = _streams(cfg, params, mk, kw, kv_paging=True,
                            kv_page_size=8)
        assert got == ref
        # the drained pool leaks nothing
        assert int(PG.free_page_count(eng.pool)) == eng.spec.n_pages


def test_paged_fp_non_dividing_page_size():
    """A page size that does not divide max_len (logical capacity rounds
    up past max_len): the over-capacity tail rows are masked and streams
    still match the contiguous cache."""
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 8)))
               .astype(np.int32) for _ in range(4)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]

    ref, _ = _streams(cfg, params, mk, dict(fused=False))
    got, _ = _streams(cfg, params, mk, dict(fused=True), kv_paging=True,
                      kv_page_size=5)  # cap = 25 > max_len = 24
    assert got == ref


def test_paged_fp_folded_deltas_parity():
    """A fold_deltas serving copy streams identically with paging on."""
    cfg = configs.get_reduced("qwen2-1.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    bb = lm_backbone(cfg, tokens_per_batch=2 * 16, batch_size=2)
    units, seen = [], set()
    for c in reversed(bb.unit_costs):
        if c.kind not in seen:
            units.append(SelectedUnit(
                c.layer, c.kind, tuple(sorted({0, c.n_channels - 1}))))
            seen.add(c.kind)
    units.sort(key=lambda u: (u.layer, u.kind))
    policy = SparseUpdatePolicy(horizon=0, units=tuple(units))
    deltas = bb.init_deltas(policy)
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    leaves = [jax.random.normal(k, x.shape, x.dtype) * 0.05
              for k, x in zip(keys, leaves)]
    folded = fold_deltas(cfg, params, jax.tree_util.tree_unflatten(
        treedef, leaves), policy)

    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 8)))
               .astype(np.int32) for _ in range(4)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]

    ref, _ = _streams(cfg, folded, mk, dict(fused=False))
    got, _ = _streams(cfg, folded, mk, dict(fused=True), kv_paging=True,
                      kv_page_size=8)
    assert got == ref


def test_paged_fp_sampled_streams_parity():
    """Seeded temperature/top-k sampling: paged streams match contiguous
    (sample keys depend on request id and token index, and fp pages
    reproduce the contiguous logits)."""
    cfg = configs.get_reduced("qwen2-1.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 10)))
               .astype(np.int32) for _ in range(4)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=5)
                for i, p in enumerate(prompts)]

    kw = dict(temperature=0.7, top_k=8, sample_seed=11)
    ref, _ = _streams(cfg, params, mk, dict(fused=True), max_len=32, **kw)
    got, _ = _streams(cfg, params, mk, dict(fused=True), max_len=32,
                      kv_paging=True, kv_page_size=8, **kw)
    assert got == ref


def test_rolling_window_cache_stays_contiguous():
    """Sliding-window buffers with window < max_len roll in place (already
    O(window)); paging must leave them alone and still stream identically
    (mixtral-smoke has window 32)."""
    cfg = configs.get_reduced("mixtral-8x7b")
    assert cfg.sliding_window == 32
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (8, 45)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]

    ref, _ = _streams(cfg, params, mk, dict(fused=False), max_len=80,
                      chunk=16)
    got, eng = _streams(cfg, params, mk, dict(fused=True), max_len=80,
                        chunk=16, kv_paging=True, kv_page_size=8)
    assert got == ref
    # window (32) < max_len (80): the K/V leaves must be rolling buffers,
    # not page stores
    g0 = eng.caches["g0"]["attn"]
    assert "page_table" not in g0
    assert g0["k"].shape[2] == cfg.sliding_window


# ---------------------------------------------------------------------------
# int8 pages: stated tolerance, unchanged sync budget
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_int8_pages_teacher_forced_logit_tolerance(arch):
    """Teacher-forced decode of one token sequence through fp-contiguous
    vs int8-paged caches: per-step logits stay within 5% relative L2
    error — the stated int8 tolerance (per-token absmax scales keep the
    row quantisation error at the ~1/127 level)."""
    cfg = configs.get_reduced(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    B, max_len, steps = 2, 16, 8
    spec = PG.PagingSpec.build(max_len, page_size=4, slots=B, int8=True)
    c_fp = T.init_caches(cfg, B, max_len)
    c_i8 = T.init_caches(cfg, B, max_len, paging=spec)
    pool = PG.reserve(PG.make_pool(spec, B),
                      jnp.full((B,), spec.max_pages, jnp.int32),
                      jnp.ones((B,), bool))
    c_i8 = PG.set_page_table(c_i8, pool.table)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, steps), 0, cfg.vocab)
    pos = jnp.zeros((B,), jnp.int32)
    for t in range(steps):
        tk = toks[:, t][:, None]
        l_fp, c_fp = T.decode_step(cfg, params, tk, c_fp, pos, drop_free=True)
        l_i8, c_i8 = T.decode_step(cfg, params, tk, c_i8, pos, drop_free=True)
        rel = (jnp.linalg.norm(l_fp - l_i8)
               / jnp.maximum(jnp.linalg.norm(l_fp), 1e-9))
        assert float(rel) < 0.05, f"step {t}: relative logit error {rel}"
        pos = pos + 1


def test_int8_engine_completes_within_sync_budget():
    """The int8 pack/unpack runs entirely in-graph: the fused engine still
    performs at most one blocking host sync per chunk."""
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, fused=True, chunk=8,
                      kv_paging=True, kv_page_size=8, kv_int8=True)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=int(
                        rng.integers(3, 8))).astype(np.int32), max_new=4)
            for i in range(6)]
    adapt_mod.reset_host_sync_count()
    eng.run(reqs)
    rep = eng.last_run_report
    assert all(r.done and len(r.out) == 4 for r in reqs)
    assert rep["chunks"] >= 2
    assert rep["host_syncs"] <= rep["chunks"]
    assert rep["memory"]["kv_int8"] is True
    # int8 arenas store 1 byte per element (+ f32 per-row scales): the
    # cache footprint must undercut the same geometry in fp32
    fp = ServeEngine(cfg, params, slots=2, max_len=32, kv_paging=True,
                     kv_page_size=8)
    assert (rep["memory"]["kv_cache_bytes"]
            < fp.memory_report()["kv_cache_bytes"] / 2)


# ---------------------------------------------------------------------------
# per-request max_len: reservation, eviction, head-of-line blocking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True])
def test_per_request_max_len_evicts_early(fused):
    """A request's own max_len bounds its KV budget: generation truncates
    at the request budget, not the engine-wide max_len — identically on
    both paths, paged or not."""
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=4).astype(np.int32)
    for paged in (False, True):
        kw = dict(kv_paging=True, kv_page_size=4) if paged else {}
        eng = ServeEngine(cfg, params, slots=2, max_len=32, fused=fused,
                          chunk=8, **kw)
        short = Request(uid=0, prompt=prompt, max_new=100, max_len=8)
        free = Request(uid=1, prompt=prompt, max_new=3)
        eng.run([short, free])
        # evicted at pos budget-1 = 7 after a 4-token prefill: 4 tokens out
        assert short.done and short.truncated and len(short.out) == 4
        assert free.done and not free.truncated and len(free.out) == 3


def test_tight_page_budget_blocks_admission_until_pages_free():
    """Worstcase reservation: with pages for only one worst-case request,
    concurrent slots cannot all be resident — admission stalls head-of-line
    until eviction releases pages, every request still completes, and
    streams match the roomy engine.  (Pinned to ``reserve='worstcase'``:
    the reserve-as-you-go default admits on prompt pages and packs more
    streams under the same budget — covered by the pressure tests.)"""
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 6)))
               .astype(np.int32) for _ in range(4)]

    def mk():
        return [Request(uid=i, prompt=p, max_new=3)
                for i, p in enumerate(prompts)]

    ref, _ = _streams(cfg, params, mk, dict(fused=True), max_len=16)
    for fused in (False, True):
        got, eng = _streams(cfg, params, mk, dict(fused=fused), max_len=16,
                            kv_paging=True, kv_page_size=4,
                            reserve="worstcase",
                            page_budget=4)  # one 16-token request's worth
        assert got == ref
        assert eng.last_run_report["peak_resident"] == 1
        assert int(PG.free_page_count(eng.pool)) == 4

    # mixed workload: short-budget requests pack 2-up into the same pool
    def mk_short():
        return [Request(uid=i, prompt=p, max_new=3, max_len=8)
                for i, p in enumerate(prompts)]

    got, eng = _streams(cfg, params, mk_short, dict(fused=True), max_len=16,
                        kv_paging=True, kv_page_size=4, page_budget=4,
                        reserve="worstcase")
    assert eng.last_run_report["peak_resident"] == 2
    assert [o for o, _ in got] == [o for o, _ in ref]  # none truncated sooner


# ---------------------------------------------------------------------------
# unified prompt/budget validation (bugfix satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True])
def test_submit_validation_unified(fused):
    """Empty, exact-fit and oversize prompts validate against the
    *effective* budget (request max_len or engine max_len) on both paths;
    the dead engine-wide ``max_prompt`` alias is gone."""
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=1, max_len=8, fused=fused)
    assert not hasattr(eng, "max_prompt")
    # exact fit: max_len - 2 leaves one generate slot before eviction
    eng.submit(Request(uid=0, prompt=np.zeros(6, np.int32), max_new=2))
    with pytest.raises(ValueError, match="cannot fit"):
        eng.submit(Request(uid=1, prompt=np.zeros(7, np.int32), max_new=2))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=2, prompt=np.zeros(0, np.int32), max_new=2))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(uid=3, prompt=np.zeros(3, np.int32), max_new=0))
    # per-request budgets: the same prompt fits or not by its own max_len
    eng2 = ServeEngine(cfg, params, slots=1, max_len=32, fused=fused)
    eng2.submit(Request(uid=4, prompt=np.zeros(6, np.int32), max_new=2,
                        max_len=8))
    with pytest.raises(ValueError, match="cannot fit"):
        eng2.submit(Request(uid=5, prompt=np.zeros(7, np.int32), max_new=2,
                            max_len=8))
    with pytest.raises(ValueError, match="exceeds the engine"):
        eng2.submit(Request(uid=6, prompt=np.zeros(3, np.int32), max_new=2,
                            max_len=64))
    with pytest.raises(ValueError, match="no room"):
        eng2.submit(Request(uid=7, prompt=np.zeros(1, np.int32), max_new=2,
                            max_len=1))
    # run the accepted work so the engines end clean
    eng.run([])
    eng2.run([])
    assert all(len(q) == 0 for q in (eng.queue, eng2.queue))


# ---------------------------------------------------------------------------
# paged Pallas kernel vs gather oracle
# ---------------------------------------------------------------------------


def test_paged_flash_kernel_matches_gather_oracle():
    """Interpret-mode paged kernel == masked jnp oracle on the gathered
    view, with ragged per-slot tables (unmapped tails) and offsets."""
    from repro.kernels.ops import paged_flash_attention
    from repro.models.layers import dot_attention

    rng = np.random.default_rng(0)
    B, Sq, Hq, Hkv, D = 3, 8, 4, 2, 16
    ps, n_pages, mp = 4, 10, 6
    spec = PG.PagingSpec(page_size=ps, n_pages=n_pages, max_pages=mp)
    # head-major arenas, as PG.store_init lays them out
    kp = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, D)), jnp.float32)
    table = np.full((B, mp), -1, np.int32)
    perm = rng.permutation(n_pages)
    off = 0
    for b, n in enumerate([6, 3, 4]):
        table[b, :n] = perm[off:off + n]
        off += n
    table = jnp.asarray(table)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    q_off = jnp.asarray([10, 2, 7], jnp.int32)
    kv_len = q_off + jnp.asarray([8, 5, 8], jnp.int32)
    out = paged_flash_attention(q, kp, vp, table, q_offset=q_off,
                                kv_len=kv_len, block_q=8, interpret=True)
    vk = PG.read_rows({"pages": kp}, table, spec, jnp.float32)
    vv = PG.read_rows({"pages": vp}, table, spec, jnp.float32)
    ref = dot_attention(q, vk, vv, causal=True, q_offset=q_off,
                        kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_rowwise_quant_roundtrip_error_bound():
    """The paged int8 pack/unpack: per-row absmax scaling bounds the
    roundtrip error by scale/2 = absmax/254 per element."""
    from repro.optim.compress import rowwise_dequant, rowwise_quant

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 5, 4, 8)) * 3.0, jnp.float32)
    q, scale = rowwise_quant(x, 2)
    assert q.dtype == jnp.int8 and scale.shape == (6, 5)
    back = rowwise_dequant(q, scale)
    bound = np.asarray(jnp.max(jnp.abs(x), axis=(2, 3))) / 254.0 + 1e-6
    err = np.asarray(jnp.max(jnp.abs(back - x), axis=(2, 3)))
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# memory_report observability
# ---------------------------------------------------------------------------


def test_memory_report_accounting():
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    plain = ServeEngine(cfg, params, slots=4, max_len=32)
    rep = plain.memory_report()
    assert rep["kv_paging"] is False
    assert rep["kv_bytes_per_stream"] == rep["kv_cache_bytes"] // 4

    eng = ServeEngine(cfg, params, slots=4, max_len=32, kv_paging=True,
                      kv_page_size=8)
    rep = eng.memory_report()
    assert rep["kv_paging"] is True and rep["pages_in_use"] == 0
    assert rep["n_pages"] == 4 * 4 and rep["pages_free"] == rep["n_pages"]

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=4)
                    .astype(np.int32), max_new=4, max_len=8)
            for i in range(4)]
    eng.run(reqs)
    rep = eng.last_run_report["memory"]
    assert rep["resident_streams"] == 0  # drained
    assert 0.0 <= rep["page_utilisation"] <= 1.0
    assert eng.last_run_report["peak_resident"] >= 2
    # mid-flight occupancy: admit without draining via the eager path
    # (worstcase pins the full budget at admission, so the ledger is
    # exact; the as-you-go default would hold only the prompt's page)
    eager = ServeEngine(cfg, params, slots=4, max_len=32, fused=False,
                        kv_paging=True, kv_page_size=8, reserve="worstcase")
    eager.submit(Request(uid=9, prompt=np.zeros(4, np.int32), max_new=50,
                         max_len=16))
    eager.step()
    rep = eager.memory_report()
    assert rep["resident_streams"] == 1
    assert rep["pages_in_use"] == 2  # ceil(16 / 8)
    assert rep["kv_bytes_per_stream"] == 2 * rep["page_bytes"]
    # as-you-go: the same admission holds only ceil(prompt / page) pages
    rayg = ServeEngine(cfg, params, slots=4, max_len=32, fused=False,
                       kv_paging=True, kv_page_size=8)
    rayg.submit(Request(uid=9, prompt=np.zeros(4, np.int32), max_new=50,
                        max_len=16))
    rayg.step()
    rep = rayg.memory_report()
    assert rep["resident_streams"] == 1
    assert rep["pages_in_use"] == 1  # ceil(4 / 8)
