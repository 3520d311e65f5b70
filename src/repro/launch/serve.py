"""Serving driver: batched requests through the continuous-batching engine.

With ``--adapt``, first runs TinyTrain through the façade on a synthetic
task and folds the deltas into the engine before serving (adapted models
serve at exactly base cost).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --preset smoke \
        --requests 16 --max-new 16 [--adapt --device jetson-nano]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from .. import api, configs
from ..models import transformer as T
from ..utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=32,
                    help="decode ticks per fused scan dispatch")
    ap.add_argument("--prefill-block", type=int, default=None,
                    help="prompt tokens ingested per prefilling slot per "
                         "tick (default: the arch's serve_prefill_block; "
                         "1 = token-by-token prefill)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="in-graph sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter for sampled decoding (0 = off)")
    ap.add_argument("--eager", action="store_true",
                    help="host-driven per-tick loop instead of scan_ticks")
    ap.add_argument("--paging", action="store_true",
                    help="paged KV cache: page-pool allocation at admission "
                         "instead of fixed per-slot stripes")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: arch kv_page_size)")
    ap.add_argument("--page-budget", type=int, default=None,
                    help="total pages per layer arena (default: the "
                         "fixed-stripe capacity slots*ceil(max_len/page))")
    ap.add_argument("--kv-int8", action="store_true",
                    help="store KV pages in int8 with per-token scales")
    ap.add_argument("--reserve", default=None,
                    choices=["asyougo", "worstcase"],
                    help="page reservation discipline (default: the arch's "
                         "kv_reserve; asyougo grows page-by-page in-scan)")
    ap.add_argument("--pressure", type=float, default=None, metavar="FRAC",
                    help="oversubscribe the page pool to FRAC of the "
                         "fixed-stripe capacity (e.g. 0.5); implies --paging")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request resident-tick budget; expired "
                         "requests end with outcome='expired'")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="admission backpressure: shed submissions beyond "
                         "this backlog with outcome='rejected'")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="fault injection, e.g. "
                         "'nan:3:2,pre:1:4,exhaust:10:20,qlimit:8' "
                         "(see repro.serving.faults.parse_inject)")
    ap.add_argument("--adapt", action="store_true",
                    help="TinyTrain-adapt to a synthetic task, fold, serve")
    ap.add_argument("--device", default="jetson-nano",
                    help="device profile preset used with --adapt")
    ap.add_argument("--adapt-iters", type=int, default=10)
    ap.add_argument("--personalise", action="store_true",
                    help="per-slot delta arena + online refresh: requests "
                         "are spread over --users users, finished streams "
                         "feed a background adapt_many pass between chunks "
                         "and refreshed delta sets hot-swap in without "
                         "draining (int8-EF compressed exchange)")
    ap.add_argument("--users", type=int, default=4,
                    help="distinct users sharing the engine with "
                         "--personalise (uid = request index mod users)")
    ap.add_argument("--fleet", type=int, default=None, metavar="R",
                    help="serve through R data-parallel engine replicas "
                         "behind one FleetRouter (least-loaded routing, "
                         "sticky uid placement, typed shedding only when "
                         "every replica is saturated); replicas pin "
                         "round-robin over the visible devices")
    ap.add_argument("--refresh-cap", type=int, default=None,
                    help="with --personalise: max users refreshed per "
                         "between-chunks window, ranked by stale-delta age "
                         "x banked streams (default: every eligible user)")
    args = ap.parse_args()
    if args.fleet is not None and args.fleet < 1:
        raise SystemExit("[serve] --fleet must be >= 1")
    if args.fleet and args.eager:
        raise SystemExit("[serve] --fleet requires the fused engine "
                         "(drop --eager)")

    enable_compile_cache()
    cfg = configs.preset_config(args.arch, args.preset)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    faults = None
    if args.inject:
        from ..serving.faults import parse_inject

        faults = parse_inject(args.inject)
    page_budget = args.page_budget
    paging = args.paging
    if args.pressure is not None:
        paging = True
        ps = args.page_size or cfg.kv_page_size
        stripe = args.slots * (-(-args.max_len // ps))
        page_budget = max(1, int(stripe * args.pressure))
        print(f"[serve] pressure {args.pressure}x: {page_budget} pages "
              f"(fixed-stripe capacity {stripe})")
    rng = np.random.default_rng(0)
    session = policy = None
    if args.personalise:
        # one probe adaptation fixes the shared delta structure: every
        # user's refresh runs policy_override=policy, so arena rows stay
        # template-compatible across hot-swaps
        bb = api.backbone(args.arch, preset=args.preset, batch_size=48,
                          seq=64)
        session = api.TinyTrainSession(bb, params, max_way=8)
        probe = session.adapt(api.sample_lm_task(rng, cfg.vocab, seq=64,
                                                 max_way=5),
                              api.device_profile(args.device), iters=1)
        if probe.policy.n_units == 0:
            print(f"[serve] WARNING: {args.device} budget selected no "
                  "units; --personalise disabled, serving base weights")
        else:
            policy = probe.policy
            print(f"[serve] personalising {args.users} users under "
                  f"{args.device}: {policy.describe()}")
    engine_kw = dict(slots=args.slots, max_len=args.max_len,
                     fused=not args.eager, chunk=args.chunk,
                     prefill_block=args.prefill_block,
                     temperature=args.temperature, top_k=args.top_k,
                     kv_paging=paging or None,
                     kv_page_size=args.page_size,
                     kv_int8=args.kv_int8 or None,
                     page_budget=page_budget,
                     reserve=args.reserve,
                     deadline_ticks=args.deadline_ticks,
                     queue_limit=args.queue_limit,
                     faults=faults,
                     personalise=policy)
    if args.fleet:
        eng = api.FleetRouter(cfg, params, replicas=args.fleet, **engine_kw)
        print(f"[serve] fleet: {args.fleet} replicas over "
              f"{len(set(map(str, eng.devices)))} device(s)")
    else:
        eng = api.ServeEngine(cfg, params, **engine_kw)

    if args.adapt:
        bb = api.backbone(args.arch, preset=args.preset, batch_size=48, seq=64)
        session = api.TinyTrainSession(bb, params, max_way=8)
        task = api.sample_lm_task(rng, cfg.vocab, seq=64, max_way=5)
        adaptation = session.adapt(task, api.device_profile(args.device),
                                   iters=args.adapt_iters)
        if adaptation.policy.n_units == 0:
            print(f"[serve] WARNING: {args.device} budget selected no "
                  "units (probe batch too large for the envelope); "
                  "serving base weights unchanged")
        else:
            if args.fleet:
                # fold into every replica, re-pinning each folded copy
                for e in eng.engines:
                    adaptation.fold_into(e)
                    if e.device is not None:
                        e.params = jax.device_put(e.params, e.device)
            else:
                adaptation.fold_into(eng)
            print(f"[serve] adapted on {args.device}: "
                  f"{adaptation.policy.describe()}")

    def enc_feats() -> "np.ndarray | None":
        # encoder-decoder / multimodal families carry precomputed frontend
        # embeddings per the config stubs (whisper frames / SigLIP patches)
        if cfg.is_encoder_decoder:
            return rng.standard_normal(
                (cfg.enc_len, cfg.d_model)).astype(np.float32)
        if cfg.family == "vlm":
            return rng.standard_normal(
                (cfg.n_img_tokens, cfg.img_embed_dim)).astype(np.float32)
        return None

    reqs = [
        api.Request(
            uid=i % args.users if policy is not None else i,
            prompt=rng.integers(0, cfg.vocab, size=int(rng.integers(4, 24))).astype(np.int32),
            max_new=args.max_new,
            enc_feats=enc_feats())
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    if policy is not None:
        pers = api.Personaliser(session, eng, policy,
                                profile=args.device,
                                iters=args.adapt_iters,
                                refresh_cap=args.refresh_cap)
        online = pers.run_online(reqs)
        dt = time.perf_counter() - t0
        for ref in online["refreshes"]:
            deferred = (f", {len(ref['deferred_users'])} deferred"
                        if ref.get("deferred_users") else "")
            wire = " (serialized)" if ref.get("wire_serialized") else ""
            print(f"[serve] refresh {ref['round']}: users {ref['users']}"
                  f"{deferred}, "
                  f"{ref['resident_rows_swapped']} resident rows swapped, "
                  f"wire{wire} {ref['payload_bytes_wire']} B vs f32 "
                  f"{ref['payload_bytes_f32']} B "
                  f"({ref['payload_ratio']:.1f}x), adapt "
                  f"{ref['adapt_seconds']:.2f}s, swap "
                  f"{1000 * ref['swap_seconds']:.1f}ms")
    else:
        eng.run(reqs)
        dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in reqs)
    prompt_toks = sum(len(r.prompt) for r in reqs)
    mode = ("eager" if args.eager else
            f"fused chunk={args.chunk} prefill_block={eng.prefill_block}, "
            f"{eng.last_run_report.get('host_syncs', 0)} host syncs")
    print(f"[serve] {args.requests} requests, {toks} new tokens "
          f"(+{prompt_toks} prompt tokens ingested) in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, {eng.ticks} engine ticks, "
          f"{args.slots} slots, {mode})")
    # under pressure a request legitimately ends rejected / expired /
    # preempted / numerics — report the outcome mix; only a request the
    # engine *lost* (no terminal outcome at all) is an engine error
    outcomes = eng.last_run_report.get("outcomes", {})
    if outcomes:
        print("[serve] outcomes: "
              + ", ".join(f"{k}={v}" for k, v in sorted(outcomes.items())))
    preempts = sum(r.preempts for r in reqs)
    if preempts:
        print(f"[serve] {preempts} preempt-and-requeue recompute swaps")
    lost = [r.uid for r in reqs if r.outcome is None]
    if lost:
        raise SystemExit(
            f"[serve] ENGINE ERROR: requests {lost} reached no terminal "
            "outcome")
    mem = eng.last_run_report.get("memory", eng.memory_report())
    peak = eng.last_run_report.get("peak_resident", 0)
    if args.fleet:
        print(f"[serve] fleet: {mem['alive']}/{mem['replicas']} replicas, "
              f"aggregate KV {mem['kv_cache_bytes']/2**20:.2f} MiB; "
              + ", ".join(
                  f"r{r['replica']}: {r.get('ticks', 0)} ticks/"
                  f"{r.get('host_syncs', 0)} syncs"
                  for r in eng.last_run_report.get("replicas", [])))
        mem = mem["per_replica"][0]  # per-replica layout details below
    if mem["kv_paging"]:
        print(f"[serve] paged KV: {mem['kv_cache_bytes']/2**20:.2f} MiB "
              f"({'int8' if mem['kv_int8'] else cfg.dtype} pages, "
              f"{mem['page_size']} tok/page, {mem['n_pages']} pages/layer, "
              f"{mem['page_bytes']} B/page), peak {peak} resident streams, "
              f"worst-case {mem['kv_bytes_per_stream']/2**10:.1f} KiB/stream")
    else:
        print(f"[serve] fixed-stripe KV: {mem['kv_cache_bytes']/2**20:.2f} "
              f"MiB across {args.slots} slots "
              f"({mem['kv_bytes_per_stream']/2**10:.1f} KiB/stream), "
              f"peak {peak} resident streams")
    if mem.get("delta_arena_bytes"):
        print(f"[serve] delta arena: {mem['delta_arena_bytes']/2**10:.1f} "
              f"KiB ({mem['delta_bytes_per_stream']/2**10:.2f} KiB/stream) "
              f"vs {mem['params_bytes_folded_copy']/2**20:.2f} MiB per "
              "folded params copy")
    if mem.get("enc_tokens"):
        per = (f"{mem['enc_pages_per_stream']} pages/stream"
               if mem["kv_paging"] else "fixed stripe")
        print(f"[serve] encoder runs: {mem['enc_tokens']} enc tokens "
              f"pinned per stream ({per}), arena "
              f"{mem['enc_arena_bytes']/2**10:.1f} KiB, resident "
              f"{mem['enc_run_bytes']/2**10:.1f} KiB")
    if any(r.truncated for r in reqs):
        print(f"[serve] {sum(r.truncated for r in reqs)} requests truncated "
              f"at max_len={args.max_len}")


if __name__ == "__main__":
    main()
