"""On-device personalisation flow: one deployed model, many user tasks.

Demonstrates the production adaptation engine behind the façade: the
session compiles one sparse step per policy *structure* and reuses it
across users; each user gets their own delta pack (the base weights are
never touched), which can be folded into a serving copy per user.

    PYTHONPATH=src:. python examples/ondevice_adaptation.py
"""
import time

import numpy as np

from repro import api

bb = api.backbone("tiny-cnn", in_res=32, batch_size=64)
session = api.TinyTrainSession(bb, max_way=8, seed=0)
profile = api.STM32F746.scaled(mem=1.6, name="demo-mcu")  # ~512 KB envelope

users = [("user-a", "stripes"), ("user-b", "spots"), ("user-c", "waves"),
         ("user-d", "stripes")]
rng = np.random.default_rng(0)
delta_store = {}

for uid, domain in users:
    task = api.sample_task(rng, domain, res=32, max_way=8,
                           support_pad=64, query_pad=96)
    t0 = time.perf_counter()
    adaptation = session.adapt(task, profile, iters=20)
    dt = time.perf_counter() - t0
    # keep only the per-user delta pack + policy, not the episode tensors
    delta_store[uid] = (adaptation.deltas, adaptation.policy)
    print(f"{uid} ({domain}): adapted in {dt:.1f}s "
          f"(fisher {adaptation.fisher_seconds:.1f}s), "
          f"{adaptation.delta_param_count()/1e3:.1f}k delta params, "
          f"query acc {adaptation.accuracy()*100:.1f}%")

print(f"\ncompiled step variants: {session.compiled_steps()} "
      f"(vs {len(users)} users — structure reuse)")

# fleet mode: the same users adapted in O(#policy structures) dispatches —
# one batched probe per episode shape, one scanned fine-tune per structure
fleet_tasks = [api.sample_task(rng, domain, res=32, max_way=8,
                               support_pad=64, query_pad=96,
                               max_support_total=64,
                               max_support_per_class=16)
               for _, domain in users]
t0 = time.perf_counter()
fleet = session.adapt_many(fleet_tasks, profile, iters=20)
dt = time.perf_counter() - t0
accs = ", ".join(f"{a.accuracy()*100:.0f}%" for a in fleet)
print(f"fleet adapt_many: {len(fleet)} users in {dt:.1f}s "
      f"(query accs {accs})")

# heterogeneous fleet: real traffic never shares one episode shape — every
# user brings their own way/shot.  Bucketed padding (default) groups any
# mix into a handful of canonical buckets, so the whole fleet still runs
# in O(#buckets x #policy-structures) compiled calls; padded rows carry
# label -1 and contribute exactly nothing to the results.
het_tasks = [api.sample_task(rng, domain, res=32, max_way=8,
                             min_way=2 + i % 4,
                             support_pad=None, query_pad=None,
                             max_support_total=6 + 7 * (i % 3),
                             max_support_per_class=8, query_per_class=4)
             for i, (_, domain) in enumerate(users * 2)]
shapes = {t.support["episode_labels"].shape[0] for t in het_tasks}
t0 = time.perf_counter()
het = session.adapt_many(het_tasks, profile, iters=20)
dt = time.perf_counter() - t0
rep = session.last_fleet_report
print(f"heterogeneous fleet: {len(het)} users, {len(shapes)} episode "
      f"shapes -> {rep['buckets']} buckets, {rep['groups']} compiled "
      f"dispatches in {dt:.1f}s")

# mesh mode: on a multi-device host, adapt_many(mesh=...) shards each
# group's stacked task axis across the mesh's data axis (params stay
# replicated) — one host drives the whole fleet across all local devices.
# Force devices on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=8
import jax

if jax.device_count() > 1:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    t0 = time.perf_counter()
    sharded = session.adapt_many(het_tasks, profile, iters=20, mesh=mesh)
    dt = time.perf_counter() - t0
    print(f"mesh fleet: {len(sharded)} users across "
          f"{jax.device_count()} devices in {dt:.1f}s "
          f"(axes {session.last_fleet_report['mesh_axes']})")
