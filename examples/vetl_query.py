"""End-to-end V-ETL: Extract/Transform (fused ingestion engine) ->
**Load** (device-resident columnar warehouse) -> compiled queries.

    PYTHONPATH=src python examples/vetl_query.py

The paper's founding premise is that video analytics is a data
warehousing problem: video must become "an application-specific format
that is easy to query". This example runs a day of synthetic traffic
video through the fused engine with a ``SegmentStore`` sink (ingestion
-> store is zero per-segment host transfers), then answers analyst
questions as single compiled dispatches::

    store = SegmentStore(out_dim=K)
    IG.run_skyscraper_fused(fitted, stream, sink=store, ...)
    table, mask = store.query((
        Filter("quality", "ge", 0.6),
        WindowAgg(window=150, value="quality", agg="mean",
                  num_windows=windows_for(store, 150)),
        TopK(5, by="quality"),
    ))

Re-running a plan with new thresholds reuses the same executable (the
plan's VALUES are dynamic operands), older chunks spill to an
int8-quantized cold tier, and the whole warehouse survives a process
restart through ``checkpoint/ckpt.py``.

The final section scales the Load layer HORIZONTALLY: a ``ShardedStore``
partitions rows by stream-id hash across a device mesh and answers the
same plans through the partial/merge engine as ONE shard_map dispatch.
It runs on any CPU — the line below forces 4 host-platform devices
before jax initializes, so even a laptop gets a real 4-device shard
mesh (drop the env var to see the stacked single-device fallback).
"""
import os
import sys
# must be set BEFORE jax initializes: gives a plain CPU host 4 devices
# for the sharded-warehouse section
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.core.offline import fit
from repro.data.stream import generate
from repro.warehouse import (Filter, GroupBy, MultiGroupBy, SegmentStore,
                             ShardedStore, TieredStore, TopK, WindowAgg,
                             load_warehouse, save_warehouse, to_host,
                             windows_for)
from repro.warehouse import query as Q


def main():
    print("== offline phase (fit on 2 days of historical stream) ==")
    fitted = fit(COVID, n_cores=8, days_unlabeled=2.0, n_categories=4)
    K = len(fitted.configs)
    print(f"K={K} Pareto configs")

    print("\n== Extract/Transform/LOAD: 24h through the fused engine ==")
    stream = generate(COVID, days=1.0, seed=99)
    store = SegmentStore(out_dim=K, chunk_rows=8192)
    res = IG.run_skyscraper_fused(fitted, stream, n_cores=8,
                                  cloud_budget_core_s=15_000.0,
                                  buffer_gb=4.0, plan_days=0.25,
                                  sink=store)
    print(f"run quality {res.quality_pct:.2f}%  ->  {store}")

    print("\n== query 1: worst five 5-min windows (mean quality), "
          "confident segments only ==")
    nw = windows_for(store, 150)
    plan = (Filter("quality", "ge", 0.05),
            WindowAgg(window=150, value="quality", agg="mean",
                      num_windows=nw),
            TopK(5, by="quality", largest=False))
    worst = to_host(*store.query(plan))
    for w, q in zip(worst["window"], worst["quality"]):
        print(f"   window {w:4d} ({w * 150 * 2 / 3600:5.2f}h): "
              f"mean quality {q:.3f}")

    print("\n== query 2: on-prem work per content category ==")
    spend = to_host(*store.query(
        (GroupBy("category", "on_core_s", agg="sum",
                 num_groups=fitted.centers.shape[0]),)))
    for c, s, n in zip(spend["category"], spend["on_core_s"],
                       spend["count"]):
        print(f"   category {c}: {s:9.1f} core-s over {int(n)} segments")

    print("\n== re-query with a new threshold: same compiled kernel ==")
    before = Q.compile_cache_size()
    store.query((Filter("quality", "ge", 0.5),) + plan[1:])
    store.query((Filter("quality", "ge", 0.9),) + plan[1:])
    assert Q.compile_cache_size() == before, "recompiled!"
    print(f"   0 recompiles ({before} cached plan shapes total)")

    print("\n== tiering: spill old chunks to the int8 cold tier ==")
    ts = TieredStore(store, seed=0)
    spilled = ts.spill(keep_hot=store.n_rows // 4)
    print(f"   {ts} (spilled {spilled} rows, "
          f"max cold scale {ts.max_cold_scale():.2e})")
    cold_ans = to_host(*ts.query(plan))
    print(f"   same query across both tiers: windows "
          f"{cold_ans['window'].tolist()}")

    print("\n== persistence: the warehouse survives restart ==")
    path = "/tmp/vetl_warehouse.rsk"
    save_warehouse(path, ts)
    back = load_warehouse(path)
    again = to_host(*back.query(plan))
    assert np.array_equal(again["window"], cold_ans["window"])
    assert np.array_equal(again["quality"], cold_ans["quality"])
    print(f"   restored {back} from {path}; answers identical")

    print("\n== sharded warehouse: 4 streams hashed across 4 devices ==")
    import jax
    print(f"   host devices: {jax.device_count()}")
    V = 4
    streams = [generate(COVID, days=0.05, seed=10 + v) for v in range(V)]
    shard_store = ShardedStore(out_dim=K, n_shards=4, chunk_rows=2048)
    print(f"   mesh: {shard_store.mesh}"
          if shard_store.mesh is not None
          else "   (1 device: stacked fallback, same semantics)")
    # the fused multi-stream engine routes every stream's trace to its
    # owning shard device-side — ONE shard_map ingest dispatch
    IG.run_skyscraper_multi([fitted] * V, streams, n_cores_each=8,
                            cloud_budget_core_s=4_000.0, plan_days=0.25,
                            sink=shard_store)
    print(f"   {shard_store}")
    # the same plan runs as ONE dispatch: per-shard partial kernel
    # (masked segment_sum) + collective merge (psum) + top-k
    nw4 = windows_for(shard_store, 150)
    splan = (Filter("quality", "ge", 0.05),
             WindowAgg(window=150, value="quality", agg="mean",
                       num_windows=nw4),
             TopK(5, by="quality", largest=False))
    worst4 = to_host(*shard_store.query(splan))
    for w, q in zip(worst4["window"], worst4["quality"]):
        print(f"   window {w:4d}: mean quality {q:.3f}")
    before = Q.sharded_compile_cache_size()
    shard_store.query((Filter("quality", "ge", 0.5),) + splan[1:])
    assert Q.sharded_compile_cache_size() == before, "recompiled!"
    print("   re-query with a new threshold: 0 recompiles")
    # multi-key GroupBy: per (window x category) mean quality, fused
    # into one segment_sum pass
    by_wc = to_host(*shard_store.query((
        MultiGroupBy(keys=("t", "category"), value="quality", agg="mean",
                     nums=(nw4, fitted.centers.shape[0]),
                     windows=(150, 0)),
        TopK(3, by="quality", largest=False))))
    for w, c, q in zip(by_wc["t"], by_wc["category"], by_wc["quality"]):
        print(f"   window {w:4d} x category {c}: mean quality {q:.3f}")

    print("\nOK: ingest -> store -> query -> spill -> restore -> shard "
          "all good.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
