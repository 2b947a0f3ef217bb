"""Flight-recorder walkthrough: the observability layer end to end.

    PYTHONPATH=src python examples/vetl_observe.py

1. Fit a tiny Skyscraper on historical COVID stream, then run one day
   of fused ingestion with ``telemetry=True`` — the per-segment health
   counters (drops, buffer high-water mark, core-seconds, config
   switches) ride inside the SAME compiled scan, so the flight recorder
   costs zero extra dispatches.
2. Land the run in a SegmentStore sink and read the store-side
   counters: rows per shard, ingest-to-queryable lag, dispatch counts.
3. Trace a few ticks of a serving pool with ``jax.profiler``: the
   program's spans (``pool.tick``, ``pool.transform``, ``sink.ingest``,
   ``host.gc``, ... — the table in ``repro.obs``) land in the same
   trace as the device's ops, with their counts as stats. Open the
   directory in TensorBoard's profile plugin, or its
   ``perfetto_trace.json.gz`` in Perfetto.

The dispatch audit over EVERY engine plus the regression gate against
the committed baseline is one command::

    python -m repro.obs --json OBS_NEW.json --compare OBS.json
"""
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.core.api import Skyscraper, SkyscraperPool
from repro.core.offline import fit
from repro.data.stream import generate
from repro.warehouse import (Filter, GroupBy, SegmentStore,
                             StandingQueries, TopK)


def main():
    print("== 1. fused ingestion with the on-device flight recorder ==")
    fitted = fit(COVID, n_cores=8, days_unlabeled=2.0, n_categories=4,
                 seed=0)
    stream = generate(COVID, days=0.02, seed=7)
    store = SegmentStore(out_dim=len(fitted.configs), chunk_rows=512)
    tau = fitted.workload.segment_seconds
    res = IG.run_skyscraper_fused(
        fitted, stream, n_cores=8, cloud_budget_core_s=5_000.0,
        plan_days=64.5 * tau / 86400, forecast_mode="model",
        sink=store, telemetry=True)
    tel = res.telemetry
    print(f"   quality {res.quality_pct:6.2f}%  over "
          f"{stream.n_segments} segments")
    print(f"   telemetry: {tel.summary()}")
    # the counters are accumulated INSIDE the scan carry; the host
    # mirror in repro.obs.telemetry_ref reproduces them bit-exactly
    assert tel.segments == stream.n_segments
    # counter also sees a first-segment switch away from the boot
    # config, which diff(k_trace) cannot
    switches = int((np.diff(res.k_trace) != 0).sum())
    assert switches <= tel.config_switches <= switches + 1

    print("\n== 2. warehouse-side counters (same store, zero probes) ==")
    table, mask = store.query((Filter("quality", "ge", 0.0),
                               TopK(5, by="on_core_s")))
    stel = store.telemetry()
    print(f"   store: {stel.summary()}")
    assert stel.n_rows == stream.n_segments
    assert stel.query_dispatches == 1
    # fused batch ingest: row t waited T-1-t ticks before queryable
    assert stel.lag_max_ticks == stream.n_segments - 1

    print("\n== 3. a traced serving pool: the program's spans ==")
    sky = Skyscraper(segment_seconds=2.0, n_categories=3)
    sky.set_resources(num_cores=4)
    sky.register_knob("det", [1, 5, 10])

    def proc(seg, kv):
        return seg, float(np.clip(1 - seg * (1 - 1.0 / kv["det"]), 0, 1))

    sky.fit(list(np.linspace(0, 1, 40)), proc, plan_segments=4)
    V = 8
    sink = SegmentStore(out_dim=len(sky.configs), chunk_rows=64)
    StandingQueries(sink).subscribe(
        (GroupBy("stream_id", "buffer_s", "max", num_groups=V),),
        Filter("buffer_s", "gt", 30.0))
    pool = SkyscraperPool(sky, n_streams=V, sink=sink, telemetry=True)
    rng = np.random.default_rng(0)
    pool.process(list(rng.random(V)))        # compile outside the trace
    logdir = tempfile.mkdtemp(prefix="vetl_profile_")
    with jax.profiler.trace(logdir, create_perfetto_trace=True):
        for _ in range(8):
            pool.process(list(rng.random(V)))
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    counts = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("pool.", "sink.", "host.")):
                    counts[e.name] = counts.get(e.name, 0) + 1
    for name, n in sorted(counts.items()):
        print(f"   {name:18s} x{n}")
    assert counts["pool.tick"] == 8 and counts["sink.alert_poll"] == 8
    ex = pool.telemetry().extras
    print(f"   transfers: host_pulls={ex['host_pulls']:.0f} "
          f"uploads={ex['uploads']:.0f} (pool), "
          f"{sink.obs['host_pulls']}/{sink.obs['uploads']} (sink)")
    print(f"   wrote the profile to {logdir} (TensorBoard; Perfetto: "
          f"perfetto_trace.json.gz)")
    print("\nOK: flight recorder, counters and program spans healthy.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
