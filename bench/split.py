#!/usr/bin/env python3
"""The served tick split into its layers, from one traced window.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up as ``bench/run.py`` does, runs its window under the
profiler and prints one JSON line, reduced by ``bench/spans.py``:

- ``host_ms``: per window tick, the host time of each program span
  (its interval less the program spans inside it and device busy
  time), with ``pool.tick``'s own share as ``tick_untraced``;
- ``tick_host_ms``: ``bench/metrics/tick_host_ms.*``'s reading of the
  same trace, and ``pool_tick_host_ms``, the mean host time inside a
  ``pool.tick`` (all its spans');
- ``stats``: the mean of every count stat, per span;
- ``gc``: collections in the window by generation, and their time;
- ``idle_by_span``: idle device seconds by the innermost span;
- ``device_scope_ms``: per window tick, device time of the ops that
  carry a program name scope, and ``op_stat_keys``, what the device
  ops carry;
- ``slow_ticks``: every ``bench.tick`` over 100 ms, with its device
  busy time, the spans inside it of 5 ms or more (length, busy inside,
  stats), its longest device ops and the other host events of 20 ms
  or more in it;
- ``end_to_end``: the window's end-to-end numbers, traced.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SPANS = ("pool.tick", "pool.dispatch", "pool.replan", "pool.pull",
         "pool.transform", "pool.recorder", "pool.load", "sink.ingest",
         "sink.grow", "sink.alert_poll", "host.gc")
SLOW_S, PART_S = 0.1, 0.005


def split(red, ticks: int) -> dict:
    """The reduction of one traced window of ``ticks`` ticks."""
    per = max(ticks, 1)
    host = {n: 1e3 * sum(red.program_host_s(n)) / per for n in SPANS}
    host["tick_untraced"] = host.pop("pool.tick")
    pool_tick = [e - s for s, e, _ in red.program_spans("pool.tick")]
    busy_in = [red.busy_in(s, e) for s, e, _ in
               red.program_spans("pool.tick")]
    bench_tick = red.host_self_s("bench.tick")
    stats = {}
    for n in SPANS:
        keys = {k for _, _, st in red.program_spans(n) for k in st}
        stats[n] = {k: _mean(red.program_stat(n, k)) for k in sorted(keys)}
    gens = {}
    for s, e, st in red.program_spans("host.gc"):
        g = gens.setdefault(str(st.get("generation")), [0, 0.0])
        g[0] += 1
        g[1] += 1e3 * (e - s) * 1e-9
    slow = []
    for s, e in red.spans_named("bench.tick"):
        if (e - s) * 1e-9 < SLOW_S:
            continue
        parts = [[n, 1e3 * (pe - ps) * 1e-9,
                  1e3 * red.busy_in(ps, pe), st]
                 for n, ps, pe, st in red.program_events
                 if ps >= s and pe <= e and (pe - ps) * 1e-9 >= PART_S]
        slow.append({"lo": s, "hi": e, "start_s": (s - red.lo) * 1e-9,
                     "ms": 1e3 * (e - s) * 1e-9,
                     "busy_ms": 1e3 * red.busy_in(s, e), "spans": parts,
                     "ops": _ops_in(red, s, e)})
    return {
        "ticks": ticks,
        "host_ms": host,
        "host_ms_sum": sum(host.values()),
        "tick_host_ms": 1e3 * _mean(bench_tick),
        "pool_tick_host_ms": 1e3 * _mean(
            [(d * 1e-9 - b) for d, b in zip(pool_tick, busy_in)]),
        "stats": stats,
        "gc": gens,
        "idle_by_span": red.idle_by_program(),
        "idle_share": 100.0 * (1.0 - red.busy_s / red.window_s),
        "device_scope_ms": {k: 1e3 * v / per
                            for k, v in red.scoped_device_s().items()},
        "slow_ticks": slow,
    }


def _ops_in(red, lo, hi, n=3):
    """The ``n`` longest device ops overlapping [lo, hi], in ms."""
    ops = [[name, 1e3 * (min(e, hi) - max(s, lo)) * 1e-9]
           for v in red.devices.values() for name, s, e in v["ops"]
           if e > lo and s < hi]
    return sorted(ops, key=lambda x: -x[1])[:n]


def _others(logdir, slow, min_s=0.02):
    """Add to each slow tick the 12 longest host events of 20 ms or more
    inside it that are no span of the benchmark or the program:
    ``[plane, line, name, ms]``, what else the host did there."""
    import glob
    import jax
    if not slow:
        return
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    own = ("bench.", "pool.", "sink.", "host.")
    for t in slow:
        t["others"] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                d = (e.end_ns - e.start_ns) * 1e-9
                if d < min_s or e.name.startswith(own):
                    continue
                for t in slow:
                    if e.end_ns > t["lo"] and e.start_ns < t["hi"]:
                        t["others"].append([plane.name, line.name,
                                            e.name[:100], 1e3 * d])
    for t in slow:
        del t["lo"], t["hi"]
        t["others"] = sorted(t["others"], key=lambda o: -o[3])[:12]


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run, spans
    t0 = time.perf_counter()
    spec, cell, cfg, traffic = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"split.py: cell {args.workload} needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    drv = run.driver_module(traffic["driver"]).Driver(
        cfg, traffic, args.seed, None, lambda s: print(s, flush=True))
    drv.setup(lambda name: None)
    setup_s = time.perf_counter() - t0
    tdir = tempfile.mkdtemp(prefix="bench-split-")
    jax.profiler.start_trace(tdir, profiler_options=run._trace_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        e2e = drv.window(args.seconds)
    jax.profiler.stop_trace()
    drv.report()
    raw = spans.collect(tdir)
    out = split(spans.Split(raw), drv.window_ticks)
    _others(tdir, out["slow_ticks"])
    shutil.rmtree(tdir, ignore_errors=True)
    out.update(workload=args.workload, seed=args.seed, setup_s=setup_s,
               end_to_end=e2e, op_stat_keys=raw["op_stat_keys"],
               device=jax.devices()[0].device_kind)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
