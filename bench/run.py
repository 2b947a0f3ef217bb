#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the driver that
generates it (``bench/drivers/<driver>.py``, whose limits file is
``bench/drivers/<driver>.limits.json``); a per-layer metric ``<name>``
is read by ``bench/metrics/<name>.py``. Adding a configuration, a mix,
a driver or a metric is adding such files and an entry.

A driver's ``Driver(cfg, traffic, seed, scale, log)`` has ``setup``,
``window``, ``report``, ``needs``, ``collect``, ``free`` and
``numbers``, and the attributes ``capacity``, ``window_ticks``,
``attempted`` and ``failed``; its module has ``LIMITS``.

A run: finds the chips the cell asks for (a TPU, or it exits 3 with no
result), sets up from the seed (``setup_s``), measures for
``--seconds`` (with ``--trace 1`` under the profiler, and then the
per-layer metrics in place of the end-to-end ones), reads the device's
peak memory, frees the program's state and compares what the window
produced with the reference (the driver's ``numbers``). The last line
of standard output is the result; the numbers compared, each beside
its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
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


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, cfg, traffic


def cell_metrics(spec, cell, kind: str):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        elif "workloads" not in e2e[m["moves"]] \
                or cell["name"] in e2e[m["moves"]]["workloads"]:
            out.append(m)
    return out


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Counts lowerings and backend compiles (``jax.monitoring``)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.names = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowered",
                      dispatch.BACKEND_COMPILE_EVENT: "compiled"}
        self.n = {"lowered": 0, "compiled": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.names:
            self.n[self.names[event]] += 1

    def snapshot(self):
        return dict(self.n)


class Run:
    """What a per-layer metric's reader may look at."""

    def __init__(self, trace, ticks, needs, peaks):
        self.trace, self.ticks, self.needs, self.peaks = (trace, ticks,
                                                          needs, peaks)


def peaks_for(kind: str):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def device_info(jax, n_chips):
    devs = jax.devices()[:n_chips]
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats)}


def driver_module(name: str):
    path = os.path.join(BENCH, "drivers", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_driver_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             scale=None, require_chip=True, log=print, control=False):
    """One run; returns ``(result, numbers, limits)`` or ``None`` where
    the chips are missing. With ``control``, ``numbers`` also holds the
    control's numbers under ``"control"``: the reference in bfloat16
    in the program's place (``control.py``)."""
    t_start = time.perf_counter()
    spec, cell, cfg, traffic = load_cell(name)
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        print(f"run.py: cell {name} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return None
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles()
    mod = driver_module(traffic["driver"])
    phases, t_phase = [], t_start

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases.append(f"{name}={now - t_phase:.3f}")
        t_phase = now

    phase("start")
    drv = mod.Driver(cfg, traffic, seed, scale, log)
    drv.setup(phase)
    cap0, before = drv.capacity, compiles.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"setup seconds: {' '.join(phases)} lowered={before['lowered']} "
        f"compiled={before['compiled']}")
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=_trace_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        e2e = drv.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    after = compiles.snapshot()
    drv.report()
    log(f"window compiles: lowered={after['lowered'] - before['lowered']} "
        f"compiled={after['compiled'] - before['compiled']} "
        f"capacity={cap0}->{drv.capacity}")
    device = device_info(jax, cell["chips"])
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as tr
        red = tr.Reduced(tr.collect(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        peaks = (peaks_for(device["kind"]) if device["platform"] == "tpu"
                 else None)
        run = Run(red, drv.window_ticks, drv.needs(), peaks)
        for m in cell_metrics(spec, cell, "per_layer"):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        breakdown = {"device_ops": red.top_ops(),
                     "idle_gaps": red.idle_by_host()}
    else:
        e2e["setup_s"] = setup_s
        for m in cell_metrics(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    got = drv.collect()
    attempted, failed = drv.attempted, drv.failed
    drv.free()
    gc.collect()
    numbers = drv.numbers(got)
    if control:
        import ml_dtypes
        numbers["control"] = drv.numbers(got, ml_dtypes.bfloat16)
    from bench import oracle
    limits = mod.LIMITS
    result = {"correct": oracle.judge(numbers, limits),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    return result, numbers, limits


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   log=lambda s: print(s, flush=True))
    if out is None:
        return 3
    result, numbers, limits = out
    from bench import oracle
    for line in oracle.report_lines(numbers, limits):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
