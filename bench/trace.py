"""Reduction of a profiler trace to the benchmark's device numbers.

``collect(logdir)`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote into a plain dict (``{"spans": [[name, start_ns, end_ns], ...],
"devices": {plane: {"ops": [...], "modules": [...]}}}``), and
``Reduced`` answers questions about it. Tests build the same dict by
hand.

- Host spans are the benchmark's own annotations, named ``bench.*``.
- Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
  the operations and their ``XLA Modules`` line the programs, whose
  names carry the jitted function's name (``jit__pool_tick_fn``).
- Busy time is the union of the operation intervals, per device, inside
  the traced window (the ``bench.window`` span), averaged over devices.
- A program's device time is the sum of its module events whose name
  holds the program's stable name, inside the window, averaged over
  devices.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def op_name(hlo: str) -> str:
    """A device op's short name from its HLO text: the instruction's
    name, result shape and opcode (``copy.71 f32[67108864,9] copy``)."""
    lhs, _, rhs = hlo.partition(" = ")
    op = _OPCODE.search(" " + rhs)
    if not rhs or not op:
        return hlo[:120]
    shape = "tuple" if rhs.startswith("(") else rhs.split("{")[0].split()[0]
    return f"{lhs.lstrip('%')} {shape} {op.group(1)}"


def collect(logdir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    spans, devices = [], {}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key == "ops":
                    dev[key] = [[op_name(e.name), e.start_ns, e.end_ns]
                                for e in line.events]
                elif key:
                    dev[key] = [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events
                             if e.name.startswith("bench."))
    return {"spans": spans, "devices": devices}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _overlap(merged, ends, lo, hi) -> float:
    """Length of ``merged`` (disjoint, sorted; ``ends`` its ends) that
    lies inside [lo, hi]."""
    tot = 0.0
    for s, e in merged[bisect.bisect_right(ends, lo):]:
        if s >= hi:
            break
        tot += min(e, hi) - max(s, lo)
    return tot


class Reduced:
    def __init__(self, raw: dict):
        self.spans = [(n, float(s), float(e)) for n, s, e in raw["spans"]]
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if not wins:
            raise ValueError("trace holds no bench.window span")
        self.lo, self.hi = wins[0]
        self.devices = raw["devices"]
        self.busy = {d: _union(_clip([(s, e) for _, s, e in v["ops"]],
                                     self.lo, self.hi))
                     for d, v in self.devices.items()}
        self._ends = {d: [e for _, e in b] for d, b in self.busy.items()}

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return (sum(e - s for b in self.busy.values() for s, e in b)
                / self.n_devices * 1e-9)

    def spans_named(self, name: str):
        return [(s, e) for n, s, e in self.spans
                if n == name and s >= self.lo and e <= self.hi]

    def busy_in(self, lo: float, hi: float) -> float:
        """Device busy seconds inside [lo, hi], averaged over devices."""
        if not self.devices:
            return 0.0
        return (sum(_overlap(b, self._ends[d], lo, hi)
                    for d, b in self.busy.items())
                / self.n_devices * 1e-9)

    def program(self, stable: str):
        """(seconds, launches) of the modules named by ``stable``, per
        device on average; (0, 0) where none ran."""
        if not self.devices:
            return 0.0, 0
        tot, n = 0.0, 0
        for v in self.devices.values():
            for name, s, e in v["modules"]:
                if stable in name and e > self.lo and s < self.hi:
                    tot += min(e, self.hi) - max(s, self.lo)
                    n += 1
        return tot / self.n_devices * 1e-9, n // self.n_devices

    def host_self_s(self, name: str):
        """Per span called ``name`` in the window: its length less the
        device busy time inside it."""
        return [(e - s) * 1e-9 - self.busy_in(s, e)
                for s, e in self.spans_named(name)]

    def top_ops(self, n: int = 10):
        tot = {}
        for v in self.devices.values():
            for name, s, e in v["ops"]:
                if e > self.lo and s < self.hi:
                    d = min(e, self.hi) - max(s, self.lo)
                    tot[name] = tot.get(name, 0.0) + d
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(self.n_devices, 1) * 1e-9] for k, v in rows]

    def idle_by_host(self, n: int = 10):
        """Idle device time in the window, summed by the innermost
        ``bench.*`` host span at each gap's midpoint (``none`` where
        the host was in no span), longest first. With several devices,
        the gaps of each are counted and the sums averaged."""
        inner = sorted((s, e, nm) for nm, s, e in self.spans
                       if nm != WINDOW)
        starts = [s for s, _, _ in inner]
        tot = {}
        for b in self.busy.values():
            edges = [self.lo] + [x for iv in b for x in iv] + [self.hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e <= s:
                    continue
                mid = 0.5 * (s + e)
                name = "none"
                # the benchmark's inner spans do not overlap: the last
                # one starting before the midpoint is the only candidate
                j = bisect.bisect_right(starts, mid) - 1
                if j >= 0 and inner[j][1] >= mid:
                    name = inner[j][2]
                tot[name] = tot.get(name, 0.0) + (e - s)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(self.n_devices, 1) * 1e-9] for k, v in rows]
