"""The program's own spans in a profiler trace: the served tick split
into its layers, on the device trace's clock.

``collect(logdir)`` returns ``bench.trace.collect``'s dict with one key
more, ``"program"``: ``[[name, start_ns, end_ns, {stats}], ...]``, the
host events named ``pool.*``, ``sink.*`` and ``host.*`` (the program's
``repro.obs`` spans, whose stats are the counts given to them).
``Split`` is a ``bench.trace.Reduced`` of that dict that also answers
``program_host_s``, ``program_stat`` and ``idle_by_program``; a dict
without ``"program"`` reduces as before, with no program spans.

The program's spans nest (``repro.obs`` lists them): a span's host
time is the part of its interval covered neither by the program spans
inside it nor by device busy time, the rule of ``host_self_s`` applied
one level down.
"""
from __future__ import annotations

import bisect
import glob
import os

from bench import trace

PREFIXES = ("pool.", "sink.", "host.")
# the program's device name scopes, innermost last in an op's name path
SCOPES = ("pool.switch", "pool.shed", "pool.forecast", "pool.lp",
          "sink.write", "sink.fold", "sink.answer")


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def collect(logdir: str) -> dict:
    import jax
    raw = trace.collect(logdir)
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    program, scoped, keys = [], {}, set()
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend([e.name, e.start_ns, e.end_ns, _stats(e)]
                               for e in line.events
                               if e.name.startswith(PREFIXES))
        elif trace._DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    scoped[plane.name] = _scoped_ops(line.events, keys)
    raw["program"] = sorted(program, key=lambda p: (p[1], -p[2]))
    raw["scoped_ops"], raw["op_stat_keys"] = scoped, sorted(keys)
    return raw


def _scoped_ops(events, keys):
    """[[scope, start_ns, end_ns], ...] of the ops whose name or string
    stats name one of the program's device scopes; ``keys`` gathers the
    stat names the ops carry."""
    out = []
    for e in events:
        stats = _stats(e)
        keys.update(stats)
        text = " ".join([e.name] + [v for v in stats.values()
                                    if isinstance(v, str)])
        hit = [sc for sc in SCOPES if sc in text]
        if hit:
            out.append([max(hit, key=text.rfind), e.start_ns, e.end_ns])
    return out


def _covered(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in trace._union(trace._clip(intervals, lo,
                                                          hi)))


class Split(trace.Reduced):
    def __init__(self, raw: dict):
        super().__init__(raw)
        self.program_events = sorted(
            ((n, float(s), float(e), dict(st))
             for n, s, e, st in raw.get("program", [])),
            key=lambda p: (p[1], -p[2]))
        self._starts = [p[1] for p in self.program_events]
        self._busy_starts = {d: [s for s, _ in b]
                             for d, b in self.busy.items()}
        self.raw_scoped = raw.get("scoped_ops", {})

    def program_spans(self, name: str):
        """(start, end, stats) of the program spans called ``name``
        inside the window."""
        return [(s, e, st) for n, s, e, st in self.program_events
                if n == name and s >= self.lo and e <= self.hi]

    def _inside(self, lo: float, hi: float):
        """Program spans strictly inside [lo, hi] (not [lo, hi] itself)."""
        out = []
        for j in range(bisect.bisect_left(self._starts, lo),
                       len(self.program_events)):
            n, s, e, _ = self.program_events[j]
            if s > hi:
                break
            if e <= hi and (s, e) != (lo, hi):
                out.append((s, e))
        return out

    def program_host_s(self, name: str):
        """Per span called ``name`` in the window: the seconds of its
        interval that neither a program span inside it nor device busy
        time covers, averaged over devices."""
        out = []
        for lo, hi, _ in self.program_spans(name):
            kids = self._inside(lo, hi)
            if not self.devices:
                out.append((hi - lo - _covered(kids, lo, hi)) * 1e-9)
                continue
            free = 0.0
            for d, b in self.busy.items():
                i = bisect.bisect_right(self._ends[d], lo)
                j = bisect.bisect_left(self._busy_starts[d], hi, lo=i)
                near = [tuple(iv) for iv in b[i:j]]
                free += hi - lo - _covered(kids + near, lo, hi)
            out.append(free / self.n_devices * 1e-9)
        return out

    def scoped_device_s(self):
        """Device seconds per name scope in the window, averaged over
        devices ({} where the ops carry no scope)."""
        tot = {}
        for ops in self.raw_scoped.values():
            for sc, s, e in ops:
                if e > self.lo and s < self.hi:
                    tot[sc] = tot.get(sc, 0.0) + min(e, self.hi) - max(
                        s, self.lo)
        return {k: v / max(self.n_devices, 1) * 1e-9
                for k, v in sorted(tot.items())}

    def program_stat(self, name: str, key: str):
        """The ``key`` stat of every span called ``name`` in the window
        that carries it."""
        return [st[key] for _, _, st in self.program_spans(name)
                if key in st]

    def idle_by_program(self, n: int = 12):
        """Idle device time in the window, summed by the innermost
        program span at each gap's midpoint, else by the innermost
        ``bench.*`` span there, else ``none``; longest first, averaged
        over devices."""
        prog = [(s, e, nm) for nm, s, e, _ in self.program_events]
        bench = sorted((s, e, nm) for nm, s, e in self.spans
                       if nm != trace.WINDOW)
        bench_starts = [s for s, _, _ in bench]
        tot = {}
        for b in self.busy.values():
            edges = [self.lo] + [x for iv in b for x in iv] + [self.hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e <= s:
                    continue
                mid = 0.5 * (s + e)
                name = _innermost(prog, self._starts, mid) \
                    or _innermost(bench, bench_starts, mid) \
                    or "none"
                tot[name] = tot.get(name, 0.0) + (e - s)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(self.n_devices, 1) * 1e-9] for k, v in rows]


def _innermost(spans, starts, t):
    """Name of the latest-starting span of ``spans`` (sorted by start;
    properly nested) that covers ``t``, or None."""
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0:
        s, e, name = spans[j]
        if e >= t:
            return name
        j -= 1
    return None
