"""Host milliseconds per pool tick: each ``bench.tick`` span (around
``SkyscraperPool.process`` and the wait for its standing state) less
the device busy time inside it, averaged over the window's ticks."""


def read(run):
    self_s = run.trace.host_self_s("bench.tick")
    if not self_s or not run.trace.devices:
        return None
    return 1e3 * sum(self_s) / len(self_s)
