"""Device milliseconds per tick of the fused switch and shed program
(``_pool_tick``)."""


def read(run):
    s, n = run.trace.program("_pool_tick")
    return 1e3 * s / run.ticks if n and run.ticks else None
