"""Device milliseconds per tick of the pool's sink ingest with its
standing folds (``_ingest_tick_masked``)."""


def read(run):
    s, n = run.trace.program("_ingest_tick_masked")
    return 1e3 * s / run.ticks if n and run.ticks else None
