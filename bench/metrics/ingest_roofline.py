"""Share (%) of the HBM roofline that the tick ingest reaches: the
bytes one tick's append needs (``needs.ingest_bytes``: the landed rows
and the standing state read and written, not the store copy) over the
peak bandwidth, divided by its device time per tick."""


def read(run):
    s, n = run.trace.program("_ingest_tick_masked")
    if not n or not run.ticks or run.peaks is None:
        return None
    least_s = run.needs["ingest_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (s / run.ticks)
