"""Device milliseconds per replan (``_pool_replan``: the vmapped
forecaster and LP over every slot)."""


def read(run):
    s, n = run.trace.program("_pool_replan")
    return 1e3 * s / n if n else None
