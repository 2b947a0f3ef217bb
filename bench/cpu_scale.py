"""The tiny size at which the CPU tests run every cell end to end."""

TINY = {"pool": {"streams": 16},
        "warehouse": {"history_streams": 16, "history_segments": 256,
                      "chunk_rows": 256},
        "standing": {"queries": 8}}


def run_tiny(cell: str, seed: int = 5, seconds: float = 0.5, **kw):
    from bench import run
    return run.run_cell(cell, seed, seconds, False, scale=TINY,
                        require_chip=False, log=lambda s: None, **kw)
