"""What a kernel's work needs, counted from shapes: the numerators of
the roofline shares. They count the work the plan or the append needs,
whatever kernel carries it out, so that a later change to a kernel
cannot move its own yardstick."""
from __future__ import annotations

F32 = 4
SCALAR_COLUMNS = 8          # stream_id t category k quality on cloud buffer


def row_bytes(out_dim: int) -> int:
    """Bytes of one warehouse row: eight 4-byte scalars and the
    ``out_dim``-wide float32 output vector."""
    return F32 * (SCALAR_COLUMNS + out_dim)


def standing_state_bytes(groups) -> int:
    """Bytes of standing state for queries of ``groups`` result rows
    each: per query the closed-block sums, the open-block partials and
    the counts (float32 per result row) and the rows folded (int32)."""
    return sum(3 * F32 * g + F32 for g in groups)


def ingest_bytes(rows: int, out_dim: int, groups) -> int:
    """Bytes one tick's ingest needs: the landed rows written once, and
    every standing query's state read and written once. Not the copy
    of the whole store that an ingest without donated buffers makes."""
    return rows * row_bytes(out_dim) + 2 * standing_state_bytes(groups)
