"""Of the token gaps at or above the bucket that holds the window's 95th
percentile (``engine.gap_p95_ms``'s bucket of ``stats()["gaps"]["edges"]``),
the share that a step carrying a prompt chunk made. Near 100, the tail
``itl_p95_ms`` reads is the chunk steps'; well under it, decode steps fill
the tail and a shorter chunk step would move nothing. None where the program
files no gaps, or none in the window."""
from harness.measure import load_reader

_base = load_reader("engine.gap_p95_ms")
name, unit = "sched.tail_mixed_pct", "%"
layer, moves, source = "scheduler (engine/scheduler.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    win = _base.window(ctx)
    tail = win and _base.tail(win)
    return 100.0 * tail["mixed_rows"] / tail["rows"] if tail else None
