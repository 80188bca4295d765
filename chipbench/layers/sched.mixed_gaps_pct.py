"""Of the token gaps the engine filed in the window, the share that a step
carrying a prompt chunk made (class ``mixed`` of ``stats()["gaps"]``;
``engine.gap_p95_ms`` has the mechanism). ``itl_p95_ms`` reads the decode
steps' spread while this stands under 5 and a mixed step's length once it
passes 5: the cliff. None where the program files no gaps, or none in the
window."""
from harness.measure import load_reader

_base = load_reader("engine.gap_p95_ms")
name, unit = "sched.mixed_gaps_pct", "%"
layer, moves, source = "scheduler (engine/scheduler.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    win = _base.window(ctx)
    if win is None:
        return None
    rows = sum(_base.merged(win))
    return 100.0 * sum(_base.merged(win, only="mixed")) / rows if rows else None
