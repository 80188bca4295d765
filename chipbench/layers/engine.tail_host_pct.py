"""Of the seconds of the token gaps at or above the bucket that holds the
window's 95th percentile, the share in which the engine thread was not
blocked on the device for the step that made the gap: 100 x (1 - ``wait_s``
/ ``gap_s``) over those buckets of ``stats()["gaps"]`` (``engine.gap_p95_ms``
has the mechanism; ``wait_s`` is the step's ``engine.finalize.wait``, at most
the gap). Planning and dispatching the next step, the finalize's host work,
the ledgers and the post are in it whether the device was busy under them or
not, so it does not read 0 in a device-paced cell; a tail made behind a host
stall reads near 100. None where the program files no gaps, or none in the
window."""
from harness.measure import load_reader

_base = load_reader("engine.gap_p95_ms")
name, unit = "engine.tail_host_pct", "%"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    win = _base.window(ctx)
    tail = win and _base.tail(win)
    if not tail or not tail["gap_s"]:
        return None
    return 100.0 * (1.0 - tail["wait_s"] / tail["gap_s"])
