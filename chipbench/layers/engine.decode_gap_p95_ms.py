"""The 95th percentile of the token gaps that decode steps made: the gaps
the engine filed in the window under the class ``decode`` (every program of
the step a decode program; ``stats()["gaps"]``, ``engine.gap_p95_ms`` has
the mechanism). What the cell's ``itl_p95_ms`` would read with no chunk step
in its tail: the decode program's own pace and spread, the host's part of
the period included. None where the program files no gaps, or none of that
class in the window."""
from harness.measure import load_reader

_base = load_reader("engine.gap_p95_ms")
name, unit = "engine.decode_gap_p95_ms", "ms"
layer, moves, source = "model forward, decode (models/llama.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    win = _base.window(ctx)
    if win is None:
        return None
    p95 = _base.quantile(_base.merged(win, only="decode"), win["edges"], 95)
    return None if p95 is None else 1e3 * p95
