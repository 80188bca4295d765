"""How much of the device's busy time the program's phase scopes leave
unnamed: the self time of the operations to which no phase table gives an
inner phase, over busy time. Unnamed are an operation of no step program
(an eager program, as ``reset_slot``'s), an instruction its program's table
does not name, and the two scopes that only enclose: ``layer``, a whole
transformer layer, whose rest is what no inner scope names (norms, rope,
residual adds, the moves around the kernel, and any new work that brings no
scope of its own), and ``layout``, the step's preparation. Small says the
other phase readers see the whole device. None without the tables."""
from pathlib import Path

from harness import measure, xevents

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")

name, unit = "device.unscoped_pct", "%"
layer, moves, source = "device (TPU v5e)", "itl_p95_ms", "device_trace"
UNNAMED = (None, "layer", "layout")


def read(ctx):
    j = join.current()
    if j is None or not j.tables:
        return None
    busy = xevents.current().busy_ns()
    if busy <= 0:
        return None
    return 100.0 * j.self_ns(lambda _i, phase: phase in UNNAMED) / busy
