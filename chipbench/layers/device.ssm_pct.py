"""Share of the device's busy time in the recurrent layers' mixers: the self
time of the operations whose phase scope is ``ssm_proj`` (the in and out
projections), ``ssm_conv`` or ``ssm_scan`` (``models/mamba.py``), found by
each program's phase table and not by an instruction's name
(``step_join.py``). None without the tables or on a program with no such
phase."""
from pathlib import Path

from harness import measure, xevents

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")

name, unit = "device.ssm_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "itl_p95_ms", "device_trace"

PHASES = ("ssm_proj", "ssm_conv", "ssm_scan")


def read(ctx):
    j = join.current()
    if j is None or not j.tables:
        return None
    busy = xevents.current().busy_ns()
    own = j.self_ns(lambda _i, phase: phase in PHASES)
    return 100.0 * own / busy if busy > 0 and own > 0 else None
