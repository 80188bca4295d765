"""Share of the device's busy time in the routed experts, whatever computes
them: the self time of the operations whose phase scope is ``moe_experts``
(``models/moe.py``: the sort of the rows by expert, the gather, the grouped
matmuls and their metadata, the weighted scatter back), found by each
program's phase table and not by an instruction's name (``step_join.py``).
The router and the shared expert are other phases (``device.moe_pct`` counts
them in). None without the tables or on a program with no such phase."""
from pathlib import Path

from harness import measure, xevents

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")

name, unit = "device.moe_experts_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    j = join.current()
    if j is None or not j.tables:
        return None
    busy = xevents.current().busy_ns()
    own = j.self_ns(lambda _i, phase: phase == "moe_experts")
    return 100.0 * own / busy if busy > 0 and own > 0 else None
