"""The whole step's share of its roofline: over the matched steps, the sum
of each step's ideal time, max(bytes / 819 GB/s, FLOP / 197 TFLOP/s) from
its own count (``step_work_counts.py``: the parameters a program reads once
whatever its rows, an expert's times the experts touched, the KV blocks
walked, the head; off the step's ``engine.record`` span), over the device
time of those steps' programs (``step_join.py``). A decode step is bound by
its bytes, a chunk step of hundreds of tokens by its FLOP, and each is held
to its own bound. None without the program's spans."""
from pathlib import Path

from harness import measure

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
work = measure.load_module(Path(__file__).with_name("step_work_counts.py"),
                           "step_work_counts")

name, unit = "engine.step_roofline_pct", "%"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "device_trace"


def read(ctx):
    found = join.matched(ctx)
    if found is None:
        return None
    j, shapes, pk = found
    took = sum(s.device_ns for s in j.steps) * 1e-9
    ideal = sum(work.ideal_seconds(work.step(shapes, s.counts), pk)
                for s in j.steps)
    return 100.0 * ideal / took if took > 0 else None
