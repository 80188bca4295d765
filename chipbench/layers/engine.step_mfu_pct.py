"""The whole step's share of the chip's bf16 peak: the FLOP of the matched
steps' live tokens (``step_work_counts.py`` over each step's own count, off
its ``engine.record`` span: the layers' matmuls, the head's, attention's)
over the device time of those steps' programs (their ``XLA Modules`` events,
``step_join.py``) times 197 TFLOP/s. Counts and time are of the same steps.
A decode step of a few rows reads a few per cent: it is bound by the bytes
it reads (``engine.step_roofline_pct``). None without the program's spans."""
from pathlib import Path

from harness import measure

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
work = measure.load_module(Path(__file__).with_name("step_work_counts.py"),
                           "step_work_counts")

name, unit = "engine.step_mfu_pct", "%"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "device_trace"


def read(ctx):
    found = join.matched(ctx)
    if found is None:
        return None
    j, shapes, pk = found
    took = sum(s.device_ns for s in j.steps) * 1e-9
    flop = sum(work.step(shapes, s.counts)[1] for s in j.steps)
    return 100.0 * flop / (took * pk.flops_bf16) if took > 0 else None
