"""The attention kernel against its roofline: over the matched steps the
kernel's ideal time (``step_work_counts.kernel``: the KV blocks walked, each
layer's window counted, times a block's bytes, the queries in and the output
back; QK^T and PV over the (query, key) pairs the rows see; the larger of
bytes / 819 GB/s and FLOP / 197 TFLOP/s a step) over the self time of the
``paged_attention*`` calls in those steps' programs (``step_join.py``). The
kernel computes whole blocks and copies them itself, so it reads well under
100. None without the program's spans or the kernel's name."""
from pathlib import Path

from harness import measure

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
work = measure.load_module(Path(__file__).with_name("step_work_counts.py"),
                           "step_work_counts")

name, unit = "attn.kernel_roofline_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    found = join.matched(ctx)
    if found is None:
        return None
    j, shapes, pk = found
    took = j.self_ns(lambda ins, _p: ins.startswith("paged_attention"),
                     j.step_modules()) * 1e-9
    ideal = sum(work.ideal_seconds(work.kernel(shapes, s.counts), pk)
                for s in j.steps)
    return 100.0 * ideal / took if took > 0 else None
