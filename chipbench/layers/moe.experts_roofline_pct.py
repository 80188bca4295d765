"""The routed experts' phase against the grouped matmuls' roofline: over
the matched steps, each step's ideal time (``moe_gemm_counts.ideal_seconds``
at the step's own rows and experts touched a layer-step, times its
layer-steps: the device's counts off the step's ``engine.record`` span) over
the self time of the operations whose phase is ``moe_experts`` in those
steps' programs (``step_join.py``). The phase holds the sort, the gather,
the matmuls' metadata and the scatter back beside the matmuls, so it reads
under ``moe.expert_gemm_roofline_pct``; it does not depend on what computes
the matmuls or what it is called. None without the spans or the tables."""
from pathlib import Path

from harness import measure, peaks

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
gemm = measure.load_module(Path(__file__).with_name("moe_gemm_counts.py"),
                           "moe_gemm_counts")

name, unit = "moe.experts_roofline_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    facts = ctx.counters[1].get("moe")
    j = join.current() if facts else None
    if j is None or not j.steps or not j.tables:
        return None
    took = j.self_ns(lambda _i, phase: phase == "moe_experts",
                     j.step_modules()) * 1e-9
    if took <= 0:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    pk = peaks.peaks_for(kind)
    ideal = 0.0
    for s in j.steps:
        n = join.number(s.counts.get("moe_layer_steps"))
        if n:
            ideal += n * gemm.ideal_seconds(
                join.number(s.counts.get("moe_rows")) / n,
                join.number(s.counts.get("moe_experts_touched")) / n,
                facts["hidden_size"], facts["expert_width"],
                facts["bytes_per_param"], pk)
    return 100.0 * ideal / took if ideal > 0 else None
