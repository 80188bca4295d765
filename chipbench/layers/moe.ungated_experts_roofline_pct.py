"""``moe.experts_roofline_pct``'s reading with a count that knows how many
matrices an expert has: over the matched steps, each step's ideal time
(``moe_ungated_counts.ideal_seconds`` at the step's own rows and experts
touched a layer-step, times its layer-steps, with ``expert_matrices`` from
``stats()["moe"]``) over the self time of the operations whose phase is
``moe_experts`` in those steps' programs (``step_join.py``). None without
the spans or the tables, or where the program does not say how many matrices
an expert has (one from before it could have two)."""
from pathlib import Path

from harness import measure, peaks

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
gemm = measure.load_module(Path(__file__).with_name("moe_ungated_counts.py"),
                           "moe_ungated_counts")

name, unit = "moe.ungated_experts_roofline_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    facts = ctx.counters[1].get("moe")
    if not facts or not facts.get("expert_matrices"):
        return None
    j = join.current()
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
                facts["expert_matrices"], facts["bytes_per_param"], pk)
    return 100.0 * ideal / took if ideal > 0 else None
