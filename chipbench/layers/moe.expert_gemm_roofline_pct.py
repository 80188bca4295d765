"""The routed layers' grouped matmuls against their roofline: the ideal
time of a routed layer-step (``moe_gemm_counts.py``, beside this file:
max(bytes / 819 GB/s, FLOP / 197 TFLOP/s) from the rows computed and the
experts touched, the window's means a layer-step off the program's
counters) over the device time of one: the self time of the ``ragged-dot*``
operations in the traced slice (XLA's grouped matmul and its metadata op;
three matmuls a layer-step) over the layer-steps the slice held. The counts
are the window's and the time the slice's, both a layer-step: a slice that
holds lighter steps than the window reads high, so the ideal is taken at the
mean counts (max of the means, never above the mean of the maxes). None
without a trace, the operations or the counters."""
from pathlib import Path

from harness import measure, peaks, xevents

name, unit = "moe.expert_gemm_roofline_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "device_trace"

GEMMS_PER_LAYER_STEP = 3
counts = measure.load_module(Path(__file__).with_name("moe_gemm_counts.py"),
                             "moe_gemm_counts")


def gemm_seconds_per_layer_step(ev) -> float | None:
    """Mean device time of a routed layer-step's grouped matmuls in the
    slice; None where it held none."""
    if not ev.ops:
        return None
    own = xevents.self_times(ev.ops[0])
    ns = sum(t for hlo, t in own.items()
             if "ragged-dot" in xevents.instruction(hlo))
    calls = sum(1 for hlo, _, _ in ev.ops[0]
                if "ragged-dot" in xevents.instruction(hlo)
                and "metadata" not in xevents.instruction(hlo))
    if not calls or ns <= 0:
        return None
    return ns * 1e-9 / (calls / GEMMS_PER_LAYER_STEP)


def read(ctx):
    facts = ctx.counters[1].get("moe")
    sched = ctx.counters[1].get("sched") or {}
    if not facts or "moe_rows_total" not in sched:
        return None
    steps = ctx.delta("sched", "moe_layer_steps_total")
    took = gemm_seconds_per_layer_step(xevents.current())
    if not steps or took is None:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    ideal = counts.ideal_seconds(
        ctx.delta("sched", "moe_rows_total") / steps,
        ctx.delta("sched", "moe_experts_touched_total") / steps,
        facts["hidden_size"], facts["expert_width"],
        facts["bytes_per_param"], peaks.peaks_for(kind))
    return 100.0 * ideal / took
