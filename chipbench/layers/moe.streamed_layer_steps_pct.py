"""The share of routed layer-steps whose experts the streaming kernel
computed (``ops/moe_stream.py``: every touched expert's three matrices read
once, no sort, gather or scatter): ``moe_streamed_layer_steps_total`` over
``moe_layer_steps_total`` of the scheduling ledger, the first counted for
the programs of which ``models/moe.py streams_experts`` says yes (decode-
sized, an expert fits VMEM twice, a TPU). 100 where every step is such a
program, 0 where the experts are too large for the kernel or every step is
a chunk's. None on a program without the counters."""
name, unit = "moe.streamed_layer_steps_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    sched = ctx.counters[1].get("sched") or {}
    if "moe_streamed_layer_steps_total" not in sched:
        return None
    steps = ctx.delta("sched", "moe_layer_steps_total")
    if not steps:
        return None
    return 100.0 * ctx.delta("sched", "moe_streamed_layer_steps_total") / steps
