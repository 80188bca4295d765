"""The share of the held experts' weights a routed layer-step reads: the
experts that had rows (``moe_experts_touched_total`` of the scheduling
ledger: counted on the device in ``models/moe.py held_rows``, fetched with
the step's tokens) over routed layer-steps times experts held. A grouped
matmul reads the weights of the groups that have rows, so where the experts
are most of a step's bytes this share sets the step: at R rows of k choices
over E experts it is about 1 - (1 - k/E)^R (64 experts, 6 a token: 80 % at
16 rows, 96 % at 32). None on a program without the counters."""
name, unit = "moe.experts_touched_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    facts = ctx.counters[1].get("moe")
    sched = ctx.counters[1].get("sched") or {}
    if not facts or "moe_experts_touched_total" not in sched:
        return None
    steps = ctx.delta("sched", "moe_layer_steps_total")
    if not steps:
        return None
    return 100.0 * ctx.delta("sched", "moe_experts_touched_total") / (
        steps * facts["experts_held"])
