"""Rows an expert held here computes in a routed layer-step: the (token,
choice) rows routed to the held experts (``moe_rows_total`` of the
scheduling ledger: summed on the device in ``models/moe.py held_rows``,
fetched with the step's tokens) over routed layer-steps times experts held.
At R rows in a step an expert of this share sees R x k / E of them (R / 16
here); a chip of the 8-way deployment at the same R rows a chip would see
R / 2. None on a program without the counters."""
name, unit = "moe.rows_per_expert_step", "rows"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    facts = ctx.counters[1].get("moe")
    sched = ctx.counters[1].get("sched") or {}
    if not facts or "moe_rows_total" not in sched:
        return None
    steps = ctx.delta("sched", "moe_layer_steps_total")
    if not steps:
        return None
    return ctx.delta("sched", "moe_rows_total") / (steps * facts["experts_held"])
