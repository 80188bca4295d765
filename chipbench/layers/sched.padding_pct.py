"""Share of the tokens the step programs computed that were padding:
1 - live / scheduled, from the scheduling ledger's two counts (the [B, T]
bucket against the rows' live tokens)."""
name, unit = "sched.padding_pct", "%"
layer, moves, source = "scheduler (engine/scheduler.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    if "sched" not in ctx.counters[0]:
        return None
    sched = ctx.delta("sched", "sched_tokens_total")
    if not sched:
        return None
    return 100.0 * (1.0 - ctx.delta("sched", "live_tokens_total") / sched)
