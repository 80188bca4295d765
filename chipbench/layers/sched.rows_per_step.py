"""Decode rows a step carries: tokens decoded over steps taken, in the
window (``EngineMetrics.decode_tokens`` / ``num_steps``)."""
name, unit = "sched.rows_per_step", "rows"
layer, moves, source = "scheduler (engine/scheduler.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    steps = ctx.delta("num_steps")
    return ctx.delta("decode_tokens") / steps if steps else None
