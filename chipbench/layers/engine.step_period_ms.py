"""Window seconds over steps taken: the mean time from one step to the
next, idle time included."""
name, unit = "engine.step_period_ms", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    steps = ctx.delta("num_steps")
    return 1e3 * ctx.seconds / steps if steps else None
