"""Share of the window the engine thread was blocked on the device (the
``engine.finalize.wait`` loop phase: the first read of a step's tokens).
Near 100 the device sets the pace and the host has slack; as host work grows
it falls."""
name, unit = "engine.device_wait_pct", "%"
layer, moves, source = "step dispatch (EngineCore.step_*)", "tokens_per_s", "program_counter"


def read(ctx):
    try:
        return 100.0 * ctx.delta("loop", "engine.finalize.wait") / ctx.seconds
    except KeyError:      # a program without the loop clock
        return None
