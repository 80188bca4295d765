"""Share of the window the engine thread had nothing to do (the
``engine.idle_wait`` loop phase: no request running, none waiting, no step
in flight). Beside ``device.idle_pct`` it says how much of the device's idle
time is the absence of work and how much the host's doing."""
name, unit = "engine.idle_wait_pct", "%"
layer, moves, source = "step dispatch (EngineCore.step_*)", "tokens_per_s", "program_counter"


def read(ctx):
    try:
        return 100.0 * ctx.delta("loop", "engine.idle_wait") / ctx.seconds
    except KeyError:      # a program without the loop clock
        return None
