"""Host work of the engine thread per step: the loop phases that are not
waits (inbox, plan, dispatch less the compiles inside it, finalize.host,
record, post; ``stats()["loop"]``) over the steps of the window. When this
nears the device's step time the host sets the pace."""
name, unit = "engine.host_ms_per_step", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


HOST = ("engine.inbox", "engine.plan", "engine.dispatch",
        "engine.finalize.host", "engine.record", "engine.post")


def read(ctx):
    try:
        steps = ctx.delta("num_steps")
        host = sum(ctx.delta("loop", k) for k in HOST) \
            - ctx.delta("loop", "engine.compile")
    except KeyError:      # a program without the loop clock
        return None
    return 1e3 * host / steps if steps else None
