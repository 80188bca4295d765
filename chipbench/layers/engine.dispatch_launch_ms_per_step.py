"""Host time a step spends in the jitted call that enqueues its program:
the ``engine.dispatch.launch`` loop phase less the compiles nested in it
(``engine.compile``), over the steps of the window (``stats()["loop"]``).
None on a program without that phase."""
name, unit = "engine.dispatch_launch_ms_per_step", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    try:
        steps = ctx.delta("num_steps")
        host = ctx.delta("loop", "engine.dispatch.launch") \
            - ctx.delta("loop", "engine.compile")
    except KeyError:
        return None
    return 1e3 * host / steps if steps else None
