"""Host time a step spends building and placing a step program's inputs:
the ``engine.dispatch.fill`` (the numpy arrays, row by row) and
``engine.dispatch.place`` (host to device) loop phases, nested in
``engine.dispatch`` (``stats()["loop"]``), over the steps of the window.
None on a program without those phases."""
name, unit = "engine.dispatch_fill_ms_per_step", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    try:
        steps = ctx.delta("num_steps")
        host = ctx.delta("loop", "engine.dispatch.fill") \
            + ctx.delta("loop", "engine.dispatch.place")
    except KeyError:
        return None
    return 1e3 * host / steps if steps else None
