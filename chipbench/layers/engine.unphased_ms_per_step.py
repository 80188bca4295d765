"""Time of the engine thread's loop outside every phase: the iterations'
wall less their phases (``loop["engine.unphased"]``: the statements between
the phases of ``AsyncJaxEngine._run``), over the steps of the window. It
says that the loop's phases are the whole of the loop: 0.10-0.13 ms a step
in every cell at PR 43. (It is not what ``breakdown.idle_gaps`` shows as
``no host event``: that is the slice's tail after the host's tracer has
stopped, ``PERF.md`` section 6, PR 43.) None on a program without it."""
name, unit = "engine.unphased_ms_per_step", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    try:
        steps = ctx.delta("num_steps")
        host = ctx.delta("loop", "engine.unphased")
    except KeyError:
        return None
    return 1e3 * host / steps if steps else None
