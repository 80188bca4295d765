"""What the always-on ledgers cost per step (``_record_step``: the step
ring, ``StepPerfProfiler.measure``, the scheduling ledger with
``step_geometry``, the memory ledger): the ``engine.record`` loop phase over
the steps of the window."""
name, unit = "engine.record_ms_per_step", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    try:
        steps = ctx.delta("num_steps")
        return 1e3 * ctx.delta("loop", "engine.record") / steps if steps else None
    except KeyError:      # a program without the loop clock
        return None
