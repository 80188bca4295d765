"""Host-to-device placements of step inputs per step: the arrays
``ModelRunner.dispatch`` placed (``stats()["placed_inputs"]``) over the steps
of the window. One a greedy step program since the inputs are packed (two
where a row samples; a step cut by ``pack_rows`` is several programs);
thirteen before. None on a program without the counter."""
name, unit = "engine.placed_inputs_per_step", "arrays"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"


def read(ctx):
    if "placed_inputs" not in ctx.counters[0]:
        return None
    steps = ctx.delta("num_steps")
    return ctx.delta("placed_inputs") / steps if steps else None
