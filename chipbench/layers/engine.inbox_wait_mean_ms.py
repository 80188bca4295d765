"""Time to first token, first part: from a request's arrival at
``generate()`` to ``add_request`` taking it out of the engine's inbox (the
engine thread drains the inbox once per loop iteration, and an iteration
blocks for a whole device step). The mean is over the sequences whose first token
was posted inside the window, not over the requests due in it (the set
``ttft_mean_ms`` is taken over): the counters are the engine's own
(``EngineMetrics.ttft_*``), read at the window's edges."""
name, unit = "engine.inbox_wait_mean_ms", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "ttft_mean_ms", "program_counter"


def read(ctx):
    try:
        n = ctx.delta("ttft_count")
        return 1e3 * ctx.delta("ttft_inbox_s") / n if n else None
    except KeyError:      # a program without the counter
        return None
