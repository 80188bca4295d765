"""Time to first token, up to the scheduler: from a request's arrival at
``generate()`` to the first plan that carried a chunk of it, so the inbox
wait (``engine.inbox_wait_mean_ms``) is inside. The mean is over the sequences whose first token
was posted inside the window, not over the requests due in it (the set
``ttft_mean_ms`` is taken over): the counters are the engine's own
(``EngineMetrics.ttft_*``), read at the window's edges."""
name, unit = "sched.queue_wait_mean_ms", "ms"
layer, moves, source = "scheduler (engine/scheduler.py)", "ttft_mean_ms", "program_counter"


def read(ctx):
    try:
        n = ctx.delta("ttft_count")
        return 1e3 * ctx.delta("ttft_queue_s") / n if n else None
    except KeyError:      # a program without the counter
        return None
