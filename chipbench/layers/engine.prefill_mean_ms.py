"""Time to first token, last part: from the first plan that carried a chunk
of the sequence to its first token being posted (every chunk step of the
prompt, and the steps of other requests between them). The mean is over the sequences whose first token
was posted inside the window, not over the requests due in it (the set
``ttft_mean_ms`` is taken over): the counters are the engine's own
(``EngineMetrics.ttft_*``), read at the window's edges."""
name, unit = "engine.prefill_mean_ms", "ms"
layer, moves, source = "model forward, prefill (models/llama.py)", "ttft_mean_ms", "program_counter"


def read(ctx):
    try:
        n = ctx.delta("ttft_count")
        return 1e3 * ctx.delta("ttft_prefill_s") / n if n else None
    except KeyError:      # a program without the counter
        return None
