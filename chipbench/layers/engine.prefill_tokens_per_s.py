"""Live prompt tokens prefilled per second of the window."""
name, unit = "engine.prefill_tokens_per_s", "tokens/s"
layer, moves, source = "model forward, prefill (models/llama.py)", "ttft_mean_ms", "program_counter"


def read(ctx):
    return ctx.delta("prefill_tokens") / ctx.seconds
