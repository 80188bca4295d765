"""Largest share of the KV pool in use, sampled twice a second in the
window (``kv_usage``)."""
name, unit = "kv.peak_usage_pct", "%"
layer, moves, source = "KV pool (engine/prefix_pool.py)", "tokens_per_s", "program_counter"


def read(ctx):
    return 100.0 * max(ctx.kv_usage) if ctx.kv_usage else None
