"""Sequences preempted for want of blocks in the window."""
name, unit = "kv.preemptions", "count"
layer, moves, source = "KV pool (engine/prefix_pool.py)", "tokens_per_s", "program_counter"


def read(ctx):
    return ctx.delta("preemptions")
