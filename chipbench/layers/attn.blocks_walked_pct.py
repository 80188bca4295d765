"""KV blocks the attention kernel walks over all the layers, of what it
would walk were every layer full: ``kv_blocks_walked_total`` over
``kv_blocks_live_total`` x layers (the scheduling ledger's, host
arithmetic from the rows' positions and each layer's window: a sliding
layer's walk of a row begins at the block of the oldest key it sees). 100
for a model without windows; None on a program without the counter."""
name, unit = "attn.blocks_walked_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    sched = ctx.counters[1].get("sched") or {}
    shape = ctx.counters[1].get("kv_cache_shape") or ()
    if "kv_blocks_walked_total" not in sched or len(shape) != 5:
        return None
    live = ctx.delta("sched", "kv_blocks_live_total") * shape[0]
    return 100.0 * ctx.delta("sched", "kv_blocks_walked_total") / live \
        if live else None
