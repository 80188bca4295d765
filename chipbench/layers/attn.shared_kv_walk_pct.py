"""Share of the KV blocks the attention kernel walked in the window that were
the cross layers' rereads of the one shared layer: the scheduling ledger's
``kv_blocks_walked_shared_total`` over ``kv_blocks_walked_total`` (host
arithmetic from the rows' positions: a cross layer walks a row's whole
context for its last token, a windowed layer the blocks its window reaches).
None on a program without the counter (every program from before PR 56) or
where nothing was walked; 0 for a model without cross layers."""
name, unit = "attn.shared_kv_walk_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    sched = ctx.counters[1].get("sched") or {}
    if "kv_blocks_walked_shared_total" not in sched:
        return None
    walked = ctx.delta("sched", "kv_blocks_walked_total")
    return 100.0 * ctx.delta("sched", "kv_blocks_walked_shared_total") \
        / walked if walked else None
