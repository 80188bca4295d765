"""Share of the query positions attention was handed that were no live
token: 100 x (1 - live / handed), from the scheduling ledger's two counts
(`rect_tokens_total`: the `b x t` of a program's rectangle, or a packed
step's token bucket where the kernel takes the tokens as they lie, against
the rows' live tokens). None on a program without the count, or where no
step ran in the window."""
name, unit = "attn.q_padding_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "ttft_mean_ms", "program_counter"


def read(ctx):
    if "rect_tokens_total" not in ctx.counters[0].get("sched", {}):
        return None
    handed = ctx.delta("sched", "rect_tokens_total")
    if not handed:
        return None
    return 100.0 * (1.0 - ctx.delta("sched", "live_tokens_total") / handed)
