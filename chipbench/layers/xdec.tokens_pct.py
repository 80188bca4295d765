"""Share of the window's live tokens that ran the second half of the stack:
the scheduling ledger's ``cross_tokens_total`` (the tokens that entered the
cross-decoder, one a row a step) over ``live_tokens_total``. A decode row's
one token is its last, so a decode-only window reads 100; a prompt's chunk
sends one of its tokens on. Lower is better at a given traffic: the tokens
that skip fourteen layers. None on a program without the counter (every
program from before PR 56) or for a model without a cross-decoder."""
name, unit = "xdec.tokens_pct", "%"
layer, moves, source = "model forward, prefill (models/llama.py)", "tokens_per_s", "program_counter"


def read(ctx):
    sched = ctx.counters[1].get("sched") or {}
    if "cross_tokens_total" not in sched:
        return None
    live = ctx.delta("sched", "live_tokens_total")
    cross = ctx.delta("sched", "cross_tokens_total")
    return 100.0 * cross / live if live and cross else None
