"""Mean context under a chunk row in the window: the tokens a prefill
chunk's last query sees (``start + length``), summed over the chunk rows of
several tokens that ran (``stats()["attn"]["chunk_ctx_tokens"]``, counted by
``obs/sched_ledger.py step_counts`` from the rows of each step), over those
rows (``chunk_rows``). It says whether the cell's long contexts reach the
kernel: with prompts of 8k-30k in chunks of 512 it reads near half the mean
prompt, 8k-10k. None on a program without the counters (every program from
before PR 61, every model of keys and values by head) or in a window in
which no chunk row ran."""
name, unit = "mla.chunk_ctx_tokens", "tokens"
layer, moves, source = "scheduler (engine/scheduler.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    attn = ctx.counters[1].get("attn") or {}
    if "chunk_ctx_tokens" not in attn or "attn" not in ctx.counters[0]:
        return None
    rows = ctx.delta("attn", "chunk_rows")
    if not rows:
        return None
    return ctx.delta("attn", "chunk_ctx_tokens") / rows
