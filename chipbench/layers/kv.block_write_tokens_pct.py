"""Share of the window's live tokens whose keys and values went into the
paged cache by runs of consecutive slots, one small kernel a layer
(``dynamo_tpu/ops/kv_write.py``), and not by one scatter update a token:
the scheduling ledger's ``kv_block_written_tokens_total`` (the live tokens
of the steps whose program is packed, ``n < b x t``, under the kernel and
over a plain pool) over ``live_tokens_total``. A prompt's chunks go that
way and a decode program's rows keep the scatter, so it reads the share of
the window's tokens that were prompt tokens, near 100 where prompts are
long. None on a program without the counter (every program from before
PR 60) or in a window in which no step ran."""
name, unit = "kv.block_write_tokens_pct", "%"
layer, moves, source = "KV cache carry (models/llama.py scan)", "ttft_mean_ms", "program_counter"


def read(ctx):
    sched = ctx.counters[1].get("sched") or {}
    if "kv_block_written_tokens_total" not in sched:
        return None
    live = ctx.delta("sched", "live_tokens_total")
    if not live:
        return None
    return 100.0 * ctx.delta("sched", "kv_block_written_tokens_total") / live
