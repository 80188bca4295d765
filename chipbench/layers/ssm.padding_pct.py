"""Share of the positions the recurrent layers' mixers computed that were
padding: 1 - live / scanned, from the scheduling ledger's two counts (the
rows' live tokens against what the program computes for them: its token
bucket and, for each row of several tokens, the ``t`` positions of that
row's blocked scan). None on a program without those counts."""
name, unit = "ssm.padding_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    if "ssm_scanned_positions_total" not in ctx.counters[0].get("sched", {}):
        return None
    scanned = ctx.delta("sched", "ssm_scanned_positions_total")
    if not scanned:
        return None
    return 100.0 * (1.0 - ctx.delta("sched", "ssm_live_tokens_total") / scanned)
