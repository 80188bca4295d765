"""Share of the rows the one-token update's grid was given that it passed
by: 100 x (1 - moved / given), from the scheduling ledger's two counts (a
program's bucket of rows times the recurrent layers, against the rows of one
token among them, whose state the kernel reads and writes; padded rows and
rows of several tokens cost it no byte). None on a program without those
counts, or where no such program ran in the window."""
name, unit = "ssm.update_rows_skipped_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "itl_p95_ms", "program_counter"


def read(ctx):
    if "ssm_update_rows_given_total" not in ctx.counters[0].get("sched", {}):
        return None
    given = ctx.delta("sched", "ssm_update_rows_given_total")
    if not given:
        return None
    return 100.0 * (1.0 - ctx.delta("sched", "ssm_update_rows_moved_total") / given)
