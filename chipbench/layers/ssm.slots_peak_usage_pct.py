"""Largest share of the recurrent state's pool (its rows, a sequence each)
held at once in the window: what ``kv.peak_usage_pct`` is for the other
pool, and sampled as it is. ``stats()["ssm"]`` gives the pool's rows
(``slots``) and those held at the window's edges (``slots_in_use``);
between them the harness's samples, twice a second, are of the requests
running and waiting (``in_flight``). A running request holds a row and a
waiting one does not, so a sample reads high by what waited at it and the
peak is cut at the pool's rows. None on a program without a state pool or
without ``slots_in_use``."""
name, unit = "ssm.slots_peak_usage_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "tokens_per_s", "program_counter"


def read(ctx):
    before, after = (c.get("ssm") or {} for c in ctx.counters)
    if "slots_in_use" not in before or "slots_in_use" not in after \
            or not after.get("slots"):
        return None
    held = max(before["slots_in_use"], after["slots_in_use"], *ctx.in_flight)
    return 100.0 * min(held, after["slots"]) / after["slots"]
