"""Step programs the serving path had to build inside the window (compile
ledger events with source ``serve``). Should be 0: the cell's shapes are
warmed in set-up."""
name, unit = "engine.compiles_in_window", "count"
layer, moves, source = "step dispatch (EngineCore.step_*)", "ttft_mean_ms", "program_counter"


def read(ctx):
    lo, hi = ctx.window_wall
    return sum(1 for e in ctx.compile_events
               if e.get("source") == "serve" and lo <= e["ts"] + e["seconds"]
               and e["ts"] < hi)
