"""Seconds a step program took to warm in set-up: the compile ledger's
seconds less those of programs the serving path had to build
(``stats()["compile"]``: ``compile_seconds_total`` - ``serve_stall_seconds``)
over the programs it holds (``cache_entries``), at the window's end: warm-up
is over before the window's first edge. A warmed program's seconds are its
trace, its lowering, the executable's compile or its fetch from the cache,
and its first execution. None on a program without the ledger's keys."""
name, unit = "engine.warmup_s_per_program", "s"
layer, moves, source = "step program build (ModelRunner.warmup)", "setup_s", "program_counter"


def read(ctx):
    led = ctx.counters[1].get("compile") or {}
    if not {"compile_seconds_total", "serve_stall_seconds",
            "cache_entries"} <= led.keys() or not led["cache_entries"]:
        return None
    return (led["compile_seconds_total"] - led["serve_stall_seconds"]) \
        / led["cache_entries"]
