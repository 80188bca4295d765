"""The share of the step programs that came whole from the program store
(``dynamo_tpu/engine/program_store.py``: the executables an earlier start
compiled, kept beside the persistent compile cache under a key that takes
no trace) and were neither traced nor lowered: ``programs_loaded`` over
``cache_entries`` of the compile ledger (``stats()["compile"]``) at the
window's end. 0 on a cold start, which builds every program and writes it;
100 on a warm one. None on a program whose ledger lacks the count (the
parent of PR 57), or that recorded no program."""
name, unit = "engine.programs_loaded_pct", "%"
layer, moves, source = "step program build (ModelRunner.warmup)", "setup_s", "program_counter"


def read(ctx):
    led = ctx.counters[1].get("compile") or {}
    if "programs_loaded" not in led or not led.get("cache_entries"):
        return None
    return 100.0 * led["programs_loaded"] / led["cache_entries"]
