"""The share of the step programs' layer bodies that their builds traced and
lowered: ``layer_bodies_traced`` over ``layer_bodies`` of the compile ledger
(``stats()["compile"]``), both summed over the programs it recorded, at the
window's end. A program holds a body for each leading layer, each layer of
the scanned period and each layer behind it; a description that stands there
more than once is traced once and called (``models/llama.py _run_layers``).
100 where no body repeats (a model of identical layers: one body). None on
a program without the counts (the parent of PR 54)."""
name, unit = "engine.layer_bodies_traced_pct", "%"
layer, moves, source = "step program build (ModelRunner.warmup)", "setup_s", "program_counter"


def read(ctx):
    led = ctx.counters[1].get("compile") or {}
    if not led.get("layer_bodies"):
        return None
    return 100.0 * led["layer_bodies_traced"] / led["layer_bodies"]
