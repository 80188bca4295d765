"""The latent (MLA) walk against its roofline: over the matched steps, each
step's ideal time (``mla_counts.ideal_seconds`` at the step's own
``kv_blocks_walked``, ``attn_q_ctx`` and ``live_tokens``, off its
``engine.record`` span, with the widths of ``stats()["attn"]``: the useful
576 and 512, not the stored row's padding) over the self time of the
``paged_attention*`` calls in those steps' programs (``step_join.py``): the
kernel's own calls by name, which in a model of latent attention are the
walk in its latent form and nothing else. The kernel walks a chunk row's
context once a tile of 16 tokens and computes the padded lanes, so it reads
well under 100. None without the spans, on a program that states no latent
cache (every program from before PR 61, every model of keys and values by
head) or whose trace has no such call."""
from pathlib import Path

from harness import measure, peaks

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
counts = measure.load_module(Path(__file__).with_name("mla_counts.py"),
                             "mla_counts")

name, unit = "attn.latent_roofline_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    facts = ctx.counters[1].get("attn")
    shapes = ctx.counters[1].get("step_shapes") or {}
    if not facts or facts.get("cache_kind") != "latent" \
            or "block_size" not in shapes:
        return None
    j = join.current()
    if j is None or not j.steps:
        return None
    took = j.self_ns(lambda ins, _p: ins.startswith("paged_attention"),
                     j.step_modules()) * 1e-9
    if took <= 0:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    pk = peaks.peaks_for(kind)
    ideal = sum(counts.ideal_seconds(
        join.number(s.counts.get("kv_blocks_walked")),
        join.number(s.counts.get("attn_q_ctx")),
        join.number(s.counts.get("live_tokens")), facts,
        shapes["block_size"], pk) for s in j.steps)
    return 100.0 * ideal / took if ideal > 0 else None
