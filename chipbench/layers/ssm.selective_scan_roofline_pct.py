"""The Mamba-1 recurrence's kernel against its roofline: over the matched
steps, each step's ideal time (``selective_scan_counts.ideal_seconds`` at the
step's own ``ssm_scan_rows`` and ``ssm_scan_positions``, off its
``engine.record`` span, with the shapes of ``stats()["ssm"]``) over the self
time of the ``selective_scan*`` calls in those steps' programs
(``step_join.py``): the kernel's own calls by name, not a phase, so what
surrounds it (the casts of its operands, the gate) is ``ssm.scan_roofline_pct``'s.
None without the spans, on a program whose steps carry no such count (every
program from before PR 56, every model without a Mamba-1 layer) or whose
trace has no such call."""
from pathlib import Path

from harness import measure, peaks

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
counts = measure.load_module(
    Path(__file__).with_name("selective_scan_counts.py"),
    "selective_scan_counts")

name, unit = "ssm.selective_scan_roofline_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    facts = ctx.counters[1].get("ssm")
    j = join.current() if facts else None
    if j is None or not j.steps:
        return None
    took = j.self_ns(lambda ins, _p: ins.startswith("selective_scan"),
                     j.step_modules()) * 1e-9
    if took <= 0:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    pk = peaks.peaks_for(kind)
    ideal = sum(counts.ideal_seconds(
        join.number(s.counts.get("ssm_scan_rows")),
        join.number(s.counts.get("ssm_scan_positions")), facts, pk)
        for s in j.steps)
    return 100.0 * ideal / took if ideal > 0 else None
