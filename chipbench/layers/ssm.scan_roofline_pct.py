"""The recurrent layers' convolution and scan against their roofline: over
the matched steps, each step's ideal time (``ssm_counts.ideal_seconds`` at
the step's own ``ssm_state_rows`` and ``ssm_live_tokens``, off its
``engine.record`` span, with the shapes of ``stats()["ssm"]``) over the self
time of the operations whose phase is ``ssm_conv`` or ``ssm_scan`` in those
steps' programs (``step_join.py``): the state's gather and scatter, the
recurrence or the blocked scan, the gate and its norm, whatever computes
them. None without the spans, the tables or such a phase (a program without
recurrent layers, or from before they were)."""
from pathlib import Path

from harness import measure, peaks

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")
counts = measure.load_module(Path(__file__).with_name("ssm_counts.py"),
                             "ssm_counts")

name, unit = "ssm.scan_roofline_pct", "%"
layer, moves, source = "recurrent layer (models/mamba.py)", "itl_p95_ms", "device_trace"

PHASES = ("ssm_conv", "ssm_scan")


def read(ctx):
    facts = ctx.counters[1].get("ssm")
    j = join.current() if facts else None
    if j is None or not j.steps or not j.tables:
        return None
    took = j.self_ns(lambda _i, phase: phase in PHASES,
                     j.step_modules()) * 1e-9
    if took <= 0:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    pk = peaks.peaks_for(kind)
    ideal = sum(counts.ideal_seconds(
        join.number(s.counts.get("ssm_state_rows")),
        join.number(s.counts.get("ssm_live_tokens")), facts, pk)
        for s in j.steps)
    return 100.0 * ideal / took if ideal > 0 else None
