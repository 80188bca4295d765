"""Share of the traced slice device 0 spent in collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all;
synchronous ones, and asynchronous ones from ``-start`` to ``-done``) while
no other operation ran there: the time tensor parallelism adds to a step
that nothing hides. Only a program on several chips has any."""
from harness import xevents

name, unit = "mesh.collective_exposed_pct", "%"
layer, moves, source = "mesh collectives (parallel/mesh.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    if not ctx.trace or ctx.chips < 2:
        return None
    ns = xevents.collective_exposed_ns(xevents.current())
    return None if ns is None else 100.0 * ns * 1e-9 / ctx.trace["window_s"]
