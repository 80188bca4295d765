"""How late the generator sent: send time less due time, 99th percentile
over the requests due in the window. A starved generator is not a fast
server."""
from harness.stats import percentile

name, unit = "loadgen.late_p99_ms", "ms"
layer, moves, source = "load generator (chipbench)", "ttft_mean_ms", "host_clock"


def read(ctx):
    late = [(r.sent - r.due) * 1e3 for r in ctx.due_in_window if r.sent]
    return percentile(late, 99) if late else None
