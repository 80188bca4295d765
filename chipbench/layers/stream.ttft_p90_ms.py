"""Due time to first token on the ``generate()`` stream, 90th percentile over
the requests due in the window. Not an end-to-end metric: a chat window holds
41-49 requests, so 4 lie beyond it, and the same requests in another order
move it by 21-24 % (PERF.md, section 2)."""
from harness.stats import percentile

name, unit = "stream.ttft_p90_ms", "ms"
layer, moves, source = "request stream (AsyncJaxEngine.generate)", "ttft_mean_ms", "host_clock"


def read(ctx):
    ttft = [(r.first_token - r.due) * 1e3 for r in ctx.due_in_window
            if r.first_token is not None]
    return percentile(ttft, 90) if ttft else None
