"""Due time to first token on the ``generate()`` stream, median over the
requests due in the window. Not an end-to-end metric: the chat prompts'
median length sits on a chunk-bucket edge, so the median request flips
between two step shapes from run to run (PERF.md, Findings)."""
from harness.stats import percentile

name, unit = "stream.ttft_p50_ms", "ms"
layer, moves, source = "request stream (AsyncJaxEngine.generate)", "ttft_mean_ms", "host_clock"


def read(ctx):
    ttft = [(r.first_token - r.due) * 1e3 for r in ctx.due_in_window
            if r.first_token is not None]
    return percentile(ttft, 50) if ttft else None
