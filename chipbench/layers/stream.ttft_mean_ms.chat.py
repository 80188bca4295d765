"""Due time to first token on the ``generate()`` stream, mean over the
requests due in the window: what ``ttft_mean_ms`` reads where it is end to
end. In the chat cells it is a per-layer metric since PR 29: a mean of 41-49
times near 200 ms moves 5 % when the machine stops for one second of the 51,
and the machine does (PERF.md, Findings, PR 27 and 29). Read it beside the
result line's ``host.stall_max_ms``."""
name, unit = "stream.ttft_mean_ms.chat", "ms"
layer, moves, source = "request stream (AsyncJaxEngine.generate)", "itl_p95_ms", "host_clock"


def read(ctx):
    ttft = [(r.first_token - r.due) * 1e3 for r in ctx.due_in_window
            if r.first_token is not None]
    return sum(ttft) / len(ttft) if ttft else None
