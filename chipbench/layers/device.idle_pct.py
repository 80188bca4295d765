"""Share of the traced slice in which no operation ran on the device."""
name, unit = "device.idle_pct", "%"
layer, moves, source = "device (TPU v5e)", "itl_p95_ms", "device_trace"


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
