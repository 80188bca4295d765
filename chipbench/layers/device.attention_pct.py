"""Share of the device's busy time in the paged-attention kernel: the self
time of the ``paged_attention`` / ``paged_attention_splitk`` custom calls
(``ops/paged_attention.py`` names them). Not a roofline share: that needs
each call's live block count joined to its event."""
from harness import xevents

name, unit = "device.attention_pct", "%"
layer, moves, source = "paged attention kernel (ops/paged_attention.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    ev = xevents.current()
    pct = xevents.self_time_pct(
        ev, lambda hlo: xevents.instruction(hlo).startswith("%paged_attention"))
    return pct or None    # 0: the kernel is not named so in this program
