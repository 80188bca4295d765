"""Share of the device's busy time in the routed layers: the self time of
the operations that are the grouped matmuls (instruction ``ragged-dot*``),
that read their result, or that touch a matrix of the routed layer: an
expert stack, the router, the shared expert, by the shapes the program
states in ``stats()["moe"]["shapes"]`` (the trace names an operation by its
HLO text and carries no scope). The sort of the rows and the top-k, a few
microseconds a layer, are outside it. A share of time, not of a roofline.
None on a program that states no such shapes."""
import re

from harness import xevents

name, unit = "device.moe_pct", "%"
layer, moves, source = "routed expert layer (models/moe.py)", "itl_p95_ms", "device_trace"


def matcher(shapes):
    """hlo text -> whether it names ``ragged-dot`` or an array whose
    trailing dimensions are one of ``shapes`` (a stack of L layers of it,
    or one layer cut out, has the same tail)."""
    tails = [",".join(str(d) for d in s) + "]" for s in shapes]
    arrays = re.compile(r"\w+\[([\d,]+\])")

    def keep(hlo: str) -> bool:
        if "ragged-dot" in hlo:
            return True
        return any(dims == t or dims.endswith("," + t)
                   for dims in arrays.findall(hlo) for t in tails)
    return keep


def read(ctx):
    facts = ctx.counters[1].get("moe")
    if not facts or not facts.get("shapes"):
        return None
    return xevents.self_time_pct(xevents.current(), matcher(facts["shapes"]))
