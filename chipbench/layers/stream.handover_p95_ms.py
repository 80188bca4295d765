"""The 95th percentile of the hand-over in the window: from the engine
thread's stamp of a step's post, taken as the last row's output goes to the
event loop, to the moment the loop has put that output on its stream's
queue, behind the step's other ``put_nowait``s; one a step that posted
(``AsyncJaxEngine._post_step``, ``stats()["gaps"]["handover"]``:
``buckets`` from ``lo`` on over ``stats()["gaps"]["edges"]``).
What ``generate()``'s loop adds to a token gap before a consumer can take
the tokens; the loop that also runs the load generator shows here when it
is held. None where the program files none (the parent of PR 59), or none
in the window."""
from harness.measure import load_reader

_base = load_reader("engine.gap_p95_ms")
name, unit = "stream.handover_p95_ms", "ms"
layer, moves, source = "request stream (AsyncJaxEngine.generate)", "itl_p95_ms", "program_counter"


def read(ctx):
    first, last = (c.get("gaps") for c in ctx.counters)
    if last is None:
        return None
    edges = list(last["edges"])
    n = len(edges) + 1

    def dense(g):
        h = g["handover"]
        return _base._dense({"lo": h["lo"], "rows": h["buckets"]}, "rows", n)

    rows = dense(last)
    if first is not None:
        rows = [b - a for a, b in zip(dense(first), rows)]
    p95 = _base.quantile(rows, edges, 95)
    return None if p95 is None else 1e3 * p95
