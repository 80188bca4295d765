"""The engine's own ``itl_p95_ms``: the 95th percentile of the token gaps
the engine filed in the window, whatever step made them.

The engine thread files, at the end of ``engine.post``, the seconds since
each row was last posted tokens (``EngineCore.outputs_posted``,
``SchedLedger.record_post``): ``stats()["gaps"]["by_class"][cls][b]`` holds,
per bucket of ``stats()["gaps"]["edges"]`` from ``lo`` on, the ``rows``
filed, the sum of their gaps ``gap_s`` and the seconds of those the engine
thread was blocked on the device ``wait_s``, cumulative. The readers of PR
59 take the difference of the window's two snapshots, through the functions
below; this file is their base. None where the program has no such key (the
parent of PR 59, or ``DYN_SCHED_LEDGER=0``) or the window filed no gap."""
name, unit = "engine.gap_p95_ms", "ms"
layer, moves, source = "step dispatch (EngineCore.step_*)", "itl_p95_ms", "program_counter"

KEYS = ("rows", "gap_s", "wait_s")


def _dense(hist: dict, key: str, n: int) -> list[float]:
    out = [0.0] * n
    lo = hist["lo"]
    out[lo:lo + len(hist[key])] = hist[key]
    return out


def window(ctx) -> dict | None:
    """``{"edges": [...], cls: {"rows" | "gap_s" | "wait_s": [per bucket]}}``
    of the gaps filed between the window's edges, the row buckets of a
    class added up; None where the program files none."""
    first, last = (c.get("gaps") for c in ctx.counters)
    if last is None:
        return None
    edges = list(last["edges"])
    n = len(edges) + 1
    out: dict = {"edges": edges}
    for cls, cells in last["by_class"].items():
        total = {k: [0.0] * n for k in KEYS}
        before = (first or {}).get("by_class", {}).get(cls, {})
        for b, cell in cells.items():
            for k in KEYS:
                was = _dense(before[b], k, n) if b in before else [0.0] * n
                total[k] = [t + x - w for t, x, w in
                            zip(total[k], _dense(cell, k, n), was)]
        out[cls] = total
    return out


def merged(win: dict, key: str = "rows", only: str | None = None) -> list[float]:
    """One histogram of ``key`` over the classes (or the class ``only``)."""
    n = len(win["edges"]) + 1
    out = [0.0] * n
    for cls, total in win.items():
        if cls != "edges" and only in (None, cls):
            out = [a + b for a, b in zip(out, total[key])]
    return out


def quantile_bucket(rows: list[float], q: float) -> tuple[int, float] | None:
    """The bucket that holds the ``q``-th percentile (0..100) of the values
    counted in ``rows``, and how far into its count the percentile's rank
    lies (0..1): the rank is ``(n - 1) q / 100`` of the sorted values, as
    ``harness/stats.py percentile`` takes it. None of no values."""
    n = sum(rows)
    if n <= 0:
        return None
    pos, seen = (n - 1) * q / 100.0, 0.0
    for i, c in enumerate(rows):
        if c > 0 and pos < seen + c:
            return i, (pos - seen + 0.5) / c
        seen += c
    return None


def quantile(rows: list[float], edges: list[float], q: float) -> float | None:
    """The ``q``-th percentile in seconds, interpolated inside its bucket
    as if the bucket's values lay evenly between its edges (the first
    bucket starts at 0; the last has no upper edge and reads its lower)."""
    found = quantile_bucket(rows, q)
    if found is None:
        return None
    i, frac = found
    lo = edges[i - 1] if i else 0.0
    hi = edges[i] if i < len(edges) else lo
    return lo + (hi - lo) * min(max(frac, 0.0), 1.0)


def tail(win: dict) -> dict | None:
    """The gaps at or above the bucket of the window's 95th percentile:
    ``{"rows", "mixed_rows", "gap_s", "wait_s"}``; None of no gaps."""
    found = quantile_bucket(merged(win), 95)
    if found is None:
        return None
    i = found[0]
    return {"rows": sum(merged(win)[i:]),
            "mixed_rows": sum(merged(win, only="mixed")[i:]),
            "gap_s": sum(merged(win, "gap_s")[i:]),
            "wait_s": sum(merged(win, "wait_s")[i:])}


def read(ctx):
    win = window(ctx)
    if win is None:
        return None
    p95 = quantile(merged(win), win["edges"], 95)
    return None if p95 is None else 1e3 * p95
