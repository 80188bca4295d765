"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
operations that took most device time, and the longest idle gaps with what
the host was doing in them. Reads with ``jax.profiler.ProfileData`` alone.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation (nested where an operation, such as a
``while``, encloses others). Busy time is the union of those events, so
nesting is not counted twice; an operation's own time is its duration less
its children's.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = "XLA Ops"
HOST_PLANE = r"^/host:CPU$"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds (in the events' unit) each name ran itself: duration less the
    part covered by events nested inside it. ``events`` are
    ``(name, start, end)`` of one line."""
    total: dict[str, float] = {}
    stack: list[list] = []       # [name, end, own]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= (min(e, stack[-1][1]) - s)
        stack.append([name, e, e - s])
    close(float("inf"))
    return total


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] given merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap: tuple[float, float],
              host: list[tuple[str, float, float]]) -> str:
    """What the host was doing in ``gap``: the shortest host event that
    covers at least half of it (the innermost span that explains it)."""
    gs, ge = gap
    best, best_len = "no host event", float("inf")
    for name, s, e in host:
        cover = min(e, ge) - max(s, gs)
        if cover >= 0.5 * (ge - gs) and (e - s) < best_len:
            best, best_len = name, e - s
    return best


def short_name(hlo: str) -> str:
    """``%fusion.150 = bf16[32,512,14336]{2,1,0:T(8,128)} fusion(...)`` ->
    ``fusion bf16[32,512,14336]``: the instruction's name without its
    number, and its result's type, so that the same operation of one step
    program adds up and those of different buckets stay apart."""
    m = re.match(r"%?([\w\-.]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])", hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


def find_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def reduce(path: Path, traced_s: float = 0.0,
           device_plane: str = DEVICE_PLANE, op_line: str = OP_LINE,
           host_plane: str = HOST_PLANE, top: int = 10) -> dict:
    """``busy_s`` and the rest are missing when the trace holds no device
    operations; ``planes`` (events per line of each plane) is always there,
    so that a trace the reduction cannot read can be looked at.

    ``traced_s`` is how long the profiler ran, by the host's clock. The
    trace itself only spans its first to its last event, and a slice that
    begins or ends with nothing in flight has no event there: the window
    is the longer of the two, and what the events do not span is idle."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ns = 1e-9
    lo, hi = float("inf"), float("-inf")
    per_device: list[list[tuple[str, float, float]]] = []
    host: list[tuple[str, float, float]] = []
    shape: dict[str, dict[str, int]] = {}
    for plane in data.planes:
        is_dev = re.match(device_plane, plane.name) is not None
        is_host = re.match(host_plane, plane.name) is not None
        for line in plane.lines:
            evs = [(short_name(e.name) if is_dev else e.name, e.start_ns,
                    e.start_ns + e.duration_ns) for e in line.events]
            shape.setdefault(plane.name, {})[line.name] = len(evs)
            if not evs:
                continue
            lo = min(lo, min(s for _, s, _ in evs))
            hi = max(hi, max(e for _, _, e in evs))
            if is_dev and line.name == op_line:
                per_device.append(evs)
            elif is_host:
                host.extend((f"{line.name}: {n}", s, e) for n, s, e in evs
                            if e > s)
    if not per_device or hi <= lo:
        return {"planes": shape}
    busy_s, ops, idle = [], {}, {}
    for evs in per_device:
        merged = union([(s, e) for _, s, e in evs])
        busy_s.append(sum(e - s for s, e in merged) * ns)
        for name, t in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + t * ns / len(per_device)
    # Idle gaps of the first device, attributed to the host's spans.
    merged = union([(s, e) for _, s, e in per_device[0]])
    longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
    for g in longest[:60]:
        name = attribute(g, host)
        idle[name] = idle.get(name, 0.0) + (g[1] - g[0]) * ns
    span_s = (hi - lo) * ns
    if traced_s > span_s:
        idle["before the first or after the last event"] = traced_s - span_s
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy_s) / len(busy_s), "window_s": max(span_s, traced_s),
            "devices": len(per_device), "device_ops": rank(ops),
            "idle_gaps": rank(idle), "planes": shape}
