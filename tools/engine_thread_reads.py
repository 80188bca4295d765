"""What the engine thread did outside its loop phases, from a profiler trace.

    python tools/engine_thread_reads.py <trace dir or .xplane.pb> [--out f.json]

The engine thread's loop is cut into ``engine.*`` phases (obs/profiler.py
``loop_phase``), written into ``jax.profiler``'s trace beside the runtime's
own events of that thread (``np.asarray(jax.Array)``: a host read of a
device array; ``PjitFunction(...)``: a jitted call). This reads the newest
``*.xplane.pb`` under the directory, takes the host lines that hold
``engine.*`` spans, and reports, as one JSON object:

- ``uncovered``: per name, the count and seconds of the runtime's events on
  those lines that no ``engine.*`` span covers, longest first: a read the
  loop makes outside every phase shows here, and nowhere else by name;
  ``edge`` the same of the events before the line's first recorded span or
  after its last (a span open when the session starts or stops is not
  recorded, the events inside it are);
- ``reads``: per innermost covering phase, the count, seconds and longest
  of the ``np.asarray(jax.Array)`` events, with the ``step`` of the phase
  that held the longest;
- ``steps``: how many ``step`` ordinals carry all three of
  ``engine.finalize.wait``, ``engine.record`` and ``engine.post``, and how
  many carry some of them only (the slice's edges cut a step or two).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

READ = "np.asarray(jax.Array)"
JOINED = ("engine.finalize.wait", "engine.record", "engine.post")


def newest_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    files = sorted(path.glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise SystemExit(f"no *.xplane.pb under {path}")
    return files[-1]


def engine_lines(path: Path) -> list[list[tuple[str, int, int, dict]]]:
    """The events ``(name, start_ns, end_ns, stats)`` of each host line that
    holds an ``engine.*`` span, by start."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
                   for e in line.events]
            if any(name.startswith("engine.") for name, *_ in evs):
                out.append(sorted(
                    ((n, s, e, dict(ev.stats) if n.startswith("engine.")
                      else {}) for n, s, e, ev in evs),
                    key=lambda x: (x[1], -x[2])))
    return out


def reduce(lines) -> dict:
    uncovered: dict[str, list[float]] = {}
    edge: dict[str, list[float]] = {}
    reads: dict[str, dict] = {}
    steps: dict[int, set[str]] = {}
    for evs in lines:
        open_spans: list[tuple[str, int, int, dict]] = []
        spans = [(s, e) for name, s, e, _ in evs if name.startswith("engine.")]
        first, last = min(s for s, _ in spans), max(e for _, e in spans)
        for name, s, e, stats in evs:
            while open_spans and open_spans[-1][2] <= s:
                open_spans.pop()
            if name.startswith("engine."):
                open_spans.append((name, s, e, stats))
                if name in JOINED and int(stats.get("step", 0)):
                    steps.setdefault(int(stats["step"]), set()).add(name)
                continue
            inside = open_spans[-1] if open_spans else None
            at_edge = inside is None and not first <= s < last
            if inside is None:
                c = (edge if at_edge else uncovered).setdefault(
                    name, [0, 0.0, 0.0])
                c[0] += 1
                c[1] += (e - s) * 1e-9
                c[2] = max(c[2], (e - s) * 1e-9)
            if name == READ:
                key = (inside[0] if inside else "the slice's edge" if at_edge
                       else "outside every phase")
                r = reads.setdefault(key, {"count": 0, "seconds": 0.0,
                                           "longest_s": 0.0, "step": None})
                r["count"] += 1
                r["seconds"] += (e - s) * 1e-9
                if (e - s) * 1e-9 > r["longest_s"]:
                    r["longest_s"] = (e - s) * 1e-9
                    r["step"] = inside and inside[3].get("step")
    whole = sum(1 for names in steps.values() if len(names) == len(JOINED))
    rank = lambda d: {k: {"count": c, "seconds": t, "longest_s": m}
                      for k, (c, t, m) in sorted(
                          d.items(), key=lambda kv: -kv[1][1])[:20]}
    return {
        "uncovered": rank(uncovered), "edge": rank(edge), "reads": reads,
        "steps": {"joined": whole, "partial": len(steps) - whole},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", type=Path)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    path = newest_xplane(args.trace)
    out = {"file": str(path), **reduce(engine_lines(path))}
    text = json.dumps(out, indent=1, default=str)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
