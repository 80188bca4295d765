"""The join the step readers share: a step's programs on the device
(``XLA Modules`` events) to the ``engine.program`` spans that enqueued them,
to the step's own count (its ``engine.record`` span), and each device
operation to the phase scope its program gave it. No metric of its own.

What the program writes (``dynamo_tpu/engine/engine.py``): one
``engine.program`` span a program enqueued, with ``step`` (the step's
ordinal), ``index`` (its place in the step) and ``program``, the name the
``XLA Modules`` line shows without its hash; the step's ``engine.record``
span with the same ``step`` and the one count of the step's rows, with the
routed layers' device counts; and, at ``shutdown()`` of an engine that saw a
profiler session, ``{program: {instruction: phase}}`` as JSON at
``<tempdir>/dynamo-tpu-phases-<pid>.json``, read off each program's compiled
text (``obs/profiler.py phase_table``).

- Programs to events, by name and order: an event takes the earliest
  ``engine.program`` span of its name, not yet taken and not before the last
  one taken, that began before it (the host enqueues before the device
  runs) and whose step's ``engine.finalize.wait`` ended after it did (the
  host has a step's tokens only after its programs ran). The unmatched at the slice's two ends are dropped (a program
  enqueued before the trace began has no span, one enqueued at its end no
  event); under ``MIN_MATCHED`` of the rest matched the join is None.
- A matched step is one whose programs are all matched and whose
  ``engine.record`` span is in the trace: its counts and its device time are
  then of the same step.
- A device operation belongs to the program whose ``XLA Modules`` event
  holds its start, and takes its phase from that program's table by its
  instruction's name: two programs may both have a ``%fusion.83``, in
  different phases. An operation of no program, or that its program's table
  does not name, has no phase.

Everything is None (or empty) where the trace has no ``engine.program``
span, as on a program from before PR 43, or no table was written.
"""

import functools
import json
import os
import re
import tempfile
from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path

from harness import xevents

MIN_MATCHED = 0.9
SLACK_NS = 1e6           # host and device clocks of one trace: far closer
STEP_PROGRAM = "jit_step_"
_HASH = re.compile(r"\(\d*\)$")


@dataclass
class Step:
    step: int
    counts: dict                                   # the engine.record span's
    programs: list[int] = field(default_factory=list)   # module indices
    device_ns: float = 0.0


@dataclass
class Joined:
    modules: list[tuple[str, float, float]]        # program, start, end
    steps: list[Step]                              # the matched steps
    matched_share: float
    # per operation event of device 0: (module index or None, instruction,
    # phase or None, self ns)
    ops: list[tuple[int | None, str, str | None, float]]
    tables: dict

    def step_modules(self) -> set[int]:
        return {i for s in self.steps for i in s.programs}

    def self_ns(self, keep, modules: set[int] | None = None) -> float:
        """Self time of the operations for which ``keep(instruction,
        phase)`` holds, in the programs ``modules`` (all where None)."""
        return sum(ns for mod, ins, phase, ns in self.ops
                   if (modules is None or mod in modules) and keep(ins, phase))


def program_of(module_name: str) -> str:
    """``jit_step_decode_b8_n512(1234)`` -> ``jit_step_decode_b8_n512``."""
    return _HASH.sub("", module_name)


def number(x) -> float:
    try:
        return float(x)
    except (TypeError, ValueError):
        return 0.0


def match(events: list[tuple[str, float, float]],
          spans: list[tuple[str, float, float]]) -> list[int | None]:
    """For each event ``(program, start, end)`` in time order the index of
    the span ``(program, start, deadline)`` (in time order) it takes, or
    None. ``deadline`` is when the host had the step's tokens (the end of
    its ``engine.finalize.wait``; inf where the trace does not hold it): the
    host has them only after the step's programs ended, so a span whose
    deadline lies before the event's end (``SLACK_NS`` for the two clocks)
    is of an earlier execution that the trace did not hold, and is passed
    over."""
    taken: list[int | None] = []
    floor = 0                # spans before this index are behind the join
    by_name: dict[str, list[int]] = {}
    for i, (name, _s, _d) in enumerate(spans):
        by_name.setdefault(name, []).append(i)
    cursor = dict.fromkeys(by_name, 0)
    for name, start, end in events:
        idx = by_name.get(name, ())
        k = cursor.get(name, 0)
        while k < len(idx) and (idx[k] < floor
                                or spans[idx[k]][2] + SLACK_NS < end):
            k += 1
        hit = k < len(idx) and spans[idx[k]][1] < start
        taken.append(idx[k] if hit else None)
        if hit:
            floor = idx[k] + 1
            k += 1
        if name in cursor:
            cursor[name] = k
    return taken


def matched_share(taken: list[int | None]) -> float:
    """Share matched of the events between the first and the last matched
    one: the unmatched at the two ends are the slice's cut, not a fault."""
    hits = [i for i, t in enumerate(taken) if t is not None]
    if not hits:
        return 0.0
    inner = taken[hits[0]:hits[-1] + 1]
    return sum(t is not None for t in inner) / len(inner)


def self_time_events(events):
    """``[(name, start, self ns)]`` of one line's nested events: an event's
    duration less what the events inside it cover."""
    out, stack = [], []          # stack of [name, end, own, start]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, _e, own, s0 = stack.pop()
            out.append((n, s0, max(own, 0.0)))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s, s])
    while stack:
        n, _e, own, s0 = stack.pop()
        out.append((n, s0, max(own, 0.0)))
    return out


def table_path() -> Path:
    return Path(tempfile.gettempdir()) / f"dynamo-tpu-phases-{os.getpid()}.json"


def load_tables(path: Path | None = None) -> dict:
    path = path or table_path()
    try:
        if path.stat().st_mtime < xevents.process_start():
            return {}             # another process's, of the same id
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def build(ev, tables: dict) -> Joined | None:
    spans = [(a.get("program", ""), s, a) for name, s, _e, a in ev.host
             if name == "engine.program"]
    if not spans or not ev.modules or not ev.ops:
        return None
    records = {int(number(a.get("step"))): a for name, _s, _e, a in ev.host
               if name == "engine.record" and "live_tokens" in a}
    done: dict[int, float] = {}      # step -> when the host had its tokens
    for name, _s, e, a in ev.host:
        if name == "engine.finalize.wait" and "step" in a:
            step = int(number(a["step"]))
            done[step] = max(done.get(step, 0.0), e)
    modules = sorted(((program_of(n), s, e) for n, s, e in ev.modules),
                     key=lambda m: m[1])
    which = [i for i, m in enumerate(modules) if m[0].startswith(STEP_PROGRAM)]
    taken = match(
        [modules[i] for i in which],
        [(p, s, done.get(int(number(a.get("step"))), float("inf")))
         for p, s, a in spans])
    share = matched_share(taken)
    if share < MIN_MATCHED:
        return None
    by_step: dict[int, Step] = {}
    for mod, t in zip(which, taken):
        if t is None:
            continue
        step = int(number(spans[t][2].get("step")))
        if step not in records:
            continue
        st = by_step.setdefault(step, Step(step, records[step]))
        st.programs.append(mod)
        st.device_ns += modules[mod][2] - modules[mod][1]
    steps = [s for s in by_step.values()
             if len(s.programs) == int(number(s.counts.get("programs")))]
    starts = [m[1] for m in modules]
    ops = []
    for hlo, s0, own in self_time_events(ev.ops[0]):
        i = bisect_right(starts, s0) - 1
        mod = i if i >= 0 and s0 < modules[i][2] else None
        ins = xevents.instruction(hlo).lstrip("%")
        phase = tables.get(modules[mod][0], {}).get(ins) \
            if mod is not None else None
        ops.append((mod, ins, phase, own))
    return Joined(modules, steps, share, ops, tables)


@functools.lru_cache(maxsize=2)
def _cached(path):
    return build(xevents.load(path), load_tables())


def matched(ctx):
    """``(join, step_shapes, peaks)`` for a reader that prices the matched
    steps, or None where there is nothing to price: no
    ``stats()["step_shapes"]`` (a program from before PR 43), no join, no
    matched step."""
    from harness import peaks

    shapes = ctx.counters[1].get("step_shapes")
    j = current() if shapes else None
    if j is None or not j.steps:
        return None
    kind = (ctx.counters[1].get("device") or {}).get("device_kind", "")
    return j, shapes, peaks.peaks_for(kind)


def current() -> Joined | None:
    """The join over this run's trace, made once; None without a trace, a
    device plane, the program's spans, or under ``MIN_MATCHED`` matched."""
    ev = xevents.current()
    return _cached(ev.path) if ev.path is not None else None
