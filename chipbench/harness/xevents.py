"""The events of this run's profiler trace, for the per-layer readers that
need more than ``trace.reduce()`` keeps.

``current()`` finds the newest ``*.xplane.pb`` under ``manifest.OUT`` that
was written since this process started (a ``--trace 1`` run writes exactly
one), parses it once and keeps:

- ``modules``: the ``XLA Modules`` line of the first device plane, one event
  per executed program, named ``jit_step_decode_b8_n32(<hash>)`` and the
  like (``dynamo_tpu/engine/engine.py`` names the step programs);
- ``ops``: per device plane, the ``XLA Ops`` line (one event per executed
  operation, nested where an operation encloses others) with the HLO text
  as the name, and ``async_ops``, the ``Async XLA Ops`` line (``-start`` to
  ``-done`` of asynchronous operations);
- ``host``: the ``engine.*`` spans the engine thread writes
  (``obs/profiler.py loop_phase``), with their attributes.

Times are nanoseconds on the trace's one clock. No plane of that kind, or
no trace at all: empty lists, and every reader built on this returns
``None``. A ``benchmark`` issue may later pass the path through ``Context``
instead of looking for it.
"""

from __future__ import annotations

import functools
import os
import re
from dataclasses import dataclass, field
from pathlib import Path

from . import manifest
from .trace import DEVICE_PLANE, HOST_PLANE, OP_LINE, self_times, union

MODULE_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "engine."
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")

Event = tuple[str, float, float]          # name, start_ns, end_ns


@dataclass
class Events:
    path: Path | None = None
    modules: list[Event] = field(default_factory=list)
    ops: list[list[Event]] = field(default_factory=list)        # per device
    async_ops: list[list[Event]] = field(default_factory=list)  # per device
    host: list[tuple[str, float, float, dict]] = field(default_factory=list)

    def busy_ns(self, device: int = 0) -> float:
        """Union of the device's ``XLA Ops`` events: what
        ``trace.reduce()`` calls busy time."""
        return sum(e - s for s, e in
                   union([(s, e) for _, s, e in self.ops[device]]))


def process_start() -> float:
    """When this process started, on ``time.time()``'s clock (Linux keeps
    it as the age of ``/proc/<pid>``); 0 where that cannot be read, so that
    any trace counts."""
    try:
        return Path(f"/proc/{os.getpid()}").stat().st_mtime
    except OSError:
        return 0.0


def newest_xplane(root: Path | None = None,
                  since: float | None = None) -> Path | None:
    since = process_start() if since is None else since
    files = [p for p in Path(root or manifest.OUT).glob("**/*.xplane.pb")
             if p.stat().st_mtime >= since]
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def instruction(hlo: str) -> str:
    """``%all-reduce.7 = bf16[8,1,5120]{...} all-reduce(...)`` ->
    ``%all-reduce.7``: the instruction's own name."""
    return hlo.split(" = ", 1)[0].strip()


def result_shape(hlo: str) -> tuple[int, ...] | None:
    """The dimensions of the instruction's result, of the first element
    where it is a tuple: ``%copy-start = (bf16[34928,8,128]{...}, ...)`` ->
    ``(34928, 8, 128)``."""
    m = re.match(r"%?[\w\-.]+ = \(?\w+\[([\d,]*)\]", hlo)
    if m is None:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


@functools.lru_cache(maxsize=2)
def load(path: Path) -> Events:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ev = Events(path=path)
    for plane in data.planes:
        if re.match(DEVICE_PLANE, plane.name):
            lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (MODULE_LINE, OP_LINE, ASYNC_LINE)}
            if not lines.get(OP_LINE):
                continue
            if not ev.ops:
                ev.modules = lines.get(MODULE_LINE, [])
            ev.ops.append(lines[OP_LINE])
            ev.async_ops.append(lines.get(ASYNC_LINE, []))
        elif re.match(HOST_PLANE, plane.name):
            for ln in plane.lines:
                ev.host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in ln.events if e.name.startswith(SPAN_PREFIX))
    ev.host.sort(key=lambda x: x[1])
    return ev


def current() -> Events:
    """The events of this run's trace, parsed once; empty without one."""
    path = newest_xplane()
    return load(path) if path is not None else Events()


def module_mean_ms(ev: Events, prefix: str) -> float | None:
    """Mean device duration of the executions of the programs whose name
    starts with ``prefix``; None when the slice held none."""
    ds = [e - s for name, s, e in ev.modules if name.startswith(prefix)]
    return sum(ds) / len(ds) * 1e-6 if ds else None


def self_time_pct(ev: Events, keep) -> float | None:
    """Self time of the operations of device 0 for which ``keep(hlo text)``
    holds, as a share of that device's busy time."""
    if not ev.ops:
        return None
    busy = ev.busy_ns()
    if busy <= 0:
        return None
    own = self_times(ev.ops[0])
    return 100.0 * sum(t for name, t in own.items() if keep(name)) / busy


def subtract(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> float:
    """Length of the merged intervals ``a`` not covered by the merged
    intervals ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        at = s
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                total += b[k][0] - at
            at = max(at, b[k][1])
            k += 1
        if at < e:
            total += e - at
    return total


def collective_exposed_ns(ev: Events, device: int = 0) -> float | None:
    """Time device ``device`` spent in collective operations while no other
    operation ran there. A synchronous collective is its event on the
    ``XLA Ops`` line; an asynchronous one runs from its ``-start`` to its
    ``-done`` (the ``Async XLA Ops`` line, or the pair on the ``XLA Ops``
    line). Other operations are the innermost events of the ``XLA Ops``
    line that are not collectives (a ``while`` that encloses everything is
    not work of its own)."""
    if len(ev.ops) <= device:
        return None
    ops = sorted(ev.ops[device], key=lambda x: (x[1], -x[2]))
    coll: list[tuple[float, float]] = []
    starts: dict[str, float] = {}     # -start instruction -> when it began
    other: list[tuple[float, float]] = []
    for i, (hlo, s, e) in enumerate(ops):
        name = instruction(hlo)
        m = COLLECTIVE.match(name)
        if m is None:
            encloses = i + 1 < len(ops) and ops[i + 1][1] < e \
                and ops[i + 1][2] <= e
            if not encloses:
                other.append((s, e))
        elif m.group(2) == "-start":
            starts[name.lstrip("%")] = s
            other.append((s, e))      # issuing it is work of the core
        elif m.group(2) == "-done":
            operand = re.search(r"-done\(%?([\w\-.]+)", hlo)
            coll.append((starts.pop(operand.group(1), s) if operand else s,
                         e))
        else:
            coll.append((s, e))
    for hlo, s, e in ev.async_ops[device]:
        if COLLECTIVE.match(instruction(hlo)):
            coll.append((s, e))
    return subtract(union(coll), union(other))
