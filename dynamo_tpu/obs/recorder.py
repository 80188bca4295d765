"""Flight recorder: bounded, thread-safe ring of completed request
timelines plus a fixed ring of engine step records.

Two export formats, both dependency-free:
  * JSONL — one span per line, consumed by ``tools/trace_report.py``.
  * Chrome trace-event JSON — ``{"traceEvents": [...]}`` with complete
    ("ph":"X") events in microseconds, loadable in Perfetto / chrome://tracing.
"""

from __future__ import annotations

import json
import threading
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import TYPE_CHECKING, Iterable

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from dynamo_tpu.obs.tracer import Span


@dataclass
class StepRecord:
    """One engine step: wall time plus batch composition. Fixed-size
    fields only — recording is a deque append, always-on cheap.

    The perf fields (flops…roofline_frac) are the step profiler's analytic
    hardware counters (obs/profiler.py); they stay 0 when the profiler is
    disabled so the ring schema is stable either way."""

    ts: float
    # Seconds from step_finalize's start to _record_step (the rest of the
    # device's step after the next was dispatched, plus the finalize's host
    # work): not the step's period, nor the gap a stream saw.
    wall_s: float
    num_prefill: int
    num_decode: int
    num_waiting: int
    num_preempted: int
    occupancy: float
    decode_tokens: int = 0
    prefill_tokens: int = 0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    tok_s: float = 0.0
    mfu: float = 0.0
    bw_util: float = 0.0
    roofline_frac: float = 0.0

    def to_dict(self) -> dict:
        return {
            "ts": self.ts, "wall_s": self.wall_s,
            "num_prefill": self.num_prefill, "num_decode": self.num_decode,
            "num_waiting": self.num_waiting,
            "num_preempted": self.num_preempted,
            "occupancy": self.occupancy,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "tok_s": self.tok_s, "mfu": self.mfu,
            "bw_util": self.bw_util, "roofline_frac": self.roofline_frac,
        }


class StepProfiler:
    """Ring of the last N engine step records (see StepRecord)."""

    def __init__(self, capacity: int = 2048):
        self._ring: deque[StepRecord] = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, ts: float, wall_s: float, *, num_prefill: int = 0,
               num_decode: int = 0, num_waiting: int = 0,
               num_preempted: int = 0, occupancy: float = 0.0,
               decode_tokens: int = 0, prefill_tokens: int = 0,
               flops: float = 0.0, hbm_bytes: float = 0.0,
               tok_s: float = 0.0, mfu: float = 0.0, bw_util: float = 0.0,
               roofline_frac: float = 0.0) -> None:
        rec = StepRecord(ts, wall_s, num_prefill, num_decode, num_waiting,
                         num_preempted, occupancy, decode_tokens,
                         prefill_tokens, flops, hbm_bytes, tok_s, mfu,
                         bw_util, roofline_frac)
        with self._lock:
            self._ring.append(rec)

    def snapshot(self) -> list[StepRecord]:
        with self._lock:
            return list(self._ring)


class FlightRecorder:
    """Ring of the last ``capacity`` request timelines, keyed by
    trace_id. A timeline is the list of closed spans sharing a trace_id;
    eviction is LRU on trace insertion order (a trace that keeps
    receiving spans stays fresh)."""

    def __init__(self, capacity: int = 256, spans_per_trace: int = 512):
        self.capacity = max(capacity, 1)
        self.spans_per_trace = spans_per_trace
        self._traces: "OrderedDict[str, list[Span]]" = OrderedDict()
        self._span_ids: dict[str, set[str]] = {}
        self._lock = threading.Lock()
        self.steps = StepProfiler()

    def record(self, span: "Span") -> bool:
        """File a closed span. Returns False on duplicate span_id (wire
        replays) or per-trace overflow."""
        with self._lock:
            spans = self._traces.get(span.trace_id)
            if spans is None:
                spans = []
                self._traces[span.trace_id] = spans
                self._span_ids[span.trace_id] = set()
                while len(self._traces) > self.capacity:
                    old, _ = self._traces.popitem(last=False)
                    del self._span_ids[old]
            else:
                self._traces.move_to_end(span.trace_id)
            ids = self._span_ids[span.trace_id]
            if span.span_id in ids or len(spans) >= self.spans_per_trace:
                return False
            ids.add(span.span_id)
            spans.append(span)
            return True

    def trace_ids(self) -> list[str]:
        with self._lock:
            return list(self._traces)

    def spans_for(self, trace_id: str) -> "list[Span]":
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def _snapshot(self, trace_id: str | None) -> "list[Span]":
        with self._lock:
            if trace_id is not None:
                return list(self._traces.get(trace_id, ()))
            return [s for spans in self._traces.values() for s in spans]

    # -- exporters ------------------------------------------------------
    def dump_jsonl(self, trace_id: str | None = None) -> str:
        spans = self._snapshot(trace_id)
        spans.sort(key=lambda s: (s.trace_id, s.start))
        return "".join(
            json.dumps(s.to_dict(), separators=(",", ":")) + "\n"
            for s in spans)

    def dump_chrome(self, trace_id: str | None = None,
                    include_steps: bool = True) -> dict:
        """Chrome trace-event JSON. pid = component (process row in the
        Perfetto UI), tid = short trace id (one track per request)."""
        events: list[dict] = []
        pids: dict[str, int] = {}
        tids: dict[str, int] = {}

        def _pid(comp: str) -> int:
            if comp not in pids:
                pids[comp] = len(pids) + 1
                events.append({
                    "ph": "M", "name": "process_name", "pid": pids[comp],
                    "tid": 0, "args": {"name": comp or "proc"}})
            return pids[comp]

        for s in self._snapshot(trace_id):
            key = (s.component, s.trace_id)
            pid = _pid(s.component)
            if key not in tids:
                tids[key] = len(tids) + 1
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tids[key],
                    "args": {"name": f"trace {s.trace_id[:8]}"}})
            args = {"trace_id": s.trace_id, "span_id": s.span_id,
                    "status": s.status, **s.attrs}
            if s.parent_id:
                args["parent_id"] = s.parent_id
            events.append({
                "ph": "X", "name": s.name, "cat": s.component or "span",
                "pid": pid, "tid": tids[key],
                "ts": s.start * 1e6, "dur": s.duration * 1e6,
                "args": args,
            })
        if include_steps and trace_id is None:
            for rec in self.steps.snapshot():
                events.append({
                    "ph": "C", "name": "engine.batch", "pid": 0, "tid": 0,
                    "ts": rec.ts * 1e6,
                    "args": {"prefill": rec.num_prefill,
                             "decode": rec.num_decode,
                             "waiting": rec.num_waiting,
                             "tok_s": round(rec.tok_s, 1),
                             "mfu": round(rec.mfu, 4),
                             "bw_util": round(rec.bw_util, 4)}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def iter_spans(self) -> "Iterable[Span]":
        return self._snapshot(None)
