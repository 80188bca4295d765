"""From the run's records and counter snapshots to metrics.

End-to-end metrics are computed here, over all requests due in the window
and all the window's time; per-layer metrics are read by the small readers
under ``chipbench/layers/``, one file each, found by the metric's name.

``tokens_per_s`` is the output tokens of the requests due in the window
over the time they took: from the window's start to the last of their
deltas or the window's end, whichever is later, per chip. That is the
convention of the field's serving benchmarks (vLLM ``benchmark_serving.py``,
genai-perf: output tokens over the time to the last completion); the floor
at the window's end keeps it at or under the offered load. The count it
replaced (PR 27), every delta of any request that arrived inside the
window's edges, stays beside it unlisted as ``tokens_in_window_per_s``.
"""

from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field

from .loadgen import Record
from .manifest import BENCH
from .stats import percentile, token_gaps


@dataclass
class Context:
    """What a per-layer reader may read."""

    window: tuple[float, float]            # perf_counter clock
    window_wall: tuple[float, float]       # time.time clock (ledger events)
    chips: int
    records: list[Record]                  # every request of the run
    counters: tuple[dict, dict]            # engine.stats() at the edges
    kv_usage: list[float] = field(default_factory=list)   # sampled in window
    in_flight: list[int] = field(default_factory=list)    # running + waiting
    compile_events: list[dict] = field(default_factory=list)
    memory_peak_bytes: int = 0
    beat_late_s: list[float] = field(default_factory=list)  # loop heartbeat
    trace: dict | None = None    # harness.trace.reduce(), if it found ops

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def delta(self, *path: str) -> float:
        a, b = self.counters
        for k in path:
            a, b = a[k], b[k]
        return b - a

    @property
    def due_in_window(self) -> list[Record]:
        lo, hi = self.window
        return [r for r in self.records if lo <= r.due < hi]


def end_to_end(ctx: Context, setup_s: float) -> dict[str, float]:
    """Every end-to-end metric the benchmark knows; the caller reports the
    ones its cell lists (``ttft_p50_ms``, ``ttft_p90_ms`` and
    ``tokens_in_window_per_s`` are listed by none: they go to the log)."""
    lo, hi = ctx.window
    due = ctx.due_in_window
    ttft = [(r.first_token - r.due) * 1e3 for r in due
            if r.first_token is not None]
    gaps = [g * 1e3 for r in due for g in token_gaps(r.deltas)]
    # A record holds the deltas that arrived before the run's cut (window's
    # end + drain_s, where run_schedule cancels), so a request cut there
    # counts what it delivered, over the stretch to its last delta.
    own = [(t, k) for r in due for t, k in r.deltas]
    took = max([hi] + [t for t, _ in own]) - lo
    in_window = sum(k for r in ctx.records for t, k in r.deltas
                    if lo <= t < hi)
    out = {"setup_s": setup_s,
           "tokens_per_s": sum(k for _, k in own) / took / ctx.chips,
           "tokens_in_window_per_s": in_window / ctx.seconds / ctx.chips}
    if ttft:
        out["ttft_mean_ms"] = sum(ttft) / len(ttft)
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p90_ms"] = percentile(ttft, 90)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95)
    return out


STALL_S = 0.25   # over the ~0.1 s pause most windows have once (PERF.md)


def stalls(ctx: Context) -> dict[str, float]:
    """What the heartbeat on the generator's loop saw in the window: its
    worst lateness, and the stretches in which it ran over ``STALL_S`` late
    (one stall makes every beat due inside it late: one stretch). Logged
    and printed beside the result, never a metric: a run the machine froze
    in is far off in every latency (PERF.md, Findings, PR 27 and 29)."""
    late = ctx.beat_late_s
    starts = [b for a, b in zip([0.0] + late, late)
              if b > STALL_S >= a]
    return {"stall_max_ms": max(late, default=0.0) * 1e3,
            "stalls": len(starts), "stalled_ms": sum(starts) * 1e3}


def failed(ctx: Context) -> int:
    return sum(1 for r in ctx.due_in_window
               if r.finish is None or r.error or r.finish == "error")


def load_module(path, label: str):
    """The module in the file at ``path`` (a reader, a configuration's
    reference: files found by a name in the data, not packages)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + "".join(c if c.isalnum() else "_" for c in label),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_module(BENCH / "layers" / f"{name}.py", "layer_" + name)


def per_layer(ctx: Context, names: list[str]) -> dict[str, dict]:
    """``{name: {"value", "unit"}}`` for the readers that found something."""
    out = {}
    for name in names:
        mod = load_reader(name)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.unit}
    return out
