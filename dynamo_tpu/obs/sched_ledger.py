"""Scheduling ledger: per-step goodput, padding waste, HOL-stall attribution.

The compile ledger makes XLA stalls observable; this module does the same
for the *scheduler's* decisions. Every dispatched engine step files one
``SchedStepRecord``:

* **Goodput** — the fraction of scheduled (bucket-padded) FLOPs that were
  live tokens. The engine dispatches static-shape programs (each batch
  carries the ``BucketSig`` dispatch() ran it under); the gap between the ragged batch it planned and the padded batch it ran is
  pure waste, priced through the same analytic cost model the perf
  profiler uses (obs/costmodel.py) and exported as
  ``dynamo_sched_goodput_fraction`` plus cumulative padding FLOPs/bytes.
* **Token gaps** — when a step's tokens have been handed on
  (``EngineCore.outputs_posted``), every row that had been posted tokens
  before files the seconds since that earlier post under the class of the
  step that ran (decode | mixed | verify) and its widest row bucket, with
  the seconds of it the engine thread was blocked on the device for that
  step: fixed-size histograms over ``GAP_EDGES``, measured, nothing priced
  (``record_post``, ``stats()["gaps"]``). The hand-over from the engine
  thread to the stream's event loop has a histogram of its own
  (``record_handover``).
* **HOL interference** — when a prefill chunk shares a step with decode
  streams, every decode row's token delivery is delayed by the chunk.
  The engine's stall is measured: the mixed step's post-to-post gap less
  the mean gap the ledger holds for decode steps of the victims' row
  bucket (``record_post``); the mocker passes a share of its simulated
  wall (``record_step(hol=...)``). Each victim stream
  accrues an ``engine.hol_stall`` span in its OWN trace carrying the
  culprit request id, aggregated into
  ``dynamo_sched_hol_stall_seconds{qos_class}`` and a per-step
  interference index (stalled-decode-row-seconds).
* **Admission & preemption causes** — why waiting seqs could not admit
  (no free blocks vs. batch full vs. WDRR lane gate) and how many tokens
  preemption forces back through prefill
  (``dynamo_sched_preempt_recompute_tokens_total{cause}``).

Disabled mode (``DYN_SCHED_LEDGER=0``) flips ``SchedLedger.enabled``; the
engine and scheduler gate on that flag BEFORE building any step info, so a
disabled ledger adds zero per-step work — the same contract as the
profiler's ``DYN_PERF_PROFILE`` gate.

The ``dynamo_sched_*`` family (lint-checked by tools/lint_metrics.py
SCHED_METRICS) installs on workers via ``install_sched_metrics`` and is
mirrored device-free by the mocker, so fleet scenarios exercise the
``decode_stall`` SLI without a TPU. ``/debug/sched`` (frontend + worker
status server) serves ``debug_info()``: the recent-step ring, the goodput
trend, and the top stall culprits.
"""

from __future__ import annotations

import os
import threading
import time
from bisect import bisect_right
from collections import Counter, deque
from dataclasses import dataclass, field

from dynamo_tpu.utils.metrics import MetricsRegistry

SCHED_ENV = "DYN_SCHED_LEDGER"

#: Admission-block causes (engine/scheduler.py _try_admit / plan):
#: ``no_free_blocks`` — the pool (or its watermark) refused the prompt;
#: ``batch_full`` — no sampling slot / running at max_batch_size;
#: ``wdrr_gate`` — the WDRR-committed head lane blocks while other
#: non-empty lanes wait behind the commitment.
BLOCK_CAUSES = ("no_free_blocks", "batch_full", "wdrr_gate")

#: Preemption causes: ``blocks`` — recompute preemption reclaiming KV
#: blocks for a growing decode stream; ``qos`` — the reclaimed victim
#: belonged to a different QoS class than the stream that grew.
PREEMPT_CAUSES = ("blocks", "qos")

#: Victim stalls span one fused decode window (~ms) to a full 32k-prompt
#: prefill chunk on CPU fallback. (MetricsRegistry appends +Inf.)
_STALL_SECONDS_BUCKETS = (
    0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0,
)


def sched_enabled(default: bool = True) -> bool:
    """The module-level gate: DYN_SCHED_LEDGER=0 disables all per-step
    scheduling accounting (record paths return before any work)."""
    val = os.environ.get(SCHED_ENV, "")
    if val == "":
        return default
    return val not in ("0", "false", "no", "off")


# ---------------------------------------------------------------------------
# Prometheus family
# ---------------------------------------------------------------------------

class SchedMetrics:
    """The dynamo_sched_* family (names cross-checked by
    tools/lint_metrics.py SCHED_METRICS)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.bind(registry or MetricsRegistry())

    def bind(self, registry: MetricsRegistry) -> None:
        self.registry = registry
        self.goodput = registry.gauge(
            "sched_goodput_fraction",
            "Live-token FLOPs over scheduled (bucket-padded) FLOPs for the "
            "last engine step (1.0 = zero padding waste)")
        self.budget_util = registry.gauge(
            "sched_token_budget_utilization",
            "Fraction of max_tokens_per_step the last step's planned rows "
            "actually used (decode window rows + prefill chunk tokens)")
        self.queue_depth = registry.gauge(
            "sched_queue_depth",
            "Waiting seqs per QoS class at the last step's record point "
            "(WDRR lane depths, qos_class label)")
        self.steps = registry.counter(
            "sched_steps_total",
            "Engine steps recorded by the scheduling ledger, by batch kind "
            "(prefill|decode|window|verify|guided|mixed; a multi-batch "
            "step counts once per kind it dispatched)")
        self.prefill_chunk = registry.gauge(
            "sched_prefill_chunk_tokens",
            "Effective prefill chunk size in tokens per QoS class "
            "(SLO-driven per-class when --prefill-chunk 0 auto mode is "
            "on, uniform otherwise), qos_class label")
        self.admission_blocked = registry.counter(
            "sched_admission_blocked_total",
            "Admission attempts blocked, by cause (no_free_blocks|"
            "batch_full|wdrr_gate)")
        self.preempt_recompute = registry.counter(
            "sched_preempt_recompute_tokens_total",
            "Tokens whose KV a preemption discarded and prefill must "
            "recompute, by cause (blocks|qos)")
        self.padding_flops = registry.counter(
            "sched_padding_flops_total",
            "Cumulative analytic FLOPs spent on bucket padding rather than "
            "live tokens (scheduled minus live)")
        self.padding_bytes = registry.counter(
            "sched_padding_hbm_bytes_total",
            "Cumulative analytic HBM bytes moved for bucket padding rather "
            "than live tokens (scheduled minus live)")
        self.hol_stall = registry.histogram(
            "sched_hol_stall_seconds",
            "Per-victim head-of-line stall: seconds one decode-ready "
            "stream's token delivery waited on a step that carried a "
            "prefill chunk (the step's measured token gap less the mean "
            "gap of decode steps of the same row bucket), by qos_class",
            buckets=_STALL_SECONDS_BUCKETS)
        self.interference = registry.counter(
            "sched_interference_row_seconds_total",
            "Interference index: cumulative stalled-decode-row-seconds "
            "(per step, victims x measured stall)")


_metrics: SchedMetrics | None = None


def get_sched_metrics() -> SchedMetrics:
    global _metrics
    if _metrics is None:
        _metrics = SchedMetrics()
    return _metrics


def install_sched_metrics(registry: MetricsRegistry) -> SchedMetrics:
    """Re-home the singleton's metrics into ``registry`` (the worker's
    runtime registry) so the family is exposed on /metrics. Gauges are
    republished from the live ledger so an install that lands AFTER the
    engine recorded steps still exposes the current goodput; counters stay
    monotonic and are not replayed."""
    m = get_sched_metrics()
    m.bind(registry)
    led = get_sched_ledger()
    with led._lock:
        last = led.steps[-1] if led.steps else None
    if last is not None:
        m.goodput.set(last.goodput)
        m.budget_util.set(last.budget_util)
        for cls, d in last.queue_depths.items():
            m.queue_depth.set(float(d), qos_class=cls)
    for cls, chunk in led.prefill_chunks.items():
        m.prefill_chunk.set(float(chunk), qos_class=cls)
    return m


# ---------------------------------------------------------------------------
# Step records
# ---------------------------------------------------------------------------

@dataclass
class HolStall:
    """One step's head-of-line interference: the culprit prefill and the
    decode-ready streams whose token delivery its chunk delayed.

    The engine hands it to ``record_post`` when the step's tokens have
    been handed on, and the stall is measured there: the step's gap less
    the decode mean of the victims' row bucket. ``stall_share`` is for a
    caller with no gap to measure (the mocker, through ``record_step``): it
    scales the per-victim stall below the step's wall; None charges the
    whole wall."""

    culprit: str                    # culprit request id (largest chunk)
    culprit_tokens: int             # prefill tokens the step carried
    victims: list = field(default_factory=list)  # (trace_ctx, rid, qos_class)
    stall_share: float | None = None  # record_step only: fraction of wall_s


@dataclass
class SchedStepRecord:
    """One dispatched engine step as the scheduler saw it."""

    ts: float                       # record timestamp (epoch, at finalize)
    # Seconds from step_finalize's start to _record_step: in the pipelined
    # loop the rest of the device's step after the next one was dispatched,
    # plus the finalize's host work. Not the gap a stream saw: ``gap_s``.
    wall_s: float
    kinds: tuple                    # batch kinds dispatched, in order
    prefill_rows: int = 0
    decode_rows: int = 0
    live_tokens: int = 0            # tokens the plan actually needed
    sched_tokens: int = 0           # tokens the dense layers computed (N)
    rect_tokens: int = 0            # query positions attention was handed
    kv_blocks_live: int = 0         # KV blocks the rows hold (a full layer's walk)
    kv_blocks_walked: int = 0       # ... the kernel walks over all layers, windows counted
    # A routed model under moe_impl="held", summed over the step's routed
    # layers (device counts, fetched with the step's tokens):
    moe_layer_steps: int = 0        # routed layers x programs run
    moe_rows: int = 0               # (token, choice) rows computed here
    moe_experts_touched: int = 0    # held experts that had rows
    moe_largest_group: int = 0      # rows of each layer's largest group
    # ... of them, those of programs whose experts the streaming kernel
    # computed (models/moe.py streams_experts, asked of each program's N)
    moe_streamed_layer_steps: int = 0
    live_flops: float = 0.0
    sched_flops: float = 0.0
    live_bytes: float = 0.0
    sched_bytes: float = 0.0
    goodput: float = 1.0            # live/sched FLOPs (token ratio fallback)
    budget_util: float = 0.0        # planned tokens / max_tokens_per_step
    queue_depths: dict = field(default_factory=dict)   # qos_class -> waiting
    blocked: dict = field(default_factory=dict)        # cause -> attempts
    preempt: dict = field(default_factory=dict)        # cause -> tokens
    hol_culprit: str = ""
    hol_victims: int = 0
    hol_stall_s: float = 0.0        # per-victim stall (gap less decode mean)
    interference_row_s: float = 0.0  # victims x stall
    # Filed when the step's tokens were handed on (record_post): the
    # post-to-post seconds of the rows that were in the previous step too
    # (0.0: none were), the rows that filed a gap, the step's class.
    gap_s: float = 0.0
    gap_rows: int = 0
    gap_class: str = ""

    def to_dict(self) -> dict:
        d = {
            "ts": self.ts,
            "wall_s": round(self.wall_s, 6),
            "kinds": list(self.kinds),
            "prefill_rows": self.prefill_rows,
            "decode_rows": self.decode_rows,
            "live_tokens": self.live_tokens,
            "sched_tokens": self.sched_tokens,
            "rect_tokens": self.rect_tokens,
            "kv_blocks_live": self.kv_blocks_live,
            "kv_blocks_walked": self.kv_blocks_walked,
            "goodput": round(self.goodput, 4),
            "budget_util": round(self.budget_util, 4),
        }
        if self.moe_layer_steps:
            d.update(moe_layer_steps=self.moe_layer_steps,
                     moe_rows=self.moe_rows,
                     moe_experts_touched=self.moe_experts_touched,
                     moe_largest_group=self.moe_largest_group,
                     moe_streamed_layer_steps=self.moe_streamed_layer_steps)
        if self.queue_depths:
            d["queue_depths"] = dict(self.queue_depths)
        if self.blocked:
            d["blocked"] = dict(self.blocked)
        if self.preempt:
            d["preempt_recompute_tokens"] = dict(self.preempt)
        if self.gap_rows:
            d["gap"] = {"class": self.gap_class, "rows": self.gap_rows,
                        "seconds": round(self.gap_s, 6)}
        if self.hol_victims:
            d["hol"] = {
                "culprit": self.hol_culprit,
                "victims": self.hol_victims,
                "stall_s": round(self.hol_stall_s, 6),
                "row_seconds": round(self.interference_row_s, 6),
            }
        return d


# ---------------------------------------------------------------------------
# Token gaps: the histograms' edges and one (class, row bucket)'s arrays
# ---------------------------------------------------------------------------

#: The classes a step's gaps are filed under: every program a decode
#: program, one that carried a prompt chunk, a speculative verify beside
#: decode programs.
GAP_CLASSES = ("decode", "mixed", "verify")

_FINE = 308    # 2000 ** (1 / 308) = 1.02499: edges at most 2.5 % apart


def _gap_edges() -> tuple[float, ...]:
    """Shared by every gap histogram, in seconds, six significant digits:
    geometric between 0.5 ms and 1 s, coarser outside. Bucket ``i`` holds
    ``edges[i - 1] <= gap < edges[i]``; the first starts at 0 and the last
    (index ``len(edges)``) has no upper edge."""
    fine = (5e-4 * 2000.0 ** (i / _FINE) for i in range(_FINE + 1))
    return ((1e-5, 2e-5, 5e-5, 1e-4, 2e-4, 3.5e-4)
            + tuple(float(f"{x:.6g}") for x in fine)
            + (1.25, 1.6, 2.0, 2.5, 3.2, 4.0, 5.0, 6.4, 8.0, 10.0, 15.0,
               20.0, 30.0, 60.0))


GAP_EDGES = _gap_edges()


class _GapHist:
    """Per bucket of ``GAP_EDGES``: how many gaps were filed, the sum of
    their seconds and the seconds of them the engine thread was blocked on
    the device; beside them the steps counted and the sum of their periods
    (a step counts where some row of it was in the previous step too).
    ``lo`` / ``hi`` bound the buckets ever filed, so a snapshot copies
    those and not the zeros around them."""

    __slots__ = ("rows", "gap_s", "wait_s", "lo", "hi", "steps", "period_s")

    def __init__(self) -> None:
        n = len(GAP_EDGES) + 1
        self.rows = [0] * n
        self.gap_s = [0.0] * n
        self.wait_s = [0.0] * n
        self.lo, self.hi = n, 0
        self.steps = 0
        self.period_s = 0.0

    def file(self, seconds: float, rows: int = 1, wait_s: float = 0.0) -> None:
        i = bisect_right(GAP_EDGES, seconds)
        self.rows[i] += rows
        self.gap_s[i] += rows * seconds
        self.wait_s[i] += rows * min(wait_s, seconds)
        if i < self.lo:
            self.lo = i
        if i >= self.hi:
            self.hi = i + 1

    def snapshot(self) -> dict:
        lo, hi = min(self.lo, self.hi), self.hi
        return {"lo": lo, "rows": self.rows[lo:hi],
                "gap_s": self.gap_s[lo:hi], "wait_s": self.wait_s[lo:hi],
                "steps": self.steps, "period_s": self.period_s}


class GapStamps:
    """One engine's side of the gap ledger: which step posted tokens to a
    row last (``Seq.post_step``, set by ``stamp``), and when each of the
    last ``RING`` steps' outputs were handed on, so that ``close`` turns
    the ordinals into seconds with one clock read a step and no work a row
    beyond the stamp. The engine thread alone touches it."""

    RING = 1024     # a row's gap reaches that many steps back, no further

    __slots__ = ("step", "posted", "times", "same", "odd", "wait_s")

    def __init__(self) -> None:
        self.step = 0           # the step being finalized (0: none, or off)
        self.posted = 0         # the last step whose outputs were handed on
        self.times = [0.0] * self.RING      # when, by step % RING
        self.same = 0           # rows stamped that ``posted`` posted to too
        self.odd: list[int] = []            # the others' last steps
        self.wait_s = 0.0       # the finalize's wait on the device

    def stamp(self, seq) -> None:
        """``seq`` is posted tokens by the step being finalized. A first
        post is no gap."""
        prev, seq.post_step = seq.post_step, self.step
        if prev == self.posted:
            self.same += prev > 0
        elif prev:
            self.odd.append(prev)

    def close(self, now: float) -> tuple[float, int, list[float]]:
        """The step's outputs were handed on at ``now``: the post-to-post
        period of the rows that were in the previous step too (0.0 of
        none), how many they are, and the others' own gaps (a row away for
        more steps than the ring holds: since the oldest it holds)."""
        step, times, ring = self.step, self.times, self.RING
        rows, self.same = self.same, 0
        period = now - times[self.posted % ring] if rows else 0.0
        odd = [now - times[max(p, step - ring + 1) % ring] for p in self.odd]
        self.odd.clear()
        times[step % ring] = now
        self.posted, self.step = step, 0
        return period, rows, odd


def step_class(batches) -> tuple[str, int]:
    """The class a step's gaps are filed under and its widest row bucket,
    off ``PendingStep.batches``' signatures: ``mixed`` where a program
    carried a prompt chunk, else ``verify`` where one verified a proposal,
    else ``decode``."""
    decode, mixed, verify = GAP_CLASSES
    cls, b = decode, 0
    for sig, *_ in batches:
        b = max(b, sig.b)
        if sig.kind == "verify":
            cls = verify if cls == decode else cls
        elif sig.t > 1:
            cls = mixed
    return cls, b


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

class SchedLedger:
    """Process-global per-step scheduling record.

    Thread-safe: the engine-core thread records steps/blocks/preempts
    while the asyncio side reads snapshots for stats/debug endpoints. The
    step ring is bounded (``cap``); totals stay exact past the cap."""

    _CULPRIT_CAP = 512  # trim the per-culprit stall table past this

    def __init__(self, cap: int = 2048):
        self._lock = threading.Lock()
        self.cap = cap
        self.enabled = sched_enabled()
        self.steps: deque[SchedStepRecord] = deque(maxlen=cap)
        self.steps_total = 0
        self.live_tokens_total = 0
        self.sched_tokens_total = 0
        self.rect_tokens_total = 0
        self.kv_blocks_live_total = 0
        self.kv_blocks_walked_total = 0
        self.kv_blocks_walked_shared_total = 0
        self.cross_tokens_total = 0
        self.kv_block_written_tokens_total = 0
        # layer steps, rows, touched, largest, streamed layer steps
        self.moe_totals = [0, 0, 0, 0, 0]
        self.ssm_totals = [0] * len(SSM_COUNTS)
        self.padding_flops_total = 0.0
        self.padding_bytes_total = 0.0
        self.hol_stall_seconds_total = 0.0
        self.hol_victims_total = 0
        self.interference_row_seconds_total = 0.0
        self.blocked_totals: dict[str, int] = {}
        self.preempt_totals: dict[str, int] = {}
        # effective per-QoS prefill chunk sizes (engine publishes at init)
        self.prefill_chunks: dict[str, int] = {}
        # per-culprit {rid: (stall_seconds, victim_count)}
        self._culprits: dict[str, tuple[float, int]] = {}
        # accumulated between steps, flushed into the next record
        self._blocked_step: dict[str, int] = {}
        self._preempt_step: dict[str, int] = {}
        # Token gaps by (class, row bucket), written by the engine thread
        # (record_post); the hand-over to the stream's loop, written by
        # that loop (record_handover). Both under the lock, a step at once.
        self._gaps: dict[tuple[str, int], _GapHist] = {}
        self._handover = _GapHist()
        self._handover_max = 0.0

    # -- configuration --------------------------------------------------
    def configure(self, enabled: bool | None = None) -> None:
        """Engine-startup hook: re-read the env gate (or force a value)."""
        with self._lock:
            self.enabled = sched_enabled() if enabled is None else enabled

    def reset(self) -> None:
        """Test hook: drop all records/totals (metrics counters are
        monotonic and keep their values)."""
        with self._lock:
            self.steps.clear()
            self.steps_total = 0
            self.live_tokens_total = 0
            self.sched_tokens_total = 0
            self.rect_tokens_total = 0
            self.kv_blocks_live_total = 0
            self.kv_blocks_walked_total = 0
            self.kv_blocks_walked_shared_total = 0
            self.cross_tokens_total = 0
            self.kv_block_written_tokens_total = 0
            self.moe_totals = [0, 0, 0, 0, 0]
            self.ssm_totals = [0] * len(SSM_COUNTS)
            self.padding_flops_total = 0.0
            self.padding_bytes_total = 0.0
            self.hol_stall_seconds_total = 0.0
            self.hol_victims_total = 0
            self.interference_row_seconds_total = 0.0
            self.blocked_totals.clear()
            self.preempt_totals.clear()
            self._culprits.clear()
            self._blocked_step.clear()
            self._preempt_step.clear()
            self.prefill_chunks = {}
            self._gaps.clear()
            self._handover = _GapHist()
            self._handover_max = 0.0

    def set_prefill_chunks(self, chunk_by_qos: dict) -> None:
        """Publish the effective per-QoS prefill chunk sizes (resolved at
        engine construction — SLO-driven in auto mode, uniform otherwise)
        to the dynamo_sched_prefill_chunk_tokens gauge."""
        if not self.enabled:
            return
        with self._lock:
            self.prefill_chunks = dict(chunk_by_qos)
        m = get_sched_metrics()
        for qos, chunk in chunk_by_qos.items():
            m.prefill_chunk.set(float(chunk), qos_class=qos)

    # -- recording ------------------------------------------------------
    def record_block(self, cause: str) -> None:
        """One blocked admission attempt (engine/scheduler.py)."""
        if not self.enabled:
            return
        with self._lock:
            self._blocked_step[cause] = self._blocked_step.get(cause, 0) + 1
            self.blocked_totals[cause] = self.blocked_totals.get(cause, 0) + 1
        get_sched_metrics().admission_blocked.inc(cause=cause)

    def record_preempt(self, tokens: int, cause: str = "blocks") -> None:
        """One preemption: ``tokens`` of KV discarded and due for
        recompute-prefill (read from seq.num_computed BEFORE the reset)."""
        if not self.enabled:
            return
        tokens = max(int(tokens), 0)
        with self._lock:
            self._preempt_step[cause] = (
                self._preempt_step.get(cause, 0) + tokens)
            self.preempt_totals[cause] = (
                self.preempt_totals.get(cause, 0) + tokens)
        if tokens:
            get_sched_metrics().preempt_recompute.inc(tokens, cause=cause)

    def record_step(
        self, *,
        wall_s: float,
        kinds: tuple | list,
        prefill_rows: int = 0,
        decode_rows: int = 0,
        live_tokens: int = 0,
        sched_tokens: int = 0,
        rect_tokens: int = 0,
        kv_blocks_live: int = 0,
        kv_blocks_walked: int = 0,
        kv_blocks_walked_shared: int = 0,
        cross_tokens: int = 0,
        kv_block_written_tokens: int = 0,
        moe: tuple[int, int, int, int, int] | None = None,
        ssm: tuple[int, ...] | None = None,
        live_flops: float = 0.0,
        sched_flops: float = 0.0,
        live_bytes: float = 0.0,
        sched_bytes: float = 0.0,
        budget_util: float = 0.0,
        queue_depths: dict | None = None,
        hol: HolStall | None = None,
        ts: float | None = None,
    ) -> SchedStepRecord | None:
        """File one step record; returns it (None when disabled).

        ``hol`` is for a caller with no token gap to measure (the mocker;
        the engine hands its own to ``record_post``): its victims are
        charged ``wall_s`` times ``stall_share`` (``_file_hol``)."""
        if not self.enabled:
            return None
        end = ts if ts is not None else time.time()
        if sched_flops > 0:
            goodput = min(live_flops / sched_flops, 1.0)
        elif sched_tokens > 0:
            goodput = min(live_tokens / sched_tokens, 1.0)
        else:
            goodput = 1.0
        rec = SchedStepRecord(
            ts=end, wall_s=wall_s, kinds=tuple(kinds),
            prefill_rows=prefill_rows, decode_rows=decode_rows,
            live_tokens=live_tokens, sched_tokens=sched_tokens,
            rect_tokens=rect_tokens, kv_blocks_live=kv_blocks_live,
            kv_blocks_walked=kv_blocks_walked,
            **(dict(zip(("moe_layer_steps", "moe_rows", "moe_experts_touched",
                         "moe_largest_group", "moe_streamed_layer_steps"),
                        moe)) if moe else {}),
            live_flops=live_flops, sched_flops=sched_flops,
            live_bytes=live_bytes, sched_bytes=sched_bytes,
            goodput=goodput, budget_util=budget_util,
            queue_depths=dict(queue_depths or {}))
        m = get_sched_metrics()
        pad_f = max(sched_flops - live_flops, 0.0)
        pad_b = max(sched_bytes - live_bytes, 0.0)
        if hol is not None and hol.victims:
            # A caller with no gap to measure (the mocker): the chunk is
            # charged its share of the single launch's wall.
            self._file_hol(rec, hol, wall_s * hol.stall_share
                           if hol.stall_share is not None else wall_s, end)
        with self._lock:
            rec.blocked, self._blocked_step = self._blocked_step, {}
            rec.preempt, self._preempt_step = self._preempt_step, {}
            self.steps.append(rec)
            self.steps_total += 1
            self.live_tokens_total += live_tokens
            self.sched_tokens_total += sched_tokens
            self.rect_tokens_total += rect_tokens
            self.kv_blocks_live_total += kv_blocks_live
            self.kv_blocks_walked_total += kv_blocks_walked
            self.kv_blocks_walked_shared_total += kv_blocks_walked_shared
            self.cross_tokens_total += cross_tokens
            self.kv_block_written_tokens_total += kv_block_written_tokens
            if moe:
                self.moe_totals = [a + b for a, b in zip(self.moe_totals, moe)]
            if ssm:
                self.ssm_totals = [a + b for a, b in zip(self.ssm_totals, ssm)]
            self.padding_flops_total += pad_f
            self.padding_bytes_total += pad_b
            if rec.hol_victims:
                self._count_hol(rec)
        for k in rec.kinds:
            m.steps.inc(kind=k)
        m.goodput.set(goodput)
        m.budget_util.set(budget_util)
        if pad_f:
            m.padding_flops.inc(pad_f)
        if pad_b:
            m.padding_bytes.inc(pad_b)
        for cls, d in rec.queue_depths.items():
            m.queue_depth.set(float(d), qos_class=cls)
        return rec

    def _file_hol(self, rec: SchedStepRecord, hol: HolStall, stall: float,
                  end: float) -> None:
        """One step's victims, each stalled ``stall`` seconds up to ``end``:
        the record's fields, the histogram, the interference index and, for
        a victim with a traced request, a retroactive ``engine.hol_stall``
        span in its own trace. The totals are ``_count_hol``'s, under the
        lock."""
        m = get_sched_metrics()
        rec.hol_culprit = hol.culprit
        rec.hol_victims = len(hol.victims)
        rec.hol_stall_s = stall
        rec.interference_row_s = stall * len(hol.victims)
        tr = None
        for v_ctx, v_rid, v_cls in hol.victims:
            m.hol_stall.observe(stall, qos_class=v_cls)
            if v_ctx is None:
                continue  # untraced stream: metrics only, no span
            if tr is None:
                from dynamo_tpu.obs.tracer import get_tracer

                tr = get_tracer()
            span = tr.start_span(
                "engine.hol_stall", ctx=v_ctx, start=end - stall,
                request_id=v_rid, culprit=hol.culprit,
                culprit_tokens=hol.culprit_tokens, qos_class=v_cls)
            tr.end_span(span, end=end, seconds=round(stall, 6))
        m.interference.inc(rec.interference_row_s)

    def _count_hol(self, rec: SchedStepRecord) -> None:
        """``rec``'s stall into the totals and the culprit table (the
        caller holds the lock)."""
        self.hol_stall_seconds_total += rec.interference_row_s
        self.hol_victims_total += rec.hol_victims
        self.interference_row_seconds_total += rec.interference_row_s
        s, n = self._culprits.get(rec.hol_culprit, (0.0, 0))
        self._culprits[rec.hol_culprit] = (
            s + rec.interference_row_s, n + rec.hol_victims)
        if len(self._culprits) > self._CULPRIT_CAP:
            keep = sorted(self._culprits.items(), key=lambda kv: kv[1][0],
                          reverse=True)[: self._CULPRIT_CAP // 2]
            self._culprits = dict(keep)

    def record_post(
        self, rec: SchedStepRecord | None, *,
        cls: str,
        b: int,
        period_s: float = 0.0,
        rows: int = 0,
        odd_gaps: tuple | list = (),
        wait_s: float = 0.0,
        hol: HolStall | None = None,
        hol_b: int = 0,
        ts: float | None = None,
    ) -> None:
        """A step's tokens have been handed on: file its rows' gaps under
        the step's class ``cls`` and widest row bucket ``b``.

        ``rows`` of them were posted tokens by the previous step too and
        waited ``period_s``, the post-to-post period; each of ``odd_gaps``
        is a row that sat steps out (a verify pause, a preemption) and has
        its own. A first post files nothing (the caller leaves it out: it
        is time to first token). ``wait_s`` is what the engine thread was
        blocked on the device for this step, filed against each gap as far
        as the gap is long. ``rec`` is the step's record, which gains the
        gap. One lock hold a step: a snapshot sees a step whole or not at
        all.

        ``hol``'s victims are charged the step's gap (``period_s``; the
        shortest of ``odd_gaps`` where no row was in the previous step)
        less the mean period the ledger holds for decode steps of row
        bucket ``hol_b``, the program the victims would have run alone;
        the whole gap while it holds no such step."""
        if not self.enabled:
            return
        if not rows and not odd_gaps:
            return      # first posts, or none: no gap, nobody stalled
        stall = None
        with self._lock:
            cell = self._gaps.get((cls, b))
            if cell is None:
                cell = self._gaps[cls, b] = _GapHist()
            if rows:
                cell.file(period_s, rows, wait_s)
                cell.steps += 1
                cell.period_s += period_s
            for g in odd_gaps:
                cell.file(g, wait_s=wait_s)
            if hol is not None and hol.victims:
                alone = self._gaps.get(("decode", hol_b))
                mean = (alone.period_s / alone.steps
                        if alone is not None and alone.steps else 0.0)
                stall = max((period_s if rows else min(odd_gaps)) - mean, 0.0)
        if rec is not None:
            rec.gap_class = cls
            rec.gap_rows = rows + len(odd_gaps)
            rec.gap_s = period_s if rows else 0.0
        if stall is None:
            return
        rec = rec or SchedStepRecord(ts=0.0, wall_s=0.0, kinds=())
        self._file_hol(rec, hol, stall, ts if ts is not None else time.time())
        with self._lock:
            self._count_hol(rec)

    def record_handover(self, seconds: float) -> None:
        """From the engine thread's hand-off of a step's outputs to the
        moment the stream's event loop could take them (one a step)."""
        if not self.enabled:
            return
        with self._lock:
            self._handover.file(seconds)
            if seconds > self._handover_max:
                self._handover_max = seconds

    # -- accounting -----------------------------------------------------
    def gaps_snapshot(self) -> dict:
        """``stats()["gaps"]``: cumulative since the ledger's start or
        ``reset``; a reader takes the difference of two. ``by_class[cls]
        [str(b)]`` holds, for buckets ``lo`` onward of ``edges``, the rows
        filed, the sum of their gaps and the seconds of those the engine
        thread was blocked on the device, with the steps counted and the
        sum of their periods; ``handover`` the same of the hand-over."""
        with self._lock:
            by_class: dict[str, dict[str, dict]] = {}
            for (cls, b), cell in self._gaps.items():
                by_class.setdefault(cls, {})[str(b)] = cell.snapshot()
            hand = self._handover.snapshot()
            hand_max = self._handover_max
        return {"edges": GAP_EDGES, "by_class": by_class,
                "handover": {"count": sum(hand["rows"]),
                             "sum_s": sum(hand["gap_s"]), "max_s": hand_max,
                             "lo": hand["lo"], "buckets": hand["rows"]}}

    def top_culprits(self, top: int = 5) -> list[dict]:
        """Worst HOL offenders: [{request_id, stall_seconds, victims}]."""
        with self._lock:
            items = sorted(self._culprits.items(),
                           key=lambda kv: kv[1][0], reverse=True)[:top]
        return [{"request_id": rid, "stall_seconds": round(s, 6),
                 "victims": n} for rid, (s, n) in items]

    def snapshot(self, steps: bool = False) -> dict:
        """Compact dict for stats publishing / bench artifacts."""
        with self._lock:
            recent = list(self.steps)
            out = {
                "enabled": self.enabled,
                "steps_total": self.steps_total,
                "goodput_fraction": (recent[-1].goodput if recent else 1.0),
                "budget_utilization": (recent[-1].budget_util
                                       if recent else 0.0),
                "live_tokens_total": self.live_tokens_total,
                "sched_tokens_total": self.sched_tokens_total,
                "rect_tokens_total": self.rect_tokens_total,
                "kv_blocks_live_total": self.kv_blocks_live_total,
                "kv_blocks_walked_total": self.kv_blocks_walked_total,
                "kv_blocks_walked_shared_total":
                    self.kv_blocks_walked_shared_total,
                "cross_tokens_total": self.cross_tokens_total,
                "kv_block_written_tokens_total":
                    self.kv_block_written_tokens_total,
                "moe_layer_steps_total": self.moe_totals[0],
                "moe_rows_total": self.moe_totals[1],
                "moe_experts_touched_total": self.moe_totals[2],
                "moe_largest_group_total": self.moe_totals[3],
                "moe_streamed_layer_steps_total": self.moe_totals[4],
                **dict(zip((k + "_total" for k in SSM_COUNTS),
                           self.ssm_totals)),
                "padding_flops_total": self.padding_flops_total,
                "padding_hbm_bytes_total": self.padding_bytes_total,
                "admission_blocked": dict(self.blocked_totals),
                "preempt_recompute_tokens": dict(self.preempt_totals),
                "hol_stall_seconds_total": round(
                    self.hol_stall_seconds_total, 6),
                "hol_victims_total": self.hol_victims_total,
                "interference_row_seconds_total": round(
                    self.interference_row_seconds_total, 6),
            }
            if self.prefill_chunks:
                out["prefill_chunk_tokens"] = dict(self.prefill_chunks)
        if recent:
            out["goodput_mean_recent"] = round(
                sum(r.goodput for r in recent) / len(recent), 4)
        out["top_culprits"] = self.top_culprits()
        if steps:
            out["steps"] = [r.to_dict() for r in recent[-64:]]
        return out

    def debug_info(self, recorder=None, limit: int = 64) -> dict:
        """The /debug/sched document: recent-step ring, goodput trend, top
        culprits — plus span-derived culprit aggregation when a
        FlightRecorder is given (the frontend's recorder holds hol spans
        INGESTED from workers, so a frontend that never ran an engine
        still attributes fleet-wide stalls)."""
        with self._lock:
            recent = list(self.steps)[-limit:]
        out = {
            "enabled": self.enabled,
            "env": SCHED_ENV,
            "totals": self.snapshot(),
            "recent_steps": [r.to_dict() for r in recent],
            "goodput_trend": [round(r.goodput, 4) for r in recent],
            "top_culprits": self.top_culprits(),
        }
        if recorder is not None:
            out["trace_culprits"] = hol_span_culprits(recorder)
        return out


def hol_span_culprits(recorder, top: int = 5) -> list[dict]:
    """Aggregate ``engine.hol_stall`` spans in a FlightRecorder by culprit
    — the cross-process view (workers ship victim spans on the wire)."""
    agg: dict[str, tuple[float, int]] = {}
    for span in recorder.iter_spans():
        if span.name != "engine.hol_stall":
            continue
        culprit = str(span.attrs.get("culprit", ""))
        s, n = agg.get(culprit, (0.0, 0))
        agg[culprit] = (s + span.duration, n + 1)
    items = sorted(agg.items(), key=lambda kv: kv[1][0], reverse=True)[:top]
    return [{"request_id": rid, "stall_seconds": round(s, 6),
             "victims": n} for rid, (s, n) in items]


_ledger: SchedLedger | None = None
_ledger_lock = threading.Lock()


def get_sched_ledger() -> SchedLedger:
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = SchedLedger()
        return _ledger


# ---------------------------------------------------------------------------
# A step's work, counted once — and its live-vs-scheduled geometry
# ---------------------------------------------------------------------------

#: A step's counts of its recurrent layers' work (``step_counts`` with
#: ``ssm_layers``), in the order the ledger totals them.
SSM_COUNTS = ("ssm_layer_steps", "ssm_live_tokens", "ssm_scanned_positions",
              "ssm_state_rows", "ssm_update_rows_given",
              "ssm_update_rows_moved", "ssm_scan_rows", "ssm_scan_positions")


def step_counts(batches, block_size: int, windows, *, dec_rows: int = 0,
                ssm_layers: int = 0, attn_tokens: bool = False,
                cross_layers: int = 0, scan_layers: int = 0,
                block_writes: bool = False) -> dict:
    """What one step did, in the program's own terms and nothing priced:
    THE walk over a step's rows, made once between plan and record
    (EngineCore._record_step). The profiler prices it, the ledger's goodput
    and totals read it, the ``engine.record`` span carries it.

    ``batches`` is PendingStep.batches: (sig, rows, sample_rows, toks, lps)
    with rows of (seq, start, length) and ``sig`` the ``BucketSig``
    dispatch() ran the batch under. ``windows`` gives each layer's window
    (0: a full layer). ``dec_rows`` is the plan-time count of decode rows
    among the step batches' rows, counted from the first (the rest are
    prefill chunks); verify rows count as decode rows beside it.

    Returns

    - ``kinds``, ``programs``: the batches' kinds, and how many there were;
    - ``prefill_rows``, ``decode_rows``, and ``prefill_tokens`` /
      ``decode_tokens`` (a "mixed" batch's rows of several tokens are
      prefill chunks, every other row decodes; a one-token prefill tail
      lands on the decode side);
    - ``live_tokens``; ``sched_tokens``, the dense layers' token bucket
      (``sig.n`` a program); ``rect_tokens``, the query positions attention
      is handed: the ``b x t`` of a program's rectangle, or with
      ``attn_tokens`` (the paged kernel takes a packed step's tokens as
      they lie: models/llama.py ``_attention``) its token bucket too;
      ``logit_rows`` (one a row) and
      ``sched_logit_rows`` (``sig.b`` a program);
    - ``kv_block_written_tokens``: with ``block_writes`` (the engine's
      ``writes_blocks``), the live tokens of the packed programs (``sig.n <
      sig.b x sig.t``), whose K and V go into the pools by runs of
      consecutive slots (ops/kv_write.py); a rectangle program's keep the
      scatter, an update a token;
    - ``chunk_rows``, ``chunk_ctx_tokens``: the prefill chunks of several
      tokens among the rows, and the context under them, ``start + length``
      a chunk (what its last query sees): how long the contexts are that
      reach attention under chunk rows;
    - ``kv_blocks_live``: ``ceil((start + length) / block_size)`` a row,
      the blocks the rows hold, what a full layer's kernel walks for them;
    - ``kv_blocks_walked``: the same over all the layers, a sliding layer's
      walk of a row beginning at the block that holds the oldest key the
      row's first query sees (ops/paged_attention.py
      ``chunk_first_blocks``);
    - ``attn_q_ctx``: the attention volume, the (query, key) pairs the
      rows' queries see, over all the layers: query ``p`` of a full layer
      sees ``p + 1`` keys, of a layer of window ``w`` ``min(p + 1, w)``;
    - ``table_q_ctx``, ``table_blocks``: what the programs' block tables
      span over all the layers (``b x t x nblk`` entries' keys, ``b x nblk``
      blocks): the dense gather pays for that, the kernel for the two above;
    - for a model with ``ssm_layers`` recurrent layers (models/mamba.py; 0
      for every other model) ``SSM_COUNTS``: ``ssm_layer_steps`` (a program
      times those layers), ``ssm_live_tokens`` (``live_tokens``),
      ``ssm_scanned_positions`` (the positions the mixer computes: a
      program's token bucket ``sig.n`` and, for each row of several tokens,
      the ``t`` positions of its blocked scan), ``ssm_state_rows`` (the
      rows whose state is read and written, times the layers), and of the
      one-token update's kernel (ops/ssm_update.py) ``ssm_update_rows_given``
      (the rows its grid has, a program's bucket ``sig.b``, times the layers)
      and ``ssm_update_rows_moved`` (the rows of one token among them, whose
      state it moves, times the layers; the others cost it no byte).
      ``scan_layers`` of the ``ssm_layers`` are Mamba-1's, whose recurrence
      is one kernel over every row (ops/selective_scan.py): they count no
      ``ssm_update_rows_*`` and no blocked scan's ``t`` positions, and
      instead ``ssm_scan_rows`` (the rows with live tokens, times those
      layers) and ``ssm_scan_positions`` (the live tokens, times them): what
      that kernel ran;
    - for a model with ``cross_layers`` attention mixers that reread another
      layer's keys and values for each row's last token alone (SambaY's
      cross-decoder; 0 for every other model): ``cross_tokens``, the tokens
      that entered those layers (one a row), and ``kv_blocks_walked_shared``,
      their walks' blocks, a row's whole context a layer. Both are in
      ``kv_blocks_walked``, and ``attn_q_ctx`` has their one query a row.

    ``windows`` has the layers that have attention and write it, and
    ``layers`` below counts those: the KV cache's layers.
    """
    bs = block_size
    layer_kinds = tuple(Counter(windows).items())   # a few kinds, many rows
    layers = len(windows)
    kinds: list[str] = []
    programs = pf_rows = n_dec = pf_tokens = dec_tokens = 0
    live = logit_rows = sched = rect = sched_rows = 0
    blocks = walked = q_ctx = table_q = table_blocks = scanned = ones = 0
    shared = by_blocks = chunk_rows = chunk_ctx = 0
    blocked = ssm_layers > scan_layers      # some layer scans in blocks
    dec_left = dec_rows
    for sig, rows, *_ in batches:
        if not rows:
            continue
        programs += 1
        n = len(rows)
        if sig.kind == "verify":
            kinds.append("verify")
            n_dec += n
        else:
            # Leading rows of a step's batches are decode/guided by
            # construction; the split is captured at plan time because
            # prefill_target() moves as finalize appends tokens.
            guided = (n <= dec_left and getattr(
                rows[0][0], "guided", None) is not None)
            kinds.append("guided" if guided else sig.kind)
            d = min(dec_left, n)
            dec_left -= d
            n_dec += d
            pf_rows += n - d
        chunks = sig.kind == "mixed"
        logit_rows += n
        packed = block_writes and sig.n != sig.b * sig.t
        for _seq, start, length in rows:
            end = start + length
            live += length
            by_blocks += length * packed
            ones += length == 1
            used = -(-end // bs)
            blocks += used
            for w, count in layer_kinds:
                if not w:
                    walked += count * used
                    q_ctx += count * (length * start
                                      + length * (length + 1) // 2)
                    continue
                first = min(max(start - (w - 1), 0) // bs, used - 1)
                walked += count * (used - first)
                # The first ``whole`` queries see every key before them.
                whole = max(0, min(end, w) - start)
                q_ctx += count * (whole * start + whole * (whole + 1) // 2
                                  + (length - whole) * w)
            if cross_layers:
                shared += cross_layers * used
                q_ctx += cross_layers * end
            if chunks and length > 1:
                pf_tokens += length
                chunk_rows += 1
                chunk_ctx += end
                scanned += sig.t * blocked
            else:
                dec_tokens += length
        sched += sig.n
        scanned += sig.n
        rect += sig.n if attn_tokens else sig.b * sig.t
        sched_rows += sig.b
        table_q += layers * sig.b * sig.t * sig.nblk * bs
        table_blocks += layers * sig.b * sig.nblk
    return {
        "kinds": tuple(kinds), "programs": programs,
        "prefill_rows": pf_rows, "decode_rows": n_dec,
        "prefill_tokens": pf_tokens, "decode_tokens": dec_tokens,
        "live_tokens": live, "sched_tokens": sched, "rect_tokens": rect,
        "logit_rows": logit_rows, "sched_logit_rows": sched_rows,
        "kv_blocks_live": blocks, "kv_blocks_walked": walked + shared,
        "kv_blocks_walked_shared": shared,
        "cross_tokens": logit_rows if cross_layers else 0,
        "kv_block_written_tokens": by_blocks,
        "chunk_rows": chunk_rows, "chunk_ctx_tokens": chunk_ctx,
        "attn_q_ctx": q_ctx,
        "table_q_ctx": table_q, "table_blocks": table_blocks,
        "ssm_layer_steps": programs * ssm_layers,
        "ssm_live_tokens": live if ssm_layers else 0,
        "ssm_scanned_positions": scanned if ssm_layers else 0,
        "ssm_state_rows": logit_rows * ssm_layers,
        "ssm_update_rows_given": sched_rows * (ssm_layers - scan_layers),
        "ssm_update_rows_moved": ones * (ssm_layers - scan_layers),
        "ssm_scan_rows": logit_rows * scan_layers,
        "ssm_scan_positions": live * scan_layers,
    }


def recurrent_and_cross(model_cfg) -> dict:
    """What :func:`step_counts` takes of a model beside its windows: its
    recurrent layers, those of them that are Mamba-1's, and its cross
    layers."""
    scan = model_cfg.layers_of("S")
    return {"ssm_layers": model_cfg.layers_of("M") + scan,
            "scan_layers": scan, "cross_layers": model_cfg.layers_of("X")}


def step_geometry(model_cfg, engine_cfg, batches, *, dec_rows: int = 0,
                  counts: dict | None = None, moe=None,
                  shapes: dict | None = None, live_cost=None) -> dict:
    """Live and scheduled (bucket-padded) work for one finalized step, as
    ``SchedLedger.record_step`` takes it.

    The live side is the step's one count (``counts``: ``step_counts`` of
    ``batches``, made here where the caller has none); the padded side is
    the signatures the batches ran under: their token bucket ``n`` through
    the dense layers, ``b`` rows through the head, and through attention
    what their block tables span where attention is the dense gather (the
    kernel walks the live blocks whatever the table's width: both sides
    then price those). Both sides run through obs/costmodel.step_work over
    ``shapes`` (``costmodel.step_shapes``), with the routed layers' device
    counts ``moe`` where the step has them, so goodput is a pure FLOPs
    ratio hand-computable at any known bucket geometry. ``live_cost``: the
    live side where the profiler has priced it already.
    """
    from dynamo_tpu.obs import costmodel as cm
    from dynamo_tpu.obs.compile_ledger import (
        attends_tokens,
        walks_live_context,
        writes_blocks,
    )

    ec = engine_cfg
    if counts is None:
        counts = step_counts(
            batches, ec.block_size, model_cfg.attn_windows,
            dec_rows=dec_rows, attn_tokens=attends_tokens(ec),
            # (a latent row is written by the scatter)
            block_writes=writes_blocks(ec)
            and not model_cfg.latent,
            **recurrent_and_cross(model_cfg))
    if shapes is None:
        shapes = cm.step_shapes(
            model_cfg, block_size=ec.block_size,
            kv_dtype=ec.kv_dtype or "bfloat16",
            quantization=ec.quantization or "none")
    lc = sc = None
    if counts["live_tokens"]:
        lc = live_cost or cm.step_work(shapes, counts, moe)
        kernel = walks_live_context(ec)
        sc = cm.step_work(shapes, {
            "programs": counts["programs"],
            "live_tokens": counts["sched_tokens"],
            "logit_rows": counts["sched_logit_rows"],
            "attn_q_ctx": counts["attn_q_ctx" if kernel else "table_q_ctx"],
            "kv_blocks_walked": counts[
                "kv_blocks_walked" if kernel else "table_blocks"]}, moe)
    return {
        **{k: counts[k] for k in (
            "kinds", "prefill_rows", "decode_rows", "live_tokens",
            "sched_tokens", "rect_tokens", "kv_blocks_live",
            "kv_blocks_walked", "kv_blocks_walked_shared", "cross_tokens",
            "kv_block_written_tokens")},
        "live_flops": lc.flops if lc else 0.0,
        "sched_flops": sc.flops if sc else 0.0,
        "live_bytes": lc.hbm_bytes if lc else 0.0,
        "sched_bytes": sc.hbm_bytes if sc else 0.0,
    }
