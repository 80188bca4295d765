"""Step performance profiler: hardware counters for every engine step.

Four pieces:

* ``phase(name)`` — the hook models/llama.py and engine/engine.py wrap
  their device phases in (scatter, gather, attention, logits, sampling):
  plain ``jax.named_scope``. Zero runtime ops (the scope only annotates the
  traced HLO, so XLA profiles group by phase), and since the model runs
  under ``jax.jit`` the context manager itself executes only at trace time.

* ``loop_phase(clock, name)`` / ``LoopClock`` — the boundaries of the
  engine thread's own loop (idle wait, inbox, plan, dispatch, finalize,
  record, post, compile), written two ways from one context manager: a
  ``jax.profiler.TraceAnnotation`` (recorded only while a profiler session
  is open; it lands on the engine thread's line of the trace, on the device
  trace's clock) and always-on seconds per phase (``stats()["loop"]``,
  ``dynamo_engine_loop_seconds_total{phase}``).

* ``StepPerfProfiler`` — folds the analytic cost model (obs/costmodel.py)
  over each dispatched step's batches and, with the measured step wall,
  derives tokens/s, MFU, HBM-bandwidth utilization, and the achieved
  roofline fraction. EngineCore calls ``measure()`` from its always-on
  step recording; the returned fields land in the FlightRecorder step ring
  (obs/recorder.py StepRecord) so /debug/traces carries hardware counters.
  Disabled (``DYN_PERF_PROFILE=0``) it returns ``{}`` before touching the
  cost model — zero extra ops, zero extra host math.

* ``PerfMetrics`` — the ``dynamo_engine_perf_*`` Prometheus family
  (lint-checked by tools/lint_metrics.py PERF_METRICS), re-homeable into a
  worker's runtime registry via ``install_perf_metrics`` exactly like the
  disagg KV-transfer family.
"""

from __future__ import annotations

import os
import time
from typing import Any

from dynamo_tpu.obs import costmodel as cm
from dynamo_tpu.utils.metrics import MetricsRegistry

PERF_ENV = "DYN_PERF_PROFILE"

# Engine steps span sub-ms fused-window decode on a chip to multi-second
# CPU-fallback prefill compiles. (MetricsRegistry appends the +Inf bucket.)
_STEP_SECONDS_BUCKETS = (
    0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0,
)


def perf_enabled(default: bool = True) -> bool:
    """The module-level gate: DYN_PERF_PROFILE=0 disables all per-step
    cost-model math (the phase hooks are free either way)."""
    val = os.environ.get(PERF_ENV, "")
    if val == "":
        return default
    return val not in ("0", "false", "no", "off")


# ---------------------------------------------------------------------------
# Phase hooks
# ---------------------------------------------------------------------------

def phase(name: str):
    """Wrap one device phase: ``jax.named_scope`` (annotation only, zero ops
    in the compiled program)."""
    import jax

    return jax.named_scope(name)


# The engine thread's loop, cut at one set of boundaries
# (engine/engine.py: AsyncJaxEngine._run, EngineCore.step_begin,
# step_finalize). They do not nest, except engine.compile inside
# engine.dispatch: a step's host self time is the sum of the non-wait
# phases less engine.compile.
LOOP_PHASES = (
    "engine.idle_wait",       # nothing to do: waiting on the wake event
    "engine.inbox",           # add_request (prefix match), aborts, exec ops
    "engine.plan",            # session sweep, sched.plan(), accounting
    "engine.dispatch",        # input prep, H2D, enqueue of the step program
    "engine.finalize.wait",   # host blocked on the device's tokens
    "engine.finalize.host",   # token append, hash commit, stop checks
    "engine.record",          # the always-on ledgers (_record_step)
    "engine.post",            # hand-off to the asyncio loop, stream waves
    "engine.compile",         # a step program built inside serving
)


class LoopClock:
    """Seconds the engine thread spent in each loop phase since the engine
    was built. The engine thread is the only writer; readers copy
    (``snapshot``). Every key exists from the start, so a copy never meets
    a dict that is growing."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self._published: dict[str, float] = dict(self.seconds)

    def snapshot(self) -> dict[str, float]:
        return dict(self.seconds)

    def publish(self) -> None:
        """Feed ``dynamo_engine_loop_seconds_total{phase}`` with what has
        accrued since the last call (once a step, from ``_record_step``)."""
        counter = get_perf_metrics().loop_seconds
        for name, total in self.seconds.items():
            delta = total - self._published[name]
            if delta > 0.0:
                counter.inc(delta, phase=name)
                self._published[name] = total


class loop_phase:
    """One phase of the engine loop: a ``TraceAnnotation`` named ``name``
    (a no-op unless a profiler session is open) and ``clock.seconds[name]``
    plus the elapsed ``perf_counter`` seconds. Observational only: no
    decision reads either. ``set(**attrs)`` adds attributes known only
    inside the phase (the bucket a dispatch picked)."""

    __slots__ = ("_clock", "_name", "_ann", "_t0")

    def __init__(self, clock: LoopClock, name: str, **attrs: Any):
        import jax

        self._clock, self._name = clock, name
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def set(self, **attrs: Any) -> None:
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._clock.seconds[self._name] += dt
        return False


# ---------------------------------------------------------------------------
# Prometheus family
# ---------------------------------------------------------------------------

class PerfMetrics:
    """The dynamo_engine_perf_* family (names cross-checked by
    tools/lint_metrics.py PERF_METRICS)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.bind(registry or MetricsRegistry())

    def bind(self, registry: MetricsRegistry) -> None:
        self.registry = registry
        self.tok_s = registry.gauge(
            "engine_perf_tokens_per_second",
            "Generated tokens/s over recent engine steps (EWMA), by kind "
            "(decode|prefill) and kv_dtype (bfloat16|int8|int4) — label set "
            "declared in tools/lint_metrics.py PERF_METRIC_LABELS")
        self.mfu = registry.gauge(
            "engine_perf_mfu",
            "Model-FLOPs utilization over recent engine steps (EWMA): "
            "analytic matmul FLOP/s over the chip's peak")
        self.bw_util = registry.gauge(
            "engine_perf_hbm_bw_util",
            "HBM bandwidth utilization over recent engine steps (EWMA): "
            "analytic bytes/s over the chip's peak bandwidth")
        self.roofline = registry.gauge(
            "engine_perf_roofline_fraction",
            "Achieved fraction of the analytic roofline floor for recent "
            "engine steps (1.0 = running at the hardware bound)")
        self.flops_total = registry.counter(
            "engine_perf_model_flops_total",
            "Cumulative analytic model FLOPs dispatched by the engine")
        self.bytes_total = registry.counter(
            "engine_perf_hbm_bytes_total",
            "Cumulative analytic HBM bytes moved by engine steps")
        self.step_seconds = registry.histogram(
            "engine_perf_step_seconds",
            "Time step_finalize took for one engine step: mostly the host's "
            "wait for the device to finish it, plus token append, commits "
            "and stop checks",
            buckets=_STEP_SECONDS_BUCKETS)
        self.loop_seconds = registry.counter(
            "engine_loop_seconds_total",
            "Seconds the engine thread spent in each phase of its loop "
            "(phase = engine.idle_wait|inbox|plan|dispatch|finalize.wait|"
            "finalize.host|record|post|compile; compile nests in dispatch)")


_metrics: PerfMetrics | None = None


def get_perf_metrics() -> PerfMetrics:
    global _metrics
    if _metrics is None:
        _metrics = PerfMetrics()
    return _metrics


def install_perf_metrics(registry: MetricsRegistry) -> PerfMetrics:
    """Re-home the singleton's metrics into ``registry`` (the worker's
    runtime registry) so the family is exposed on /metrics."""
    m = get_perf_metrics()
    m.bind(registry)
    return m


# ---------------------------------------------------------------------------
# Per-step measurement
# ---------------------------------------------------------------------------

class StepPerfProfiler:
    """Analytic per-step hardware counters for one EngineCore.

    ``measure(batches, wall_s)`` charges each dispatched batch via the cost
    model and returns the perf fields for the step ring; it also feeds the
    dynamo_engine_perf_* family. O(rows) host work per step; disabled it
    returns ``{}`` immediately.
    """

    _EWMA_ALPHA = 0.2

    def __init__(self, model_cfg, engine_cfg, device_kind: str | None = None,
                 enabled: bool | None = None):
        self.cfg = model_cfg
        self.block_size = engine_cfg.block_size
        self.kv_dtype = engine_cfg.kv_dtype or "bfloat16"
        self.quantization = engine_cfg.quantization or "none"
        self.enabled = perf_enabled() if enabled is None else enabled
        if device_kind is None:
            device_kind = _detect_device_kind()
        self.hw = cm.hw_spec_for(device_kind)
        self._ewma: dict[str, float] = {}

    def _smooth(self, key: str, value: float) -> float:
        prev = self._ewma.get(key)
        cur = value if prev is None else (
            prev + self._EWMA_ALPHA * (value - prev))
        self._ewma[key] = cur
        return cur

    def measure(self, batches: list, wall_s: float) -> dict[str, Any]:
        """Perf fields for one finalized step. ``batches`` is
        PendingStep.batches: (sig, rows, sample_rows, toks, lps) with rows
        of (seq, start, length) and ``sig`` the dispatched ``BucketSig``."""
        if not self.enabled or not batches:
            return {}
        bs = self.block_size
        tokens = logit_rows = 0
        attn_q_ctx = kv_blocks = 0.0
        dec_tokens = pf_tokens = 0
        for sig, rows, _sample_rows, _toks, _lps in batches:
            for (_seq, start, length) in rows:
                tokens += length
                logit_rows += 1
                nblk = -(-(start + length) // bs)
                attn_q_ctx += length * nblk * bs
                kv_blocks += nblk
                # "mixed" batches carry both phases: multi-token rows are
                # prefill chunks, single-token rows decode. (A 1-token
                # prefill tail lands on the decode counter — one token of
                # split drift; the aggregate volumes above stay exact.)
                if sig.kind == "mixed" and length > 1:
                    pf_tokens += length
                else:
                    dec_tokens += length
        phases = cm.model_step_cost(
            self.cfg, tokens=tokens, logit_rows=logit_rows,
            attn_q_ctx=attn_q_ctx, kv_blocks=kv_blocks, block_size=bs,
            kv_dtype=self.kv_dtype, quantization=self.quantization)
        cost = cm.total_cost(phases)
        gen = dec_tokens if dec_tokens else tokens
        tok_s = gen / wall_s if wall_s > 0 else 0.0
        fields = {
            "decode_tokens": dec_tokens,
            "prefill_tokens": pf_tokens,
            "flops": cost.flops,
            "hbm_bytes": cost.hbm_bytes,
            "tok_s": tok_s,
            "mfu": cm.mfu(cost.flops, wall_s, self.hw),
            "bw_util": cm.bw_util(cost.hbm_bytes, wall_s, self.hw),
            "roofline_frac": cm.roofline_fraction(cost, wall_s, self.hw),
        }
        m = get_perf_metrics()
        kind = "decode" if dec_tokens >= pf_tokens else "prefill"
        m.tok_s.set(self._smooth(f"tok_s:{kind}", tok_s), kind=kind,
                    kv_dtype=self.kv_dtype)
        m.mfu.set(self._smooth("mfu", fields["mfu"]))
        m.bw_util.set(self._smooth("bw_util", fields["bw_util"]))
        m.roofline.set(self._smooth("roofline", fields["roofline_frac"]))
        m.flops_total.inc(cost.flops)
        m.bytes_total.inc(cost.hbm_bytes)
        m.step_seconds.observe(wall_s)
        return fields


def _detect_device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind
