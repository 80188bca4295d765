"""Step performance profiler: hardware counters for every engine step.

Four pieces:

* ``phase(name)`` — the hook models/llama.py, models/moe.py and
  engine/engine.py wrap their device phases in (``DEVICE_PHASES``): plain
  ``jax.named_scope``. Zero runtime ops (the scope only annotates the
  traced HLO), and since the model runs under ``jax.jit`` the context
  manager itself executes only at trace time. ``phase_table(text)`` reads
  the scopes back off a compiled step program's text: ``{instruction:
  innermost phase}``, which a reader of a device trace joins to the
  device's events by the instruction's name (the events themselves carry
  no scope).

* ``loop_phase(clock, name)`` / ``LoopClock`` — the boundaries of the
  engine thread's own loop (idle wait, inbox, plan, dispatch, finalize,
  record, post, compile), written two ways from one context manager: a
  ``jax.profiler.TraceAnnotation`` (recorded only while a profiler session
  is open; it lands on the engine thread's line of the trace, on the device
  trace's clock) and always-on seconds per phase (``stats()["loop"]``,
  ``dynamo_engine_loop_seconds_total{phase}``).

* ``StepPerfProfiler`` — prices each finalized step's one count
  (obs/sched_ledger.py ``step_counts``, with the routed layers' device
  counts) by obs/costmodel.py ``step_work`` and, with the host's wall of
  ``step_finalize``, derives tokens/s, MFU, HBM-bandwidth utilization, and
  the achieved roofline fraction. EngineCore calls ``measure()`` from its always-on
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
import re
import tempfile
import time
from collections import Counter
from pathlib import Path
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


# The scopes the step programs name their work by, as ``phase_table`` finds
# them again. ``layer`` encloses a whole transformer layer, so what no
# inner scope names (norms, rope, residual adds, the moves of ``q`` and the
# attention output around the kernel) is the layer's rest; ``layout`` is
# the step's own preparation (token layout, positions, slots, the device-fed
# first token).
DEVICE_PHASES = (
    "layout", "embed", "layer", "proj", "scatter", "gather", "attention",
    "mlp", "moe_route", "moe_experts", "moe_shared", "ssm_proj", "ssm_conv",
    "ssm_scan", "logits", "sampling",
    # differential attention's combine on the kernel's output, and SambaY's
    # gated memory unit (models/llama.py)
    "attn_diff", "gmu",
    # latent attention (models/llama.py ``_latent_attention``): the two
    # down-projections with their norms, the query's up-projection and the
    # rope; ``q_nope W_uk^T``; the row's write into the latent pool; the
    # paged walk in its latent form; ``o_lat W_uv``
    "mla_down", "mla_absorb", "mla_write", "mla_walk", "mla_unabsorb",
)

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w\-.]+) = .*?\s([\w\-]+)\(([^)]*)\)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w\-.]+) \(.*\{\s*$")
_OPERAND = re.compile(r"%([\w\-.]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w\-.]+)")
_MATMULS = ("dot", "convolution", "ragged-dot")
# What encloses other instructions or only names a value: no work of its
# own to give a phase to, and no phase to hand on.
_NO_WORK = ("parameter", "constant", "tuple", "while", "call", "conditional")


def innermost_phase(op_name: str) -> str | None:
    """``jit(step)/while/body/layer/moe_experts/add`` -> ``moe_experts``:
    the last scope of an instruction's ``op_name`` that is a phase. The
    last component is the primitive's name, never a scope (a ``gather`` or
    a ``scatter`` outside every phase is not in the phase of that name)."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in DEVICE_PHASES:
            return part
    return None


def _most(phases) -> str | None:
    seen = Counter(p for p in phases if p)
    return seen.most_common(1)[0][0] if seen else None


def phase_table(text: str) -> dict[str, str]:
    """``{instruction name: innermost phase}`` of one compiled program,
    from its text (``jax.stages.Compiled.as_text()``): an instruction
    carries the scopes it was traced under as ``metadata={op_name=...}``,
    fusions and custom calls included, and a device trace names its events
    by the instruction.

    - A fusion is named by what it computes, not by the instruction XLA
      made its root: where the fused computation holds a matmul, the
      matmul's phase (``wo``'s product with the next norm's sum of squares
      fused in is ``proj``, not the norm's); else its own, else the one
      most of its instructions have.
    - An instruction the compiler made itself carries no scope (on a TPU
      ``lax.ragged_dot`` becomes ``ragged-dot-metadata`` and custom calls
      whose ``op_name`` is their own name): it takes the phase of what
      reads its result, else of what it reads, through as many of its like
      as lie between. So a phase holds its work whatever instruction does
      it, by any name.

    Instructions of fused computations are left out (the device has no
    event for them), and so is whatever has no phase at all."""
    # computation -> [name, opcode, phase, called computation, operands]
    comps: dict[str, list[list]] = {}
    cur: list | None = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None or " = " not in line:
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        cur.append([m.group(1), m.group(2),
                    innermost_phase(op.group(1)) if op else None,
                    calls.group(1) if calls else None,
                    _OPERAND.findall(m.group(3))])
    fused = {c for ins in comps.values()
             for _n, opcode, _p, c, _o in ins if opcode == "fusion" and c}
    table: dict[str, str] = {}
    for comp, ins in comps.items():
        if comp in fused:
            continue
        for row in ins:
            _name, opcode, own, calls, _ops = row
            if opcode == "fusion" and calls in comps:
                inner = comps[calls]
                row[2] = (_most(p for _n, o, p, _c, _o in inner
                                if o in _MATMULS)
                          or own or _most(p for _n, _o, p, _c, _o2 in inner))
        known = {name: p for name, opcode, p, _c, _o in ins
                 if p and opcode not in _NO_WORK}
        users: dict[str, list[str]] = {}
        for name, _opcode, _p, _c, ops in ins:
            for o in ops:
                users.setdefault(o, []).append(name)
        todo = [r for r in ins if not r[2] and r[1] not in _NO_WORK]
        while todo:
            found = {}
            for name, _opcode, _p, _c, ops in todo:
                p = (_most(known.get(u) for u in users.get(name, ()))
                     or _most(known.get(o) for o in ops))
                if p:
                    found[name] = p
            if not found:
                break
            known.update(found)
            todo = [r for r in todo if r[0] not in found]
        table.update(known)
    return table


def phase_table_path() -> Path:
    """Where an engine that saw a profiler session leaves its phase tables
    at shutdown, and where a reader in the same process looks for them:
    ``<tempdir>/dynamo-tpu-phases-<pid>.json``."""
    return Path(tempfile.gettempdir()) / (
        f"dynamo-tpu-phases-{os.getpid()}.json")


# /debug/phases (runtime/status.py): the engine that serves in this process
# registers how to build its tables; nothing is built until someone asks.
_phase_source = None


def register_phase_source(fn) -> None:
    global _phase_source
    _phase_source = fn


def debug_phases() -> dict:
    """``{program: {instruction: phase}}`` of the step programs this
    process's engine has built, lowered and read now (seconds: a lowering a
    program, compiled from the persistent cache)."""
    return _phase_source() if _phase_source is not None else {}


# The engine thread's loop, cut at one set of boundaries
# (engine/engine.py: AsyncJaxEngine._run, EngineCore.step_begin,
# step_finalize). The top-level ones do not nest; engine.dispatch has parts
# nested in it (NESTED_PHASES: their sum is at most engine.dispatch), and
# engine.compile nests in engine.dispatch.launch. A step's host self time
# is the sum of the top-level non-wait phases less engine.compile.
# engine.unphased is no span's: an iteration's wall less its top-level
# phases, the statements between them.
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
    "engine.dispatch.reset",  # reset_slot: a new sequence's sampling state
    "engine.dispatch.fill",   # the step's numpy inputs, row by row
    "engine.dispatch.place",  # those inputs, host to device
    "engine.dispatch.launch",  # the jitted call (a compile nests in it)
    "engine.unphased",        # the loop's wall outside every phase
)
NESTED_PHASES = ("engine.compile", "engine.dispatch.reset",
                 "engine.dispatch.fill", "engine.dispatch.place",
                 "engine.dispatch.launch")


class LoopClock:
    """Seconds the engine thread spent in each loop phase since the engine
    was built. The engine thread is the only writer; readers copy
    (``snapshot``). Every key exists from the start, so a copy never meets
    a dict that is growing."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self._published: dict[str, float] = dict(self.seconds)
        # Seconds in phases entered outside any other (nested ones are
        # inside those already), and how deep the thread is in phases now:
        # what ``loop_iteration`` takes an iteration's wall less.
        self.phased = 0.0
        self.depth = 0

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
        self._clock.depth += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        clock = self._clock
        clock.seconds[self._name] += dt
        clock.depth -= 1
        if not clock.depth:
            clock.phased += dt
        return False


class loop_iteration:
    """One iteration of the engine thread's loop:
    ``clock.seconds["engine.unphased"]`` plus the iteration's wall less the
    phases inside it, the statements of ``AsyncJaxEngine._run`` between its
    phases. Always-on seconds and no span: what says that the phases'
    seconds are the whole of the loop's."""

    __slots__ = ("_clock", "_t0", "_phased0")

    def __init__(self, clock: LoopClock):
        self._clock = clock

    def __enter__(self):
        self._phased0 = self._clock.phased
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        clock = self._clock
        clock.seconds["engine.unphased"] += max(
            dt - (clock.phased - self._phased0), 0.0)
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
            "Model-FLOPs utilization over recent engine steps (EWMA): the "
            "FLOP of the step's live tokens over the host's wall of "
            "step_finalize, over the chip's peak")
        self.bw_util = registry.gauge(
            "engine_perf_hbm_bw_util",
            "HBM bandwidth utilization over recent engine steps (EWMA): the "
            "bytes the step had to read (weights once a program, the experts "
            "touched, the KV blocks walked) over the host's wall of "
            "step_finalize, over the chip's peak bandwidth")
        self.roofline = registry.gauge(
            "engine_perf_roofline_fraction",
            "Achieved fraction of the analytic roofline floor for recent "
            "engine steps (1.0 = running at the hardware bound), by the "
            "host's wall of step_finalize, not by device time")
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
            "finalize.host|record|post|compile|dispatch.reset|dispatch.fill|"
            "dispatch.place|dispatch.launch|unphased; dispatch.* nest in "
            "dispatch, compile in dispatch.launch; unphased is the loop's "
            "wall outside every phase)")


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
    """Per-step hardware counters for one EngineCore, from what the step did.

    ``measure(counts, wall_s, moe)`` prices the step's one count
    (obs/sched_ledger.py ``step_counts``) and the routed layers' device
    counts by obs/costmodel.py ``step_work`` over the program's shapes, and
    returns the perf fields for the step ring; it also feeds the
    dynamo_engine_perf_* family. A few multiplications a step, no walk over
    its rows; disabled it returns ``{}`` immediately.

    What the gauges divide by is ``wall_s``, the host's wall of
    ``step_finalize`` (mostly its wait for the device, with the token
    appends behind it): an operator's gauge, not a device time. The
    benchmark's ``engine.step_mfu_pct`` / ``engine.step_roofline_pct``
    price the same counts the same way and divide by the programs' device
    time from a trace.
    """

    _EWMA_ALPHA = 0.2

    def __init__(self, model_cfg, engine_cfg, device_kind: str | None = None,
                 enabled: bool | None = None, shapes: dict | None = None):
        self.kv_dtype = engine_cfg.kv_dtype or "bfloat16"
        self.enabled = perf_enabled() if enabled is None else enabled
        if device_kind is None:
            device_kind = _detect_device_kind()
        self.hw = cm.hw_spec_for(device_kind)
        self.shapes = shapes or cm.step_shapes(
            model_cfg, block_size=engine_cfg.block_size,
            kv_dtype=self.kv_dtype,
            quantization=engine_cfg.quantization or "none")
        # The last step's priced count: the scheduling ledger's goodput
        # takes its live side from here (None: nothing priced).
        self.last_cost: cm.KernelCost | None = None
        self._ewma: dict[str, float] = {}

    def _smooth(self, key: str, value: float) -> float:
        prev = self._ewma.get(key)
        cur = value if prev is None else (
            prev + self._EWMA_ALPHA * (value - prev))
        self._ewma[key] = cur
        return cur

    def measure(self, counts: dict, wall_s: float,
                moe: tuple | list | None = None) -> dict[str, Any]:
        """Perf fields for one finalized step. ``counts``: the step's one
        count (``step_counts``); ``moe``: the routed layers' device counts
        for it (layer steps, rows, experts touched), where its programs
        returned any: the experts' bytes are then what the step read, not
        an estimate."""
        self.last_cost = None
        if not self.enabled or not counts.get("programs"):
            return {}
        cost = self.last_cost = cm.step_work(self.shapes, counts, moe)
        dec_tokens, pf_tokens = counts["decode_tokens"], counts["prefill_tokens"]
        gen = dec_tokens if dec_tokens else counts["live_tokens"]
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
