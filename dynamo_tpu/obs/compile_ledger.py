"""XLA compile ledger: per-bucket compile events, warmup lattice, metrics.

Every hot-path program the engine runs is a bucketed ``jax.jit`` compile —
the decode step, the mixed step, spec verify, embed — and each compile
blocks the engine-core thread for its full trace+compile wall. This
module makes those stalls observable and schedulable:

* ``CompileLedger`` — process-global record of every compile event keyed by
  bucket signature ``(kind, b, t, nblk, greedy, kv_dtype)``, whose token
  bucket ``n`` follows from ``(kind, b, t)``: wall seconds,
  trigger timestamp, the victim request's trace id, and the live
  compile-cache inventory. Serve-path events additionally emit
  ``engine.compile`` spans into the Tracer/FlightRecorder so
  ``/debug/traces`` attributes a TTFT spike to the exact cold bucket that
  caused it.
* ``CompileMetrics`` — the ``dynamo_xla_compile_*`` Prometheus family
  (lint-checked by tools/lint_metrics.py COMPILE_METRICS), re-homeable into
  a worker's runtime registry via ``install_compile_metrics`` exactly like
  the perf/ring-prefill families.
* ``sig_for_rows`` — THE step geometry: the one place that turns a batch
  (rows, longest row, block need) into the ``(kind, b, t, nblk, n)`` of the
  program that serves it. ``ModelRunner.dispatch`` / ``dispatch_verify``
  (engine/engine.py) shape their inputs from it, the scheduling ledger
  prices the signature they dispatched, the mocker and the benchmark's
  warm-up list call it device-free.
* ``enumerate_buckets(EngineConfig)`` — the reachable bucket lattice over
  the same ladders, so AOT warmup precompiles exactly what serving would
  mint lazily. Embed buckets are deliberately excluded from
  the warmup plan: embeddings are off the generate hot path and their
  ``b × t`` lattice would dominate the budget (their compiles are still
  ledgered when they happen).

Disabled mode (``--warmup-mode off``) flips ``CompileLedger.enabled``; the
engine's dispatch paths gate on that flag BEFORE touching timestamps or
bucket signatures, so a disabled ledger adds zero per-dispatch work.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from dynamo_tpu.utils.metrics import MetricsRegistry

#: Warmup modes: ``off`` disables the ledger entirely; ``lazy`` records
#: organic compiles against the enumerated lattice (coverage grows as
#: traffic mints buckets); ``full`` precompiles the lattice at startup.
WARMUP_MODES = ("off", "lazy", "full")

#: Compile walls span sub-second CPU tracing to multi-minute TPU prefill
#: programs. (MetricsRegistry appends the +Inf bucket.)
_COMPILE_SECONDS_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                            60.0, 120.0)

# The bucket ladders' two rules. Import-free, so the mocker and tests
# compute signatures device-free.


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return n


def _pow2_bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return b


def token_bucket(kind: str, b: int, t: int) -> int:
    """N: the tokens a ``(b, t)`` step program runs its dense layers over
    (models/llama.py ``forward(num_tokens=N)``); attention alone runs over
    the ``b x t`` rectangle. One N for each ``(kind, b, t)``, so the
    lattice has no dimension for it.

    A step with prefill work ("mixed") holds one chunk of up to
    t tokens beside up to b - 1 one-token rows, so ``t + b`` tokens hold
    it; the rectangle where that is smaller (one row). A step whose chunks
    hold more is cut into several programs (``pack_rows``), never padded
    to the rectangle. Every other kind is the rectangle: a decode row is
    its one token (``b``), a verify row fills its chunk."""
    if kind == "mixed":
        return min(b * t, t + b)
    return b * t


def pack_rows(lengths: list[int], ec) -> list[int]:
    """Cut a batch's rows, in dispatch order, into runs that each fit the
    token bucket of the program their own ``(b, t)`` buckets name; returns
    the rows in each run. ``lengths`` is each row's live tokens. A run
    grows while its tokens fit: several short chunks share a program, two
    full chunks do not. One row always fits, and so do one chunk and the
    one-token rows before it (``t + b > t_max + n - 1``): a decode batch,
    or a mixed step with one chunk, is one run as it always was."""
    runs: list[int] = []
    n = t_max = total = 0
    for length in lengths:
        n, t_max, total = n + 1, max(t_max, length), total + length
        if n > 1 and t_max > 1 and total > token_bucket(
                "mixed", _bucket(n, ec.decode_bucket),
                _pow2_bucket(t_max, 16, ec.prefill_chunk)):
            runs.append(n - 1)
            n, t_max, total = 1, length, length
    runs.append(n)
    return runs


@dataclass(frozen=True)
class BucketSig:
    """One compiled program's bucket signature. ``kind`` is one of
    decode | mixed | verify | embed; ``greedy`` is the argmax-only fast
    path variant (always True for verify/embed). "decode" is the step
    whose every row is one token, "mixed" the ragged step that carries a
    prefill chunk (decode rows beside it or not): b buckets over the
    decode ladder, t over the prefill chunk ladder.
    ``n`` is the program's token bucket, read off ``(kind, b, t)``."""

    kind: str
    b: int
    t: int
    nblk: int
    greedy: bool
    kv_dtype: str

    @property
    def n(self) -> int:
        return token_bucket(self.kind, self.b, self.t)

    def program(self, *, sp_prefill: bool = False, mm: bool = False,
                masked: bool = False) -> str:
        """The name the program is built under (``ModelRunner``'s builders
        jit a function of this name): a device trace's ``XLA Modules`` line
        shows it as ``jit_<name>(<hash>)``, the engine's ``engine.program``
        span as ``jit_<name>``. The variants that are no part of the
        signature ride as suffixes: ring prefill over "seq" (its dense
        layers run the whole ``b x t``), multimodal embeds, a logit mask."""
        if self.kind == "embed":
            return f"embed_b{self.b}_t{self.t}"
        if self.kind == "verify":
            return f"step_verify_b{self.b}_t{self.t}_n{self.nblk}"
        if self.t == 1:
            name = f"step_decode_b{self.b}_n{self.nblk}"
        else:
            n = self.b * self.t if sp_prefill else self.n
            name = f"step_mixed_b{self.b}_t{self.t}_k{n}_n{self.nblk}"
        for flag, suffix in ((sp_prefill, "_sp"), (not self.greedy, "_sampled"),
                             (mm, "_mm"), (masked, "_masked")):
            if flag:
                name += suffix
        return name

    def to_dict(self) -> dict:
        return {"kind": self.kind, "b": self.b, "t": self.t,
                "nblk": self.nblk, "n": self.n, "greedy": self.greedy,
                "kv_dtype": self.kv_dtype}


@dataclass
class CompileEvent:
    """One observed (or warmup-forced) XLA compile."""

    sig: BucketSig
    seconds: float
    ts: float                     # trigger timestamp (epoch)
    trace_id: str | None = None   # victim request's trace, if any
    source: str = "serve"         # "serve" | "warmup"
    loaded: bool = False          # from the program store, not built

    def to_dict(self) -> dict:
        d = {**self.sig.to_dict(), "seconds": self.seconds, "ts": self.ts,
             "source": self.source, "loaded": self.loaded}
        if self.trace_id:
            d["trace_id"] = self.trace_id
        return d


# ---------------------------------------------------------------------------
# Prometheus family
# ---------------------------------------------------------------------------

class CompileMetrics:
    """The dynamo_xla_compile_* family (names cross-checked by
    tools/lint_metrics.py COMPILE_METRICS)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.bind(registry or MetricsRegistry())

    def bind(self, registry: MetricsRegistry) -> None:
        self.registry = registry
        self.events = registry.counter(
            "xla_compile_events_total",
            "XLA compiles observed by the ledger, by kind (decode|window|"
            "prefill|mixed|verify|embed) and source (serve|warmup)")
        self.seconds = registry.histogram(
            "xla_compile_seconds",
            "Wall seconds one XLA trace+compile blocked the engine-core "
            "thread (or the warmup loop)",
            buckets=_COMPILE_SECONDS_BUCKETS)
        self.cache_entries = registry.gauge(
            "xla_compile_cache_entries",
            "Live compiled-program cache inventory (distinct bucket "
            "signatures the ledger has seen compile)")
        self.inflight = registry.gauge(
            "xla_compile_inflight",
            "Compiles currently blocking a dispatch (0 or 1 per engine — "
            "compiles serialize on the engine-core thread)")
        self.stall_seconds = registry.counter(
            "xla_compile_stall_seconds_total",
            "Cumulative wall seconds SERVING dispatches were stalled by "
            "compiles (warmup compiles excluded: they burn startup, not "
            "requests)")
        self.warmup_coverage = registry.gauge(
            "xla_compile_warmup_coverage",
            "Fraction of the enumerated warmup bucket lattice already "
            "compiled (1.0 = no serving request can hit a cold bucket)")
        self.warmup_buckets = registry.gauge(
            "xla_compile_warmup_buckets",
            "Size of the enumerated warmup bucket lattice for this "
            "engine's config")


_metrics: CompileMetrics | None = None


def get_compile_metrics() -> CompileMetrics:
    global _metrics
    if _metrics is None:
        _metrics = CompileMetrics()
    return _metrics


def install_compile_metrics(registry: MetricsRegistry) -> CompileMetrics:
    """Re-home the singleton's metrics into ``registry`` (the worker's
    runtime registry) so the family is exposed on /metrics. Gauges are
    republished from the live ledger so an install that lands AFTER the
    engine was built (single-process launch) still exposes the plan size
    and coverage; counters stay monotonic and are not replayed."""
    m = get_compile_metrics()
    m.bind(registry)
    led = get_compile_ledger()
    with led._lock:
        m.warmup_buckets.set(float(len(led.plan or ())))
        m.cache_entries.set(float(len(led.inventory)))
    m.warmup_coverage.set(led.coverage())
    return m


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

class CompileLedger:
    """Process-global compile event record + warmup coverage accounting.

    Thread-safe: the engine-core thread records serve compiles while the
    asyncio side reads snapshots for stats/bench. Events are bounded
    (``cap``) — the inventory and counters stay exact past the cap; only
    the per-event detail rolls."""

    def __init__(self, cap: int = 2048):
        self._lock = threading.Lock()
        self.cap = cap
        self.enabled = True
        self.mode = "lazy"
        self.events: list[CompileEvent] = []
        self.inventory: set[BucketSig] = set()
        # of the inventory, the programs that were loaded from the program
        # store (engine/program_store.py) and not traced, lowered and
        # compiled or fetched
        self.loaded: set[BucketSig] = set()
        self._dropped = 0
        # (layer bodies held, traced) summed over the recorded programs
        self._bodies = (0, 0)
        # Warmup plan: the enumerated lattice; None until an engine
        # configures warmup (coverage reads 0 with an empty plan).
        self.plan: set[BucketSig] | None = None

    # -- configuration --------------------------------------------------
    def configure(self, mode: str) -> None:
        """Engine-startup hook: sets the mode and the enabled gate."""
        if mode not in WARMUP_MODES:
            raise ValueError(
                f"warmup_mode must be one of {WARMUP_MODES}, got {mode!r}")
        with self._lock:
            self.mode = mode
            self.enabled = mode != "off"

    def set_plan(self, sigs: list[BucketSig] | set[BucketSig]) -> None:
        with self._lock:
            self.plan = set(sigs)
            get_compile_metrics().warmup_buckets.set(float(len(self.plan)))
        self._publish_coverage()

    def reset(self) -> None:
        """Test hook: drop all events/inventory/plan (metrics counters are
        monotonic and keep their totals)."""
        with self._lock:
            self.events.clear()
            self.inventory.clear()
            self.loaded.clear()
            self.plan = None
            self._dropped = 0
            self._bodies = (0, 0)

    # -- recording ------------------------------------------------------
    def record(self, sig: BucketSig, seconds: float, *,
               trace_ctx=None, source: str = "serve",
               ts: float | None = None,
               bodies: tuple[int, int] = (0, 0),
               loaded: bool = False) -> CompileEvent | None:
        """File one compile event; returns it (None when disabled).
        ``bodies``: the layer bodies the program holds and how many of them
        its build traced and lowered (``LayerPlan.bodies`` and the distinct
        among them), read off the model's plan by the caller and summed.
        ``loaded``: the program came whole from the program store
        (engine/program_store.py) and nothing of it was traced; its seconds
        are the load and the first run, stalled on like any other's.

        Serve-path events with a traced victim emit an ``engine.compile``
        span under the victim's trace; untraced serve events still land on
        the process timeline. Warmup events skip spans entirely — they
        stall startup, not a request."""
        if not self.enabled:
            return None
        end = ts if ts is not None else time.time()
        trace_id = getattr(trace_ctx, "trace_id", None)
        ev = CompileEvent(sig=sig, seconds=seconds, ts=end - seconds,
                          trace_id=trace_id, source=source, loaded=loaded)
        with self._lock:
            if len(self.events) < self.cap:
                self.events.append(ev)
            else:
                self._dropped += 1
            self.inventory.add(sig)
            if loaded:
                self.loaded.add(sig)
            n_inv = len(self.inventory)
            self._bodies = (self._bodies[0] + bodies[0],
                            self._bodies[1] + bodies[1])
        m = get_compile_metrics()
        m.events.inc(kind=sig.kind, source=source)
        m.seconds.observe(seconds, kind=sig.kind)
        m.cache_entries.set(float(n_inv))
        if source == "serve":
            m.stall_seconds.inc(seconds)
            from dynamo_tpu.obs.tracer import get_tracer

            tr = get_tracer()
            span = tr.start_span(
                "engine.compile", ctx=trace_ctx, start=ev.ts,
                kind=sig.kind, b=sig.b, t=sig.t, nblk=sig.nblk,
                greedy=sig.greedy, kv_dtype=sig.kv_dtype)
            tr.end_span(span, end=end, seconds=round(seconds, 6))
        self._publish_coverage()
        return ev

    def mark_inflight(self, on: bool) -> None:
        if self.enabled:
            get_compile_metrics().inflight.set(1.0 if on else 0.0)

    # -- accounting -----------------------------------------------------
    def coverage(self) -> float:
        """Fraction of the warmup plan already compiled. 0.0 with no plan
        (nothing enumerated yet — the conservative answer for routers)."""
        with self._lock:
            if not self.plan:
                return 0.0
            return len(self.plan & self.inventory) / len(self.plan)

    def _publish_coverage(self) -> None:
        get_compile_metrics().warmup_coverage.set(self.coverage())

    def total_seconds(self) -> float:
        with self._lock:
            return sum(e.seconds for e in self.events)

    def by_bucket(self) -> dict[BucketSig, tuple[int, float]]:
        """{sig: (event count, total seconds)} over recorded events."""
        out: dict[BucketSig, tuple[int, float]] = {}
        with self._lock:
            events = list(self.events)
        for e in events:
            n, s = out.get(e.sig, (0, 0.0))
            out[e.sig] = (n + 1, s + e.seconds)
        return out

    def snapshot(self, events: bool = False) -> dict:
        """Compact dict for stats publishing / bench artifacts."""
        with self._lock:
            out = {
                "mode": self.mode,
                "enabled": self.enabled,
                "cache_entries": len(self.inventory),
                "programs_loaded": len(self.loaded),
                "events_total": len(self.events) + self._dropped,
                "compile_seconds_total": sum(e.seconds for e in self.events),
                "serve_stall_seconds": sum(
                    e.seconds for e in self.events if e.source == "serve"),
                "warmup_buckets": len(self.plan) if self.plan else 0,
                # over the recorded programs: the layer bodies they hold
                # and those their builds traced and lowered (a description
                # a program repeats is traced once: llama._run_layers)
                "layer_bodies": self._bodies[0],
                "layer_bodies_traced": self._bodies[1],
            }
            if events:
                out["events"] = [e.to_dict() for e in self.events]
        out["warmup_coverage"] = round(self.coverage(), 4)
        return out


_ledger: CompileLedger | None = None
_ledger_lock = threading.Lock()


def get_compile_ledger() -> CompileLedger:
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = CompileLedger()
        return _ledger


# ---------------------------------------------------------------------------
# Bucket lattice enumeration — the ladders sig_for_rows picks from.
# ---------------------------------------------------------------------------

def walks_live_context(ec) -> bool:
    """Whether this engine's attention is the paged kernel
    (ops/paged_attention.py), which reads each row's live block count at
    run time and fetches those blocks itself: the block table's width then
    specialises nothing but an operand's shape. The dense gather builds a
    ``[B, nblk x BS, KH, D]`` context and pays for every entry. Read off
    the implementation the engine resolved into its config (``"auto"``
    unresolved, a mocker's arguments: the gather's ladder, the superset)."""
    return getattr(ec, "attn_impl", "") in ("pallas", "pallas_interpret")


def attends_tokens(ec) -> bool:
    """Whether a packed step's attention is handed the step's tokens as they
    lie, ``n`` query positions and no ``b x t`` rectangle: the kernel's
    token-major entry (models/llama.py ``_attention``; rows split over
    "data" keep the rectangle, the tokens having no batch axis to split)."""
    return walks_live_context(ec) and getattr(ec, "dp", 1) == 1


def writes_blocks(ec) -> bool:
    """Whether a packed step's K and V go into the pools by runs of
    consecutive slots, one small kernel a layer (ops/kv_write.py), and not
    by one scatter update a token: where its attention takes the tokens as
    they lie and the pool is a plain array (a quantized pool's scatter
    rescales the blocks it touches: models/llama.py ``_scatter_kv_quant``)."""
    return attends_tokens(ec) and getattr(ec, "kv_dtype", "") not in (
        "int8", "int4")


def _nblk_ladder(ec) -> list[int]:
    """Reachable block-table widths. Under the kernel one, ``max_nblk``
    (``walks_live_context``); under the dense gather ``sig_for_rows``
    computes ``min(_pow2_bucket(need, 4, max_nblk), max_nblk)`` — the pow2
    ladder from 4, clamped to (and always including) max_nblk."""
    max_nblk = -(-ec.max_model_len // ec.block_size)
    if walks_live_context(ec):
        return [max_nblk]
    out: list[int] = []
    b = 4
    while b < max_nblk:
        out.append(b)
        b *= 2
    out.append(max_nblk)
    return sorted({min(n, max_nblk) for n in out})


def _reachable_batch_buckets(maxn: int, buckets: tuple[int, ...]) -> list[int]:
    """Batch sizes ``_bucket(n, buckets)`` can return for n in 1..maxn.
    Past the ladder, _bucket returns n itself; only ``maxn`` (the cap) is
    enumerated for that open tail — intermediate fallthrough sizes are
    organic-compile territory, not warmup's."""
    out: list[int] = []
    for x in buckets:
        out.append(x)
        if x >= maxn:
            break
    else:
        out.append(maxn)
    return sorted(set(out))


def _prefill_t_ladder(ec) -> list[int]:
    """Reachable prefill chunk buckets: ``_pow2_bucket(t, 16, prefill_chunk)``
    over t in 1..min(prefill_chunk, max_model_len, max_tokens_per_step)."""
    cap = min(ec.prefill_chunk, ec.max_model_len, ec.max_tokens_per_step)
    out = [16]
    t = 16
    while t < cap:
        t *= 2
        out.append(t)
    return out


def _verify_t_ladder(spec_k: int) -> list[int]:
    """Reachable verify chunk buckets: ``min(_pow2_bucket(t, 2, k+1), k+1)``
    over t in 1..spec_k+1 (chunk = current token + up to k proposals)."""
    k1 = spec_k + 1
    return sorted({min(_pow2_bucket(t, 2, k1), k1) for t in range(1, k1 + 1)})


#: Embed's row ladder. Bounded: client batch sizes must not mint unbounded
#: compile-cache entries (each compile blocks the engine-core thread).
_EMBED_ROWS = (1, 2, 4, 8, 16, 32, 64)


def embed_bucket_ladders(ec) -> tuple[list[int], list[int]]:
    """Embed's (b, t) ladders — exported for tests/tools; embed buckets are
    NOT part of the warmup plan (off the generate hot path)."""
    bs = list(_EMBED_ROWS)
    ts = [16]
    t = 16
    while t < ec.max_model_len:
        t *= 2
        ts.append(t)
    return bs, ts


def enumerate_buckets(ec) -> list[BucketSig]:
    """The reachable generate-path bucket lattice for one EngineConfig —
    what ``--warmup-mode full`` precompiles and what coverage is measured
    against. Excludes: embed (off-path), sp-prefill/multimodal/guided
    variants (workload-dependent; organic compiles, still ledgered).

    A step without prefill work is a "decode" program; one that carries a
    chunk is ONE ragged "mixed" program (decode-ladder b x prefill t
    ladder)."""
    kv = ec.kv_dtype or "bfloat16"
    nblks = _nblk_ladder(ec)
    bs = _reachable_batch_buckets(ec.max_batch_size, ec.decode_bucket)
    out = [BucketSig("decode", b, 1, nblk, g, kv)
           for b in bs for nblk in nblks for g in (True, False)]
    out += [BucketSig("mixed", b, t, nblk, g, kv)
            for b in bs for t in _prefill_t_ladder(ec)
            for nblk in nblks for g in (True, False)]
    if ec.spec_ngram > 0:
        out += [BucketSig("verify", b, t, nblk, True, kv)
                for b in bs for t in _verify_t_ladder(ec.spec_k)
                for nblk in nblks]
    return out


def sig_for_rows(kind: str, n_rows: int, t_max: int, nblk_need: int,
                 ec, greedy: bool = True) -> BucketSig:
    """The program that serves a batch of ``n_rows`` rows whose longest
    holds ``t_max`` tokens and whose widest block table needs
    ``nblk_need`` entries: THE step geometry, which dispatch() shapes its
    inputs by and every ledger reads back. The batch is one run of
    ``pack_rows``; the signature's ``n`` is the ``[N, H]`` its dense layers
    compute, ``b x t`` the rows its attention sees, ``nblk`` the width of
    its block table: under the kernel ``max_nblk`` whatever the need (the
    kernel walks a row's live blocks and no more), under the dense gather
    the need's pow2 bucket.

    ``kind`` says only whether the batch is a speculative "verify" chunk
    or an "embed" call (its own ladders, no block table); any other batch
    is a step, and a step whose longest row is one token IS the decode
    program, whatever it was planned as."""
    kv = ec.kv_dtype if getattr(ec, "kv_dtype", None) else "bfloat16"
    if kind == "embed":
        return BucketSig("embed", _bucket(n_rows, _EMBED_ROWS),
                         _pow2_bucket(t_max, 16, ec.max_model_len), 0,
                         True, kv)
    max_nblk = -(-ec.max_model_len // ec.block_size)
    if walks_live_context(ec):
        nblk = max_nblk
    else:
        # The gather pays for every entry: width from the batch's KV
        # coverage, pow2-bucketed to bound the number of compiled programs.
        nblk = min(_pow2_bucket(max(nblk_need, 1), 4, max_nblk), max_nblk)
    b = _bucket(n_rows, ec.decode_bucket)
    if kind == "verify":
        # clamp: _pow2_bucket's hi stops further doubling but doesn't cap
        # the result — a 5-token chunk must not mint (and pay for) T=8
        t = min(_pow2_bucket(t_max, 2, ec.spec_k + 1), ec.spec_k + 1)
        return BucketSig("verify", b, t, nblk, True, kv)
    if t_max <= 1:
        return BucketSig("decode", b, 1, nblk, greedy, kv)
    t = _pow2_bucket(t_max, 16, ec.prefill_chunk)
    return BucketSig("mixed", b, t, nblk, greedy, kv)
