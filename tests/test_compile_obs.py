"""Compile-aware observability: ledger, bucket lattice, warmup, mirrors.

The load-bearing invariant here is that ``enumerate_buckets`` /
``sig_for_rows`` (obs/compile_ledger.py) compute the SAME geometry as the
engine's dispatch paths (engine/engine.py) — the lattice tests below pin
both against hand-computed bucket math, so a drift in either side fails
loudly instead of silently leaving warmup holes. The real-engine test is
the tentpole acceptance check: ``--warmup-mode full`` on a minuscule
lattice, then a served request minting ZERO serve-path compile events.
"""

from __future__ import annotations

import asyncio
import dataclasses

import pytest

from dynamo_tpu.obs.compile_ledger import (
    WARMUP_MODES,
    BucketSig,
    CompileLedger,
    embed_bucket_ladders,
    enumerate_buckets,
    get_compile_ledger,
    get_compile_metrics,
    install_compile_metrics,
    sig_for_rows,
)
from dynamo_tpu.utils.config import EngineConfig
from dynamo_tpu.utils.logging import TraceContext
from dynamo_tpu.utils.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def clean_ledger():
    """Isolate the process-global singleton: fresh events/plan and a fresh
    metrics registry per test (counters are monotonic; rebinding gives each
    test zeroed series without touching other suites' totals)."""
    led = get_compile_ledger()
    led.reset()
    led.configure("lazy")
    install_compile_metrics(MetricsRegistry())
    yield led
    led.reset()
    led.configure("lazy")


def sig(kind="decode", b=4, t=1, nblk=8, greedy=True, kv="bfloat16"):
    return BucketSig(kind, b, t, nblk, greedy, kv)


# ---------------------------------------------------------------------------
# Event schema & recording
# ---------------------------------------------------------------------------

def test_event_schema_and_victim_attribution(clean_ledger):
    led = clean_ledger
    ctx = TraceContext.new()
    ev = led.record(sig(kind="mixed", t=64), 1.25, trace_ctx=ctx,
                    ts=1000.0)
    assert ev is not None
    d = ev.to_dict()
    assert d["kind"] == "mixed" and d["b"] == 4 and d["t"] == 64
    assert d["nblk"] == 8 and d["greedy"] is True
    assert d["kv_dtype"] == "bfloat16" and d["source"] == "serve"
    assert d["seconds"] == 1.25
    assert d["trace_id"] == ctx.trace_id
    # the event's start is the trigger: end minus the compile wall
    assert d["ts"] == pytest.approx(1000.0 - 1.25)
    assert led.inventory == {sig(kind="mixed", t=64)}
    # untraced warmup event: no trace_id key at all
    ev2 = led.record(sig(), 0.5, source="warmup")
    assert "trace_id" not in ev2.to_dict()


def test_serve_event_emits_span_warmup_does_not(clean_ledger):
    from dynamo_tpu.obs.tracer import get_tracer

    ctx = TraceContext.new()
    clean_ledger.record(sig(kind="decode"), 2.0, trace_ctx=ctx)
    clean_ledger.record(sig(kind="mixed", t=32), 2.0, trace_ctx=ctx,
                        source="warmup")
    spans = [s for s in get_tracer().recorder.spans_for(ctx.trace_id)
             if s.name == "engine.compile"]
    assert len(spans) == 1  # serve yes, warmup no
    s = spans[0]
    assert s.attrs["kind"] == "decode" and s.attrs["b"] == 4
    assert s.attrs["seconds"] == pytest.approx(2.0)
    assert s.end - s.start == pytest.approx(2.0)


def test_disabled_mode_records_nothing(clean_ledger):
    led = clean_ledger
    led.configure("off")
    assert led.enabled is False
    assert led.record(sig(), 1.0) is None
    assert led.events == [] and led.inventory == set()
    m = get_compile_metrics()
    assert m.events.get(kind="decode", source="serve") == 0.0
    with pytest.raises(ValueError):
        led.configure("sometimes")
    assert set(WARMUP_MODES) == {"off", "lazy", "full"}


def test_event_cap_keeps_counters_exact():
    led = CompileLedger(cap=3)
    for i in range(5):
        led.record(sig(nblk=4 * (i + 1)), 0.1)
    assert len(led.events) == 3              # detail rolls at the cap...
    snap = led.snapshot()
    assert snap["events_total"] == 5         # ...counters stay exact
    assert snap["cache_entries"] == 5


def test_coverage_math_and_snapshot(clean_ledger):
    led = clean_ledger
    assert led.coverage() == 0.0             # no plan → conservative 0
    plan = [sig(nblk=n) for n in (4, 8, 16, 32)]
    led.set_plan(plan)
    assert led.coverage() == 0.0
    led.record(plan[0], 0.2, source="warmup")
    led.record(plan[1], 0.3)
    led.record(sig(kind="embed", t=64), 0.4)  # off-plan: no coverage credit
    assert led.coverage() == pytest.approx(0.5)
    snap = led.snapshot()
    assert snap["mode"] == "lazy" and snap["enabled"] is True
    assert snap["cache_entries"] == 3 and snap["events_total"] == 3
    assert snap["warmup_buckets"] == 4
    assert snap["warmup_coverage"] == pytest.approx(0.5)
    assert snap["compile_seconds_total"] == pytest.approx(0.9)
    assert snap["serve_stall_seconds"] == pytest.approx(0.7)  # warmup excluded
    m = get_compile_metrics()
    assert m.warmup_coverage.get() == pytest.approx(0.5)
    assert m.stall_seconds.get() == pytest.approx(0.7)
    assert m.events.get(kind="decode", source="warmup") == 1.0


def test_by_bucket_totals(clean_ledger):
    led = clean_ledger
    led.record(sig(), 1.0)
    led.record(sig(), 0.5)
    led.record(sig(kind="mixed", t=16), 2.0)
    bb = led.by_bucket()
    assert bb[sig()] == (2, 1.5)
    assert bb[sig(kind="mixed", t=16)] == (1, 2.0)


# ---------------------------------------------------------------------------
# Bucket lattice — pinned against hand-computed dispatch geometry
# ---------------------------------------------------------------------------

def tiny_ec(**kw) -> EngineConfig:
    defaults = dict(model="tiny-llama", max_model_len=128, block_size=16,
                    max_batch_size=4, decode_bucket=(2, 4), prefill_chunk=32,
                    num_blocks=64)
    defaults.update(kw)
    return EngineConfig(**defaults)


def test_enumerate_tiny_config_hand_computed():
    """max_model_len=128/block=16 → max_nblk=8 → nblk ladder {4, 8}.
    decode b ∈ {2, 4} (ladder covers max_batch_size); prefill-carrying
    rungs enumerate as "mixed" over the DECODE b ladder (the batch carries
    decode rows too), t ∈ {16, 32}; ×2 greedy variants, no spec:
    decode 2×2×2=8, mixed 2×2×2×2=16 → 24."""
    sigs = enumerate_buckets(tiny_ec())
    assert len(sigs) == len(set(sigs)) == 24
    kinds = {}
    for s in sigs:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    assert kinds == {"decode": 8, "mixed": 16}
    assert {s.b for s in sigs if s.kind == "decode"} == {2, 4}
    assert {s.nblk for s in sigs} == {4, 8}
    assert {s.t for s in sigs if s.kind == "mixed"} == {16, 32}
    assert {s.b for s in sigs if s.kind == "mixed"} == {2, 4}
    assert BucketSig("decode", 2, 1, 8, True, "bfloat16") in sigs
    assert BucketSig("mixed", 4, 32, 4, False, "bfloat16") in sigs


@pytest.mark.parametrize("attn_impl, widths, want", [
    ("dense", 8, {"decode": 64, "mixed": 384}),
    ("auto", 8, {"decode": 64, "mixed": 384}),      # unresolved: the superset
    ("pallas", 1, {"decode": 8, "mixed": 48}),
    ("pallas_interpret", 1, {"decode": 8, "mixed": 48}),
])
def test_enumerate_default_config_size(attn_impl, widths, want):
    """Default EngineConfig: max_nblk=-(-8192//16)=512. decode b: ladder
    (1,2,4,8,...) through max_batch_size → 4 rungs ≤ 64. Prefill rungs are
    "mixed" over the same 4-rung decode b ladder × t ladder {16..512} (6
    rungs) × 2 greedy. Under the dense gather × the nblk ladder
    {4,8,...,256,512} (8 rungs): 64 + 384 = 448. Under the kernel, which
    walks a row's live blocks whatever the table's width, × the one width
    512: 8 + 48 = 56, what ``--warmup-mode full`` compiles on a TPU."""
    ec = EngineConfig(model="tiny-llama", attn_impl=attn_impl)
    sigs = enumerate_buckets(ec)
    kinds = {}
    for s in sigs:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    assert kinds == want
    assert len(sigs) == {8: 448, 1: 56}[widths]
    assert len({s.nblk for s in sigs}) == widths and max(
        s.nblk for s in sigs) == 512


def test_the_wide_table_fits_smem_at_every_batch_bucket():
    """One width means every step program prefetches a ``[b, max_nblk]``
    table into SMEM: 16 KB at 8 rows, 128 KB at 64, of the ~1 MiB usable
    (what ModelRunner._check_kernel_fits checks at construction)."""
    from dynamo_tpu.ops.paged_attention import (
        SMEM_USABLE_BYTES,
        scalar_prefetch_bytes,
    )

    ec = EngineConfig(model="tiny-llama", attn_impl="pallas")
    sigs = enumerate_buckets(ec)
    (nblk,) = {s.nblk for s in sigs}
    table = {b: scalar_prefetch_bytes(batch=b, nblk=nblk) - 3 * 512 - 512
             for b in sorted({s.b for s in sigs})}
    assert table == {8: 16 << 10, 16: 32 << 10, 32: 64 << 10, 64: 128 << 10}
    assert scalar_prefetch_bytes(batch=64, nblk=nblk) < SMEM_USABLE_BYTES // 7


def test_enumerate_spec_variants():
    ec = tiny_ec(max_batch_size=8, decode_bucket=(4, 8), prefill_chunk=64,
                 spec_ngram=3, spec_k=4)
    sigs = enumerate_buckets(ec)
    kinds = {}
    for s in sigs:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    # verify t ladder for k=4: min(pow2(t,2,5),5) over t∈1..5 → {2,4,5}
    assert {s.t for s in sigs if s.kind == "verify"} == {2, 4, 5}
    assert all(s.greedy for s in sigs if s.kind == "verify")
    # decode 2b×2nblk×2g=8, mixed (decode b ladder) 2b×3t×2nblk×2g=24
    # with t∈{16,32,64}
    assert kinds == {"decode": 8, "mixed": 24, "verify": 12}
    assert len(sigs) == 44


def test_enumerate_excludes_embed_but_ladders_exported():
    ec = tiny_ec()
    assert not any(s.kind == "embed" for s in enumerate_buckets(ec))
    bs, ts = embed_bucket_ladders(ec)
    assert 16 in ts and ts[-1] >= ec.max_model_len


def test_kv_dtype_threads_into_sigs():
    sigs = enumerate_buckets(tiny_ec(kv_dtype="int8"))
    assert {s.kv_dtype for s in sigs} == {"int8"}


def test_sig_for_rows_lands_inside_enumeration():
    """Every geometry a serving batch can present must map to a sig the
    warmup plan contains — otherwise full warmup leaves reachable holes."""
    ec = tiny_ec(spec_ngram=3, spec_k=4)
    plan = set(enumerate_buckets(ec))
    for n in range(1, ec.max_batch_size + 1):
        for need in (1, 3, 8):
            for g in (True, False):
                assert sig_for_rows("decode", n, 1, need, ec, g) in plan
    for n in (1, 2, 4):
        for t in (1, 7, 16, 30, 32):
            for need in (1, 5, 8):
                # Prefill-carrying batches dispatch as "mixed"; t_max==1
                # degenerates to the decode program.
                assert sig_for_rows("mixed", n, t, need, ec, True) in plan
    for n in range(1, ec.max_batch_size + 1):
        for t in (1, 2, 3, 5):
            assert sig_for_rows("verify", n, t, 4, ec) in plan


@pytest.mark.parametrize("kind, n, t, need", [
    ("decode", 3, 1, 1), ("decode", 3, 1, 5), ("decode", 1, 1, 8),
    ("mixed", 3, 20, 1), ("mixed", 2, 32, 8), ("verify", 2, 3, 4),
])
def test_sig_for_rows_has_one_table_width_under_the_kernel(kind, n, t, need):
    """Where attention is the kernel (the name EngineCore resolved into its
    config), the block need picks nothing: the table is ``max_nblk`` wide,
    and (kind, b, t) are what the gather's signature has."""
    kernel = sig_for_rows(kind, n, t, need, tiny_ec(attn_impl="pallas"))
    gather = sig_for_rows(kind, n, t, need, tiny_ec(attn_impl="dense"))
    assert kernel.nblk == 8 and gather.nblk == (4 if need <= 4 else 8)
    assert kernel == dataclasses.replace(gather, nblk=8)
    assert kernel in set(enumerate_buckets(
        tiny_ec(attn_impl="pallas", spec_ngram=3, spec_k=4)))


def test_engine_writes_the_resolved_attention_into_its_config(clean_ledger):
    """"auto" leaves nothing behind (as prefill_chunk's 0 does not): the
    engine's config names the implementation that runs, so sig_for_rows,
    the plan and dispatch() agree on the table's width. On the CPU that is
    the gather and its ladder; asked for the kernel, one width."""
    from dynamo_tpu.engine.engine import EngineCore

    kw = dict(model="tiny-llama", block_size=16, num_blocks=16,
              max_batch_size=2, max_model_len=128, prefill_chunk=16,
              decode_bucket=(2,), allow_random_weights=True)
    core = EngineCore(EngineConfig(**kw))
    assert core.engine_cfg.attn_impl == core.runner.attn_impl == "dense"
    assert {s.nblk for s in get_compile_ledger().plan} == {4, 8}
    core = EngineCore(EngineConfig(attn_impl="pallas_interpret", **kw))
    assert core.engine_cfg.attn_impl == "pallas_interpret"
    assert core.runner.engine_cfg is core.engine_cfg
    assert {s.nblk for s in get_compile_ledger().plan} == {8}
    assert core.runner._widest_bucket().nblk == 8


def test_sig_for_rows_matches_hand_computed_dispatch():
    ec = tiny_ec()
    # decode: b=_bucket(3,(2,4))=4, nblk=min(pow2(5,4,8),8)=8
    assert sig_for_rows("decode", 3, 1, 5, ec) == \
        BucketSig("decode", 4, 1, 8, True, "bfloat16")
    # mixed: b over the DECODE ladder (2,4) → 3→4; t=pow2(20,16,32)=32;
    # need 1→nblk 4
    assert sig_for_rows("mixed", 3, 20, 1, ec) == \
        BucketSig("mixed", 4, 32, 4, True, "bfloat16")
    # degenerate mixed (every live row one token) IS the decode program
    assert sig_for_rows("mixed", 3, 1, 5, ec) == \
        BucketSig("decode", 4, 1, 8, True, "bfloat16")


# ---------------------------------------------------------------------------
# Metrics plumbing
# ---------------------------------------------------------------------------

def test_metrics_family_on_scrape(clean_ledger):
    reg = MetricsRegistry()
    install_compile_metrics(reg)
    clean_ledger.set_plan([sig()])
    clean_ledger.record(sig(), 0.3, source="serve")
    text = reg.expose()
    for name in ("dynamo_xla_compile_events_total",
                 "dynamo_xla_compile_seconds",
                 "dynamo_xla_compile_cache_entries",
                 "dynamo_xla_compile_stall_seconds_total",
                 "dynamo_xla_compile_warmup_coverage",
                 "dynamo_xla_compile_warmup_buckets"):
        assert name in text, name
    clean_ledger.mark_inflight(True)
    assert get_compile_metrics().inflight.get() == 1.0
    clean_ledger.mark_inflight(False)
    assert get_compile_metrics().inflight.get() == 0.0


# ---------------------------------------------------------------------------
# Mocker mirror (device-free dispatch mirror + simulated stalls)
# ---------------------------------------------------------------------------

def _mock_args(**kw):
    from dynamo_tpu.mocker.engine import MockEngineArgs

    defaults = dict(block_size=4, speedup_ratio=1000.0, max_model_len=256,
                    num_blocks=128, compile_s=0.5)
    defaults.update(kw)
    return MockEngineArgs(**defaults)


async def _gen_mock(engine, ntok=24, max_tokens=4, base=5):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    toks = []
    async for out in engine.generate(PreprocessedRequest(
            token_ids=list(range(base, base + ntok)),
            stop_conditions=StopConditions(max_tokens=max_tokens,
                                           ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))):
        toks.extend(out.token_ids)
    return toks


def _run_mock(engine, ntok=24, max_tokens=4):
    # One asyncio.run per engine lifetime: the mocker's step loop binds to
    # the event loop of its first generate.
    return asyncio.run(_gen_mock(engine, ntok, max_tokens))


def test_mocker_lazy_records_serve_compiles(clean_ledger):
    from dynamo_tpu.mocker.engine import MockEngine

    eng = MockEngine(_mock_args(warmup_mode="lazy"))
    led = get_compile_ledger()
    assert led.plan, "mocker must enumerate its lattice"

    async def two_same_geometry():
        await _gen_mock(eng, base=5)
        n = len(led.events)
        # Same geometry, different tokens (identical tokens would hit the
        # mocker's prefix cache, shrinking the prefill into a DIFFERENT —
        # genuinely cold — bucket): the warm cache absorbs this one.
        await _gen_mock(eng, base=500)
        return n

    n = asyncio.run(two_same_geometry())
    assert len(led.events) == n
    kinds = {e.sig.kind for e in led.events}
    assert kinds == {"mixed", "decode"}
    assert all(e.source == "serve" for e in led.events)
    assert eng.stats()["compile"]["events_total"] == n


def test_mocker_full_warmup_prevents_serve_compiles(clean_ledger):
    from dynamo_tpu.mocker.engine import MockEngine

    eng = MockEngine(_mock_args(warmup_mode="full"))
    summary = eng.warmup()
    led = get_compile_ledger()
    assert summary["coverage"] == 1.0
    assert led.inventory >= led.plan
    assert all(e.source == "warmup" for e in led.events)
    n = len(led.events)
    _run_mock(eng)
    serve = [e for e in led.events[n:] if e.source == "serve"]
    assert serve == []  # the acceptance invariant, mirrored device-free


def test_mocker_off_mode_is_silent(clean_ledger):
    from dynamo_tpu.mocker.engine import MockEngine

    eng = MockEngine(_mock_args(warmup_mode="off"))
    led = get_compile_ledger()
    _run_mock(eng)
    assert led.events == []
    assert "compile" not in eng.stats()


def test_mocker_sig_mirror_matches_ledger_module(clean_ledger):
    """The mocker feeds sig_for_rows with its real dispatch geometry; the
    recorded mixed sig (the prompt's chunk dispatches as one ragged mixed
    step) must equal the hand-computed one."""
    from dynamo_tpu.mocker.engine import MockEngine

    eng = MockEngine(_mock_args(warmup_mode="lazy"))
    led = get_compile_ledger()
    _run_mock(eng, ntok=24, max_tokens=2)
    mixed = [e.sig for e in led.events if e.sig.kind == "mixed"]
    assert mixed == [sig_for_rows("mixed", 1, 24, 6, eng._lattice_cfg)]
    assert {e.sig.kind for e in led.events} <= {"decode", "mixed"}


# ---------------------------------------------------------------------------
# Real engine: the tentpole acceptance check on a minuscule lattice
# ---------------------------------------------------------------------------

def test_real_engine_full_warmup_zero_serve_compiles(clean_ledger):
    """EngineCore with warmup_mode=full on a 4-sig lattice: warmup mints
    the whole enumeration, then a served request (mixed prefill+decode
    geometry) triggers ZERO serve-path compiles and coverage stays 1.0."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    ec = EngineConfig(model="tiny-llama", block_size=16, num_blocks=8,
                      max_batch_size=1, max_model_len=32, prefill_chunk=16,
                      decode_bucket=(1,), warmup_mode="full",
                      allow_random_weights=True)
    assert len(enumerate_buckets(ec)) == 4  # keep this test cheap
    core = EngineCore(ec)
    led = get_compile_ledger()
    summary = core.warmup()
    assert summary["mode"] == "full"
    assert summary["coverage"] == 1.0
    assert summary["failed"] == 0
    assert led.inventory == led.plan  # cache inventory == enumeration
    n_events = len(led.events)
    assert all(e.source == "warmup" for e in led.events)

    core.add_request(PreprocessedRequest(
        token_ids=[10, 11, 12, 13, 14],
        stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0)))
    for _ in range(100):
        if not core.has_work():
            break
        core.step()
    serve = [e for e in led.events[n_events:] if e.source == "serve"]
    assert serve == [], [e.sig for e in serve]
    assert led.coverage() == 1.0


def test_real_engine_lazy_records_victim_spans(clean_ledger):
    """Lazy mode: the first request pays the compiles, the ledger attributes
    them to its trace, and engine.compile spans land in the recorder."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.obs.tracer import TRACE_KEY, get_tracer
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    ec = EngineConfig(model="tiny-llama", block_size=16, num_blocks=8,
                      max_batch_size=1, max_model_len=32, prefill_chunk=16,
                      decode_bucket=(1,), warmup_mode="lazy",
                      allow_random_weights=True)
    core = EngineCore(ec)
    led = get_compile_ledger()
    ctx = TraceContext.new()
    core.add_request(PreprocessedRequest(
        token_ids=[10, 11, 12, 13, 14],
        stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        annotations={TRACE_KEY: ctx.header()}))
    for _ in range(100):
        if not core.has_work():
            break
        core.step()
    serve = [e for e in led.events if e.source == "serve"]
    assert {e.sig.kind for e in serve} == {"mixed", "decode"}
    assert all(e.trace_id == ctx.trace_id for e in serve)
    assert all(e.seconds > 0 for e in serve)
    spans = [s for s in get_tracer().recorder.spans_for(ctx.trace_id)
             if s.name == "engine.compile"]
    assert len(spans) == len(serve)
    assert led.snapshot()["serve_stall_seconds"] > 0
