"""Scheduler goodput & interference plane: ledger, HOL attribution, wiring.

The load-bearing invariant is that ``step_geometry`` (obs/sched_ledger.py)
prices the SAME padded program the engine's dispatch() compiled — the
geometry tests below pin live and scheduled aggregates against
hand-computed bucket math, so goodput is a pure FLOPs ratio a reviewer can
recompute. The real-engine test is the tentpole acceptance check: a long
prompt admitted over a live decode stream files ``engine.hol_stall``
victim spans carrying the culprit request id.
"""

from __future__ import annotations

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from dynamo_tpu.obs.sched_ledger import (
    BLOCK_CAUSES,
    PREEMPT_CAUSES,
    SCHED_ENV,
    HolStall,
    SchedLedger,
    get_sched_ledger,
    get_sched_metrics,
    hol_span_culprits,
    install_sched_metrics,
    sched_enabled,
    step_counts,
    step_geometry,
)
from dynamo_tpu.obs.compile_ledger import BucketSig, sig_for_rows
from dynamo_tpu.utils.config import EngineConfig
from dynamo_tpu.utils.logging import TraceContext
from dynamo_tpu.utils.metrics import (
    MetricsRegistry,
    metric_sum,
    parse_prometheus,
)


@pytest.fixture(autouse=True)
def clean_ledger():
    """Isolate the process-global singleton: fresh totals and a fresh
    metrics registry per test. Teardown forces enabled=True (not an env
    re-read: a monkeypatched DYN_SCHED_LEDGER may still be set when this
    finalizer runs)."""
    led = get_sched_ledger()
    led.reset()
    led.configure(True)
    install_sched_metrics(MetricsRegistry())
    yield led
    led.reset()
    led.configure(True)


def _req(tokens, max_tokens=4, rid=None, **annotations):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    kw = {"request_id": rid} if rid is not None else {}
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        annotations=annotations or None, **kw)


# ---------------------------------------------------------------------------
# Env gate & token-ratio goodput
# ---------------------------------------------------------------------------

def test_env_gate(monkeypatch):
    monkeypatch.delenv(SCHED_ENV, raising=False)
    assert sched_enabled() is True
    assert sched_enabled(default=False) is False
    for off in ("0", "false", "no", "off"):
        monkeypatch.setenv(SCHED_ENV, off)
        assert sched_enabled() is False
    monkeypatch.setenv(SCHED_ENV, "1")
    assert sched_enabled() is True


def test_token_ratio_goodput_and_snapshot():
    led = SchedLedger()
    rec = led.record_step(wall_s=0.01, kinds=("decode",), decode_rows=3,
                          live_tokens=3, sched_tokens=4)
    assert rec is not None
    # no FLOPs given → token-ratio fallback: 3 live over 4 padded rows
    assert rec.goodput == pytest.approx(0.75)
    snap = led.snapshot(steps=True)
    assert snap["steps_total"] == 1
    assert snap["goodput_fraction"] == pytest.approx(0.75)
    assert snap["live_tokens_total"] == 3
    assert snap["sched_tokens_total"] == 4
    assert snap["goodput_mean_recent"] == pytest.approx(0.75)
    assert snap["steps"][0]["kinds"] == ["decode"]
    # FLOPs take precedence over the token ratio when present; capped at 1
    r2 = led.record_step(wall_s=0.01, kinds=("decode",), live_tokens=1,
                         sched_tokens=4, live_flops=9.0, sched_flops=10.0)
    assert r2.goodput == pytest.approx(0.9)
    r3 = led.record_step(wall_s=0.01, kinds=("decode",), live_tokens=8,
                         sched_tokens=4)
    assert r3.goodput == 1.0


# ---------------------------------------------------------------------------
# step_geometry — prices the dispatched signature, hand-computed here
# ---------------------------------------------------------------------------

def tiny_ec(**kw) -> EngineConfig:
    defaults = dict(model="tiny-llama", max_model_len=128, block_size=16,
                    max_batch_size=4, decode_bucket=(2, 4), prefill_chunk=32,
                    num_blocks=64)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _cost(model_cfg, ec, *, tokens, logit_rows, attn_q_ctx, kv_blocks):
    """One program's work priced as the step's one count is
    (obs/costmodel.py step_work): ``attn_q_ctx`` the (query, key) pairs and
    ``kv_blocks`` the blocks walked, both over all the layers."""
    from dynamo_tpu.obs import costmodel as cm

    return cm.step_work(
        cm.step_shapes(model_cfg, block_size=ec.block_size),
        {"programs": 1, "live_tokens": tokens, "logit_rows": logit_rows,
         "attn_q_ctx": attn_q_ctx, "kv_blocks_walked": kv_blocks})


def test_step_geometry_decode_hand_computed():
    """3 decode rows at contexts 1/17/31 (block=16): live attn sees the
    keys up to each row's own (1+17+31 pairs a layer) and walks the real
    block tables (1+2+2 blocks a layer); the padded program is b=4
    (bucket of 3 in (2,4)), nblk=4 (pow2 of need 2, floor 4), and the
    gather pays for every entry of its tables. Two layers."""
    from dynamo_tpu.models.config import resolve_model_config

    ec = tiny_ec()
    mc = resolve_model_config("tiny-llama")
    rows = [(None, 0, 1), (None, 16, 1), (None, 30, 1)]
    toks = np.zeros(3, dtype=np.int32)
    sig = sig_for_rows("decode", 3, 1, 2, ec)
    assert sig == BucketSig("decode", 4, 1, 4, True, "bfloat16")
    g = step_geometry(mc, ec, [(sig, rows, [True] * 3, toks, None)],
                      dec_rows=3)
    assert g["kinds"] == ("decode",)
    assert g["prefill_rows"] == 0 and g["decode_rows"] == 3
    assert g["live_tokens"] == 3 and g["sched_tokens"] == 4
    c = step_counts([(sig, rows, [True] * 3, toks, None)], ec.block_size,
                    [0, 0], dec_rows=3)
    assert c["logit_rows"] == 3 and c["attn_q_ctx"] == 2 * (1 + 17 + 31)
    assert g["kv_blocks_live"] == 5 and g["kv_blocks_walked"] == 2 * 5
    live = _cost(mc, ec, tokens=3, logit_rows=3,
                 attn_q_ctx=2 * (1 + 17 + 31), kv_blocks=2 * 5)
    sched = _cost(mc, ec, tokens=4, logit_rows=4,
                  attn_q_ctx=2 * 4 * 1 * 4 * 16, kv_blocks=2 * 16)
    assert g["live_flops"] == pytest.approx(live.flops)
    assert g["sched_flops"] == pytest.approx(sched.flops)
    assert g["live_bytes"] == pytest.approx(live.hbm_bytes)
    assert g["sched_bytes"] == pytest.approx(sched.hbm_bytes)
    led = SchedLedger()
    rec = led.record_step(wall_s=0.01, **g)
    assert rec.goodput == pytest.approx(
        min(live.flops / sched.flops, 1.0))
    assert 0.0 < rec.goodput < 1.0


def test_step_geometry_chunk_hand_computed():
    """One 20-token chunk: live prices 20 ragged tokens against 2 real
    blocks; the padded program is the mixed one at b=2 (bucket of 1 in
    (2,4)), t=pow2(20,16,32)=32, nblk=4, whose dense layers compute
    N = min(2*32, 32+2) = 34 tokens and whose attention sees 2 x 32."""
    from dynamo_tpu.models.config import resolve_model_config

    ec = tiny_ec()
    mc = resolve_model_config("tiny-llama")
    rows = [(None, 0, 20)]
    toks = np.zeros(2, dtype=np.int32)
    sig = sig_for_rows("mixed", 1, 20, 2, ec)
    assert sig == BucketSig("mixed", 2, 32, 4, True, "bfloat16")
    g = step_geometry(mc, ec, [(sig, rows, [True], toks, None)])
    assert g["kinds"] == ("mixed",)
    assert g["prefill_rows"] == 1 and g["decode_rows"] == 0
    assert g["live_tokens"] == 20 and g["sched_tokens"] == 34
    assert g["rect_tokens"] == 64
    # query p of the chunk sees p + 1 keys: 20 x 21 / 2 pairs a layer
    live = _cost(mc, ec, tokens=20, logit_rows=1,
                 attn_q_ctx=2 * 210, kv_blocks=2 * 2)
    sched = _cost(mc, ec, tokens=34, logit_rows=2,
                  attn_q_ctx=2 * 2 * 32 * 4 * 16, kv_blocks=2 * 8)
    assert g["live_flops"] == pytest.approx(live.flops)
    assert g["sched_flops"] == pytest.approx(sched.flops)
    # a decode row beside the chunk: the same program, one more live token
    mixed = step_geometry(mc, ec, [
        (sig_for_rows("mixed", 2, 20, 2, ec), [(None, 0, 1)] + rows,
         [True, True], toks, None)], dec_rows=1)
    assert mixed["kinds"] == ("mixed",)
    assert mixed["prefill_rows"] == 1 and mixed["decode_rows"] == 1
    assert mixed["live_tokens"] == 21 and mixed["sched_tokens"] == 34
    assert mixed["live_flops"] > g["live_flops"]
    # Under the paged kernel attention is handed the packed step's 34
    # tokens, not the 2 x 32 rectangle; with the rows split over "data" it
    # keeps the rectangle (obs/compile_ledger.py attends_tokens).
    for kw, handed in ((dict(attn_impl="pallas_interpret"), 34),
                       (dict(attn_impl="pallas_interpret", tp=2), 34),
                       (dict(attn_impl="pallas_interpret", dp=2), 64)):
        kernel_ec = tiny_ec(**kw)
        ksig = sig_for_rows("mixed", 1, 20, 2, kernel_ec)
        assert (ksig.b, ksig.t, ksig.n) == (2, 32, 34)
        assert step_geometry(mc, kernel_ec, [
            (ksig, rows, [True], toks, None)])["rect_tokens"] == handed


# ---------------------------------------------------------------------------
# Block / preempt accumulators flush into the next step record
# ---------------------------------------------------------------------------

def test_block_and_preempt_flush(clean_ledger):
    led = clean_ledger
    assert set(BLOCK_CAUSES) == {"no_free_blocks", "batch_full", "wdrr_gate"}
    assert set(PREEMPT_CAUSES) == {"blocks", "qos"}
    led.record_block("batch_full")
    led.record_block("batch_full")
    led.record_block("no_free_blocks")
    led.record_preempt(37, cause="qos")
    led.record_preempt(5)  # default cause: blocks
    rec = led.record_step(wall_s=0.01, kinds=("decode",), live_tokens=1,
                          sched_tokens=2)
    assert rec.blocked == {"batch_full": 2, "no_free_blocks": 1}
    assert rec.preempt == {"qos": 37, "blocks": 5}
    d = rec.to_dict()
    assert d["blocked"] == rec.blocked
    assert d["preempt_recompute_tokens"] == rec.preempt
    # accumulators drained: the next step starts clean; totals persist
    rec2 = led.record_step(wall_s=0.01, kinds=("decode",), live_tokens=1,
                           sched_tokens=2)
    assert rec2.blocked == {} and rec2.preempt == {}
    snap = led.snapshot()
    assert snap["admission_blocked"] == {"batch_full": 2,
                                         "no_free_blocks": 1}
    assert snap["preempt_recompute_tokens"] == {"qos": 37, "blocks": 5}
    m = get_sched_metrics()
    assert m.admission_blocked.get(cause="batch_full") == 2.0
    assert m.preempt_recompute.get(cause="qos") == 37.0


# ---------------------------------------------------------------------------
# HOL attribution: retro victim spans, histogram, culprit table
# ---------------------------------------------------------------------------

def test_hol_victim_spans_and_metrics(clean_ledger):
    from dynamo_tpu.obs.tracer import get_tracer

    led = clean_ledger
    reg = MetricsRegistry()
    install_sched_metrics(reg)
    ctx = TraceContext.new()
    victims = [(ctx, "victim-1", "interactive"), (None, "victim-2", "batch")]
    rec = led.record_step(
        wall_s=0.05, kinds=("decode", "prefill"), prefill_rows=1,
        decode_rows=2, live_tokens=34, sched_tokens=36,
        hol=HolStall(culprit="culprit-1", culprit_tokens=64,
                     victims=victims),
        ts=100.0)
    assert rec.hol_culprit == "culprit-1"
    assert rec.hol_victims == 2
    assert rec.interference_row_s == pytest.approx(0.1)
    assert rec.to_dict()["hol"] == {
        "culprit": "culprit-1", "victims": 2, "stall_s": 0.05,
        "row_seconds": 0.1}
    # only the traced victim gets a retroactive span, in its OWN trace
    spans = [s for s in get_tracer().recorder.spans_for(ctx.trace_id)
             if s.name == "engine.hol_stall"]
    assert len(spans) == 1
    s = spans[0]
    assert s.attrs["culprit"] == "culprit-1"
    assert s.attrs["culprit_tokens"] == 64
    assert s.attrs["request_id"] == "victim-1"
    assert s.attrs["qos_class"] == "interactive"
    assert s.start == pytest.approx(99.95) and s.end == pytest.approx(100.0)
    # both victims count in the histogram, labelled by their own class
    rollup = parse_prometheus(reg.expose())
    assert metric_sum(rollup, "dynamo_sched_hol_stall_seconds_count") == 2.0
    assert ("dynamo_sched_hol_stall_seconds_count",
            frozenset({("qos_class", "batch")})) in rollup
    snap = led.snapshot()
    assert snap["hol_victims_total"] == 2
    assert snap["hol_stall_seconds_total"] == pytest.approx(0.1)
    assert snap["interference_row_seconds_total"] == pytest.approx(0.1)
    assert led.top_culprits()[0] == {"request_id": "culprit-1",
                                     "stall_seconds": 0.1, "victims": 2}
    # span-side aggregation (the frontend's cross-process view)
    agg = [c for c in hol_span_culprits(get_tracer().recorder)
           if c["request_id"] == "culprit-1"]
    assert agg and agg[0]["victims"] >= 1


def test_disabled_mode_records_nothing(clean_ledger):
    led = clean_ledger
    led.configure(False)
    assert led.record_step(wall_s=1.0, kinds=("decode",), live_tokens=1,
                           sched_tokens=8) is None
    led.record_block("batch_full")
    led.record_preempt(100)
    assert led.steps_total == 0
    assert led.blocked_totals == {} and led.preempt_totals == {}
    snap = led.snapshot()
    assert snap["enabled"] is False and snap["goodput_fraction"] == 1.0


# ---------------------------------------------------------------------------
# Scheduler wiring: admission-block causes & preemption accounting
# ---------------------------------------------------------------------------

def _sched(pool, **kw):
    from dynamo_tpu.engine.scheduler import Scheduler

    defaults = dict(max_batch_size=4, prefill_chunk=16, max_model_len=64)
    defaults.update(kw)
    return Scheduler(pool, **defaults)


def _seq(ntok, block_size=16, **req_kw):
    from dynamo_tpu.engine.scheduler import Seq

    return Seq(req=_req(range(ntok), **req_kw), block_size=block_size)


def test_scheduler_batch_full_cause(clean_ledger):
    from dynamo_tpu.engine.prefix_pool import PrefixPool

    led = clean_ledger
    sched = _sched(PrefixPool(16, 16), max_batch_size=1)
    sched.add(_seq(17))
    sched.add(_seq(17, rid="second"))
    plan = sched.plan()
    assert plan.prefill and len(sched.running) == 1
    assert led.blocked_totals.get("batch_full", 0) >= 1
    assert "no_free_blocks" not in led.blocked_totals


def test_scheduler_counts_the_slots_in_use():
    """A running sequence holds a slot, a waiting one does not, and a
    finished one gives its slot back (``stats()["ssm"]["slots_in_use"]``)."""
    from dynamo_tpu.engine.prefix_pool import PrefixPool
    from dynamo_tpu.protocols.common import FinishReason

    sched = _sched(PrefixPool(16, 16), max_batch_size=2)
    assert sched.slots_in_use == 0
    for rid in ("first", "second", "third"):
        sched.add(_seq(17, rid=rid))
    sched.plan()
    assert sched.slots_in_use == len(sched.running) == 2
    sched.finish(sched.running[0], FinishReason.LENGTH)
    assert sched.slots_in_use == 1
    sched.plan()
    assert sched.slots_in_use == 2 and not sched.waiting


def test_scheduler_no_free_blocks_and_wdrr_causes(clean_ledger):
    from dynamo_tpu.engine.prefix_pool import PrefixPool
    from dynamo_tpu.qos.deadline import PRIORITY_KEY

    led = clean_ledger
    # 3-block pool: the first 17-token prompt takes 2; the second then
    # needs 2 + 1 running > 1 free → watermark refusal.
    sched = _sched(PrefixPool(3, 16))
    sched.add(_seq(17))
    sched.plan()
    assert led.blocked_totals == {}
    sched.add(_seq(17, rid="starved"))
    # second non-empty WDRR lane behind the blocked head → wdrr_gate too
    sched.add(_seq(17, rid="vip", **{PRIORITY_KEY: "interactive"}))
    sched.plan()
    assert led.blocked_totals.get("no_free_blocks", 0) >= 1
    assert led.blocked_totals.get("wdrr_gate", 0) >= 1


def test_scheduler_preempt_recompute_tokens(clean_ledger):
    from dynamo_tpu.engine.prefix_pool import PrefixPool

    led = clean_ledger
    sched = _sched(PrefixPool(16, 16))
    seq = _seq(17)
    sched.add(seq)
    sched.plan()
    seq.num_computed = 17  # as if the prefill chunk had been finalized
    sched.preempt(seq, cause="qos")
    assert led.preempt_totals == {"qos": 17}
    assert seq.num_computed == 0 and seq in sched.waiting


# ---------------------------------------------------------------------------
# Real engine: mixed prefill/decode run files victim spans (acceptance)
# ---------------------------------------------------------------------------

def test_real_engine_hol_attribution(clean_ledger):
    """A traced decode stream + a 33-token prompt admitted behind it: the
    co-scheduled chunks stall the stream, and its trace gains
    ``engine.hol_stall`` spans naming the long prompt as culprit."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.obs.tracer import TRACE_KEY, get_tracer

    led = clean_ledger
    ec = EngineConfig(model="tiny-llama", block_size=16, num_blocks=32,
                      max_batch_size=2, max_model_len=64, prefill_chunk=16,
                      decode_bucket=(1, 2), allow_random_weights=True)
    core = EngineCore(ec)
    ctx = TraceContext.new()
    core.add_request(_req([10, 11, 12, 13, 14], max_tokens=12,
                          **{TRACE_KEY: ctx.header()}))
    for _ in range(50):
        if any(s.in_decode for s in core.sched.running):
            break
        core.step()
    assert any(s.in_decode for s in core.sched.running)
    core.add_request(_req(range(100, 133), max_tokens=2, rid="long-prompt"))
    for _ in range(300):
        if not core.has_work():
            break
        core.step()
    assert not core.has_work()
    assert led.steps_total > 0
    assert led.hol_victims_total >= 1
    spans = [s for s in get_tracer().recorder.spans_for(ctx.trace_id)
             if s.name == "engine.hol_stall"]
    assert spans, "victim stream must carry hol spans in its own trace"
    assert all(s.attrs["culprit"] == "long-prompt" for s in spans)
    assert all(s.attrs["qos_class"] == "standard" for s in spans)
    assert led.top_culprits()[0]["request_id"] == "long-prompt"
    # goodput under ragged tiny batches: valid fraction, < 1 somewhere
    assert all(0.0 < r.goodput <= 1.0 for r in led.steps)
    assert any(r.goodput < 1.0 for r in led.steps)
    # Unified step (default): the prefill chunks rode mixed launches.
    kinds = {k for r in led.steps for k in r.kinds}
    assert {"mixed", "decode"} <= kinds
    # Measured HOL attribution: each mixed record's stall is the step's
    # own token gap less the decode mean of the victims' row bucket, never
    # more than the gap; nothing is priced.
    mixed_hol = [r for r in led.steps if "mixed" in r.kinds and r.hol_victims]
    assert mixed_hol
    assert all(r.gap_class == "mixed" and r.gap_rows >= r.hol_victims
               and 0.0 <= r.hol_stall_s <= r.gap_s for r in mixed_hol)
    alone = led.gaps_snapshot()["by_class"]["decode"]["1"]
    assert alone["steps"] and sum(alone["rows"]) >= alone["steps"]


def test_real_engine_disabled_is_inert(clean_ledger, monkeypatch):
    from dynamo_tpu.engine.engine import EngineCore

    monkeypatch.setenv(SCHED_ENV, "0")
    led = clean_ledger
    ec = EngineConfig(model="tiny-llama", block_size=16, num_blocks=8,
                      max_batch_size=1, max_model_len=32, prefill_chunk=16,
                      decode_bucket=(1,), allow_random_weights=True)
    core = EngineCore(ec)  # __init__ re-reads the env gate
    assert led.enabled is False
    core.add_request(_req([10, 11, 12, 13, 14], max_tokens=6))
    for _ in range(100):
        if not core.has_work():
            break
        core.step()
    assert led.steps_total == 0
    assert len(led.steps) == 0
    assert led.blocked_totals == {} and led.preempt_totals == {}


# ---------------------------------------------------------------------------
# Mocker mirror: device-free parity for the whole family
# ---------------------------------------------------------------------------

def _mock_args(**kw):
    from dynamo_tpu.mocker.engine import MockEngineArgs

    defaults = dict(block_size=4, speedup_ratio=1000.0, max_model_len=256,
                    num_blocks=128, compile_s=0.0)
    defaults.update(kw)
    return MockEngineArgs(**defaults)


async def _gen_mock(engine, req):
    toks = []
    async for out in engine.generate(req):
        toks.extend(out.token_ids)
    return toks


def test_mocker_sched_parity(clean_ledger):
    from dynamo_tpu.mocker.engine import MockEngine

    led = clean_ledger
    eng = MockEngine(_mock_args())
    asyncio.run(_gen_mock(eng, _req(range(5, 29), max_tokens=4)))
    sched = eng.stats()["sched"]
    assert sched["steps_total"] == led.steps_total > 0
    assert 0.0 < sched["goodput_fraction"] <= 1.0
    assert sched["live_tokens_total"] > 0
    assert sched["sched_tokens_total"] >= sched["live_tokens_total"]
    kinds = {k for r in led.steps for k in r.kinds}
    assert {"mixed", "decode"} <= kinds
    assert kinds <= {"mixed", "decode"}


def test_mocker_disabled_omits_stats_block(clean_ledger, monkeypatch):
    from dynamo_tpu.mocker.engine import MockEngine

    monkeypatch.setenv(SCHED_ENV, "0")
    eng = MockEngine(_mock_args())
    asyncio.run(_gen_mock(eng, _req(range(5, 29), max_tokens=2)))
    assert "sched" not in eng.stats()
    assert clean_ledger.steps_total == 0


async def test_mocker_concurrent_hol_attribution(clean_ledger):
    """e2e mirror of the real-engine acceptance check, device-free: a
    traced long decode stream is stalled by a second request's prefill,
    which names itself as culprit in the victim's span."""
    from dynamo_tpu.mocker.engine import MockEngine
    from dynamo_tpu.obs.tracer import TRACE_KEY, get_tracer

    led = clean_ledger
    eng = MockEngine(_mock_args(speedup_ratio=100.0))
    ctx = TraceContext.new()
    first_token = asyncio.Event()

    async def run_victim():
        async for _ in eng.generate(_req(range(5, 29), max_tokens=100,
                                         rid="victim-a",
                                         **{TRACE_KEY: ctx.header()})):
            first_token.set()

    victim = asyncio.create_task(run_victim())
    await asyncio.wait_for(first_token.wait(), 10)
    # victim-a is now prefilled and decoding: culprit-b's prefill chunk
    # runs while it sits decode-ready
    await _gen_mock(eng, _req(range(200, 232), max_tokens=2,
                              rid="culprit-b"))
    await asyncio.wait_for(victim, 30)
    assert led.hol_victims_total >= 1
    spans = [s for s in get_tracer().recorder.spans_for(ctx.trace_id)
             if s.name == "engine.hol_stall"]
    assert spans
    assert any(s.attrs["culprit"] == "culprit-b" for s in spans)
    assert any(c["request_id"] == "culprit-b" for c in led.top_culprits())
    assert eng.stats()["sched"]["hol_victims_total"] >= 1


# ---------------------------------------------------------------------------
# /debug/sched, metrics re-install, fleet decode_stall SLI
# ---------------------------------------------------------------------------

async def test_debug_sched_endpoint(clean_ledger):
    import aiohttp

    from dynamo_tpu.runtime.status import SystemStatusServer

    clean_ledger.record_block("batch_full")
    clean_ledger.record_step(wall_s=0.01, kinds=("decode",), decode_rows=2,
                             live_tokens=2, sched_tokens=4,
                             queue_depths={"standard": 1})
    srv = SystemStatusServer(MetricsRegistry(), port=0)
    port = await srv.start("127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            d = await (await s.get(
                f"http://127.0.0.1:{port}/debug/sched")).json()
    finally:
        await srv.stop()
    assert d["enabled"] is True and d["env"] == SCHED_ENV
    assert d["goodput_trend"] == [0.5]
    assert d["totals"]["admission_blocked"] == {"batch_full": 1}
    step = d["recent_steps"][-1]
    assert step["goodput"] == 0.5 and step["kinds"] == ["decode"]
    assert step["queue_depths"] == {"standard": 1}
    assert step["blocked"] == {"batch_full": 1}
    assert "top_culprits" in d and "trace_culprits" in d


def test_prefill_chunk_gauge_republishes(clean_ledger):
    """The per-QoS chunk gauge survives a late install: a registry bound
    AFTER the engine resolved its chunks still exposes every class."""
    clean_ledger.set_prefill_chunks(
        {"interactive": 64, "standard": 128, "batch": 512})
    reg = MetricsRegistry()
    install_sched_metrics(reg)
    rollup = parse_prometheus(reg.expose())
    for cls, want in (("interactive", 64), ("standard", 128), ("batch", 512)):
        key = ("dynamo_sched_prefill_chunk_tokens",
               frozenset({("qos_class", cls)}))
        assert rollup.get(key) == float(want)
    assert clean_ledger.snapshot()["prefill_chunk_tokens"] == {
        "interactive": 64, "standard": 128, "batch": 512}


def test_install_republishes_gauges(clean_ledger):
    clean_ledger.record_step(wall_s=0.01, kinds=("decode",), live_tokens=1,
                             sched_tokens=2, budget_util=0.25,
                             queue_depths={"batch": 3})
    # a registry installed AFTER the step still exposes current gauges
    reg = MetricsRegistry()
    install_sched_metrics(reg)
    rollup = parse_prometheus(reg.expose())
    assert metric_sum(rollup, "dynamo_sched_goodput_fraction") == 0.5
    assert metric_sum(
        rollup, "dynamo_sched_token_budget_utilization") == 0.25
    assert ("dynamo_sched_queue_depth",
            frozenset({("qos_class", "batch")})) in rollup


def test_fleet_decode_stall_sli():
    from dynamo_tpu.obs.fleet import (
        DEFAULT_SLO_SPECS,
        FleetAggregator,
        SloEngine,
    )

    spec = next(s for s in DEFAULT_SLO_SPECS if s.name == "decode_stall")
    assert spec.kind == "latency"
    assert spec.histogram == "dynamo_sched_hol_stall_seconds"
    assert spec.threshold_s == 0.5
    rollup = parse_prometheus("\n".join([
        'dynamo_sched_hol_stall_seconds_bucket{qos_class="standard",'
        'le="0.02"} 3',
        'dynamo_sched_hol_stall_seconds_bucket{qos_class="standard",'
        'le="0.5"} 8',
        'dynamo_sched_hol_stall_seconds_bucket{qos_class="standard",'
        'le="+Inf"} 10',
        'dynamo_sched_hol_stall_seconds_count{qos_class="standard"} 10',
    ]) + "\n")
    agg = FleetAggregator(None, registry=MetricsRegistry())
    # good = cumulative count at the smallest bound >= 0.5s
    assert agg._slo_counts(spec, rollup) == (8.0, 10.0)
    eng = SloEngine([spec], registry=MetricsRegistry())
    eng.observe("decode_stall", 0.0, 0.0, t=0.0)
    eng.observe("decode_stall", 8.0, 10.0, t=300.0)
    out = eng.evaluate()
    assert out["decode_stall"]["kind"] == "latency"
    assert out["decode_stall"]["good"] == 8.0
    assert out["decode_stall"]["total"] == 10.0


async def test_mocker_hol_stall_is_the_chunks_marginal_share(clean_ledger):
    """Acceptance mirror, device-free: a chunk co-scheduled with a decoding
    stream is one launch priced at the phase roofline max, and its victim
    is charged the chunk's marginal share of that step — more than nothing,
    less than the step's whole wall."""
    from dynamo_tpu.mocker.engine import MockEngine

    led = clean_ledger
    eng = MockEngine(_mock_args(speedup_ratio=100.0))
    first = asyncio.Event()

    async def victim():
        async for _ in eng.generate(_req(range(5, 29), max_tokens=60,
                                         rid="victim")):
            first.set()

    vt = asyncio.create_task(victim())
    await asyncio.wait_for(first.wait(), 10)
    # victim is decoding: the culprit's 32-token prefill shares its next
    # iteration
    await _gen_mock(eng, _req(range(200, 232), max_tokens=2, rid="culprit"))
    await asyncio.wait_for(vt, 30)
    stalled = [r for r in led.steps if r.hol_victims]
    assert stalled and all(r.kinds == ("mixed",) for r in stalled)
    for r in stalled:
        assert r.hol_culprit == "culprit" and r.hol_victims == 1
        assert 0.0 < r.hol_stall_s < r.wall_s
    assert led.hol_stall_seconds_total == pytest.approx(
        sum(r.interference_row_s for r in stalled))


# ---------------------------------------------------------------------------
# kv_blocks_live: the blocks a step's rows hold, beside the table's width
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["dense", "pallas_interpret"],
                ids=["gather", "kernel"])
def steps_with_blocks(request):
    """One run of the tiny preset on each attention path: a decoder, then
    two prompts of two full chunks each arriving together (their step
    overflows one token bucket and goes out as two programs). Every step's
    batches beside what the ledger filed, what the step's ``engine.record``
    span carried of its one count, and the ``engine.program`` spans'
    buckets."""
    import dynamo_tpu.engine.engine as eng
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.obs.profiler import loop_phase

    mp = pytest.MonkeyPatch()
    seen, spans, programs, walks = [], [], [], []
    real_geometry, real_set = eng.step_geometry, loop_phase.set
    real_counts = eng.step_counts
    real_meta = jax.profiler.TraceAnnotation.set_metadata

    def geometry(mc, ec, batches, **kw):
        g = real_geometry(mc, ec, batches, **kw)
        seen.append((batches, g))
        return g

    def counts(batches, *a, **kw):
        walks.append((batches, real_counts(batches, *a, **kw)))
        return walks[-1][1]

    def span_set(self, **attrs):
        if "kv_blocks_walked" in attrs:
            spans.append(attrs)
        real_set(self, **attrs)

    class Program(jax.profiler.TraceAnnotation):
        # As under a profiler session: an untraced step sets no attribute.
        is_enabled = staticmethod(lambda: True)

        def set_metadata(self, **attrs):
            if "program" in attrs:
                programs.append(attrs)
            real_meta(self, **attrs)

    mp.setattr(eng, "step_geometry", geometry)
    mp.setattr(eng, "step_counts", counts)
    mp.setattr(loop_phase, "set", span_set)
    mp.setattr(eng.jax.profiler, "TraceAnnotation", Program)
    led = get_sched_ledger()
    led.reset()
    led.configure(True)
    ec = EngineConfig(model="tiny-llama", block_size=16, num_blocks=64,
                      max_batch_size=4, max_model_len=128, prefill_chunk=16,
                      attn_impl=request.param, allow_random_weights=True)
    core = EngineCore(ec)
    core.add_request(_req(range(10, 31), max_tokens=24))
    for _ in range(8):
        core.step()
    core.add_request(_req(range(100, 140), max_tokens=3))
    core.add_request(_req(range(200, 240), max_tokens=3))
    for _ in range(200):
        if not core.has_work():
            break
        core.step()
    assert not core.has_work()
    total = led.snapshot()["kv_blocks_live_total"]
    mp.undo()
    # A step's rows are walked once between plan and record.
    assert [id(b) for b, _ in walks] == [id(b) for b, _ in seen]
    return core, seen, (spans, programs, [c for _, c in walks]), total


@pytest.mark.parametrize("step", ["decode", "one-chunk-mixed", "split"])
def test_kv_blocks_live_counts_what_the_rows_hold(steps_with_blocks, step):
    core, seen, (spans, programs, counts), total = steps_with_blocks
    ec, mc = core.engine_cfg, core.model_cfg
    kernel = ec.attn_impl == "pallas_interpret"
    pick = {
        "decode": lambda b: len(b) == 1 and b[0][0].kind == "decode",
        "one-chunk-mixed": lambda b: (
            len(b) == 1 and b[0][0].kind == "mixed"
            and sum(r[2] > 1 for r in b[0][1]) == 1),
        "split": lambda b: len(b) > 1,
    }[step]

    def by_hand(batches):
        return sum(-(-(start + length) // 16)
                   for _, rows, *_ in batches for _, start, length in rows)

    hits = [(b, g) for b, g in seen if pick(b)]
    assert hits, [[(s.kind, s.b, s.t) for s, *_ in b] for b, _ in seen]
    for batches, g in hits:
        assert g["kv_blocks_live"] == by_hand(batches) > 0
        # The table's width beside it: one under the kernel, and then it
        # prices nothing (a wider table is the same step); the gather pays
        # for every entry of its bucket.
        assert {s.nblk for s, *_ in batches} <= ({8} if kernel else {4, 8})
        wider = [(dataclasses.replace(b[0], nblk=800), *b[1:]) for b in batches]
        again = step_geometry(mc, ec, wider)
        assert (again["sched_flops"] == g["sched_flops"]) == kernel
        assert g["sched_flops"] >= g["live_flops"] > 0
    assert total == sum(by_hand(b) for b, _ in seen)
    # The span is set and the record filed from the one count: the same
    # steps in the same order, the same numbers; the span carries what the
    # benchmark's step_work_counts.py prices and nothing else.
    assert all(set(a) == {"programs", "live_tokens", "logit_rows",
                          "attn_q_ctx", "kv_blocks_walked"} for a in spans)
    for key in ("programs", "live_tokens", "logit_rows", "attn_q_ctx",
                "kv_blocks_walked"):
        assert [a[key] for a in spans] == [c[key] for c in counts], key
    for key in ("kv_blocks_live", "kv_blocks_walked", "live_tokens"):
        assert [c[key] for c in counts] == [g[key] for _, g in seen], key
    # One engine.program span a program, in dispatch order, under the
    # name its program was built by (which holds its bucket) and with the
    # ordinal of its step: what step_join.py reads, and nothing else.
    sigs = [sig for b, _ in seen for sig, *_ in b]
    assert [a["program"] for a in programs] == [
        "jit_" + s.program() for s in sigs]
    assert all(set(a) == {"step", "program"} for a in programs)
    first = programs[0]["step"]
    assert [a["step"] - first for a in programs] == [
        i for i, (b, _) in enumerate(seen) for _ in b]
    assert all(s.nblk in (4, 8) for s in sigs)
