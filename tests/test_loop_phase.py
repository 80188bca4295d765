"""The engine loop's phases (obs/profiler.py ``loop_phase``): always-on
seconds per phase in ``stats()["loop"]``, profiler spans at the same
boundaries on the engine thread's line, the parts of time to first token,
and the names the step programs carry into a device trace."""

from __future__ import annotations

import asyncio
import json
import time

import jax
import pytest

from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
from dynamo_tpu.obs.profiler import (
    DEVICE_PHASES,
    LOOP_PHASES,
    NESTED_PHASES,
    LoopClock,
    loop_iteration,
    loop_phase,
)

from tests.test_engine import make_req, run_to_completion, tiny_config

PROMPTS = [list(range(10 + 20 * i, 26 + 20 * i)) for i in range(4)]


async def _serve(engine: AsyncJaxEngine, reqs) -> dict[str, list[int]]:
    async def one(req):
        toks = []
        async for out in engine.generate(req):
            toks.extend(out.token_ids)
        return req.request_id, toks

    return dict(await asyncio.gather(*(one(r) for r in reqs)))


@pytest.fixture(scope="module")
def served():
    """A tiny engine that served four requests through its own thread:
    ``(stats at the end, wall seconds from start() to the thread's end)``."""
    engine = AsyncJaxEngine(EngineCore(tiny_config()))
    reqs = [make_req(rid=f"lp{i}", prompt=p, max_tokens=12)
            for i, p in enumerate(PROMPTS)]

    async def drive():
        t0 = time.perf_counter()
        engine.start()
        out = await _serve(engine, reqs)
        await asyncio.sleep(0.12)      # let the loop reach its idle wait
        stats = engine.stats()
        await engine.shutdown()
        return out, stats, engine.stats(), time.perf_counter() - t0

    return asyncio.run(drive())


def test_loop_clock_adds_up_and_spans_do_not_raise():
    clock = LoopClock()
    assert set(clock.seconds) == set(LOOP_PHASES)
    with loop_phase(clock, "engine.dispatch", kind="decode") as span:
        time.sleep(0.01)
        span.set(b=8, t=1, nblk=4, rows=3)
    with loop_phase(clock, "engine.dispatch"):
        pass
    assert 0.01 <= clock.seconds["engine.dispatch"] < 0.5
    snap = clock.snapshot()
    snap["engine.plan"] = 9.0
    assert clock.seconds["engine.plan"] == 0.0       # a copy, not the dict


def test_nested_phases_and_the_iterations_unphased_time():
    """``engine.dispatch``'s parts nest in it and count beside it, not on
    top of it: what an iteration's wall holds outside every top-level phase
    is ``engine.unphased``, whatever nests."""
    clock = LoopClock()
    with loop_iteration(clock):
        time.sleep(0.01)                               # between the phases
        with loop_phase(clock, "engine.dispatch"):
            with loop_phase(clock, "engine.dispatch.fill"):
                time.sleep(0.01)
            with loop_phase(clock, "engine.dispatch.launch"), \
                    loop_phase(clock, "engine.compile"):
                time.sleep(0.01)
        with loop_phase(clock, "engine.record", step=7) as span:
            span.set(live_tokens=3)
    sec = clock.seconds
    parts = sum(sec[k] for k in NESTED_PHASES if k != "engine.compile")
    assert 0.02 <= parts <= sec["engine.dispatch"]
    assert sec["engine.compile"] <= sec["engine.dispatch.launch"]
    assert clock.depth == 0
    assert clock.phased == pytest.approx(
        sec["engine.dispatch"] + sec["engine.record"])
    # (lower bounds alone: a sleep under a loaded machine runs long)
    assert 0.01 <= sec["engine.unphased"] < 0.5


def test_stats_loop_has_every_phase(served):
    out, stats, _final, _wall = served
    assert all(len(toks) == 12 for toks in out.values())
    loop = stats["loop"]
    assert set(loop) == set(LOOP_PHASES)
    for name in LOOP_PHASES:
        if name != "engine.compile":
            assert loop[name] > 0.0, name
    # The tiny engine compiles its programs while serving, inside dispatch.
    assert 0.0 < loop["engine.compile"] <= loop["engine.dispatch"]


def test_phases_cover_the_loops_wall_time(served):
    _out, _stats, final, wall = served
    loop = final["loop"]
    total = sum(v for k, v in loop.items() if k not in NESTED_PHASES)
    # The top-level phases do not nest, and with what the iterations held
    # outside them (engine.unphased) leave out only thread start-up and the
    # join.
    assert 0.9 * wall <= total <= 1.001 * wall, (total, wall, loop)
    assert loop["engine.unphased"] < 0.1 * wall
    parts = sum(loop[k] for k in NESTED_PHASES if k != "engine.compile")
    assert 0.5 * loop["engine.dispatch"] <= parts <= loop["engine.dispatch"]


def test_ttft_parts_count_every_request_once(served):
    _out, stats, _final, _wall = served
    assert stats["ttft_count"] == len(PROMPTS)
    assert 0.0 <= stats["ttft_inbox_s"] <= stats["ttft_queue_s"]
    assert stats["ttft_prefill_s"] > 0.0
    assert stats["requests_finished"] == len(PROMPTS)


def test_kv_cache_shape_is_the_per_device_pool():
    core = EngineCore(tiny_config())
    spec = core.runner.spec
    assert core.metrics.kv_cache_shape == spec.shape
    assert AsyncJaxEngine(core).stats()["kv_cache_shape"] == list(spec.shape)


def test_preempted_sequence_counts_once():
    """Three long generations in 15 usable blocks preempt one another and
    re-prefill; each still has one first token."""
    core = EngineCore(tiny_config(num_blocks=16, max_model_len=64))
    reqs = [make_req(rid=f"p{i}", prompt=PROMPTS[i], max_tokens=30)
            for i in range(3)]
    _out, fin = run_to_completion(core, reqs, max_steps=2000)
    assert len(fin) == 3 and core.sched.preemption_count > 0
    m = core.metrics
    assert m.ttft_count == 3
    # Without an arrival stamp a request arrives when add_request sees it.
    assert m.ttft_inbox_s == 0.0 and m.ttft_queue_s >= 0.0
    assert m.ttft_prefill_s > 0.0


def test_arrival_stamp_starts_the_queue_span_and_the_inbox_part():
    from dynamo_tpu.obs.tracer import TRACE_KEY, get_tracer

    core = EngineCore(tiny_config())
    tr = get_tracer()
    root = tr.start_span("request", fresh=True)
    req = make_req(rid="arr0", max_tokens=2)
    req.annotations = {TRACE_KEY: root.context().header()}
    now_p, now_w = time.perf_counter(), time.time()
    assert core.add_request(req, arrival=(now_p - 0.25, now_w - 0.25)) is None
    seq = core._seqs["arr0"]
    assert seq.trace_span.name == "engine.queue"
    assert abs(seq.trace_span.start - (now_w - 0.25)) < 1e-6
    while core.has_work():
        core.step()
    m = core.metrics
    assert m.ttft_count == 1
    assert 0.25 <= m.ttft_inbox_s <= m.ttft_queue_s < 0.25 + 60.0


def _host_spans(trace_dir) -> dict[str, set[str]]:
    """``{line name: names of the engine.* events on it}`` of the newest
    trace under ``trace_dir``."""
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    out: dict[str, set[str]] = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            names = {e.name for e in line.events
                     if e.name.startswith("engine.")}
            if names:
                out[f"{line.name}#{i}"] = names
    return out


def test_profiler_trace_holds_the_spans_on_the_engine_thread(tmp_path):
    engine = AsyncJaxEngine(EngineCore(tiny_config()))
    reqs = [make_req(rid=f"tr{i}", prompt=p, max_tokens=6)
            for i, p in enumerate(PROMPTS[:2])]

    async def drive():
        engine.start()
        await asyncio.sleep(0.1)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            await _serve(engine, reqs)
            await asyncio.sleep(0.1)
        finally:
            jax.profiler.stop_trace()
        await engine.shutdown()

    asyncio.run(drive())
    lines = _host_spans(tmp_path)
    assert len(lines) == 1, lines        # one thread writes them all
    names = next(iter(lines.values()))
    assert {"engine.inbox", "engine.plan", "engine.dispatch",
            "engine.finalize.wait", "engine.finalize.host", "engine.record",
            "engine.post", "engine.compile", "engine.program",
            "engine.dispatch.reset", "engine.dispatch.fill",
            "engine.dispatch.place", "engine.dispatch.launch"} <= names, names
    # The session was seen: the programs that ran under it are noted, and
    # their tables are what shutdown() left for a reader in this process.
    from dynamo_tpu.obs.profiler import phase_table_path

    assert engine.core.traced_programs
    tables = json.loads(phase_table_path().read_text())
    assert set(tables) == engine.core.traced_programs
    assert all(t and set(t.values()) <= set(DEVICE_PHASES)
               for t in tables.values())


@pytest.fixture(scope="module")
def runner():
    return EngineCore(tiny_config()).runner


@pytest.mark.parametrize("t, greedy, expect", [
    (1, True, "jit_step_decode_b4_n4"),
    (16, True, "jit_step_mixed_b4_t16_k20_n4"),
    (1, False, "jit_step_decode_b4_n4_sampled"),
])
def test_step_program_name_carries_the_bucket(runner, t, greedy, expect):
    fn = runner._build_step_fn(4, t, 4, fast_greedy=greedy)
    text = fn.lower(
        runner.params, runner.cache_k, runner.cache_v, runner.counts,
        runner.keys, runner.slot_toks,
        *runner._padding_inputs(4, t, 4, greedy)).as_text()
    assert f"module @{expect} " in text, text[:200]


def test_verify_and_embed_programs_are_named(runner):
    assert runner._build_verify_fn(4, 4, 8).__name__ == "step_verify_b4_t4_n8"
    assert runner._build_embed_fn(2, 16).__name__ == "embed_b2_t16"
