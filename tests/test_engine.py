"""Engine core tests: generation correctness, prefix caching, stops, preemption.

Reference test model: the reference validates framework logic with its
mocker + unit tests (SURVEY.md §4); here the tiny-llama preset makes the
*real* engine CPU-testable.
"""

import pytest

from dynamo_tpu.engine.engine import EngineCore
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.utils.config import EngineConfig


def tiny_config(**kw) -> EngineConfig:
    defaults = dict(
        model="tiny-llama",
        block_size=4,
        num_blocks=64,
        max_batch_size=8,
        max_model_len=256,
        prefill_chunk=32,
        decode_bucket=(4, 8),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def make_req(prompt=None, max_tokens=8, temperature=0.0, rid=None, **kw) -> PreprocessedRequest:
    req = PreprocessedRequest(
        token_ids=prompt or [10, 11, 12, 13, 14],
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, **kw),
    )
    if rid:
        req.request_id = rid
    return req


def run_to_completion(core: EngineCore, reqs, max_steps=500):
    for r in reqs:
        core.add_request(r)
    collected = {r.request_id: [] for r in reqs}
    finished = set()
    for _ in range(max_steps):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            collected[rid].extend(out.token_ids)
            if out.finish_reason is not None:
                finished.add(rid)
    return collected, finished


@pytest.fixture(scope="module")
def core():
    return EngineCore(tiny_config())


def test_greedy_generation_deterministic(core):
    r1, r2 = make_req(), make_req()
    out, fin = run_to_completion(core, [r1, r2])
    assert len(out[r1.request_id]) == 8
    assert out[r1.request_id] == out[r2.request_id]
    assert {r1.request_id, r2.request_id} <= fin


def test_batch_matches_solo():
    """A request generates the same greedy tokens alone and in a busy batch."""
    solo = EngineCore(tiny_config())
    out_solo, _ = run_to_completion(solo, [make_req(rid="solo")])

    busy = EngineCore(tiny_config())
    reqs = [make_req(rid=f"r{i}", prompt=[20 + i, 30 + i, 40 + i]) for i in range(4)]
    reqs.append(make_req(rid="probe"))
    out_busy, _ = run_to_completion(busy, reqs)
    assert out_busy["probe"] == out_solo["solo"]


def test_prefix_cache_reuse_same_result():
    core = EngineCore(tiny_config())
    prompt = list(range(10, 30))  # 20 tokens = 5 full blocks
    out1, _ = run_to_completion(core, [make_req(prompt=prompt, rid="a")])
    hits_before = core.metrics.prefix_hit_blocks
    out2, _ = run_to_completion(core, [make_req(prompt=prompt, rid="b")])
    assert core.metrics.prefix_hit_blocks > hits_before  # second run hit the cache
    assert out1["a"] == out2["b"]


def test_stop_token():
    core = EngineCore(tiny_config())
    probe, _ = run_to_completion(core, [make_req(rid="p", max_tokens=16)])
    tokens = probe["p"]
    stop_tok = tokens[3]
    req = make_req(rid="s", max_tokens=16)
    req.stop_conditions.stop_token_ids = [stop_tok]
    out, fin = run_to_completion(core, [req])
    assert out["s"][-1] == stop_tok
    assert len(out["s"]) <= len(tokens)
    assert "s" in fin


def test_max_tokens_finish_reason():
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="x", max_tokens=3))
    reason = None
    for _ in range(100):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            if out.finish_reason:
                reason = out.finish_reason
    assert reason == FinishReason.LENGTH


def test_abort_frees_resources():
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="a", max_tokens=1000))
    core.step()
    free_before = core.pool.num_free
    core.abort("a")
    assert not core.has_work()
    assert core.pool.num_free >= free_before


def test_preemption_under_block_pressure():
    # Distinct 16-token prompts (no prefix sharing) + 15 usable blocks:
    # three long generations must contend, preempt, and resume correctly.
    prompts = [list(range(10 + 20 * i, 26 + 20 * i)) for i in range(3)]
    # Ground truth: each prompt run alone in a roomy core (greedy).
    solo = {}
    roomy = EngineCore(tiny_config(num_blocks=256, max_model_len=64))
    for i, p in enumerate(prompts):
        out, _ = run_to_completion(roomy, [make_req(rid=f"s{i}", prompt=p, max_tokens=30)])
        solo[i] = out[f"s{i}"]

    core = EngineCore(tiny_config(num_blocks=16, max_model_len=64))
    reqs = [make_req(rid=f"r{i}", prompt=prompts[i], max_tokens=30) for i in range(3)]
    out, fin = run_to_completion(core, reqs, max_steps=2000)
    assert len(fin) == 3, f"finished={fin}"
    assert core.sched.preemption_count > 0, "test did not exercise preemption"
    assert core.metrics.num_preemptions == core.sched.preemption_count
    for i, r in enumerate(reqs):
        # resume must not duplicate or drop tokens: exact greedy match
        assert out[r.request_id] == solo[i], f"r{i} diverged after preemption"


def test_chunked_prefill_long_prompt():
    core = EngineCore(tiny_config(prefill_chunk=16, max_model_len=512, num_blocks=256))
    long_prompt = [(i * 7) % 200 + 5 for i in range(150)]
    out, fin = run_to_completion(core, [make_req(prompt=long_prompt, rid="long")])
    assert len(out["long"]) == 8 and "long" in fin
    # and matches a single-chunk prefill of the same prompt
    core2 = EngineCore(tiny_config(prefill_chunk=256, max_model_len=512, num_blocks=256))
    out2, _ = run_to_completion(core2, [make_req(prompt=long_prompt, rid="long2")])
    assert out["long"] == out2["long2"]


def test_seeded_sampling_reproducible():
    core = EngineCore(tiny_config())
    a = make_req(rid="sa", temperature=0.8, seed=42)
    b = make_req(rid="sb", temperature=0.8, seed=42)
    out, _ = run_to_completion(core, [a])
    out2, _ = run_to_completion(core, [b])
    # NOTE: seeds are applied per-slot at admission; same slot+seed → same stream
    assert len(out["sa"]) == len(out2["sb"]) == 8


def test_decode_not_stalled_by_prefill():
    """Mixed steps: while a long prompt prefills over several chunks, an
    already-decoding stream emits a token every step (VERDICT weak #5)."""
    core = EngineCore(tiny_config(prefill_chunk=16, num_blocks=128))
    core.add_request(make_req(rid="short", max_tokens=64))
    # Let the short request finish prefill and emit a couple of tokens.
    for _ in range(3):
        core.step()
    # A long prompt that needs 4 chunks of prefill.
    core.add_request(make_req(prompt=list(range(1, 65)), rid="long", max_tokens=4))
    stalls = 0
    prefill_steps = 0
    while core._seqs.get("long") is not None and core._seqs["long"].num_computed < 64:
        outs = core.step()
        prefill_steps += 1
        if "short" not in outs or not outs["short"].token_ids:
            stalls += 1
        if prefill_steps > 50:
            break
    assert prefill_steps >= 3, "expected multi-chunk prefill"
    assert stalls == 0, f"decode stalled {stalls}/{prefill_steps} steps during prefill"


def test_mixed_step_outputs_match_sequential():
    """Greedy outputs are identical whether requests arrive together or the
    second arrives mid-decode of the first (mixed prefill+decode steps must
    not change numerics)."""
    together, _ = run_to_completion(
        EngineCore(tiny_config()),
        [make_req(rid="a", max_tokens=12), make_req(prompt=[3, 4, 5, 6], rid="b", max_tokens=12)],
    )
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="a", max_tokens=12))
    collected = {"a": [], "b": []}
    for _ in range(4):
        for rid, out in core.step().items():
            collected[rid].extend(out.token_ids)
    core.add_request(make_req(prompt=[3, 4, 5, 6], rid="b", max_tokens=12))
    for _ in range(200):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            collected[rid].extend(out.token_ids)
    assert collected["a"] == together["a"]
    assert collected["b"] == together["b"]


def test_no_admit_evict_thrash_under_pressure():
    """Tight pool + active decoders + a long prompt: the admission watermark
    keeps the long prompt queued (not admit→evict→re-admit thrashing), and
    everything still completes."""
    core = EngineCore(tiny_config(num_blocks=24, prefill_chunk=16, max_batch_size=4))
    reqs = [make_req(rid=f"d{i}", max_tokens=24) for i in range(2)]
    reqs.append(make_req(prompt=list(range(1, 33)), rid="long", max_tokens=8))
    collected, finished = run_to_completion(core, reqs, max_steps=400)
    assert finished == {"d0", "d1", "long"}
    assert len(collected["long"]) == 8
    assert core.sched.preemption_count <= 4, (
        f"excessive preemption churn: {core.sched.preemption_count}")


def run_pipelined(core: EngineCore, reqs, max_steps=500):
    """Drive the engine with one step in flight (step_begin before
    step_finalize of the previous step) — the AsyncJaxEngine loop shape."""
    for r in reqs:
        core.add_request(r)
    collected = {r.request_id: [] for r in reqs}
    finished = set()
    pending = None
    for _ in range(max_steps):
        if not core.has_work() and pending is None:
            break
        nxt = core.step_begin() if core.has_work() else None
        if pending is not None:
            for rid, out in core.step_finalize(pending).items():
                collected[rid].extend(out.token_ids)
                if out.finish_reason is not None:
                    finished.add(rid)
        pending = nxt
    return collected, finished


def test_pipelined_matches_sync_greedy():
    """The overlapped loop must produce bit-identical streams to the sync
    loop: device-fed decode tokens (slot_toks) and lagged stop checks are
    invisible to the client."""
    reqs_a = [make_req(prompt=[3 * i + j for j in range(5 + i)], max_tokens=6 + i,
                       rid=f"sync{i}") for i in range(4)]
    core_a = EngineCore(tiny_config())
    got_a, fin_a = run_to_completion(core_a, reqs_a)

    reqs_b = [make_req(prompt=[3 * i + j for j in range(5 + i)], max_tokens=6 + i,
                       rid=f"pipe{i}") for i in range(4)]
    core_b = EngineCore(tiny_config())
    got_b, fin_b = run_pipelined(core_b, reqs_b)

    assert len(fin_a) == len(reqs_a) and len(fin_b) == len(reqs_b)
    for i in range(4):
        assert got_b[f"pipe{i}"] == got_a[f"sync{i}"], f"stream {i} diverged"
    # Exactly max_tokens each — the speculative overrun row was discarded.
    for i in range(4):
        assert len(got_b[f"pipe{i}"]) == 6 + i


def test_pipelined_mid_flight_abort():
    """Abort between dispatch and finalize discards the in-flight row."""
    core = EngineCore(tiny_config())
    req = make_req(max_tokens=50, rid="victim")
    core.add_request(req)
    pending = core.step_begin()
    assert pending is not None
    core.abort("victim")
    outs = core.step_finalize(pending)
    assert "victim" not in outs
    assert not core.has_work()


def _against_solo(cfg_kw, reqs_fn, pipelined=False):
    """(reference, got): the streams of ``reqs_fn``'s requests given one at
    a time to a fresh core — so no step holds a chunk beside a decode row,
    nothing is preempted, and every program serves one row — and the same
    requests given together to a core built with ``cfg_kw``."""
    reqs_a = reqs_fn("a")
    solo = EngineCore(tiny_config())
    got_a, fin_a = {}, set()
    for r in reqs_a:
        got, fin = run_to_completion(solo, [r])
        got_a.update(got)
        fin_a |= fin
    reqs_b = reqs_fn("b")
    core_b = EngineCore(tiny_config(**cfg_kw))
    runner = run_pipelined if pipelined else run_to_completion
    got_b, fin_b = runner(core_b, reqs_b)
    assert len(fin_a) == len(reqs_a) and len(fin_b) == len(reqs_b)
    return got_a, got_b


def test_pp_engine_matches_unsharded():
    """pp=2 (layer blocks sharded over 'pipe', select-and-broadcast rounds)
    must emit exactly the unsharded engine's greedy streams — SURVEY §2.7 PP."""
    def reqs(tag):
        return [make_req(prompt=[3 * i + j for j in range(5 + i)],
                         max_tokens=5 + i, rid=f"{tag}{i}") for i in range(3)]

    def run(pp):
        core = EngineCore(tiny_config(pp=pp, dtype="float32"))
        if pp > 1:
            assert core.runner.mesh is not None
            assert core.runner.mesh.shape["pipe"] == pp
        got, fin = run_to_completion(core, reqs(f"p{pp}-"))
        assert len(fin) == 3
        return got

    a, b = run(1), run(2)
    for i in range(3):
        assert b[f"p2-{i}"] == a[f"p1-{i}"], f"stream {i} diverged under pp"


def test_fast_greedy_path_matches_general():
    """An all-greedy penalty-free batch takes the fast_greedy step variant
    and emits EXACTLY the stream the general sampling path produces for the
    same greedy requests (greedy rows are independent of batch siblings, so
    co-batching a temperature request forces the general path as oracle)."""
    prompts = [[10 + i * 3 + j for j in range(9)] for i in range(2)]

    fast_core = EngineCore(tiny_config())
    fast, _ = run_to_completion(fast_core, [
        make_req(prompt=p, max_tokens=7, rid=f"g{i}")
        for i, p in enumerate(prompts)])
    assert fast_core.runner.used_fast_greedy(), \
        f"fast_greedy variant unused: {list(fast_core.runner._step_fns)}"

    gen_core = EngineCore(tiny_config())
    general, _ = run_to_completion(gen_core, [
        *(make_req(prompt=p, max_tokens=7, rid=f"g{i}")
          for i, p in enumerate(prompts)),
        # Two tokens: 9 + 9 + 2 fill the token bucket of the (b=4, t=16)
        # prefill program, so the three prompts share one batch; one
        # token more and the sampled prompt would go out in a program of
        # its own (compile_ledger.pack_rows).
        make_req(prompt=[7, 8], max_tokens=7, rid="sampled",
                 temperature=0.8, seed=3),
    ])
    assert not gen_core.runner.used_fast_greedy(), \
        "general core unexpectedly used the fast path"
    for i in range(2):
        assert fast[f"g{i}"] == general[f"g{i}"], (fast, general)


# ---------------------------------------------------------------------------
# Ragged mixed-phase steps: a stream does not depend on what shares its step.
# Decode rows and prefill chunks dispatched as ONE launch must emit the
# streams the same requests emit one at a time, where no step mixes phases
# (the numerical reference is tests/test_token_major.py). Prompts span
# multiple chunks so decode rows genuinely co-batch with in-flight prefill
# chunks mid-run.
# ---------------------------------------------------------------------------

def test_unified_matches_solo_greedy():
    def reqs(tag):
        return [make_req(prompt=[(3 * i + j) % 100 for j in range(5 + 17 * i)],
                         max_tokens=6 + 2 * i, rid=f"{tag}{i}") for i in range(4)]

    got_a, got_b = _against_solo({}, reqs)
    for i in range(4):
        assert got_b[f"b{i}"] == got_a[f"a{i}"], f"stream {i} diverged"
        assert len(got_b[f"b{i}"]) == 6 + 2 * i


@pytest.mark.slow
def test_unified_sampled_reproducible():
    """Seeded sampling + penalties: per-slot PRNG keys advance once per token
    whether the row decodes in a pure-decode launch or a mixed one."""
    def reqs(tag):
        return [make_req(prompt=[(7 * i + j) % 90 for j in range(6 + 15 * i)],
                         max_tokens=10, temperature=0.8, seed=42 + i,
                         frequency_penalty=0.3, rid=f"{tag}{i}")
                for i in range(3)]

    got_a, got_b = _against_solo({}, reqs)
    for i in range(3):
        assert got_b[f"b{i}"] == got_a[f"a{i}"], f"stream {i} diverged"


@pytest.mark.slow
def test_unified_pipelined_matches_solo():
    """Unified steps under one-step-in-flight pipelining (production loop)."""
    def reqs(tag):
        return [make_req(prompt=[(5 * i + j) % 80 for j in range(4 + 16 * i)],
                         max_tokens=7 + i, rid=f"{tag}{i}") for i in range(3)]

    got_a, got_b = _against_solo({}, reqs, pipelined=True)
    for i in range(3):
        assert got_b[f"b{i}"] == got_a[f"a{i}"], f"stream {i} diverged"


def test_unified_under_block_pressure():
    """Preemption and resume land on the mixed path too: resumed seqs
    re-prefill their chunks next to still-live decode rows."""
    def reqs(tag):
        return [make_req(prompt=[(11 * i + j) % 70 for j in range(8)],
                         max_tokens=12, rid=f"{tag}{i}") for i in range(4)]

    got_a, got_b = _against_solo({"num_blocks": 25}, reqs)
    for i in range(4):
        assert got_b[f"b{i}"] == got_a[f"a{i}"], f"stream {i} diverged"


@pytest.mark.slow
@pytest.mark.parametrize("kv", ["bfloat16", "int8", "int4"])
def test_unified_wildly_ragged_bench_geometry(kv, monkeypatch):
    """Wildly-ragged mixed batch at the bench attention geometry (8 KV heads
    x head_dim 128, the llama-3-8b shape): one-block decode rows co-batched
    with a near-chunk-size prefill arriving mid-decode, for every paged-cache
    dtype."""
    from dynamo_tpu.models.config import MODEL_PRESETS, ModelConfig
    monkeypatch.setitem(MODEL_PRESETS, "tiny-kh8-d128", ModelConfig(
        name="tiny-kh8-d128", vocab_size=256, hidden_size=1024,
        intermediate_size=256, num_layers=1, num_heads=8, num_kv_heads=8,
        head_dim=128))

    cfg = dict(model="tiny-kh8-d128", kv_dtype=kv)
    early = [dict(prompt=[10 * i + j for j in range(3)], max_tokens=14,
                  rid=f"d{i}") for i in range(3)]
    late = dict(prompt=[(7 * j) % 200 for j in range(30)], max_tokens=8,
                rid="pf")

    core = EngineCore(tiny_config(**cfg))
    for kw in early:
        core.add_request(make_req(**kw))
    got = {kw["rid"]: [] for kw in early}
    for _ in range(4):  # establish pure decode before the prefill lands
        for rid, out in core.step().items():
            got[rid].extend(out.token_ids)
    core.add_request(make_req(**late))
    got["pf"] = []
    fin = set()
    for _ in range(200):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            got[rid].extend(out.token_ids)
            if out.finish_reason is not None:
                fin.add(rid)
    assert len(fin) == 4

    solo = EngineCore(tiny_config(**cfg))
    want = {}
    for kw in [*early, late]:
        want.update(run_to_completion(solo, [make_req(**kw)])[0])
    assert got == want


def test_auto_prefill_chunk_engine_init():
    """prefill_chunk=0 resolves to concrete SLO-driven per-QoS chunks before
    bucket enumeration and the scheduler read the config — and the engine
    still serves."""
    core = EngineCore(tiny_config(prefill_chunk=0))
    ec = core.engine_cfg
    assert ec.prefill_chunk >= 16
    assert set(core.chunk_by_qos) == {"interactive", "standard", "batch"}
    assert ec.prefill_chunk == max(core.chunk_by_qos.values())
    assert core.chunk_by_qos["batch"] >= core.chunk_by_qos["interactive"]
    assert all(c & (c - 1) == 0 for c in core.chunk_by_qos.values())
    out, fin = run_to_completion(core, [make_req(rid="auto")])
    assert len(out["auto"]) == 8 and "auto" in fin


async def test_step_failure_carries_on_but_device_error_stops(monkeypatch):
    """The step loop's catch-all fails the in-flight requests and keeps
    serving — unless the device or the compiler refused the step
    (JaxRuntimeError: out of memory, a kernel that does not lower), which
    the next request would meet again: then the engine stops, says so in
    ``fatal``, and answers every later request with the error."""
    import jax

    from dynamo_tpu.engine.engine import AsyncJaxEngine

    engine = AsyncJaxEngine(EngineCore(tiny_config()))
    errors = iter([RuntimeError("transient"),
                   jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: hbm")])

    def refuse():
        raise next(errors)

    monkeypatch.setattr(engine.core, "step_begin", refuse)

    async def outcome(rid):
        outs = [o async for o in engine.generate(make_req(rid=rid))]
        return outs[-1].finish_reason, outs[-1].error

    assert await outcome("a") == (FinishReason.ERROR, "transient")
    assert engine.fatal is None and engine._thread.is_alive()
    reason, error = await outcome("b")
    assert reason is FinishReason.ERROR and "RESOURCE_EXHAUSTED" in error
    assert isinstance(engine.fatal, jax.errors.JaxRuntimeError)
    reason, error = await outcome("c")
    assert reason is FinishReason.ERROR and "engine stopped" in error
    await engine.shutdown()
    assert not engine._thread.is_alive()


def test_fit_pool_measures_the_step():
    """Auto pool sizing (a TPU path: the CPU backend reports no memory)
    takes the step's memory from XLA's buffer assignment, not from an
    assumption: at the pool it returns, the widest step's temporaries plus
    the pool itself fill the budget and do not exceed it."""
    runner = EngineCore(tiny_config(max_model_len=64)).runner
    budget = 32 << 20
    n = runner._fit_pool(budget)
    _, beyond_args = runner._probe_step_memory(runner._widest_bucket(), n)
    need = beyond_args + n * runner._block_bytes_per_device()
    assert 0.95 * budget < need <= budget
