"""The gap ledger (obs/sched_ledger.py ``record_post`` / ``record_handover``,
``stats()["gaps"]``): every post-to-post token gap filed under the class of
the step that made it, with the device wait inside it; the hand-over to the
stream's loop; the head-of-line stall measured from both. Hand-made steps
first, then the tiny engine on its own thread."""

from __future__ import annotations

import asyncio
import threading
from bisect import bisect_right

import jax
import pytest

from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
from dynamo_tpu.obs.compile_ledger import BucketSig
from dynamo_tpu.obs.sched_ledger import (
    GAP_CLASSES,
    GAP_EDGES,
    SCHED_ENV,
    GapStamps,
    HolStall,
    get_sched_ledger,
    install_sched_metrics,
)
from dynamo_tpu.utils.metrics import MetricsRegistry

from tests.test_engine import make_req, tiny_config


@pytest.fixture(autouse=True)
def led():
    """The process-wide ledger, emptied before and after (as
    tests/test_sched_obs.py does)."""
    ledger = get_sched_ledger()
    ledger.reset()
    ledger.configure(True)
    install_sched_metrics(MetricsRegistry())
    yield ledger
    ledger.reset()
    ledger.configure(True)


def _cell(led, cls: str, b: int) -> dict:
    return led.gaps_snapshot()["by_class"][cls][str(b)]


def _dense(cell: dict, key: str) -> list:
    return [0] * cell["lo"] + list(cell[key])


# ---------------------------------------------------------------------------
# The edges
# ---------------------------------------------------------------------------

def test_edges_are_shared_sorted_and_fine_where_the_gaps_are():
    e = GAP_EDGES
    assert list(e) == sorted(set(e)) and e[0] > 0.0
    fine = [x for x in e if 5e-4 <= x <= 1.0]
    assert fine[0] == 5e-4 and fine[-1] == 1.0
    assert max(b / a for a, b in zip(fine, fine[1:])) <= 1.025
    # coarser outside, and few: the whole thing stays small in stats()
    assert min(b / a for a, b in zip(e, e[1:]) if b <= 5e-4 or a >= 1.0) > 1.2
    assert len(e) < 340
    assert GAP_CLASSES == ("decode", "mixed", "verify")


# ---------------------------------------------------------------------------
# Hand-made steps into the ledger
# ---------------------------------------------------------------------------

def test_a_step_files_its_rows_under_its_class_and_bucket(led):
    led.record_post(None, cls="decode", b=16, period_s=0.012, rows=14,
                    wait_s=0.009)
    led.record_post(None, cls="decode", b=16, period_s=0.013, rows=15,
                    odd_gaps=[0.030], wait_s=0.020)
    c = _cell(led, "decode", 16)
    assert c["steps"] == 2 and c["period_s"] == pytest.approx(0.025)
    rows, gap_s, wait_s = (_dense(c, k) for k in ("rows", "gap_s", "wait_s"))
    i12, i13, i30 = (bisect_right(GAP_EDGES, g) for g in (0.012, 0.013, 0.030))
    assert len({i12, i13, i30}) == 3
    assert (rows[i12], rows[i13], rows[i30]) == (14, 15, 1)
    assert gap_s[i12] == pytest.approx(14 * 0.012)
    assert wait_s[i12] == pytest.approx(14 * 0.009)
    # a gap holds no more of the wait than it is long
    assert wait_s[i13] == pytest.approx(15 * 0.013)
    assert wait_s[i30] == pytest.approx(0.020)
    # bucket sums add up to the totals, and nothing lies outside lo..
    assert sum(rows) == 30 and len(rows) <= len(GAP_EDGES) + 1
    assert sum(gap_s) == pytest.approx(14 * 0.012 + 15 * 0.013 + 0.030)
    assert c["lo"] == i12 and len(c["rows"]) == i30 - i12 + 1
    assert GAP_EDGES[i12 - 1] <= 0.012 < GAP_EDGES[i12]


def test_a_step_with_no_gap_files_no_row_and_counts_no_period(led):
    """A first post is time to first token: the caller leaves it out."""
    led.record_post(None, cls="mixed", b=8)
    assert led.gaps_snapshot()["by_class"] == {}
    led.record_post(None, cls="mixed", b=8, odd_gaps=[0.5])
    c = _cell(led, "mixed", 8)
    assert c["steps"] == 0 and sum(c["rows"]) == 1


def test_gaps_beyond_the_edges_land_in_the_end_buckets(led):
    led.record_post(None, cls="verify", b=4, odd_gaps=[0.0, 3e-6, 500.0])
    rows = _dense(_cell(led, "verify", 4), "rows")
    assert rows[0] == 2 and rows[len(GAP_EDGES)] == 1


def test_the_measured_stall_is_the_gap_less_the_buckets_decode_mean(led):
    reg = MetricsRegistry()
    install_sched_metrics(reg)
    victims = [(None, f"v{i}", "standard") for i in range(3)]
    hol = HolStall(culprit="long", culprit_tokens=512, victims=victims)
    # no decode step of that bucket yet: the whole gap
    rec0 = led.record_step(wall_s=0.001, kinds=("mixed",))
    led.record_post(rec0, cls="mixed", b=8, period_s=0.040, rows=3,
                    hol=hol, hol_b=4, ts=50.0)
    assert rec0.hol_stall_s == pytest.approx(0.040)
    for period in (0.010, 0.012, 0.014):
        led.record_post(None, cls="decode", b=4, period_s=period, rows=3)
    # another bucket's decode steps are not the victims' program
    led.record_post(None, cls="decode", b=8, period_s=0.030, rows=7)
    rec = led.record_step(wall_s=0.001, kinds=("mixed",))
    led.record_post(rec, cls="mixed", b=8, period_s=0.040, rows=3,
                    hol=hol, hol_b=4, ts=100.0)
    assert rec.hol_stall_s == pytest.approx(0.040 - 0.012)
    assert rec.hol_victims == 3 and rec.hol_culprit == "long"
    assert rec.interference_row_s == pytest.approx(3 * 0.028)
    assert (rec.gap_class, rec.gap_rows) == ("mixed", 3)
    assert rec.gap_s == pytest.approx(0.040)
    d = rec.to_dict()
    assert d["gap"] == {"class": "mixed", "rows": 3, "seconds": 0.04}
    assert d["hol"]["stall_s"] == pytest.approx(0.028)
    # never under zero: a mixed step shorter than the decode mean
    rec2 = led.record_step(wall_s=0.001, kinds=("mixed",))
    led.record_post(rec2, cls="mixed", b=8, period_s=0.008, rows=3,
                    hol=hol, hol_b=4)
    assert rec2.hol_stall_s == 0.0 and rec2.hol_victims == 3
    # victims whose rows all sat a step out: the shortest of their gaps
    rec3 = led.record_step(wall_s=0.001, kinds=("mixed",))
    led.record_post(rec3, cls="mixed", b=8, odd_gaps=[0.050, 0.070],
                    hol=hol, hol_b=4)
    assert rec3.hol_stall_s == pytest.approx(0.050 - 0.012)
    assert rec3.gap_s == 0.0 and rec3.gap_rows == 2
    # ... and none of whom was posted anything: nothing to charge
    rec4 = led.record_step(wall_s=0.001, kinds=("mixed",))
    led.record_post(rec4, cls="mixed", b=8, hol=hol, hol_b=4)
    assert rec4.hol_victims == 0
    snap = led.snapshot()
    assert snap["hol_victims_total"] == 12
    assert snap["interference_row_seconds_total"] == pytest.approx(
        3 * (0.040 + 0.028 + 0.0 + 0.038))
    assert led.top_culprits()[0]["request_id"] == "long"
    assert get_sched_ledger().debug_info()["recent_steps"][1]["gap"]["rows"] == 3
    text = reg.expose()
    assert 'dynamo_sched_hol_stall_seconds_count{qos_class="standard"} 12' in text


def test_handover_histogram_counts_sums_and_keeps_the_worst(led):
    for dt in (0.00004, 0.00006, 0.0008, 0.120):
        led.record_handover(dt)
    h = led.gaps_snapshot()["handover"]
    assert h["count"] == 4 and sum(h["buckets"]) == 4
    assert h["sum_s"] == pytest.approx(0.1209) and h["max_s"] == 0.120
    assert h["lo"] == bisect_right(GAP_EDGES, 0.00004)
    dense = [0] * h["lo"] + h["buckets"]
    assert dense[bisect_right(GAP_EDGES, 0.120)] == 1


def test_a_snapshot_never_sees_a_half_filed_step(led):
    """The writer files a step's rows, their seconds and its period under
    one hold of the lock: every snapshot adds up."""
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            led.record_post(None, cls="decode", b=8, period_s=0.01, rows=5,
                            odd_gaps=[0.02, 0.04], wait_s=0.01)

    t = threading.Thread(target=writer)
    t.start()
    try:
        seen = 0
        for _ in range(300):
            cell = led.gaps_snapshot()["by_class"].get("decode", {}).get("8")
            if not cell:
                continue
            steps = cell["steps"]
            seen = max(seen, steps)
            assert sum(cell["rows"]) == 7 * steps
            assert sum(cell["gap_s"]) == pytest.approx(0.11 * steps)
            assert sum(cell["wait_s"]) == pytest.approx(0.07 * steps)
            assert cell["period_s"] == pytest.approx(0.01 * steps)
    finally:
        stop.set()
        t.join()
    assert seen > 0


def test_the_gate_turns_it_off_with_the_rest(led, monkeypatch):
    monkeypatch.setenv(SCHED_ENV, "0")
    core = EngineCore(tiny_config())       # __init__ reads the gate again
    assert led.enabled is False
    engine = AsyncJaxEngine(core)
    calls = []
    from dynamo_tpu.obs import costmodel

    monkeypatch.setattr(costmodel, "mixed_step_seconds",
                        lambda *a, **k: calls.append(a) or 0.0)
    core.add_request(make_req(rid="off0", max_tokens=6))
    while core.has_work():
        core.step()
    assert "gaps" not in engine.stats() and "sched" not in engine.stats()
    led.record_post(None, cls="decode", b=4, period_s=0.01, rows=1)
    led.record_handover(0.001)
    led.configure(True)
    snap = led.gaps_snapshot()
    assert snap["by_class"] == {} and snap["handover"]["count"] == 0
    assert core._seqs == {} and core._gap.odd == [] and not calls


def test_sched_context_prices_nothing(led, monkeypatch):
    """A mixed step with victims, the ledger on: no cost model is called
    from plan to post, and the stall filed is a measured one."""
    from dynamo_tpu.obs import costmodel

    def refuse(*a, **k):
        raise AssertionError("engine.plan priced a step")

    monkeypatch.setattr(costmodel, "mixed_step_seconds", refuse)
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="dec", max_tokens=24))
    while not any(s.in_decode for s in core.sched.running):
        core.step()
    core.add_request(make_req(rid="long", prompt=list(range(40, 110)),
                              max_tokens=2))
    while core.has_work():
        core.step()
    stalled = [r for r in led.steps if r.hol_victims]
    assert stalled and all(r.hol_culprit == "long" for r in stalled)
    assert all(r.gap_class == "mixed" and 0.0 <= r.hol_stall_s <= r.gap_s
               for r in stalled)


# ---------------------------------------------------------------------------
# The engine's stamps: which step posted to a row last
# ---------------------------------------------------------------------------

def test_stamps_turn_step_ordinals_into_seconds():
    """``GapStamps`` alone, on a hand-made clock: a first post files
    nothing, a row of consecutive steps the period, a row that sat steps
    out its own gap, one away longer than the ring since the oldest post
    the ring holds."""
    from types import SimpleNamespace as Row

    st = GapStamps()
    a, b, c = Row(post_step=0), Row(post_step=0), Row(post_step=0)

    def step(n, now, *rows):
        st.step = n
        for r in rows:
            st.stamp(r)
        return st.close(now)

    assert step(1, 10.0, a, b) == (0.0, 0, [])            # first posts
    assert step(2, 10.5, a, b, c) == (0.5, 2, [])         # c's first
    assert step(3, 11.5, a) == (1.0, 1, [])               # b, c sit out
    assert step(4, 12.0) == (0.0, 0, [])                  # nobody posted to
    period, rows, odd = step(5, 14.0, a, b, c)
    assert (period, rows) == (0.0, 0)                     # a skipped step 4
    assert odd == [2.5, 3.5, 3.5]                         # since 3, 2, 2
    assert (a.post_step, st.posted, st.step) == (5, 5, 0)
    far = Row(post_step=5)
    for n in range(6, 6 + GapStamps.RING + 10):
        step(n, float(n))
    n = st.posted + 1
    # step 5's post time has been overwritten: since the oldest kept
    assert step(n, float(n), far) == (0.0, 0, [float(GapStamps.RING - 1)])


def _by_class_rows(led) -> dict[str, int]:
    return {cls: sum(sum(c["rows"]) for c in cells.values())
            for cls, cells in led.gaps_snapshot()["by_class"].items()}


def test_a_rows_first_post_files_nothing_and_consecutive_steps_the_period(led):
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="g0", max_tokens=5))
    outs = []
    while core.has_work():
        outs.append(core.step())
    posts = [o for o in outs if o.get("g0") and o["g0"].token_ids]
    assert len(posts) == 5
    # five posts to one row: the first is time to first token, four gaps,
    # all of decode steps, each the step's own period
    assert _by_class_rows(led) == {"decode": 4}
    cell = _cell(led, "decode", 4)
    assert cell["steps"] == 4
    assert sum(cell["gap_s"]) == pytest.approx(cell["period_s"])
    assert 0.0 < sum(cell["wait_s"]) <= sum(cell["gap_s"])
    recs = [r for r in led.steps if r.gap_rows]
    assert len(recs) == 4 and all(r.gap_s > 0.0 for r in recs)
    assert core.metrics.ttft_count == 1


def test_a_row_that_sat_a_step_out_files_its_own_gap(led):
    """Two streams; one is held out of two steps (as a verify pause or a
    preemption holds one out): its next post files the seconds since its
    own last post, the other's files the step's period."""
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="a", max_tokens=12))
    core.add_request(make_req(rid="b", prompt=[20, 21, 22], max_tokens=12))
    for _ in range(4):
        core.step()
    seq_b = core._seqs["b"]
    assert seq_b.post_step == core._gap.posted > 0
    before = _cell(led, "decode", 4)
    core.sched.running.remove(seq_b)
    core.step()
    core.step()
    core.sched.running.append(seq_b)
    mid = _cell(led, "decode", 4)
    assert sum(mid["rows"]) - sum(before["rows"]) == 2       # a alone
    core.step()
    after = _cell(led, "decode", 4)
    assert after["steps"] - mid["steps"] == 1
    assert sum(after["rows"]) - sum(mid["rows"]) == 2        # a and b
    period = after["period_s"] - mid["period_s"]
    own = sum(after["gap_s"]) - sum(mid["gap_s"]) - period
    # b's gap spans the two steps it sat out and this one
    assert own > period and own == pytest.approx(
        period + mid["period_s"] - before["period_s"], rel=0.05)
    while core.has_work():
        core.step()


def test_a_row_away_longer_than_the_ring_files_since_the_oldest_post(led):
    core = EngineCore(tiny_config())
    core.add_request(make_req(rid="old", max_tokens=4))
    core.step()
    core.step()
    seq = core._seqs["old"]
    seq.post_step -= 3 * GapStamps.RING      # as if posted to long ago
    core.step()
    rows = _dense(_cell(led, "decode", 4), "rows")
    assert sum(rows) == 2
    while core.has_work():
        core.step()


def test_class_of_a_step_is_its_programs(led):
    """decode where every program is a decode program, mixed where one
    carried a chunk, verify beside decode programs; the widest row bucket."""
    from dynamo_tpu.engine.engine import PendingStep

    core = EngineCore(tiny_config())

    def post(step, *sigs):
        core._gap.step, core._gap.same = step, 1
        core.outputs_posted(PendingStep(
            step=step, batches=[(s, [], [], None, None) for s in sigs]))

    sig = lambda kind, b, t: BucketSig(kind, b, t, 4, True, "bfloat16")
    core._gap.posted = 1
    post(2, sig("decode", 4, 1))
    post(3, sig("decode", 4, 1), sig("decode", 8, 1))
    post(4, sig("verify", 4, 4), sig("decode", 8, 1))
    post(5, sig("mixed", 8, 16), sig("verify", 4, 4))
    post(6, sig("mixed", 4, 32))
    assert {cls: sorted(cells) for cls, cells in
            led.gaps_snapshot()["by_class"].items()} == {
        "decode": ["4", "8"], "verify": ["8"], "mixed": ["4", "8"]}
    # a post with no finalize before it (or another step's) files nothing
    core.outputs_posted(PendingStep(step=6, batches=[]))
    core.outputs_posted(None)
    assert sum(_by_class_rows(led).values()) == 5


# ---------------------------------------------------------------------------
# The engine on its own thread: both classes, the hand-over, the span
# ---------------------------------------------------------------------------

def _post_spans(trace_dir) -> list[dict]:
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    return [{"name": e.name, **dict(e.stats)}
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name in ("engine.post", "engine.finalize.wait",
                          "engine.record")]


def test_long_prompt_over_a_live_stream_on_the_engine_thread(led, tmp_path):
    engine = AsyncJaxEngine(EngineCore(tiny_config()))

    async def stream(req, started=None):
        n = 0
        async for out in engine.generate(req):
            n += len(out.token_ids)
            if started is not None and n >= 2:
                started.set()
        return n

    async def drive():
        engine.start()
        # warm both programs outside the session, so that the traced steps
        # are not compiles
        await stream(make_req(rid="w0", prompt=list(range(30, 100)),
                              max_tokens=3))
        await asyncio.sleep(0.1)     # its last, discarded step is finalized
        s0 = engine.stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            started = asyncio.Event()
            live = asyncio.create_task(
                stream(make_req(rid="live", max_tokens=40), started))
            await started.wait()
            n_long = await stream(make_req(
                rid="long", prompt=list(range(100, 190)), max_tokens=2))
            n_live = await live
            await asyncio.sleep(0.1)
        finally:
            jax.profiler.stop_trace()
        s1 = engine.stats()
        await engine.shutdown()
        return s0, s1, n_live, n_long

    s0, s1, n_live, n_long = asyncio.run(drive())
    assert (n_live, n_long) == (40, 2)
    g0, g1 = s0["gaps"], s1["gaps"]
    assert g1["edges"] == GAP_EDGES
    rows = {cls: sum(sum(c["rows"]) for c in cells.values())
            for cls, cells in g1["by_class"].items()}
    rows0 = {cls: sum(sum(c["rows"]) for c in cells.values())
             for cls, cells in g0["by_class"].items()}
    new = {cls: n - rows0.get(cls, 0) for cls, n in rows.items()}
    # 39 gaps of the live stream and 1 of the long prompt's second token,
    # the chunk steps' among them under "mixed"
    assert new["decode"] + new["mixed"] == 40
    assert new["mixed"] >= 2 and new["decode"] >= 20
    # one hand-over a step that posted to a stream
    steps = s1["num_steps"] - s0["num_steps"]
    handed = g1["handover"]["count"] - g0["handover"]["count"]
    assert 40 <= handed <= steps
    assert g1["handover"]["max_s"] >= g1["handover"]["sum_s"] / g1["handover"]["count"] > 0
    # the stall is measured: the culprit is named, the victims' stall is in
    # the ledger's totals, and each record's is no more than its gap
    stalled = [r for r in led.steps if r.hol_victims]
    assert stalled and {r.hol_culprit for r in stalled} == {"long"}
    assert all(r.hol_stall_s <= r.gap_s for r in stalled)
    # engine.post carries the step like the wait and the record, and the
    # gap while the session records
    spans = _post_spans(tmp_path)
    by_name: dict[str, dict[int, dict]] = {}
    for s in spans:
        by_name.setdefault(s["name"], {})[int(s["step"])] = s
    posts = {k: v for k, v in by_name["engine.post"].items() if k}
    assert len(posts) >= 40
    assert set(posts) <= set(by_name["engine.finalize.wait"])
    assert set(posts) <= set(by_name["engine.record"])
    classes = {str(s["cls"]) for s in posts.values() if "cls" in s}
    assert classes == {"decode", "mixed"}
    with_gap = [s for s in posts.values() if int(s.get("gap_rows", 0))]
    assert len(with_gap) >= 39
    assert all(float(s["gap_ms"]) > 0.0 for s in with_gap)


# ---------------------------------------------------------------------------
# tools/engine_thread_reads.py: what the engine thread did outside its phases
# ---------------------------------------------------------------------------

def test_reads_outside_every_phase_are_told_from_the_slices_edge():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "engine_thread_reads", Path(__file__).resolve().parents[1]
        / "tools" / "engine_thread_reads.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    read = tool.READ
    line = sorted([
        (read, 5, 9, {}),                       # its span began before the session
        ("engine.finalize.wait", 10, 40, {"step": 7}),
        (read, 12, 30, {}), (read, 31, 39, {}),
        ("engine.record", 41, 45, {"step": 7}),
        (read, 46, 48, {}),                     # a read no phase covers
        ("engine.post", 50, 60, {"step": 7}),
        ("engine.dispatch", 61, 80, {}),
        ("engine.dispatch.launch", 62, 79, {}),
        ("PjitFunction(step)", 63, 78, {}),
        ("engine.finalize.wait", 81, 90, {"step": 8}),
        (read, 82, 89, {}),
        ("engine.post", 95, 99, {"step": 0}),   # an iteration that finalized none
        (read, 120, 125, {}),                   # its span ended after the session
    ], key=lambda x: (x[1], -x[2]))
    out = tool.reduce([line])
    assert out["uncovered"] == {read: {"count": 1,
                                       "seconds": pytest.approx(2e-9),
                                       "longest_s": pytest.approx(2e-9)}}
    assert out["edge"][read]["count"] == 2
    assert out["reads"]["engine.finalize.wait"] == {
        "count": 3, "seconds": pytest.approx(33e-9),
        "longest_s": pytest.approx(18e-9), "step": 7}
    assert out["reads"]["outside every phase"]["count"] == 1
    assert out["reads"]["the slice's edge"]["count"] == 2
    assert out["steps"] == {"joined": 1, "partial": 1}
