"""Unit tests for the tracing subsystem (dynamo_tpu/obs) and the
Prometheus text exposition produced by MetricsRegistry.expose().

The exposition tests parse the generated text with a small promtext
parser (escape-aware) and round-trip it, which is what an actual
Prometheus scraper would have to do — duplicate # TYPE headers, broken
label escaping, or non-cumulative buckets all fail the parse/invariant
checks rather than a string-match.
"""

from __future__ import annotations

import json
import math
import time

import pytest

from dynamo_tpu.obs.bridge import SpanMetricsBridge
from dynamo_tpu.obs.recorder import FlightRecorder, StepProfiler
from dynamo_tpu.obs.tracer import (
    TRACE_KEY,
    Span,
    Tracer,
    trace_context_of,
)
from dynamo_tpu.utils.logging import TraceContext
from dynamo_tpu.utils.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# promtext parser (escape-aware), used to round-trip expose()

def _unescape(v: str) -> str:
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            n = v[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(n, "\\" + n))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _parse_labels(s: str) -> dict[str, str]:
    labels, i = {}, 0
    while i < len(s):
        j = s.index("=", i)
        name = s[i:j].strip(", ")
        assert s[j + 1] == '"', f"unquoted label value at {s[j:]}"
        k, buf = j + 2, []
        while True:
            c = s[k]
            if c == "\\":
                buf.append(s[k : k + 2])
                k += 2
            elif c == '"':
                break
            else:
                assert c != "\n"
                buf.append(c)
                k += 1
        labels[name] = _unescape("".join(buf))
        i = k + 1
    return labels


def parse_promtext(text: str):
    """Returns (families, samples): families[name] = (kind, help);
    samples = list of (metric_name, labels_dict, float_value)."""
    families: dict[str, tuple[str, str]] = {}
    samples: list[tuple[str, dict, float]] = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, help_ = line[len("# HELP "):].partition(" ")
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = ("", help_)
        elif line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            assert name in families, f"TYPE before HELP for {name}"
            assert families[name][0] == "", f"duplicate TYPE for {name}"
            families[name] = (kind, families[name][1])
        else:
            brace = line.find("{")
            if brace != -1:
                name = line[:brace]
                close = line.rindex("}")
                labels = _parse_labels(line[brace + 1 : close])
                value = float(line[close + 1 :].strip())
            else:
                name, _, raw = line.partition(" ")
                labels, value = {}, float(raw)
            samples.append((name, labels, value))
    return families, samples


def _family_of(sample_name: str, families: dict) -> str:
    for suffix in ("_bucket", "_sum", "_count", ""):
        base = sample_name[: len(sample_name) - len(suffix)] if suffix else sample_name
        if suffix and not sample_name.endswith(suffix):
            continue
        if base in families:
            return base
    raise AssertionError(f"sample {sample_name} has no family header")


# ---------------------------------------------------------------------------
# exposition round-trip

def test_expose_single_header_across_children():
    m = MetricsRegistry()
    m.counter("requests_total", "requests").inc(route="a")
    c1 = m.child(component="frontend")
    c2 = m.child(component="worker")
    c1.counter("requests_total", "requests").inc(route="b")
    c2.counter("requests_total", "requests").inc(route="c")
    c2.histogram("latency_seconds", "latency").observe(0.2)

    text = m.expose()
    families, samples = parse_promtext(text)
    # one header pair per family even though three registries contribute
    assert families["dynamo_requests_total"] == ("counter", "requests")
    assert text.count("# TYPE dynamo_requests_total") == 1
    assert text.count("# HELP dynamo_requests_total") == 1
    # all three registries' samples survive the merge
    got = {(s[1].get("route"), s[1].get("component"))
           for s in samples if s[0] == "dynamo_requests_total"}
    assert got == {("a", None), ("b", "frontend"), ("c", "worker")}
    # every sample sits under a declared family
    for name, _, _ in samples:
        _family_of(name, families)


def test_expose_label_escaping_round_trips():
    m = MetricsRegistry()
    nasty = 'say "hi"\\path\nnewline'
    m.counter("events_total", "events").inc(src=nasty)
    families, samples = parse_promtext(m.expose())
    (sample,) = [s for s in samples if s[0] == "dynamo_events_total"]
    assert sample[1]["src"] == nasty
    assert sample[2] == 1.0


def test_expose_histogram_invariants():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds", "latency", buckets=(0.1, 0.25, 1.0))
    for v in (0.05, 0.2, 0.2, 5.0):
        h.observe(v)
    families, samples = parse_promtext(m.expose())
    assert families["dynamo_lat_seconds"][0] == "histogram"
    buckets = [(s[1]["le"], s[2]) for s in samples
               if s[0] == "dynamo_lat_seconds_bucket"]
    # le parses as float ("+Inf" included) and counts are cumulative
    ubs = [math.inf if le == "+Inf" else float(le) for le, _ in buckets]
    assert ubs == sorted(ubs) and ubs[-1] == math.inf
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == 4.0
    (total,) = [s[2] for s in samples if s[0] == "dynamo_lat_seconds_sum"]
    assert total == pytest.approx(5.45)
    (n,) = [s[2] for s in samples if s[0] == "dynamo_lat_seconds_count"]
    assert n == 4.0


def test_func_gauge_callback_error_reads_zero():
    m = MetricsRegistry()
    def boom() -> float:
        raise RuntimeError("collector died")
    g = m.func_gauge("broken_gauge", boom, "never raises at scrape time")
    assert g.get() == 0.0
    families, samples = parse_promtext(m.expose())
    (sample,) = [s for s in samples if s[0] == "dynamo_broken_gauge"]
    assert sample[2] == 0.0


# ---------------------------------------------------------------------------
# tracer

def _mk_tracer(cap: int = 8) -> Tracer:
    return Tracer(component="test", recorder=FlightRecorder(capacity=cap))


def test_span_parent_child_ids_from_wire_context():
    tr = _mk_tracer()
    header = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    wire = TraceContext.parse(header)
    root = tr.start_span("request", ctx=wire, fresh=True)
    assert root.trace_id == "ab" * 16        # inherits the wire trace id
    assert root.parent_id == "cd" * 8        # caller's span becomes parent
    child = tr.start_span("frontend.preprocess", parent=root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    # downstream hops get ctx via the annotation, same parentage rules
    ann = {TRACE_KEY: root.context().header()}
    ctx = trace_context_of(ann)
    hop = tr.start_span("engine.queue", ctx=ctx)
    assert hop.trace_id == root.trace_id and hop.parent_id == root.span_id


def test_start_span_fresh_vs_process_timeline():
    tr = _mk_tracer()
    a = tr.start_span("request", fresh=True)
    b = tr.start_span("request", fresh=True)
    assert a.trace_id != b.trace_id and a.parent_id is None
    k1 = tr.start_span("kv.transfer")
    k2 = tr.start_span("kv.transfer")
    assert k1.trace_id == k2.trace_id == tr.proc_trace_id


def test_end_span_idempotent():
    tr = _mk_tracer()
    s = tr.start_span("x", fresh=True)
    tr.end_span(s, status="ok")
    first_end = s.end
    tr.end_span(s, status="error")
    assert s.end == first_end and s.status == "ok"
    assert len(list(tr.recorder.iter_spans())) == 1


def test_span_contextmanager_records_error_status():
    tr = _mk_tracer()
    with pytest.raises(ValueError):
        with tr.span("op", key="v"):
            raise ValueError("boom")
    (s,) = tr.recorder.iter_spans()
    assert s.status == "error" and s.attrs["error"] == "ValueError"
    with tr.span("op2"):
        pass
    spans = {x.name: x for x in tr.recorder.iter_spans()}
    assert spans["op2"].status == "ok" and spans["op2"].ended


def test_flight_recorder_ring_eviction():
    tr = _mk_tracer(cap=4)
    ids = []
    for i in range(6):
        s = tr.start_span("request", fresh=True, i=i)
        tr.end_span(s)
        ids.append(s.trace_id)
    kept = tr.recorder.trace_ids()
    assert len(kept) == 4
    assert set(kept) == set(ids[2:])        # oldest two evicted


def test_ingest_dedupes_and_validates():
    tr = _mk_tracer()
    s = tr.start_span("engine.decode", fresh=True, tokens=32)
    tr.end_span(s)
    d = s.to_dict()
    assert tr.ingest([d]) == 0              # already recorded locally
    other = Span.from_dict(d)
    other.span_id = "ff" * 8
    assert tr.ingest([other.to_dict()]) == 1
    unended = dict(d, span_id="aa" * 8, end=0.0)
    assert tr.ingest([unended, {"junk": True}, None and {}]) == 0
    assert tr.ingest(None) == 0


def test_chrome_trace_schema():
    tr = _mk_tracer()
    root = tr.start_span("request", fresh=True, request_id="r1")
    child = tr.start_span("engine.prefill", parent=root)
    tr.end_span(child)
    tr.end_span(root)
    tr.recorder.steps.record(ts=1.0, wall_s=0.004, num_prefill=1,
                             num_decode=3, num_waiting=0, num_preempted=0,
                             occupancy=0.5)
    doc = tr.recorder.dump_chrome()
    json.dumps(doc)                          # valid JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"request", "engine.prefill"}
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        assert e["args"]["trace_id"] == root.trace_id
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    counters = [e for e in evs if e["ph"] == "C"]
    assert counters and "engine.batch" in {e["name"] for e in counters}
    # child relationship survives into args
    (pe,) = [e for e in xs if e["name"] == "engine.prefill"]
    assert pe["args"]["parent_id"] == root.span_id


def test_jsonl_dump_round_trip():
    tr = _mk_tracer()
    root = tr.start_span("request", fresh=True)
    tr.end_span(root, status="cancelled")
    lines = tr.recorder.dump_jsonl().strip().splitlines()
    spans = [Span.from_dict(json.loads(l)) for l in lines]
    assert [s.span_id for s in spans] == [root.span_id]
    assert spans[0].status == "cancelled"


def test_step_profiler_ring():
    p = StepProfiler(capacity=4)
    for i in range(6):
        p.record(ts=float(i), wall_s=0.001 * i, num_prefill=0, num_decode=i,
                 num_waiting=0, num_preempted=0, occupancy=0.0)
    snap = p.snapshot()
    assert len(snap) == 4
    assert [r.ts for r in snap] == [2.0, 3.0, 4.0, 5.0]


# ---------------------------------------------------------------------------
# span → metrics bridge

def test_bridge_derives_phase_histograms():
    m = MetricsRegistry()
    bridge = SpanMetricsBridge(m)
    tr = _mk_tracer()
    tr.add_sink(bridge)

    root = tr.start_span("request", fresh=True, model="tiny")
    ttft = tr.start_span("request.ttft", parent=root, model="tiny")
    q = tr.start_span("engine.queue", parent=root, model="tiny")
    tr.end_span(q, end=q.start + 0.01)
    tr.end_span(ttft, end=ttft.start + 0.05)
    d = tr.start_span("engine.decode", parent=root, model="tiny")
    tr.end_span(d, end=d.start + 0.32, tokens=32)
    root.attrs.update(output_tokens=11, ttft_s=0.05)
    tr.end_span(root, end=root.start + 0.15)

    families, samples = parse_promtext(m.expose())
    def count_of(fam):
        return sum(s[2] for s in samples if s[0] == fam + "_count")
    assert count_of("dynamo_request_ttft_seconds") == 1
    assert count_of("dynamo_request_queue_seconds") == 1
    assert count_of("dynamo_request_e2e_seconds") == 1
    assert count_of("dynamo_request_itl_seconds") == 1
    # decode span: 0.32s / 32 tokens = 10ms/token
    (dsum,) = [s[2] for s in samples
               if s[0] == "dynamo_request_decode_per_token_seconds_sum"]
    assert dsum == pytest.approx(0.01, rel=1e-6)
    # ITL: (0.15 - 0.05) / (11 - 1) = 10ms
    (isum,) = [s[2] for s in samples
               if s[0] == "dynamo_request_itl_seconds_sum"]
    assert isum == pytest.approx(0.01, rel=1e-6)


# ---------------------------------------------------------------------------
# real engine (CPU tiny-llama): span lifecycle through the step loop

def _traced_req(rid: str, max_tokens: int = 8):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    ctx = TraceContext.new()
    req = PreprocessedRequest(
        token_ids=[10, 11, 12, 13, 14],
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        annotations={TRACE_KEY: ctx.header()},
    )
    req.request_id = rid
    return req, ctx


@pytest.fixture(scope="module")
def engine_core():
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    return EngineCore(EngineConfig(
        model="tiny-llama", block_size=4, num_blocks=64, max_batch_size=8,
        max_model_len=256, prefill_chunk=32, decode_bucket=(4, 8)))


def test_engine_phase_spans_full_lifecycle(engine_core):
    from dynamo_tpu.obs.tracer import get_tracer

    req, ctx = _traced_req("obs-full", max_tokens=8)
    engine_core.add_request(req)
    for _ in range(200):
        if not engine_core.has_work():
            break
        engine_core.step()
    spans = get_tracer().recorder.spans_for(ctx.trace_id)
    # The compile ledger (lazy mode) attributes any cold XLA compile this
    # traced request triggered as an engine.compile victim span — present
    # only when the jit cache was cold, so tolerated rather than required.
    phase_spans = [s for s in spans if s.name != "engine.compile"]
    by_name = {}
    for s in phase_spans:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) == {"engine.queue", "engine.prefill", "engine.decode"}
    assert all(s.ended for s in spans)
    # queue → prefill → decode ordering on the wall clock
    assert by_name["engine.queue"][0].end <= by_name["engine.prefill"][0].start + 1e-6
    # every decode token is accounted for exactly once across the
    # strided decode spans (the 1st output token comes from prefill)
    assert sum(s.attrs.get("tokens", 0)
               for s in by_name["engine.decode"]) == 7
    final = by_name["engine.decode"][-1]
    assert final.status == "ok" and final.attrs["output_tokens"] == 8
    # all spans share the request's trace and carry the request id
    assert {s.trace_id for s in spans} == {ctx.trace_id}
    assert {s.attrs["request_id"] for s in phase_spans} == {"obs-full"}


def test_engine_abort_closes_span_cancelled(engine_core):
    from dynamo_tpu.obs.tracer import get_tracer

    req, ctx = _traced_req("obs-abort", max_tokens=1000)
    engine_core.add_request(req)
    engine_core.step()
    engine_core.abort("obs-abort")
    spans = get_tracer().recorder.spans_for(ctx.trace_id)
    assert spans and all(s.ended for s in spans)
    assert spans[-1].status == "cancelled"
    while engine_core.has_work():  # drain so the module fixture stays clean
        engine_core.step()


def test_engine_step_profiler_always_on(engine_core):
    from dynamo_tpu.obs.tracer import get_tracer

    # The ring is process-wide and bounded: once earlier tests have filled
    # it a new record evicts an old one, so tell the new ones by their time.
    start = time.time()
    req, _ = _traced_req("obs-steps", max_tokens=4)
    engine_core.add_request(req)
    for _ in range(100):
        if not engine_core.has_work():
            break
        engine_core.step()
    new = [r for r in get_tracer().recorder.steps.snapshot()
           if r.ts >= start]
    assert new
    assert any(r.num_prefill > 0 for r in new)
    assert any(r.num_decode > 0 for r in new)
    assert all(r.wall_s >= 0 and 0 <= r.occupancy <= 1 for r in new)
