"""``tokens_per_s`` on replayed traces (CPU, no engine; no number here is a
measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider

Each cell's fixed trace (``traffic.schedule``, ramp and window) is replayed
with every request modelled as ``due + ttft + k x itl``: token k of a request
arrives that long after the request was due. The step times are the
ledger's: PR 26's parent and change in each cell (for the long-prompt cell
the gap between tokens is its decode step, as the ledger's ``itl_p95_ms``
there is the tail a chunk step makes).
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import sweep  # noqa: E402
from harness import manifest, measure, traffic  # noqa: E402
from harness.loadgen import Record  # noqa: E402

WINDOW_S = 51.0
T0 = 1000.0          # the run's start on the records' clock
# (ttft_s, itl_s) at PR 26's parent and with its change (ledger, PR 26).
STEP_TIMES = {
    "mistral-7b.chat": ((0.298, 0.0305), (0.200, 0.0140)),
    "mistral-7b.longprompt": ((2.093, 0.0320), (1.745, 0.0150)),
    "mistral-nemo-12b.chat": ((0.229, 0.0255), (0.165, 0.0117)),
}
CELLS = sorted(STEP_TIMES)
ORDERS = (1, 2, 3, 4)


def replay(cell: str, ttft_s: float, itl_s: float, order: int = traffic.ORDER,
           chips: int = 1, cut: bool = True) -> measure.Context:
    """The cell's trace as ``serve.offer`` offers it, served by the constant
    model; deltas after the run's cut never arrived."""
    tr = manifest.load_cell(cell).traffic
    ramp_s, rate = float(tr["ramp_s"]), float(tr["rate_per_s"])
    ramp = traffic.schedule(tr, 8, ramp_s, 0, rate, 1, order)
    win = traffic.schedule(tr, 8, WINDOW_S, 0, rate, 0, order)
    w0 = T0 + ramp_s
    w1 = w0 + WINDOW_S
    end_by = w1 + float(tr["drain_s"]) if cut else float("inf")
    recs = []
    for base, reqs in ((T0, ramp), (w0, win)):
        for r in reqs:
            rec = Record(len(recs), base + r.due_s, len(r.prompt),
                         r.max_tokens)
            arrivals = [rec.due + ttft_s + k * itl_s
                        for k in range(r.max_tokens)]
            rec.deltas = [(t, 1) for t in arrivals if t < end_by]
            rec.finish = "length" if len(rec.deltas) == r.max_tokens else None
            recs.append(rec)
    return measure.Context(window=(w0, w1), window_wall=(w0, w1), chips=chips,
                           records=recs, counters=({}, {}))


def offered(ctx: measure.Context) -> float:
    return sum(r.max_tokens for r in ctx.due_in_window) / ctx.seconds


def both(cell: str, order: int = traffic.ORDER) -> tuple[dict, dict]:
    slow, fast = STEP_TIMES[cell]
    return (measure.end_to_end(replay(cell, *slow, order=order), 0.0),
            measure.end_to_end(replay(cell, *fast, order=order), 0.0))


def test_the_old_count_reproduces_the_refusal_of_pr_26():
    """``mistral-nemo-12b.chat``, 229 / 25.5 ms against 165 / 11.7 ms: the
    count of whatever crossed the window's edges falls by more than the 1 %
    bound when the engine gets faster."""
    parent, change = both("mistral-nemo-12b.chat")
    old = "tokens_in_window_per_s"
    assert change[old] < parent[old] * 0.99
    # The ledger read 144.64 -> 141.98; this model reads 143.1 -> 140.7.
    assert parent[old] == pytest.approx(143.1, abs=0.3)
    assert change[old] == pytest.approx(140.7, abs=0.3)


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("cell", CELLS)
def test_a_faster_engine_reads_higher_and_never_over_the_offered_load(
        cell, order):
    parent, change = both(cell, order)
    assert change["tokens_per_s"] > parent["tokens_per_s"] * 1.01
    load = offered(replay(cell, 0.1, 0.01, order=order))
    assert parent["tokens_per_s"] <= change["tokens_per_s"] <= load


@pytest.mark.parametrize("cell", CELLS)
def test_moving_every_token_earlier_never_lowers_it(cell):
    """Ever faster engines on one trace: the metric rises, or stays at the
    offered load once every stream ends inside the window."""
    values = [measure.end_to_end(replay(cell, ttft, itl), 0.0)["tokens_per_s"]
              for ttft, itl in ((2.0, 0.08), (1.0, 0.04), (0.5, 0.02),
                                (0.2, 0.01), (0.1, 0.004), (0.01, 0.0005))]
    assert values == sorted(values)
    assert values[0] < values[-1]


@pytest.mark.parametrize("cell", CELLS)
def test_streams_that_all_end_inside_the_window_read_the_offered_load(cell):
    ctx = replay(cell, 0.001, 0.0001)      # 384 tokens in under 40 ms
    assert max(t for r in ctx.due_in_window for t, _ in r.deltas) < ctx.window[1]
    e2e = measure.end_to_end(ctx, 0.0)
    assert e2e["tokens_per_s"] == pytest.approx(offered(ctx), rel=1e-12)


def test_the_offered_loads_are_the_issues():
    """143.63 / 120.10 / 24.18 tokens/s (ISSUE 27): the ceiling of each cell."""
    got = [offered(replay(c, 0.1, 0.01)) for c in CELLS]
    assert got == pytest.approx([120.10, 24.18, 143.63], abs=0.01)


def test_a_trace_cut_at_the_drain_counts_what_arrived_over_the_stretch():
    """A saturated engine (1 s to the first token, 0.25 s a token): streams
    are cut 20 s after the window's end; the metric is the tokens delivered
    by then over the whole stretch to the cut."""
    cell = "mistral-7b.chat"
    ctx = replay(cell, 1.0, 0.25)
    lo, hi = ctx.window
    cut_at = hi + manifest.load_cell(cell).traffic["drain_s"]
    due = ctx.due_in_window
    assert any(r.finish is None for r in due)
    delivered = sum(k for r in due for _, k in r.deltas)
    assert delivered < sum(r.max_tokens for r in due)
    e2e = measure.end_to_end(ctx, 0.0)
    last = max(t for r in due for t, _ in r.deltas)
    assert cut_at - 0.25 <= last < cut_at
    assert e2e["tokens_per_s"] == pytest.approx(delivered / (last - lo))
    # Uncut, the same engine would have been credited tokens it never sent.
    uncut = replay(cell, 1.0, 0.25, cut=False)
    assert sum(k for r in uncut.due_in_window for _, k in r.deltas) > delivered


def test_ramp_records_are_ignored():
    cell = "mistral-nemo-12b.chat"
    ctx = replay(cell, 0.229, 0.0255)
    lo, _ = ctx.window
    ramp = [r for r in ctx.records if r.due < lo]
    assert ramp and any(t >= lo for r in ramp for t, _ in r.deltas)
    with_ramp = measure.end_to_end(ctx, 0.0)
    ctx.records = [r for r in ctx.records if r.due >= lo]
    without = measure.end_to_end(ctx, 0.0)
    assert with_ramp["tokens_per_s"] == without["tokens_per_s"]
    assert with_ramp["tokens_in_window_per_s"] > without["tokens_in_window_per_s"]


def test_no_request_due_in_the_window_reads_zero():
    ctx = replay("mistral-7b.chat", 0.3, 0.03)
    ctx.records = [r for r in ctx.records if r.due < ctx.window[0]]
    assert measure.end_to_end(ctx, 0.0)["tokens_per_s"] == 0.0


@pytest.mark.parametrize("name", ["tokens_per_s", "tokens_in_window_per_s"])
def test_four_chips_divide_once(name):
    one = measure.end_to_end(replay("mistral-7b.chat", 0.3, 0.03), 0.0)
    four = measure.end_to_end(replay("mistral-7b.chat", 0.3, 0.03, chips=4), 0.0)
    assert four[name] == pytest.approx(one[name] / 4)


def test_the_other_metrics_are_computed_as_before():
    """TTFT and the gaps come from the requests due in the window, from the
    instant each was due: the constant model gives them back."""
    e2e = measure.end_to_end(replay("mistral-nemo-12b.chat", 0.229, 0.0255), 7.5)
    assert e2e["setup_s"] == 7.5
    assert e2e["ttft_mean_ms"] == pytest.approx(229.0)
    assert e2e["ttft_p50_ms"] == pytest.approx(229.0)
    assert e2e["itl_p95_ms"] == pytest.approx(25.5)


@pytest.mark.parametrize("chips", [1, 4])
def test_sweeps_share_is_of_all_chips_tokens_in_the_window(chips):
    """``sweep.py``: the kept-up share is the old count, over all chips, of
    the offered tokens: on four chips it read a quarter (PERF.md, PR 25)."""
    ctx = replay("mistral-7b.chat", 0.3, 0.03, chips=chips)
    e2e = measure.end_to_end(ctx, 0.0)
    lo, hi = ctx.window
    arrived = sum(k for r in ctx.records for t, k in r.deltas if lo <= t < hi)
    share = sweep.kept_up_share(e2e, offered(ctx), ctx.chips)
    assert share == pytest.approx(arrived / (offered(ctx) * ctx.seconds))
    assert share >= sweep.KEPT_UP_SHARE
