"""The six readers of PR 59 on hand-made contexts: the token gaps the engine
files under the step that made them (``stats()["gaps"]``), off the window's
two snapshots (CPU, no engine; no number here is a measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import random
import sys
from bisect import bisect_right
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from harness import manifest, measure  # noqa: E402
from harness.stats import percentile  # noqa: E402

NAMES = ["engine.gap_p95_ms", "engine.decode_gap_p95_ms",
         "sched.mixed_gaps_pct", "sched.tail_mixed_pct",
         "engine.tail_host_pct", "stream.handover_p95_ms"]

# The program's edges are its own (``dynamo_tpu/obs/sched_ledger.py
# GAP_EDGES``) and ride in every snapshot; the readers take whatever comes.
# These have the same make: 2.5 % apart from 0.5 ms to 1 s, coarser outside.
EDGES = ([1e-5, 2e-5, 5e-5, 1e-4, 2e-4, 3.5e-4]
         + [5e-4 * 2000.0 ** (i / 308) for i in range(309)]
         + [1.25, 1.6, 2.0, 2.5, 3.2, 4.0, 5.0])


def _hist(gaps, wait_share: float = 0.0) -> dict:
    """A cell of ``by_class`` as the ledger's snapshot gives it."""
    n = len(EDGES) + 1
    rows, gap_s, wait_s = [0] * n, [0.0] * n, [0.0] * n
    for g in gaps:
        i = bisect_right(EDGES, g)
        rows[i] += 1
        gap_s[i] += g
        wait_s[i] += wait_share * g
    used = [i for i, c in enumerate(rows) if c]
    lo, hi = (used[0], used[-1] + 1) if used else (n, n)
    return {"lo": lo, "rows": rows[lo:hi], "gap_s": gap_s[lo:hi],
            "wait_s": wait_s[lo:hi], "steps": len(gaps),
            "period_s": sum(gaps)}


def _gaps(by_class: dict, handover=()) -> dict:
    h = _hist(handover)
    return {"edges": EDGES,
            "by_class": {cls: {str(b): _hist(*cell) for b, cell in cells.items()}
                         for cls, cells in by_class.items()},
            "handover": {"count": len(handover), "sum_s": sum(handover),
                         "max_s": max(handover, default=0.0), "lo": h["lo"],
                         "buckets": h["rows"]}}


def _ctx(first: dict | None, last: dict | None) -> measure.Context:
    c0, c1 = ({} if g is None else {"gaps": g} for g in (first, last))
    return measure.Context(window=(100.0, 151.0), window_wall=(1e9, 1e9 + 51),
                           chips=1, records=[], counters=(c0, c1))


def _read(name: str, ctx):
    return measure.load_reader(name).read(ctx)


def _width(ms: float) -> float:
    """The width in ms of the bucket that holds ``ms``."""
    i = bisect_right(EDGES, ms / 1e3)
    return 1e3 * (EDGES[i] - (EDGES[i - 1] if i else 0.0))


@pytest.fixture(scope="module")
def cell_like():
    """A window like the hybrid cell's: ~12,000 decode gaps near 12.8 ms in
    two row buckets, a twentieth under it mixed near 25 ms, a few stalls;
    before the window the ledger held a ramp's worth already."""
    rng = random.Random(59)
    ramp = {"decode": {16: ([rng.gauss(0.0125, 0.0004) for _ in range(3000)],
                            0.7)},
            "mixed": {32: ([rng.gauss(0.024, 0.001) for _ in range(100)], 0.9)}}
    dec16 = [rng.gauss(0.0126, 0.0004) for _ in range(5000)]
    dec32 = [rng.gauss(0.0131, 0.0005) for _ in range(7000)]
    mixed = [rng.gauss(0.0248, 0.0012) for _ in range(540)]
    stalls = [0.118, 0.204, 0.31]
    win = {"decode": {16: (ramp["decode"][16][0] + dec16, 0.7),
                      32: (dec32 + stalls, 0.7)},
           "mixed": {32: (ramp["mixed"][32][0] + mixed, 0.9)}}
    hand0 = [rng.uniform(4e-5, 9e-5) for _ in range(400)]
    hand = [rng.uniform(4e-5, 9e-5) for _ in range(3900)] + [0.0009, 0.11]
    ctx = _ctx(_gaps(ramp, hand0), _gaps(win, hand0 + hand))
    return ctx, dec16 + dec32 + stalls, mixed, hand


def test_gap_p95_is_the_percentile_of_the_windows_gaps(cell_like):
    """Against ``harness/stats.py percentile`` on the same values, to the
    width of the bucket the percentile lies in (2.5 % of it)."""
    ctx, decode, mixed, _ = cell_like
    exact = percentile([g * 1e3 for g in decode + mixed], 95)
    got = _read("engine.gap_p95_ms", ctx)
    assert abs(got - exact) <= _width(exact), (got, exact)
    exact_dec = percentile([g * 1e3 for g in decode], 95)
    got_dec = _read("engine.decode_gap_p95_ms", ctx)
    assert abs(got_dec - exact_dec) <= _width(exact_dec)
    assert got_dec < got       # the mixed gaps stand in the whole's tail


def test_shares_of_the_window_and_of_its_tail(cell_like):
    ctx, decode, mixed, _ = cell_like
    n = len(decode) + len(mixed)
    assert _read("sched.mixed_gaps_pct", ctx) == pytest.approx(
        100.0 * len(mixed) / n)
    # the tail from the percentile's bucket on: what lies there, counted
    base = measure.load_reader("engine.gap_p95_ms")
    i = base.quantile_bucket(base.merged(base.window(ctx)), 95)[0]
    lo = EDGES[i - 1]
    t_dec = [g for g in decode if g >= lo]
    t_mix = [g for g in mixed if g >= lo]
    assert 0.05 * n <= len(t_dec) + len(t_mix) <= 0.08 * n
    assert _read("sched.tail_mixed_pct", ctx) == pytest.approx(
        100.0 * len(t_mix) / (len(t_dec) + len(t_mix)))
    wait = 0.7 * sum(t_dec) + 0.9 * sum(t_mix)
    assert _read("engine.tail_host_pct", ctx) == pytest.approx(
        100.0 * (1.0 - wait / (sum(t_dec) + sum(t_mix))))


def test_handover_p95_reads_the_windows_hand_overs(cell_like):
    ctx, _, _, hand = cell_like
    exact = percentile([h * 1e3 for h in hand], 95)
    got = _read("stream.handover_p95_ms", ctx)
    assert abs(got - exact) <= _width(exact) and got < 0.1


@pytest.mark.parametrize("by_class, expect", [
    # past the cliff: more than a twentieth of the gaps are mixed steps'
    ({"decode": {8: ([0.010] * 90, 0.8)}, "mixed": {8: ([0.040] * 10, 0.9)}},
     {"sched.mixed_gaps_pct": 10.0, "sched.tail_mixed_pct": 100.0,
      "engine.tail_host_pct": 10.0}),
    # no chunk step at all: the class is absent, the shares read 0
    ({"decode": {8: ([0.010] * 100, 0.75)}},
     {"sched.mixed_gaps_pct": 0.0, "sched.tail_mixed_pct": 0.0,
      "engine.tail_host_pct": 25.0}),
    # a tail made behind a host stall: the device wait is a sliver of it
    ({"decode": {8: ([0.010] * 94, 0.8), 16: ([0.200] * 6, 0.04)}},
     {"sched.tail_mixed_pct": 0.0, "engine.tail_host_pct": 96.0}),
    # speculation on: verify steps' gaps count in the whole, not as mixed
    ({"verify": {4: ([0.015] * 50,)}, "mixed": {4: ([0.030] * 50,)}},
     {"sched.mixed_gaps_pct": 50.0, "engine.decode_gap_p95_ms": None}),
], ids=["past_the_cliff", "no_chunk_step", "host_stall", "verify"])
def test_small_windows_by_hand(by_class, expect):
    ctx = _ctx(_gaps({}), _gaps(by_class))
    for name, value in expect.items():
        got = _read(name, ctx)
        assert got == (None if value is None else pytest.approx(value)), name


def test_interpolation_inside_one_bucket():
    """A hundred equal gaps: the percentile lies inside their bucket, 95
    hundredths of the way through it."""
    ctx = _ctx(None, _gaps({"decode": {8: ([0.0128] * 100,)}}))
    i = bisect_right(EDGES, 0.0128)
    got = _read("engine.gap_p95_ms", ctx) / 1e3
    assert EDGES[i - 1] <= got < EDGES[i]
    assert got == pytest.approx(EDGES[i - 1] + 0.9455 * (EDGES[i] - EDGES[i - 1]))


@pytest.mark.parametrize("first, last", [
    (None, None),                             # the parent of PR 59
    (_gaps({}), _gaps({})),                   # the ledger on, nothing filed
    # a window that filed nothing: the totals stood still
    (_gaps({"decode": {8: ([0.01] * 10,)}}, [1e-4] * 3),
     _gaps({"decode": {8: ([0.01] * 10,)}}, [1e-4] * 3)),
], ids=["parent", "empty", "still"])
def test_none_where_there_is_nothing_to_read(first, last):
    ctx = _ctx(first, last)
    assert [_read(name, ctx) for name in NAMES] == [None] * 6
    assert measure.per_layer(ctx, NAMES) == {}


def test_the_manifest_enters_them_as_the_readers_say():
    """Six entries side by side in ``per_layer`` (a later PR's come after
    them), each what its reader declares, under a layer the benchmark
    named before, with no ``workloads`` list: every cell reports
    ``itl_p95_ms``."""
    bench = manifest.load_benchmark()
    assert manifest.check() == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    at = list(entries).index(NAMES[0])
    assert list(entries)[at:at + 6] == NAMES
    older = {m["layer"] for m in bench["per_layer"][:at]}
    for name in NAMES:
        reader = measure.load_reader(name)
        assert entries[name] == {
            "name": name, "unit": reader.unit, "better": "lower",
            "source": "program_counter", "layer": reader.layer,
            "moves": "itl_p95_ms"}
        assert reader.source == "program_counter" and reader.layer in older
        assert all(" " <= ch <= "~" for ch in reader.layer)
    for w in bench["workloads"]:
        judged, layer = manifest.cell_metrics(bench, w["name"])
        assert "itl_p95_ms" in judged and set(NAMES) <= set(layer)
