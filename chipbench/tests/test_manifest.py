"""Which metrics a cell reports (``manifest.cell_metrics``), the faults
``manifest.check`` finds in them and in a routed configuration's comparison,
and the heartbeat's reading of a stalled machine (``measure.stalls``). CPU,
no engine.

    python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from harness import manifest, measure  # noqa: E402

REAL = manifest.load_benchmark()
CELLS = [w["name"] for w in REAL["workloads"]]


def test_the_tree_has_no_fault():
    assert manifest.check() == []


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_what_the_contract_asks(cell):
    judged, layer = manifest.cell_metrics(REAL, cell)
    assert "setup_s" in judged and len(judged) >= 2 and layer
    by_name = {m["name"]: m for m in REAL["per_layer"]}
    # Every per-layer metric read in the cell moves something judged there.
    assert {by_name[n]["moves"] for n in layer} <= set(judged)
    # No quantity is read twice in one cell, under its name and its twin's.
    assert not {n + ".chat" for n in layer} & set(layer)


def test_time_to_first_token_is_judged_where_it_is_steady():
    for cell in CELLS:
        judged, layer = manifest.cell_metrics(REAL, cell)
        if "ttft_mean_ms" in judged:
            assert "stream.ttft_p50_ms" in layer
            assert "stream.ttft_mean_ms.chat" not in layer
        else:
            assert {"stream.ttft_mean_ms.chat", "stream.ttft_p50_ms.chat",
                    "engine.prefill_mean_ms.chat"} <= set(layer)
            assert "stream.ttft_p50_ms" not in layer


def test_a_metric_without_workloads_follows_what_it_moves():
    bench = copy.deepcopy(REAL)
    bench["per_layer"].append({"name": "x.new", "moves": "ttft_mean_ms"})
    for cell in CELLS:
        judged, layer = manifest.cell_metrics(bench, cell)
        assert ("x.new" in layer) == ("ttft_mean_ms" in judged)


def _faults(edit) -> list[str]:
    bench = copy.deepcopy(REAL)
    edit(bench)
    return manifest.check(bench)


def test_check_refuses_a_metric_that_moves_what_its_cell_does_not_report():
    cell = next(c for c in CELLS
                if "ttft_mean_ms" not in manifest.cell_metrics(REAL, c)[0])

    def edit(bench):
        entry = next(m for m in bench["per_layer"]
                     if m["name"] == "stream.ttft_p50_ms")
        entry["workloads"] = [cell]

    assert any("stream.ttft_p50_ms: moves ttft_mean_ms" in f and cell in f
               for f in _faults(edit))


def test_check_refuses_a_cell_with_nothing_judged_or_nothing_read():
    def only_setup(bench):
        for m in bench["end_to_end"]:
            if m["name"] != "setup_s":
                m["workloads"] = [CELLS[0]]

    assert any(f"workload {CELLS[1]}: reports ['setup_s']" in f
               for f in _faults(only_setup))

    def nothing_read(bench):
        for m in bench["per_layer"]:
            m["workloads"] = [CELLS[0]]

    assert f"workload {CELLS[1]}: no per-layer metric" in _faults(nothing_read)


ROUTED = {
    "logprob_tol": 0.1, "argmax_tol": 0.05, "rms_tol": 0.03, "margin": 0.06,
    "max_tied_share": 0.5, "why": "8 routed experts, 2 a token",
    "readings": {
        "as_stated": {"worst_logprob_diff": 0.08, "worst_argmax_gap": 0.04,
                      "rms_logprob_diff": 0.02, "tied_share": 0.4},
        "one_precision_down": {"worst_logprob_diff": 0.06,
                               "worst_argmax_gap": 0.0,
                               "rms_logprob_diff": 0.05, "tied_share": 0.4}}}
TWO_FUNCTIONS = ("def logits_at(params, model, tokens, positions, pad_to=0):\n"
                 "    return None\n\n\ndef routing_margin_at(params, model, "
                 "tokens, positions, pad_to=0):\n    return None\n")


def _with_config(tmp_path, block, reference) -> list[str]:
    """``manifest.check`` on the tree's manifest plus one configuration in
    ``tmp_path``: its faults alone."""
    about = {"source": "none", "reduced": {}, "seed": 0, "engine": {}}
    if block is not None:
        about["probe"] = block
    (tmp_path / "about.json").write_text(json.dumps(about))
    (tmp_path / "config.json").write_text("{}")
    (tmp_path / "reference.py").write_text(reference)
    bench = dict(REAL, configs=REAL["configs"] + [{
        "name": "tmp", "source": "none", "reduced": [],
        "file": str(tmp_path / "config.json")}])
    faults = manifest.check(bench)
    assert all(f.startswith("config tmp: ") for f in faults), faults
    return faults


def _block(**edits) -> dict:
    block = copy.deepcopy(ROUTED)
    for key, value in edits.items():
        where, _, leaf = key.rpartition("__")
        d = block
        for part in filter(None, where.split("__")):
            d = d[part]
        if value is None:
            del d[leaf]
        else:
            d[leaf] = value
    return block


def test_check_takes_a_routed_configurations_comparison(tmp_path):
    assert _with_config(tmp_path, ROUTED, TWO_FUNCTIONS) == []


@pytest.mark.parametrize("block, reference, said", [
    (ROUTED, TWO_FUNCTIONS.split("\n\n\n")[0] + "\n",
     "margin and max_tied_share without a routing_margin_at"),
    (_block(margin=None, max_tied_share=None), TWO_FUNCTIONS,
     "defines routing_margin_at and the probe block gives no margin"),
    (None, TWO_FUNCTIONS,
     "defines routing_margin_at and the probe block gives no margin"),
    (_block(margin="0.06"), TWO_FUNCTIONS, "margin is not a number"),
    (_block(max_tied_share=None), TWO_FUNCTIONS,
     "max_tied_share is not a number"),
    (_block(readings__as_stated__tied_share=None), TWO_FUNCTIONS,
     "no readings.as_stated.tied_share"),
    (_block(readings__as_stated__tied_share=0.6), TWO_FUNCTIONS,
     "readings.as_stated.tied_share 0.6 is over max_tied_share 0.5"),
    (_block(rms_tol=0.02), TWO_FUNCTIONS,
     "rms_tol 0.02 is not above the as_stated reading 0.02"),
    (_block(rms_tol=0.06), TWO_FUNCTIONS, "no tolerance is below"),
], ids=["margin-without-function", "function-without-margin",
        "function-without-block", "margin-not-a-number",
        "share-not-a-number", "no-tied-share-reading", "tied-share-over-cap",
        "rms-tol-not-above-stated", "nothing-fails-one-precision-down"])
def test_check_names_each_fault_of_a_routed_comparison(tmp_path, block,
                                                       reference, said):
    faults = _with_config(tmp_path, block, reference)
    assert any(said in f for f in faults), faults


def _ctx(late: list[float]) -> measure.Context:
    return measure.Context(window=(0.0, 51.0), window_wall=(0.0, 51.0),
                           chips=1, records=[], counters=({}, {}),
                           beat_late_s=late)


def test_stalls_on_a_quiet_machine():
    s = measure.stalls(_ctx([0.001, 0.002, 0.0005] * 300))
    assert s["stalls"] == 0 and s["stalled_ms"] == 0.0
    assert s["stall_max_ms"] == pytest.approx(2.0)
    assert measure.stalls(_ctx([])) == {
        "stall_max_ms": 0.0, "stalls": 0, "stalled_ms": 0.0}


def test_one_stall_makes_every_beat_inside_it_late_and_counts_once():
    # 3.2 s stopped: the 64 beats due inside it run together at its end.
    frozen = [3.2 - 0.05 * i for i in range(64)]
    late = [0.001] * 100 + frozen + [0.001] * 100 + [0.4, 0.35] + [0.001]
    s = measure.stalls(_ctx(late))
    assert s["stalls"] == 2
    assert s["stall_max_ms"] == pytest.approx(3200.0)
    assert s["stalled_ms"] == pytest.approx(3600.0)
