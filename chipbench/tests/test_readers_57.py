"""``engine.programs_loaded_pct`` (PR 57) on hand-made contexts: the share of
the step programs that came whole from the program store, off the compile
ledger at the window's end (CPU, no engine; no number here is a measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from harness import manifest, measure  # noqa: E402

NAME = "engine.programs_loaded_pct"


def _ctx(led_first: dict | None, led_last: dict | None) -> measure.Context:
    c0, c1 = ({} if led is None else {"compile": led}
              for led in (led_first, led_last))
    return measure.Context(window=(100.0, 151.0), window_wall=(1e9, 1e9 + 51),
                           chips=1, records=[], counters=(c0, c1))


def _led(programs: int, loaded: int | None) -> dict:
    led = {"cache_entries": programs, "compile_seconds_total": 2.0 * programs,
           "serve_stall_seconds": 0.0, "events_total": programs,
           "layer_bodies": 13 * programs, "layer_bodies_traced": 3 * programs}
    if loaded is not None:
        led["programs_loaded"] = loaded
    return led


@pytest.mark.parametrize("ctx, expect", [
    # a warm start: every one of the hybrid cell's 21 programs loaded
    (_ctx(_led(21, 21), _led(21, 21)), 100.0),
    # a cold start built them all, and wrote them
    (_ctx(_led(21, 0), _led(21, 0)), 0.0),
    # one entry was damaged and built again
    (_ctx(_led(14, 13), _led(14, 13)), 100.0 * 13 / 14),
    # a program the serving path had to build inside the window is a program
    # that was not loaded: the window's last edge is what is read
    (_ctx(_led(14, 14), _led(15, 14)), 100.0 * 14 / 15),
    # the parent's ledger has no such count; no program; no ledger at all
    (_ctx(_led(21, None), _led(21, None)), None),
    (_ctx(_led(0, 0), _led(0, 0)), None),
    (_ctx(None, None), None),
], ids=["warm", "cold", "one_rebuilt", "built_in_window", "parent",
        "no_programs", "no_ledger"])
def test_programs_loaded_pct_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader(NAME).read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def test_the_line_leaves_it_out_where_the_ledger_lacks_the_count():
    """What the parent of PR 57 reports in a traced run: its other build
    metrics, and not this one."""
    parent = measure.per_layer(
        _ctx(_led(21, None), _led(21, None)),
        [NAME, "engine.warmup_s_per_program",
         "engine.layer_bodies_traced_pct"])
    assert sorted(parent) == ["engine.layer_bodies_traced_pct",
                              "engine.warmup_s_per_program"]
    change = measure.per_layer(_ctx(_led(21, 21), _led(21, 21)), [NAME])
    assert change == {NAME: {"value": 100.0, "unit": "%"}}


def test_every_cell_reports_it_beside_the_other_build_metrics():
    """It moves ``setup_s``, which every cell reports: no ``workloads``."""
    bench = manifest.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    reader = measure.load_reader(NAME)
    assert entry == {"name": NAME, "unit": reader.unit, "better": "higher",
                     "source": reader.source, "layer": reader.layer,
                     "moves": reader.moves}
    assert bench["per_layer"][-1] == entry
    others = {m["layer"] for m in bench["per_layer"]
              if m["name"] in ("engine.warmup_s_per_program",
                               "engine.layer_bodies_traced_pct")}
    assert others == {entry["layer"]}
    for w in bench["workloads"]:
        _judged, layer = manifest.cell_metrics(bench, w["name"])
        assert NAME in layer
