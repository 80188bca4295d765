"""The strings of ``BENCHMARK.json`` that the driver reads before any run.

PR 55 was refused with nothing measured, ``manifest_invalid``: ``config
kanana-2-30b-a3b-ep8: why must be 1 to 200 printable characters, not ...``.
The driver checks the file itself; ``chipbench.harness.manifest.check()``
does not hold the strings to that rule (the validator is the benchmark's and
is not edited here), so nothing in the repo told the builder. This test does:
every configuration's and cell's ``why`` is 1 to 200 characters of printable
ASCII on one line, a ``source`` at most 200, and a name at most 64 of
letters, digits, ``_``, ``.`` and ``-``, not starting with ``.`` or ``-``.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
ENTRIES = [(kind, e) for kind in ("configs", "workloads")
           for e in MANIFEST[kind]]


def _printable(text: str) -> bool:
    return all(" " <= ch <= "~" for ch in text)


@pytest.mark.parametrize("kind, entry", ENTRIES,
                         ids=[f"{k}:{e['name']}" for k, e in ENTRIES])
def test_an_entrys_strings_are_what_the_driver_takes(kind, entry):
    why = entry["why"]
    assert isinstance(why, str) and 1 <= len(why) <= 200, len(why)
    assert _printable(why), [ch for ch in why if not " " <= ch <= "~"]
    assert NAME.fullmatch(entry["name"]), entry["name"]
    if kind == "configs":
        assert 1 <= len(entry["source"]) <= 200 and _printable(entry["source"])
        assert len(entry["reduced"]) <= 16
        assert all(NAME.fullmatch(key) for key in entry["reduced"])
        assert all(" " not in part and _printable(part)
                   for part in entry["file"].split("/"))
    else:
        assert NAME.fullmatch(entry["config"]) and NAME.fullmatch(entry["traffic"])
        assert entry["chips"] in (1, 4)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_a_metrics_strings_are_what_the_driver_takes(metric):
    assert NAME.fullmatch(metric["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    if "layer" in metric:
        assert 1 <= len(metric["layer"]) <= 200 and _printable(metric["layer"])
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", ())) <= cells


def test_the_file_is_one_the_driver_reads_whole():
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for kind in ("configs", "workloads"):
        own = [e["name"] for e in MANIFEST[kind]]
        assert len(own) == len(set(own)) and 1 <= len(own) <= 24
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}


def test_the_benchmarks_own_check_has_no_fault():
    sys.path.insert(0, str(ROOT / "chipbench"))
    try:
        from harness import manifest
    finally:
        sys.path.remove(str(ROOT / "chipbench"))
    assert manifest.check() == []
