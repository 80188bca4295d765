"""Find a cell's files by the names in BENCHMARK.json.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
mix (lengths, sampling, ramp, drain, traced slice) is
``chipbench/traffic/<traffic>.json``, shared by every cell that names it;
what is the cell's own (its rate and where that came from; optionally
``max_rows``, or any key of the mix it has to override) is
``chipbench/workloads/<cell name>.json``. The configuration is the directory
of its ``file`` (``config.json`` in the keys ``ModelConfig.from_hf_config``
reads, ``about.json`` beside it). Nothing here knows any cell,
configuration, mix or metric by name.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
OUT = BENCH / "out"


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config_dir: Path
    model: dict          # config.json
    about: dict          # about.json
    traffic: dict        # traffic/<traffic>.json, then workloads/<name>.json
    end_to_end: list     # metric names this cell reports
    per_layer: list


def load_benchmark(path: Path | None = None) -> dict:
    return json.loads((path or ROOT / "BENCHMARK.json").read_text())


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, bench: dict | None = None,
              data_dir: Path | None = None) -> Cell:
    """``data_dir`` holds ``traffic/`` and ``workloads/`` (the rehearsal
    keeps its own)."""
    bench = bench or load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config_dir = (ROOT / cfg["file"]).parent
    data = data_dir or BENCH
    return Cell(
        name=name, chips=entry["chips"], config_name=cfg["name"],
        config_dir=config_dir,
        model=_json(ROOT / cfg["file"]),
        about=_json(config_dir / "about.json"),
        traffic={**_json(data / "traffic" / f"{entry['traffic']}.json"),
                 **_json(data / "workloads" / f"{name}.json")},
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _in_cell(m, name)],
        per_layer=[m["name"] for m in bench["per_layer"]
                   if _in_cell(m, name)])


def check(bench: dict | None = None) -> list[str]:
    """Every file the manifest names exists and agrees with it; returns
    the faults found (none: an empty list)."""
    bench = bench or load_benchmark()
    faults: list[str] = []
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cfg in bench["configs"]:
        f = ROOT / cfg["file"]
        if not f.is_file():
            faults.append(f"config {cfg['name']}: no file {cfg['file']}")
            continue
        about = f.parent / "about.json"
        if not about.is_file():
            faults.append(f"config {cfg['name']}: no about.json")
            continue
        a = json.loads(about.read_text())
        if a["source"] != cfg["source"]:
            faults.append(f"config {cfg['name']}: source differs from about.json")
        if sorted(a["reduced"]) != sorted(cfg["reduced"]):
            faults.append(f"config {cfg['name']}: reduced differs from about.json")
    for w in bench["workloads"]:
        if not (BENCH / "workloads" / f"{w['name']}.json").is_file():
            faults.append(f"workload {w['name']}: no workloads/{w['name']}.json")
        if not (BENCH / "traffic" / f"{w['traffic']}.json").is_file():
            faults.append(f"workload {w['name']}: no traffic/{w['traffic']}.json")
        if w["config"] not in {c["name"] for c in bench["configs"]}:
            faults.append(f"workload {w['name']}: unknown config {w['config']}")
    for m in bench["per_layer"]:
        if not (BENCH / "layers" / f"{m['name']}.py").is_file():
            faults.append(f"per-layer metric {m['name']}: no layers/{m['name']}.py")
        if m["moves"] not in e2e:
            faults.append(f"per-layer metric {m['name']}: moves unknown {m['moves']}")
    return faults
