"""Find a cell's files by the names in BENCHMARK.json.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
mix (lengths, sampling, ramp, drain, traced slice) is
``chipbench/traffic/<traffic>.json``, shared by every cell that names it;
what is the cell's own (its rate and where that came from; optionally
``max_rows``, or any key of the mix it has to override) is
``chipbench/workloads/<cell name>.json``. The configuration is the directory
of its ``file`` (``config.json`` in the keys ``ModelConfig.from_hf_config``
reads, ``about.json`` beside it; optionally ``reference.py``, the
configuration's own plain reference, and a ``probe`` block in
``about.json``, its own limits: ``harness/probe.py`` finds both).
Nothing here knows any cell, configuration, mix or metric by name.
"""

from __future__ import annotations

import ast
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


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The names of the end-to-end and of the per-layer metrics that the
    cell reports. A per-layer metric is read in the cells its ``workloads``
    lists; one without the key, in every cell that reports the end-to-end
    metric it ``moves`` (a cell that does not judge that metric has nothing
    for it to move: a quantity read there too has an entry of its own that
    moves what the cell does judge, as ``stream.ttft_p50_ms.chat``)."""
    e2e = [m["name"] for m in bench["end_to_end"] if _in_cell(m, cell)]
    layer = [m["name"] for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e)]
    return e2e, layer


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
    end_to_end, per_layer = cell_metrics(bench, name)
    return Cell(
        name=name, chips=entry["chips"], config_name=cfg["name"],
        config_dir=config_dir,
        model=_json(ROOT / cfg["file"]),
        about=_json(config_dir / "about.json"),
        traffic={**_json(data / "traffic" / f"{entry['traffic']}.json"),
                 **_json(data / "workloads" / f"{name}.json")},
        end_to_end=end_to_end, per_layer=per_layer)


# A ``probe`` block's limits, each beside the reading it is held to.
PROBE_PAIRS = (("logprob_tol", "worst_logprob_diff"),
               ("argmax_tol", "worst_argmax_gap"))
RMS_PAIR = ("rms_tol", "rms_logprob_diff")     # the block may leave it out
TIE_KEYS = ("margin", "max_tied_share")        # a routed configuration's


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _functions(ref: Path) -> tuple[ast.Module, set[str]]:
    tree = ast.parse(ref.read_text(), str(ref))
    return tree, {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}


def probe_faults(config_dir: Path, about: dict) -> list[str]:
    """What is wrong with a configuration's own comparison: its ``probe``
    block (each limit above what the configuration gave as stated and at
    least one below what it gave one precision down; ``margin`` and
    ``max_tied_share`` there exactly when the reference names tied
    positions, the share that is tied as stated under its cap) and its
    ``reference.py``. Reads the reference's source and never imports it:
    this runs without JAX."""
    faults: list[str] = []
    ref = Path(config_dir) / "reference.py"
    tree, defined = _functions(ref) if ref.is_file() else (None, set())
    block = about.get("probe")
    tie = block is not None and any(k in block for k in TIE_KEYS)
    if tie != ("routing_margin_at" in defined):
        faults.append(
            "probe block: margin and max_tied_share without a "
            "routing_margin_at in reference.py" if tie else
            "reference.py defines routing_margin_at and the probe block "
            "gives no margin and max_tied_share")
    if block is not None:
        if not block.get("why"):
            faults.append("probe block: no why")
        readings = block.get("readings") or {}
        sides = {k: readings.get(k) for k in ("as_stated", "one_precision_down")}
        faults += [f"probe block: no readings.{k}"
                   for k, r in sides.items() if not isinstance(r, dict)]
        from .probe import RMS_TOL     # here: probe imports this module

        # rms_tol is held to its readings where the block gives it, and the
        # default is where the block gives the readings alone
        limits = {"rms_tol": RMS_TOL, **block}
        pairs = PROBE_PAIRS + ((RMS_PAIR,) if "rms_tol" in block or all(
            isinstance(r, dict) and RMS_PAIR[1] in r
            for r in sides.values()) else ())
        faults += [f"probe block: {tol} is not a number"
                   for tol in [t for t, _ in pairs] + (list(TIE_KEYS) if tie else [])
                   if not _number(limits.get(tol))]
        faults += [f"probe block: readings.{k}.{read} is not a number"
                   for k, r in sides.items() if isinstance(r, dict)
                   for _, read in pairs if not _number(r.get(read))]
        stated = sides["as_stated"]
        if tie and isinstance(stated, dict) and not _number(stated.get("tied_share")):
            faults.append("probe block: no readings.as_stated.tied_share")
        if not faults:
            down = sides["one_precision_down"]
            faults += [f"probe block: {tol} {limits[tol]} is not above the "
                       f"as_stated reading {stated[read]}"
                       for tol, read in pairs
                       if not limits[tol] > stated[read]]
            if not any(limits[tol] < down[read] for tol, read in pairs):
                faults.append("probe block: no tolerance is below its "
                              "one_precision_down reading: lower precision "
                              "would pass")
            if tie:
                faults += [f"probe block: readings.{k}.tied_share "
                           f"{r['tied_share']} is over max_tied_share "
                           f"{block['max_tied_share']}"
                           for k, r in sides.items()
                           if _number(r.get("tied_share"))
                           and r["tied_share"] > block["max_tied_share"]]
                if not 0 < block["max_tied_share"] < 1 or not block["margin"] > 0:
                    faults.append("probe block: margin has to be above 0 and "
                                  "max_tied_share inside (0, 1): a probe "
                                  "that ties nothing or everything")
    if tree is not None:
        if "logits_at" not in defined:
            faults.append("reference.py defines no logits_at")
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            if any(n.split(".")[0] == "dynamo_tpu" for n in names):
                faults.append("reference.py imports dynamo_tpu (line "
                              f"{node.lineno}): the reference shares no "
                              "code with the program")
    return faults


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
        faults += [f"config {cfg['name']}: {fault}"
                   for fault in probe_faults(f.parent, a)]
    for w in bench["workloads"]:
        if not (BENCH / "workloads" / f"{w['name']}.json").is_file():
            faults.append(f"workload {w['name']}: no workloads/{w['name']}.json")
        if not (BENCH / "traffic" / f"{w['traffic']}.json").is_file():
            faults.append(f"workload {w['name']}: no traffic/{w['traffic']}.json")
        if w["config"] not in {c["name"] for c in bench["configs"]}:
            faults.append(f"workload {w['name']}: unknown config {w['config']}")
        judged, layer = cell_metrics(bench, w["name"])
        if "setup_s" not in judged or len(judged) < 2:
            faults.append(f"workload {w['name']}: reports {judged}: not "
                          "setup_s and one more end-to-end metric")
        if not layer:
            faults.append(f"workload {w['name']}: no per-layer metric")
        faults += [f"per-layer metric {m['name']}: moves {m['moves']}, "
                   f"which workload {w['name']} does not report"
                   for m in bench["per_layer"]
                   if m["name"] in layer and m["moves"] not in judged]
    for m in bench["per_layer"]:
        if not (BENCH / "layers" / f"{m['name']}.py").is_file():
            faults.append(f"per-layer metric {m['name']}: no layers/{m['name']}.py")
        if m["moves"] not in e2e:
            faults.append(f"per-layer metric {m['name']}: moves unknown {m['moves']}")
    return faults
