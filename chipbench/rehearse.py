"""Rehearse the benchmark on the CPU: no chip time, and no number under a
device metric's name.

    python3 chipbench/rehearse.py

Checks the schedule arithmetic (the same multiset in every seed, due times),
the percentile and gap arithmetic on a synthetic stream, the interval
arithmetic of the trace reduction and its reading of a small recorded
``.xplane.pb``, the manifest against every file it names, the warm-up set
against the lengths a cell can reach, and then runs the whole harness, probe
against the reference included, on the tiny configurations kept under
``chipbench/rehearsal/``: ``tiny`` (dense: the default reference and
limits) and ``tiny-moe`` (routed experts: its own ``reference.py``, which
names the positions where bf16 flips an expert, at a weight seed where it
does), and on copies of them in which ``correct`` has to come out false.
``tests/test_controls.py`` holds the controls over many weight seeds.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import run as bench_run  # noqa: E402
from harness import manifest, measure, probe, stats, sut, trace, traffic  # noqa: E402


def check_schedule() -> None:
    tr = manifest.load_cell("mistral-7b.chat").traffic
    a = traffic.schedule(tr, 32768, 51.0, 1)
    b = traffic.schedule(tr, 32768, 51.0, 2**31 + 12345)
    assert len(a) == len(b) == round(tr["rate_per_s"] * 51)
    # Another seed: the same lengths at the same instants, other contents.
    assert [(len(r.prompt), r.max_tokens, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_tokens, r.due_s) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert traffic.schedule(tr, 32768, 51.0, 1) == a     # same seed, same run
    # Another order (the held-out check): the same lengths and gaps, moved.
    c = traffic.schedule(tr, 32768, 51.0, 1, order=traffic.ORDER + 1)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]
    assert sorted(len(r.prompt) for r in c) == sorted(len(r.prompt) for r in a)
    assert sorted(r.max_tokens for r in c) == sorted(r.max_tokens for r in a)
    # Two cells of one traffic mix share its file and differ in rate alone.
    nemo = manifest.load_cell("mistral-nemo-12b.chat").traffic
    assert {k: v for k, v in nemo.items() if not k.startswith("rate")} == \
        {k: v for k, v in tr.items() if not k.startswith("rate")}
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert 0 <= a[0].due_s and a[-1].due_s < 51.0
    p, n = tr["prompt_tokens"], len(a)
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(0 <= t < 32768 for r in a for t in r.prompt)
    # The lengths are the stated lognormal's quantiles, whatever the order.
    assert sorted(len(r.prompt) for r in a) == traffic.lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"])
    # The ramp is another phase: its own order and contents.
    ramp = traffic.schedule(tr, 32768, 10.0, 1, phase=1)
    assert len(ramp) == round(tr["rate_per_s"] * 10)
    gaps = traffic.exponential_gaps(n, 51.0)
    assert abs(sum(gaps) - 51.0) < 1e-9 and gaps == sorted(gaps)


def check_stats() -> None:
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert abs(stats.percentile(list(range(101)), 95) - 95) < 1e-9
    assert stats.percentile([7.0], 99) == 7.0
    # first delta 1 token, then 1 token 0.1 later, then 3 tokens 0.3 later
    g = stats.token_gaps([(1.0, 1), (1.1, 1), (1.4, 3)])
    assert [round(x, 6) for x in g] == [0.1, 0.3, 0.0, 0.0]
    assert stats.token_gaps([(1.0, 2)]) == [0.0]


def check_trace() -> None:
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    own = trace.self_times([("while", 0, 10), ("dot", 1, 4), ("dot", 5, 7),
                            ("copy", 12, 13)])
    assert own == {"dot": 5, "while": 5, "copy": 1}
    assert trace.gaps([(0, 3), (5, 6)], 0, 10) == [(3, 5), (6, 10)]
    host = [("loop: run", 0, 10), ("loop: plan", 3.2, 4.9)]
    assert trace.attribute((3, 5), host) == "loop: plan"
    assert trace.attribute((6, 10), host) == "loop: run"
    # The recorded trace is of the CPU backend (five jitted steps under
    # "bench.step" annotations): it has no device plane, so name the CPU
    # client's line as if it were one.
    rec = HERE / "rehearsal" / "cpu-5-steps.xplane.pb"
    import jax

    line = next(ln.name for pl in jax.profiler.ProfileData.from_file(
        str(rec)).planes for ln in pl.lines if "PjRtCpuClient" in ln.name)
    red = trace.reduce(rec, device_plane=r"^/host:CPU$", op_line=line,
                       host_plane=r"^/host:CPU$")
    assert 0 < red["busy_s"] < red["window_s"]
    assert any(n.startswith("dot_general") for n, _ in red["device_ops"])
    assert "busy_s" not in trace.reduce(rec)    # no TPU plane: nothing to read
    # A trace from the chip (mistral-7b.chat, overloaded, PR 24; cut to the
    # events that begin in its first 0.3 s, so a ``while`` that outlasts the
    # cut has lost its children): read with the defaults.
    chip = trace.reduce(HERE / "rehearsal" / "v5e-chat-0.3s.xplane.pb")
    assert chip["devices"] == 1 and 0 < chip["busy_s"] <= chip["window_s"]
    longer = trace.reduce(HERE / "rehearsal" / "v5e-chat-0.3s.xplane.pb", 6.0)
    assert longer["window_s"] == 6.0 and longer["busy_s"] == chip["busy_s"]
    assert longer["idle_gaps"][0][1] == 6.0 - chip["window_s"]
    assert chip["planes"]["/device:TPU:0"]["XLA Ops"] > 1000
    assert any(n.startswith("attention bf16[") for n, _ in chip["device_ops"])
    assert len(chip["device_ops"]) == 10 and chip["idle_gaps"]
    assert trace.short_name(
        "%fusion.150 = bf16[32,512,14336]{2,1,0:T(8,128)(2,1)} fusion(bf16"
    ) == "fusion bf16[32,512,14336]"


def check_manifest() -> None:
    faults = manifest.check()
    assert not faults, faults
    bench = manifest.load_benchmark()
    for m in bench["per_layer"]:
        mod = measure.load_reader(m["name"])
        for k in ("name", "unit", "layer", "moves", "source"):
            assert getattr(mod, k) == m[k], (m["name"], k)
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"], bench)
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        ec = sut.engine_config(cell.config_dir, cell.about)
        sigs = sut.reachable_buckets(cell.traffic, ec)
        greedy = cell.traffic["sampling"]["temperature"] <= 0
        assert sigs and all(s.greedy == greedy for s in sigs)
        print(f"rehearse: {w['name']}: {len(sigs)} step programs to warm "
              f"(rows {sorted({s.b for s in sigs})}, "
              f"chunks {sorted({s.t for s in sigs})}, "
              f"block tables {sorted({s.nblk for s in sigs})})")


def _rehearsal_bench(config: str, config_dir: Path) -> dict:
    """``BENCHMARK.json`` with one rehearsal cell, ``<config>.rehearsal``, on
    the configuration in ``config_dir``: one ``configs`` entry and one
    ``workloads`` entry are all a configuration of another architecture
    adds."""
    cell = f"{config}.rehearsal"
    real = manifest.load_benchmark()
    return {
        "configs": [{"name": config, "file": str(config_dir / "config.json"),
                     "source": "none", "reduced": []}],
        "workloads": [{"name": cell, "config": config,
                       "traffic": "rehearsal", "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[cell]) for m in real["per_layer"]],
    }


def _run_rehearsal(config: str, config_dir: Path, trace_flag: str = "0"):
    """The whole command on one rehearsal configuration: its result line
    and its ``probe`` log line."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
        rc = bench_run.main(
            ["--workload", f"{config}.rehearsal", "--seed", str(2**31 + 7),
             "--seconds", "3", "--trace", trace_flag],
            allow_cpu=True, bench=_rehearsal_bench(config, config_dir),
            data_dir=HERE / "rehearsal")
    lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1])
    probe_line = next(d for d in map(json.loads, reversed(lines))
                      if d.get("event") == "probe")
    assert rc == 0
    assert set(last) - {"breakdown", "host"} == {
        "correct", "attempted", "failed", "metrics", "device"}, last
    assert last["failed"] == 0 and last["attempted"] == 12, last
    assert last["device"]["platform"] == "cpu"
    return last, probe_line, buf.getvalue()


def check_harness() -> None:
    """The whole command on the tiny configurations: ``tiny`` (the default
    reference and limits, untraced and traced) and ``tiny-moe`` (its own
    ``reference.py`` and ``probe`` block: an expert flips at a probed
    position, which is tied); then the ways ``correct`` has to come out
    false: an altered token, the wrong reference, a tolerance too tight, a
    margin function that calls every position tied."""
    rehearsal = HERE / "rehearsal"
    e2e = {m["name"] for m in manifest.load_benchmark()["end_to_end"]}
    for trace_flag in ("0", "1"):
        last, pr, out = _run_rehearsal("tiny", rehearsal / "tiny", trace_flag)
        assert last["correct"] is True, out[-3000:]
        assert pr["reference"] == "chipbench/harness/reference.py", pr
        assert (pr["logprob_tol"], pr["argmax_tol"], pr["rms_tol"]) == \
            (0.1, 0.05, 0.033), pr
        assert (pr["compared"], pr["tied"], pr["margin"]) == (64, 0, None), pr
        worst_tiny = pr["worst_logprob_diff"]
        names = set(last["metrics"])
        if trace_flag == "0":
            assert names == e2e, names
        else:
            # No device plane on the CPU: the trace readers return nothing
            # and the harness leaves their metrics out.
            assert "device.idle_pct" not in names and "busy_s" not in last["device"]
            assert {"sched.rows_per_step", "engine.step_period_ms",
                    "engine.compiles_in_window",
                    "stream.ttft_p90_ms"} <= names, names
        print(f"rehearse: harness --trace {trace_flag}: control flow ok "
              f"({last['attempted']} requests, probe agrees with the reference)")
    # Another architecture is a directory: config.json, about.json and its
    # own reference.py; no line under harness/ knows it.
    last, pr, out = _run_rehearsal("tiny-moe", rehearsal / "tiny-moe")
    assert last["correct"] is True, out[-3000:]
    assert pr["reference"] == "chipbench/rehearsal/tiny-moe/reference.py", pr
    block = json.loads((rehearsal / "tiny-moe" / "about.json").read_text())["probe"]
    assert all(pr[k] == block[k] for k in (
        "logprob_tol", "argmax_tol", "rms_tol", "margin", "max_tied_share")), pr
    # Its weight seed is one where bf16 flips an expert at a probed
    # position: over a tolerance, tied, left out.
    assert pr["tied_over_tolerance"] >= 1 and pr["compared"] + pr["tied"] == 64, pr
    assert pr["worst_tied_logprob_diff"] > pr["logprob_tol"] > pr["worst_logprob_diff"]
    print("rehearse: tiny-moe agrees with its own reference.py at the "
          f"{pr['compared']} positions compared (worst "
          f"{pr['worst_logprob_diff']:.4f} / {pr['worst_argmax_gap']:.4f}, rms "
          f"{pr['rms_logprob_diff']:.4f}); {pr['tied']} tied, "
          f"{pr['tied_over_tolerance']} of them off by up to "
          f"{pr['worst_tied_logprob_diff']:.4f}")
    # A token altered where it is produced (the last of each probe
    # request's, after the engine has returned it): the comparison sees it.
    served = probe.run_schedule

    async def altered(*args, **kwargs):
        recs = await served(*args, **kwargs)
        for r in recs:
            r.tokens[-1] = (r.tokens[-1] + 1) % 512
        return recs

    probe.run_schedule = altered
    try:
        last, pr, out = _run_rehearsal("tiny", rehearsal / "tiny")
    finally:
        probe.run_schedule = served
    # each request's altered position, and the steady statistic with them
    assert last["correct"] is False and \
        sum("token" in f for f in pr["faults"]) == 4, out[-3000:]
    print("rehearse: one altered token in each probe request is not correct "
          f"({pr['faults'][0]})")
    with tempfile.TemporaryDirectory() as tmp:
        # The same configuration without its reference.py: the default,
        # dense reference cannot read a routed layer's parameters. The hook
        # decides, not the tolerance.
        bare = Path(tmp) / "tiny-moe-bare"
        bare.mkdir()
        for name in ("config.json", "about.json"):
            shutil.copy(rehearsal / "tiny-moe" / name, bare / name)
        last, pr, out = _run_rehearsal("tiny-moe", bare)
        assert pr["reference"] == "chipbench/harness/reference.py", pr
        assert last["correct"] is False, out[-3000:]
        failed = next(f for f in pr["faults"] if "failed" in f)
        print("rehearse: tiny-moe against harness/reference.py is not "
              f"correct ({failed[:120]})")
        # A margin function that calls every position tied leaves nothing
        # to compare: a fault, not a pass.
        all_tied = Path(tmp) / "tiny-moe-all-tied"
        shutil.copytree(rehearsal / "tiny-moe", all_tied)
        with open(all_tied / "reference.py", "a") as fh:
            fh.write("\n\ndef routing_margin_at(params, model, tokens, "
                     "positions, pad_to=0):\n"
                     "    return np.zeros(len(positions), np.float32)\n")
        last, pr, out = _run_rehearsal("tiny-moe", all_tied)
        assert last["correct"] is False and pr["tied"] == 64, out[-3000:]
        print("rehearse: a margin function that ties every position is not "
              f"correct ({pr['faults'][-1]})")
        # A probe block decides the tolerances: one under the CPU's own
        # worst difference turns correct false.
        tight = Path(tmp) / "tiny-tight"
        tight.mkdir()
        shutil.copy(rehearsal / "tiny" / "config.json", tight / "config.json")
        about = json.loads((rehearsal / "tiny" / "about.json").read_text())
        about["probe"] = {"logprob_tol": worst_tiny / 2, "argmax_tol": 0.05}
        (tight / "about.json").write_text(json.dumps(about))
        last, pr, out = _run_rehearsal("tiny", tight)
        assert (pr["logprob_tol"], pr["argmax_tol"]) == (worst_tiny / 2, 0.05), pr
        assert last["correct"] is False and pr["faults"], out[-3000:]
        print(f"rehearse: a probe block's logprob_tol {worst_tiny / 2:.4f}, "
              f"under tiny's worst difference {worst_tiny:.4f}, is not correct")


def main() -> int:
    for check in (check_schedule, check_stats, check_trace, check_manifest,
                  check_harness):
        check()
        print(f"rehearse: {check.__name__} ok")
    print("rehearse: all ok (CPU: no time, rate or utilization here means "
          "anything)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
