"""Run one cell of the benchmark and print its result as the last line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the engine the server serves (``EngineCore`` +
``AsyncJaxEngine``), warms the cell's shapes, offers the cell's traffic as
one open-loop arrival process (``ramp_s`` unmeasured, then ``--seconds``
measured, then a drain), checks the outputs against the plain reference and
prints one JSON object. It refuses any platform but a TPU. ``setup_s`` is
process start to the start of the measured window. A cell is one trace:
``--seed`` draws the token ids only (``harness/traffic.py``); ``--order <n>``
offers the same requests in another order, for the held-out check of a
claimed gain.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

T_PROCESS_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from harness import manifest, measure, probe, serve, traffic  # noqa: E402
from harness import sut as sut_mod  # noqa: E402


def open_log(out_dir: Path):
    out_dir.mkdir(parents=True, exist_ok=True)
    fh = open(out_dir / "log.jsonl", "a")

    def log(event: str, **fields) -> None:
        line = json.dumps({"event": event,
                           "t": round(time.perf_counter() - T_PROCESS_START, 3),
                           **fields}, default=str)
        fh.write(line + "\n")
        fh.flush()
        print(line, flush=True)

    return log


def require_device(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices)}
    if d["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(f"chipbench runs on a TPU only; JAX found {d}")
    if d["count"] < chips and not allow_cpu:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {d}")
    return d


async def _serve(cell, sut, args, log) -> tuple[measure.Context, dict]:
    ctx = await serve.offer(cell, sut, args.seed, float(args.seconds), log,
                            order=args.order, trace=bool(args.trace))
    pr = await probe.run_probe(sut, cell)
    pr["faults"] = probe.check_counts(
        ctx.records, cell.model["vocab_size"]) + pr["faults"]
    log("probe", **pr)
    await sut.engine.shutdown()
    return ctx, pr


def main(argv=None, allow_cpu: bool = False, bench: dict | None = None,
         data_dir: Path | None = None) -> int:
    """``allow_cpu``, ``bench`` and ``data_dir`` are the CPU rehearsal's
    (``rehearse.py``); the command line cannot set them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--order", type=int, default=traffic.ORDER,
                    help="the same requests in another order: the held-out "
                         "check of a claimed gain, never the driver's")
    args = ap.parse_args(argv)
    bench = bench or manifest.load_benchmark()
    cell = manifest.load_cell(args.workload, bench, data_dir)
    log = open_log(manifest.OUT / cell.name)
    device = require_device(cell.chips, allow_cpu)
    log("start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, order=args.order, device=device, pid=os.getpid())
    sut = sut_mod.build(cell, log)
    setup_until_engine = time.perf_counter() - T_PROCESS_START
    ctx, pr = asyncio.run(_serve(cell, sut, args, log))
    # Process start to the start of the measured window, less nothing: the
    # ramp is set-up too.
    setup_s = ctx.window[0] - T_PROCESS_START
    e2e = measure.end_to_end(ctx, setup_s)
    log("end_to_end", engine_ready_s=setup_until_engine, **e2e)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        metrics = measure.per_layer(ctx, cell.per_layer)
    else:
        metrics = {n: {"value": e2e[n], "unit": units[n]}
                   for n in cell.end_to_end if n in e2e}
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    result = {"correct": not pr["faults"],
              "attempted": len(ctx.due_in_window),
              "failed": measure.failed(ctx), "metrics": metrics,
              "device": device, "host": measure.stalls(ctx)}
    if args.trace and ctx.trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    # Each number compared beside its limit, last on standard error too.
    tie = (f", tied {pr['tied']} (routing margin under {pr['margin']}; at "
           f"most {pr['max_tied_share']} of {pr['positions']})"
           if pr["margin"] is not None else ", tied 0 (no routing margin)")
    print(f"probe against {pr['reference']}: compared {pr['compared']} of "
          f"{pr['positions']} positions{tie}: worst_logprob_diff "
          f"{pr['worst_logprob_diff']} (limit {pr['logprob_tol']}), "
          f"worst_argmax_gap {pr['worst_argmax_gap']} (limit "
          f"{pr['argmax_tol']}), rms_logprob_diff {pr['rms_logprob_diff']} "
          f"(limit {pr['rms_tol']}), {len(pr['faults'])} faults"
          + "".join(f"\n  {f}" for f in pr["faults"][:8]),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
