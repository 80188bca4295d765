"""Find a cell's knee, and how far its numbers move with the order of arrivals.

    python3 chipbench/sweep.py --workload <name> --rates a,b,c [--orders x,y,z]

Builds the engine once and offers the cell's traffic in turn at each rate
(in the cell's own order), then at the cell's own rate in each other order
(ramp, window, drain between passes; other token ids in every pass). A rate
kept up when the output tokens that arrived in its window, over all chips
(``tokens_in_window_per_s``, the count the recorded knees were found with;
not the cell's ``tokens_per_s``), are at least 0.97 of those offered, no
more than 2 requests are waiting at its end, and none failed: one
criterion. With some tens of requests a window it swings with what is in
flight at the window's edges (a pass of 12 requests read 0.79 at a third of
the knee), so a knee is the highest rate that kept up and is known only to
the bracket up to the lowest rate above it that did not; the
cell's ``rate_from`` records the bracket. The table goes to
``chipbench/out/sweeps/<name>.json`` (copy it to ``chipbench/sweeps/``); the
cell's ``rate_per_s`` is 0.8 x the knee, written into its workload file as
a number. Runs on a TPU only.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import run as bench_run  # noqa: E402
from harness import manifest, measure, serve, traffic  # noqa: E402
from harness import sut as sut_mod  # noqa: E402

KEPT_UP_SHARE = 0.97
KEPT_UP_WAITING = 2


def kept_up_share(e2e: dict, offered: float, chips: int) -> float:
    """The output tokens that arrived in the window, over all chips, as a
    share of those the window offered: the count the recorded knees were
    found with (``tokens_in_window_per_s`` is a chip's, ``offered`` is not)."""
    return e2e["tokens_in_window_per_s"] * chips / offered


async def _sweep(cell, sut, passes, seconds, seed, log) -> list[dict]:
    rows = []
    for i, (rate, order) in enumerate(passes):
        ctx = await serve.offer(cell, sut, seed + i, seconds, log, rate=rate,
                                order=order, tag=f"r{i}-")
        e2e = measure.end_to_end(ctx, 0.0)
        offered = sum(r.max_tokens for r in ctx.due_in_window) / ctx.seconds
        waiting = ctx.counters[1]["num_waiting"]
        steps = ctx.delta("num_steps")
        third = max(len(ctx.in_flight) // 3, 1)
        row = {"rate_per_s": rate, "order": order,
               "requests": len(ctx.due_in_window),
               "offered_tokens_per_s": offered,
               "tokens_per_s": e2e["tokens_per_s"],
               "tokens_in_window_per_s": e2e["tokens_in_window_per_s"],
               "share": kept_up_share(e2e, offered, ctx.chips),
               "waiting_at_end": waiting,
               "running_at_end": ctx.counters[1]["num_running"],
               "in_flight_max": max(ctx.in_flight, default=0),
               "in_flight_first_third": sum(ctx.in_flight[:third]) / third,
               "in_flight_last_third": sum(ctx.in_flight[-third:]) / third,
               "failed": measure.failed(ctx),
               "ttft_mean_ms": e2e.get("ttft_mean_ms"),
               "ttft_p50_ms": e2e.get("ttft_p50_ms"),
               "ttft_p90_ms": e2e.get("ttft_p90_ms"),
               "itl_p95_ms": e2e.get("itl_p95_ms"),
               "step_period_ms": 1e3 * ctx.seconds / steps if steps else None,
               "kv_peak": max(ctx.kv_usage, default=0.0),
               "preemptions": ctx.delta("preemptions"),
               "compiles_in_window": measure.load_reader(
                   "engine.compiles_in_window").read(ctx)}
        row["kept_up"] = (row["share"] >= KEPT_UP_SHARE
                          and waiting <= KEPT_UP_WAITING
                          and not row["failed"])
        log("pass", **row)
        rows.append(row)
    await sut.engine.shutdown()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--orders", default="",
                    help="other orders of the same requests, at the cell's rate")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-rows", type=int, default=0,
                    help="warm row buckets up to this many rows in flight")
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    if args.max_rows:
        cell.traffic["max_rows"] = args.max_rows
    log = bench_run.open_log(manifest.OUT / "sweeps")
    device = bench_run.require_device(cell.chips, allow_cpu=False)
    sut = sut_mod.build(cell, log)
    passes = [(float(x), traffic.ORDER) for x in args.rates.split(",") if x]
    passes += [(float(cell.traffic["rate_per_s"]), int(x))
               for x in args.orders.split(",") if x]
    rows = asyncio.run(_sweep(cell, sut, passes, args.seconds, args.seed, log))
    kept_up = [r["rate_per_s"] for r in rows
               if r["kept_up"] and r["order"] == traffic.ORDER]
    table = {"workload": cell.name, "device": device, "seed": args.seed,
             "window_s": args.seconds, "ramp_s": cell.traffic["ramp_s"],
             "rule": f"kept up: tokens arrived >= {KEPT_UP_SHARE} of offered, "
                     f"<= {KEPT_UP_WAITING} waiting at the end, none failed",
             "knee_per_s": max(kept_up) if kept_up else None,
             "pool_blocks": sut.facts["pool_blocks"],
             "rates": rows}
    out = manifest.OUT / "sweeps" / f"{cell.name}.json"
    out.write_text(json.dumps(table, indent=1))
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
