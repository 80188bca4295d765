"""One continuous arrival process against the engine: ramp (unmeasured),
window (measured), drain. Shared by ``run.py`` (one cell run) and
``sweep.py`` (the same traffic at several rates)."""

from __future__ import annotations

import asyncio
import time

from . import loadgen, manifest, measure, traffic
from . import trace as xtrace

BEAT_S = 0.05    # the heartbeat on the generator's loop, window only


async def offer(cell, sut, seed: int, seconds: float, log, *,
                rate: float | None = None, order: int = traffic.ORDER,
                trace: bool = False, tag: str = "") -> measure.Context:
    import jax

    from dynamo_tpu.obs.compile_ledger import get_compile_ledger

    tr = cell.traffic
    rate = float(tr["rate_per_s"] if rate is None else rate)
    ramp_s = float(tr["ramp_s"])
    vocab = cell.model["vocab_size"]
    # Ramp and window each get their own stratified multiset.
    ramp = traffic.schedule(tr, vocab, ramp_s, seed, rate, 1, order)
    win = traffic.schedule(tr, vocab, seconds, seed, rate, 0, order)
    reqs = ramp + [traffic.Request(len(ramp) + r.index, ramp_s + r.due_s,
                                   r.prompt, r.max_tokens, r.seed)
                   for r in win]
    log("traffic", ramp=traffic.summary(ramp), window=traffic.summary(win),
        rate_per_s=rate, order=order)
    engine = sut.engine
    engine.start()
    snaps: dict = {}
    kv: list[float] = []
    running: list[int] = []
    t0 = time.perf_counter() + 0.2
    w0, w1 = t0 + ramp_s, t0 + ramp_s + seconds

    def edge(key):
        def cb():
            snaps[key] = (engine.stats(), time.time(), time.perf_counter())
        return cb

    def sample():
        st = engine.stats()
        kv.append(st["kv_usage"])
        running.append(st["num_running"] + st["num_waiting"])

    def beat(when):
        return lambda: beats.append(time.perf_counter() - when)

    marks = [(w0, edge("start")), (w1, edge("end"))]
    marks += [(w0 + 0.5 * i, sample) for i in range(int(seconds * 2) + 1)]
    # How late a callback that only notes the time runs: a machine that
    # stops every thread for a second shows here and nowhere else.
    beats: list[float] = []
    marks += [(w0 + BEAT_S * i, beat(w0 + BEAT_S * i))
              for i in range(1, int(seconds / BEAT_S))]
    trace_dir, tstate = None, {}
    if trace:
        trace_dir = manifest.OUT / cell.name / f"trace-seed{seed}"
        slice_ = tr["trace"]
        loop = asyncio.get_running_loop()

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # it slows the engine's thread
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tstate["t0"] = time.perf_counter()

        def stop():
            tstate["t1"] = time.perf_counter()
            jax.profiler.stop_trace()

        s0 = w0 + float(slice_["offset_s"])
        marks += [(s0, lambda: loop.run_in_executor(None, start)),
                  (s0 + float(slice_["seconds"]),
                   lambda: tstate.setdefault(
                       "stop", loop.run_in_executor(None, stop)))]
    recs = await loadgen.run_schedule(
        engine, reqs, tr["sampling"], sut.ec.model, t0,
        w1 + float(tr["drain_s"]), f"{tag}s{seed}", marks)
    # Every stream may have ended before the window has: wait for its edge.
    while "end" not in snaps or (trace and "stop" not in tstate):
        await asyncio.sleep(0.05)
    if "stop" in tstate:
        await tstate["stop"]
    (c0, wall0, p0), (c1, wall1, p1) = snaps["start"], snaps["end"]
    events = get_compile_ledger().snapshot(events=True).get("events", [])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    ctx = measure.Context(
        window=(p0, p1), window_wall=(wall0, wall1), chips=cell.chips,
        records=recs, counters=(c0, c1), kv_usage=kv, in_flight=running,
        compile_events=events, memory_peak_bytes=peak, beat_late_s=beats)
    if trace_dir is not None:
        xp = xtrace.find_xplane(trace_dir)
        traced_s = tstate.get("t1", 0.0) - tstate.get("t0", 0.0)
        red = xtrace.reduce(xp, traced_s) if xp else {}
        ctx.trace = red if "busy_s" in red else None
        log("trace", file=str(xp), bytes=xp.stat().st_size if xp else 0,
            traced_s=traced_s,
            planes=red.get("planes"))
    by_phase = {"ramp": [r for r in recs if r.due < p0],
                "window": ctx.due_in_window,
                "after": [r for r in recs if r.due >= p1]}
    log("requests", **{k: {"sent": len(v),
                           "finished": sum(1 for r in v if r.finish == "length"),
                           "failed": sum(1 for r in v if r.finish != "length")}
                       for k, v in by_phase.items()},
        window_edges_late_s=[p0 - w0, p1 - w1],
        waiting_at_end=c1["num_waiting"], running_at_end=c1["num_running"],
        in_flight_max=max(running, default=0), host=measure.stalls(ctx),
        compile_events_serve=sum(1 for e in events if e["source"] == "serve"))
    return ctx
