"""Build the system under test: ``EngineCore`` + ``AsyncJaxEngine``, the
object the worker and ``launch.run in=http`` serve, with seeded random
weights made on the device and the cell's own shapes warmed.

Every ``EngineConfig`` field keeps its default except ``model``,
``allow_random_weights``, ``seed`` and the overrides the configuration's
``about.json`` lists under ``engine`` with a reason each.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from pathlib import Path


MAX_ROWS = 16    # requests in flight whose row buckets (8, 16) are warmed


@dataclass
class Sut:
    core: object
    engine: object
    params: object
    ec: object
    facts: dict          # what set-up did, for the log


def engine_config(config_dir: Path, about: dict):
    from dynamo_tpu.utils.config import EngineConfig

    over = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in about.get("engine", {}).items()
            if k != "why"}
    return EngineConfig(model=str(config_dir), allow_random_weights=True,
                        seed=int(about["seed"]), **over)


def reachable_buckets(traffic: dict, ec) -> list:
    """The step programs this cell's traffic can reach, as the program's own
    ``BucketSig``s: every (rows, chunk, block-table) bucket that some step
    over requests inside the stated length ranges dispatches. Uses the
    program's mirror of its dispatch geometry (``sig_for_rows``), so a
    change to the bucketing moves this set with it.

    Not covered, and counted by ``engine.compiles_in_window`` if they occur:
    a chunk cut by the step's token budget, a re-prefill after preemption,
    and more rows in flight than ``MAX_ROWS`` (at 0.8 x knee at most 9
    were; at the knee 16. A cell that has to hold more gives ``max_rows``
    in its workload file)."""
    from dynamo_tpu.obs.compile_ledger import sig_for_rows

    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    greedy = float(traffic["sampling"].get("temperature") or 0.0) <= 0.0
    bs, chunk = ec.block_size, ec.prefill_chunk
    rows = sorted({sig_for_rows("decode", n, 1, 1, ec).b
                   for n in range(1, int(traffic.get("max_rows", MAX_ROWS)) + 1)})
    blocks = lambda tokens: -(-tokens // bs)
    need_max = blocks(p["max"] + o["max"])
    # Chunk rows: (t bucket) -> least block need of a row with that bucket.
    least: dict[int, int] = {}
    for length in range(p["min"], p["max"] + 1):
        for start in range(0, length, chunk):
            n = min(chunk, length - start)
            if n == 1:
                continue     # a one-token chunk is the decode program
            t = sig_for_rows("mixed", 1, n, 1, ec).t
            least[t] = min(least.get(t, need_max), blocks(start + n))
    sigs = set()
    for b in rows:
        lo = sig_for_rows("decode", b, 1, blocks(p["min"]), ec).nblk
        hi = sig_for_rows("decode", b, 1, need_max, ec).nblk
        nblk = lo
        while nblk <= hi:
            sigs.add(sig_for_rows("decode", b, 1, nblk, ec, greedy))
            nblk *= 2
        for t, need in least.items():
            nblk = sig_for_rows("mixed", b, t, need, ec).nblk
            while nblk <= hi:
                sigs.add(sig_for_rows("mixed", b, t, nblk, ec, greedy))
                nblk *= 2
    return sorted(sigs, key=lambda s: (s.kind, s.b, s.t, s.nblk))


def build(cell, log) -> Sut:
    import jax

    from dynamo_tpu.engine import device
    from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import resolve_model_config

    facts: dict = {}
    t0 = time.perf_counter()
    device.configure_compile_cache()
    ec = engine_config(cell.config_dir, cell.about)
    cfg = resolve_model_config(str(cell.config_dir))
    seed = int(cell.about["seed"])
    params = None
    if all(v == 1 for v in ec.mesh_shape().values()):
        # One jitted program from the seed, in the type the weights are
        # served in: the engine's own off-mesh init is eager, leaf by leaf
        # (PERF.md). The "rbg" generator, because threefry takes 28 s to
        # draw 3.8 G normals on a v5e (PERF.md, PR 24). On a mesh the
        # engine's init is already one sharded program, from the same seed.
        params = jax.jit(lambda: llama.init_params(
            cfg, jax.random.key(seed, impl="rbg")))()
        jax.block_until_ready(params)
    facts["weights_s"] = time.perf_counter() - t0
    t1 = time.perf_counter()
    core = EngineCore(ec, params=params)
    params = core.runner.params
    facts["engine_s"] = time.perf_counter() - t1
    facts["pool_blocks"] = core.runner.spec.num_blocks
    sigs = reachable_buckets(cell.traffic, core.engine_cfg)
    facts["buckets"] = len(sigs)
    t2 = time.perf_counter()
    facts["warmup"] = core.runner.warmup(sigs)
    facts["warmup_s"] = time.perf_counter() - t2
    if facts["warmup"]["failed"]:
        raise RuntimeError(f"warm-up failed for {facts['warmup']['failed']} "
                           "buckets; see the engine's log")
    log("setup", **facts)
    return Sut(core, AsyncJaxEngine(core), params, core.engine_cfg, facts)
