"""Compile a configuration's step programs for a described v5e, with no chip.

    JAX_PLATFORMS=cpu python chipbench/aot_check.py --config <name> \
        [--blocks N] [--bucket B,T,NBLK,greedy ...]

Costs no chip time (on-chip-measurement guide, section 2.3). It says whether
the program lowers for the chip and what XLA's buffer assignment gives it,
and how long each compile takes on this host. It says nothing about run time,
and prints nothing under a device metric's name.

Without ``--bucket`` it compiles the widest bucket the default engine can
reach (B=64, T=512, NBLK=max_model_len/16, greedy: the one ``_fit_pool``
probes) at two pool sizes and solves for the pool the engine would choose
on a 16 GB chip, as ``ModelRunner._fit_pool`` does.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

V5E_HBM_BYTES = 15.75 * 2**30   # bytes_limit a v5e reported (PERF.md, PR 21)


def build_abstract_runner(config_dir: Path, overrides: dict):
    """A ModelRunner shell that can build step programs but owns no device
    state: parameters and cache are shapes on the described device."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.engine.engine import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import resolve_model_config
    from dynamo_tpu.utils.config import EngineConfig

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = resolve_model_config(str(config_dir))
    ec = EngineConfig(model=str(config_dir), allow_random_weights=True,
                      attn_impl="pallas", **overrides)
    runner = object.__new__(ModelRunner)
    runner.cfg, runner.engine_cfg, runner.mesh = cfg, ec, None
    runner._repl = None
    runner.attn_impl = "pallas"
    runner.max_nblk = -(-ec.max_model_len // ec.block_size)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    maxb = ec.max_batch_size
    state = on_chip((
        jax.ShapeDtypeStruct((maxb + 1, cfg.vocab_size), jnp.int32),
        jax.ShapeDtypeStruct((maxb + 1, 2), jnp.uint32),
        jax.ShapeDtypeStruct((maxb + 1,), jnp.int32)))
    return runner, cfg, ec, params, state, on_chip


def compile_bucket(runner, cfg, ec, params, state, on_chip, b, t, nblk,
                   greedy, num_blocks):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import KVCacheSpec, abstract_cache

    spec = KVCacheSpec.for_model(cfg, num_blocks, ec.block_size,
                                 kv_dtype=ec.kv_dtype)
    cache = on_chip(abstract_cache(spec, None))
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    inputs = on_chip((
        sds((b, t), i32), sds((b,), i32), sds((b,), i32), sds((b, nblk), i32),
        sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
        sds((b,), f32), sds((b,), f32), sds((b,), f32), sds((b,), bool),
        sds((b,), bool)))
    fn = runner._build_step_fn(b, t, nblk, fast_greedy=greedy)
    t0 = time.perf_counter()
    lowered = fn.lower(params, cache, cache, *state, *inputs)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    extra = (mem.temp_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes)
    return {"b": b, "t": t, "nblk": nblk, "greedy": greedy,
            "num_blocks": num_blocks, "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "argument_bytes": mem.argument_size_in_bytes,
            "beyond_arguments_bytes": extra,
            "kernel": "tpu_custom_call" in compiled.as_text()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--blocks", type=int, default=0)
    ap.add_argument("--bucket", action="append", default=[],
                    help="B,T,NBLK,greedy(0|1)")
    args = ap.parse_args()
    config_dir = ROOT / "chipbench" / "configs" / args.config
    about = json.loads((config_dir / "about.json").read_text())
    parts = build_abstract_runner(config_dir, about.get("engine", {}))
    runner, cfg, ec = parts[:3]
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    if args.bucket:
        for spec in args.bucket:
            b, t, nblk, g = (int(x) for x in spec.split(","))
            print(json.dumps(compile_bucket(*parts, b, t, nblk, bool(g),
                                            args.blocks or 2048)), flush=True)
        return 0
    # The pool the engine would choose: _fit_pool's arithmetic on the
    # widest bucket, with what a v5e reported free in PR 21.
    block = 2 * cfg.num_layers * ec.block_size * cfg.num_kv_heads \
        * cfg.head_dim * 2
    weights = sum(
        int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(parts[3]))
    sampling = sum(
        int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(parts[4]))
    budget = int(V5E_HBM_BYTES * 0.9) - weights - sampling
    n1 = max(budget // (2 * block) // 2, 2 * runner.max_nblk)
    n0 = n1 // 2
    b, t, nblk = ec.max_batch_size, ec.prefill_chunk, runner.max_nblk
    r0 = compile_bucket(*parts, b, t, nblk, True, n0)
    r1 = compile_bucket(*parts, b, t, nblk, True, n1)
    peak0 = r0["argument_bytes"] + r0["beyond_arguments_bytes"]
    peak1 = r1["argument_bytes"] + r1["beyond_arguments_bytes"]
    per_block = (peak1 - peak0) / (n1 - n0)
    copies = (r1["beyond_arguments_bytes"] - r0["beyond_arguments_bytes"]) \
        / (n1 - n0)
    fixed = r0["beyond_arguments_bytes"] - copies * n0
    # The arguments of the probe include weights and sampling state, which
    # the engine's budget has already paid for.
    n = int((budget - fixed) // per_block)
    print(json.dumps({
        "config": args.config, "widest_bucket": [b, t, nblk],
        "weights_bytes": weights, "block_bytes": block,
        "bytes_per_block_in_step": round(per_block),
        "fixed_bytes_beside_pool": round(fixed),
        "budget_bytes": budget, "pool_blocks": n,
        "pool_tokens": n * ec.block_size, "probes": [r0, r1],
        "note": "ahead-of-time compile for a described v5e: memory only, "
                "no time, no device metric"}, indent=1))
    return 0 if n > runner.max_nblk else 1


if __name__ == "__main__":
    raise SystemExit(main())
