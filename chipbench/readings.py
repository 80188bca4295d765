"""The readings a configuration's ``probe`` block is set from: the probe's
numbers over several weight seeds, in one process and on one engine.

    python3 chipbench/readings.py --config-dir <dir> --seeds 0,1,2 \
        [--weights int8] [--engine '{...}'] [--margin 0.05] [--out <file>]

The engine is built once, as a run builds it (``sut.build``; one chip). For
each weight seed the parameters are made by the engine's own init and put in
the runner's place (the step programs take them as an argument), the prefix
cache is emptied, and ``probe.run_probe`` serves the probe's own prompts and
compares them with the configuration's reference. ``--engine`` is merged
over ``about.json``'s ``engine``: the one-precision-down side is the same
command with ``--weights int8`` (the engine serves every matrix rounded to
int8, the same programs; the reference still reads the stated weights), or
with the program's own paths, ``--engine '{"quantization": "int8"}'`` (which
leaves a routed layer's experts in bf16) or ``{"kv_dtype": "int8",
"num_blocks": 800}``. ``--margin`` (and ``--max-tied-share``) stand in for
a block that is not written yet. One line a seed: the verdict by the configuration's limits
and by the two per-position tolerances alone (what the rule was before a
reference could name tied positions), every number compared, and, where the
reference defines ``routing_margin_at``, the margin of each position over a
tolerance: the least ``margin`` that ties them all is the largest of those.

The directory need not be a configuration of ``BENCHMARK.json``: a scratch
one (``config.json``, ``about.json``, optionally ``reference.py``) is read
the same way. Refuses anything but a TPU unless ``--allow-cpu`` (the
rehearsal's toys; no number from there is a device's).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

# What ``sut.build`` warms: the probe's shortest prompt. The other step
# programs compile as the first seed's probe reaches them.
TRAFFIC = {"prompt_tokens": {"min": 48, "max": 48},
           "output_tokens": {"min": 16, "max": 16},
           "sampling": {"temperature": 0.0}, "max_rows": 1}


def reading_cell(config_dir: Path, engine: dict | None = None,
                 probe_block: dict | None = None):
    """A cell of no ``BENCHMARK.json``: the configuration in ``config_dir``
    with ``engine`` merged over its own overrides and, if given,
    ``probe_block`` over its own block."""
    from harness import manifest, probe

    config_dir = Path(config_dir).resolve()
    about = json.loads((config_dir / "about.json").read_text())
    about["engine"] = {**about.get("engine", {}), **(engine or {})}
    if probe_block:
        about["probe"] = {"logprob_tol": probe.LOGPROB_TOL,
                          "argmax_tol": probe.ARGMAX_TOL,
                          **about.get("probe", {}), **probe_block}
    return manifest.Cell(
        name="readings", chips=1, config_name=config_dir.name,
        config_dir=config_dir,
        model=json.loads((config_dir / "config.json").read_text()),
        about=about, traffic=TRAFFIC, end_to_end=[], per_layer=[])


def int8_rounded(params):
    """Every matrix of the tree on the int8 grid of its output channel
    (symmetric, the channel's largest value at 127) and back in its own
    type: int8 weights whatever the program's own quantized path covers
    (``models/quant.py`` leaves a routed layer's experts in bf16). Norms,
    the stacked ``[L, h]`` and the final ``[h]``, stay."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    @partial(jax.jit, donate_argnums=0)    # in place: two copies do not fit
    def rounded(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127, 1e-12)
        return (jnp.clip(jnp.round(w32 / scale), -127, 127) * scale).astype(w.dtype)

    layers = {k: rounded(v) if v.ndim >= 3 else v
              for k, v in params["layers"].items()}
    return {**{k: rounded(v) if v.ndim == 2 else v for k, v in params.items()
               if k != "layers"}, "layers": layers}


class Seeds:
    """One engine, many weight seeds: ``probe(seed)`` is the probe's result
    at that seed's weights (``serve`` and ``probe.compare_probe``, which a
    control calls apart to hand the reference something else). ``weights``
    "int8": the engine serves the seed's weights rounded to int8
    (``int8_rounded``) and the reference reads them as stated."""

    def __init__(self, cell, log, weights: str = "stated"):
        import jax

        from dynamo_tpu.models import llama
        from dynamo_tpu.models.config import resolve_model_config
        from harness import sut as sut_mod

        self.cell, self.weights = cell, weights
        self.sut = sut_mod.build(cell, log)
        if self.sut.core.runner.mesh is not None:
            raise SystemExit("readings.py swaps weights on one chip only")
        self.cfg = resolve_model_config(str(cell.config_dir))
        # as sut.build makes them, the seed an argument: one program
        self.make = jax.jit(lambda seed: llama.init_params(
            self.cfg, jax.random.key(seed, impl="rbg")))

    def _stated(self, seed: int):
        import jax

        runner = self.sut.core.runner
        runner.params = self.sut.params = None    # free before making
        return jax.block_until_ready(self.make(seed))

    async def serve(self, seed: int):
        """(the seed's weights as stated, the probe's requests, what the
        engine returned for them)."""
        import jax

        from harness import probe

        self.sut.engine.start()       # once, on the loop that serves
        runner = self.sut.core.runner
        stated = self._stated(seed)
        if self.weights == "int8":
            runner.params, stated = int8_rounded(stated), None
        elif self.sut.ec.quantization == "int8":
            from dynamo_tpu.models.quant import quantize_params_int8

            runner.params = quantize_params_int8(stated, self.cfg)
            if any(x.is_deleted() for x in jax.tree.leaves(stated)):
                raise RuntimeError("quantizing consumed the stated weights "
                                   "the reference has to read")
        else:
            runner.params = stated
        self.sut.core.pool.clear()    # no prompt meets another seed's KV
        reqs, recs = await probe.serve_probe(self.sut, self.cell)
        if stated is None:      # one copy fits beside the engine, not two
            stated = self._stated(seed)
        self.sut.params = stated
        return stated, reqs, recs

    async def probe(self, seed: int) -> dict:
        from harness import probe

        stated, reqs, recs = await self.serve(seed)
        return await probe.compare_probe(stated, self.cell, reqs, recs)

    async def close(self) -> None:
        await self.sut.engine.shutdown()


def old_rule(pr: dict) -> bool:
    """``correct`` as it was decided before tied positions and the steady
    statistic: every probed position inside the two tolerances."""
    return pr["over_tolerance"] == 0


def row(seed: int, pr: dict) -> dict:
    keys = ("worst_logprob_diff", "worst_argmax_gap", "rms_logprob_diff",
            "compared", "tied", "tied_share", "tied_over_tolerance",
            "worst_tied_logprob_diff", "worst_tied_argmax_gap",
            "margins_over_tolerance", "margin_quantiles", "logprob_tol",
            "argmax_tol", "rms_tol", "margin", "max_tied_share")
    return {"weight_seed": seed, "correct": not pr["faults"],
            "old_rule": old_rule(pr),
            **{k: pr[k] for k in keys if k in pr},
            "faults": pr["faults"][:6], "n_faults": len(pr["faults"])}


async def _main(args, cell, log) -> list[dict]:
    seeds = Seeds(cell, log, args.weights)
    rows = []
    try:
        for seed in args.seeds:
            t0 = time.perf_counter()
            r = row(seed, await seeds.probe(seed))
            r["seconds"] = round(time.perf_counter() - t0, 1)
            rows.append(r)
            print("READING " + json.dumps(r), flush=True)
    finally:
        await seeds.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-dir", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--engine", default="{}", type=json.loads)
    ap.add_argument("--weights", choices=("stated", "int8"), default="stated")
    ap.add_argument("--margin", type=float)
    ap.add_argument("--max-tied-share", type=float, default=1.0)
    ap.add_argument("--out")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.allow_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run as bench_run
    from harness import manifest

    block = None
    if args.margin is not None:
        block = {"margin": args.margin,
                 "max_tied_share": args.max_tied_share}
    cell = reading_cell(Path(args.config_dir), args.engine, block)
    log = bench_run.open_log(manifest.OUT / "readings")
    device = bench_run.require_device(1, args.allow_cpu)
    log("start", device=device, config_dir=str(cell.config_dir),
        engine=cell.about["engine"], weights=args.weights, seeds=args.seeds)
    rows = asyncio.run(_main(args, cell, log))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
