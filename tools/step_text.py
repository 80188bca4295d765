"""The step programs a benchmark cell serves, as text two checkouts can be
compared by, for a described v5e and with no chip.

    JAX_PLATFORMS=cpu python tools/step_text.py --root <checkout> \
        --config <name> --out <dir> [--rows 8,16] [--chunks 1,16,512] \
        [--compile]

For each (rows, chunk) it traces the program the serving path runs (packed
inputs, greedy, ``attn_impl="pallas"``, the routed layer as a TPU backend
chooses it) through the abstract runner of ``<checkout>/chipbench/aot_check.py``
(put on a mesh of the described chips where the configuration's engine has
``tp`` > 1) and writes ``<dir>/b<rows>_t<chunk>.txt``: the lowered text passed
through ``tools/kernel_text.py``. With ``--compile`` also ``.hlo.txt``, the compiled
program less what names its source and with the compiler's names replaced by
their order, and one line of ``memory.jsonl`` with XLA's buffer assignment.
``diff -r`` of two checkouts' directories then says whether a change to the
Python that traces a program changed the program (PERF.md section 6, PR 51).
Text and memory only: nothing runs, no time is read.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _canonical(text: str) -> str:
    """A compiled program's text less what names its source (the header's
    tables of files and frames, each instruction's ``metadata``, a kernel's
    payload, which the lowered text holds parsed), every name replaced by
    its rank of first appearance: two programs that differ in the numbers
    the compiler gave its instructions come out equal."""
    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"[A-Za-z0-9+/=]+"', '"body":"<kernel>"', text)
    names: dict[str, str] = {}
    return re.sub(r"%?[A-Za-z_][\w\-]*\.[\w.\-]+|%[\w\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  text)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", type=Path, required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--rows", default="8,16")
    ap.add_argument("--chunks", default="1,16,512")
    ap.add_argument("--blocks", type=int, default=2048)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    root = args.root.resolve()
    sys.path[:0] = [str(root), str(root / "chipbench"), str(root / "tools")]
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    import aot_check
    from kernel_text import kernel_text

    from dynamo_tpu.engine.cache import KVCacheSpec, abstract_cache
    from dynamo_tpu.engine.engine import _padding_rows, pack_step_inputs
    from dynamo_tpu.models import mamba

    config_dir = root / "chipbench" / "configs" / args.config
    about = json.loads((config_dir / "about.json").read_text())
    runner, cfg, ec, params, state, on_chip = aot_check.build_abstract_runner(
        config_dir, {k: v for k, v in about.get("engine", {}).items()
                     if k != "why"})
    spec = KVCacheSpec.for_model(cfg, args.blocks, ec.block_size,
                                 kv_dtype=ec.kv_dtype)
    cache = on_chip(abstract_cache(spec, None))
    if ec.tp > 1:
        # The runner on a mesh of the described chips ("model" alone): the
        # parameters by their logical axes, the cache by its own rule,
        # everything else whole on every chip.
        from jax.experimental import topologies

        from dynamo_tpu.models import llama
        from dynamo_tpu.parallel.mesh import (
            MeshConfig,
            make_mesh,
            param_shardings,
            replicated,
        )

        runner.mesh = mesh = make_mesh(
            MeshConfig(tp=ec.tp), topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices)
        runner._repl, runner.spec = replicated(mesh), spec

        def on_chip(tree, sharding=None):
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                tree, sharding or jax.tree.map(lambda s: runner._repl, tree))

        params = on_chip(params, param_shardings(
            llama.param_logical_axes(cfg), mesh))
        state, cache = on_chip(state), abstract_cache(spec, mesh)
    pool = {"ssm": on_chip(mamba.state_shapes(cfg, ec.max_batch_size))} \
        if cfg.has_ssm else {}
    args.out.mkdir(parents=True, exist_ok=True)
    for b in map(int, args.rows.split(",")):
        for t in map(int, args.chunks.split(",")):
            packed = pack_step_inputs(
                *_padding_rows(b, t, runner.max_nblk), greedy=True)
            inputs = on_chip(tuple(
                jax.ShapeDtypeStruct(x.shape, x.dtype) for x in packed))
            # (the routed layer asks the backend whether its kernel can
            # run: traced as the chip's engine traces it)
            with mock.patch.object(jax, "default_backend", lambda: "tpu"):
                lowered = runner._build_step_fn(
                    b, t, runner.max_nblk, fast_greedy=True).lower(
                        params, cache, None if spec.latent else cache,
                        *state, *inputs, **pool)
            name = f"b{b}_t{t}"
            (args.out / f"{name}.txt").write_text(
                kernel_text(lowered.as_text(debug_info=False)))
            if args.compile:
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                (args.out / f"{name}.hlo.txt").write_text(
                    _canonical(compiled.as_text()))
                with open(args.out / "memory.jsonl", "a") as f:
                    f.write(json.dumps({
                        "program": name,
                        "argument_bytes": mem.argument_size_in_bytes,
                        "output_bytes": mem.output_size_in_bytes,
                        "alias_bytes": mem.alias_size_in_bytes,
                        "temp_bytes": mem.temp_size_in_bytes}) + "\n")
            print(name, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
