"""Single-binary launcher: ``python -m dynamo_tpu.launch.run in=<mode> out=<engine>``.

Fills the role of the reference's dynamo-run CLI
(reference: launch/dynamo-run/src/main.rs `in=http|text|batch out=engine`):
one process, no external infra (the StaticFull pipeline,
reference: lib/llm/src/entrypoint.rs:58): frontend → preprocessor → engine
→ detokenizer, all in-process.

Examples:
    python -m dynamo_tpu.launch.run in=http out=jax --model tiny-llama --port 8080
    python -m dynamo_tpu.launch.run in=text out=jax --model tiny-llama
    python -m dynamo_tpu.launch.run in=batch out=jax --model tiny-llama --input-jsonl prompts.jsonl
"""

from __future__ import annotations

import argparse
import asyncio
import json
import signal
import sys

from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
from dynamo_tpu.frontend.model_manager import ModelManager
from dynamo_tpu.frontend.service import HttpService
from dynamo_tpu.preprocessor.preprocessor import ModelDefaults
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.tokenizer import DecodeStream, load_tokenizer
from dynamo_tpu.utils.config import EngineConfig
from dynamo_tpu.utils.logging import configure_logging, get_logger

log = get_logger("launch")


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    argv = list(sys.argv[1:] if argv is None else argv)
    in_mode, out_mode = "text", "jax"
    rest = []
    for a in argv:
        if a.startswith("in="):
            in_mode = a[3:]
        elif a.startswith("out="):
            out_mode = a[4:]
        else:
            rest.append(a)
    p = argparse.ArgumentParser("dynamo-run")
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--max-batch-size", type=int, default=64)
    p.add_argument("--max-model-len", type=int, default=8192)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=0)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (layer blocks sharded over 'pipe')")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel replicas within the engine ('data' axis)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel shards ('expert' axis; MoE models)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel shards ('seq' axis; ring attention)")
    p.add_argument("--max-tokens", type=int, default=256, help="default max output tokens")
    p.add_argument("--input-jsonl", default=None)
    p.add_argument("--allow-random-weights", action="store_true",
                   help="serve RANDOM weights when the model path has no "
                        "loadable safetensors (tests/benches only)")
    p.add_argument("--spec-ngram", type=int, default=0,
                   help="n-gram speculative decoding (greedy-exact; 0 = off)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max proposed tokens per verify step")
    p.add_argument("--quantization", choices=["none", "int8"], default="none",
                   help="weight-only quantization (int8)")
    p.add_argument("--kv-dtype", choices=["bfloat16", "int8", "int4"],
                   default="bfloat16",
                   help="paged KV cache storage dtype (int8: in-kernel "
                        "dequant, ~2x KV capacity; int4: packed nibbles, "
                        "~4x capacity, even head_dim only)")
    p.add_argument("--prefill-chunk", type=int, default=512,
                   help="prefill chunk tokens per step; 0 = SLO-driven auto "
                        "sizing (largest per-QoS chunk keeping predicted "
                        "decode ITL inside --itl-slo-ms)")
    p.add_argument("--itl-slo-ms", type=float, default=50.0,
                   help="decode ITL SLO budget for --prefill-chunk 0 auto "
                        "sizing (interactive 1x, standard 2x, batch 4x)")
    p.add_argument("--host-kv-blocks", type=int, default=0, help="G2 host KV tier capacity")
    p.add_argument("--session-ttl", type=float, default=0.0,
                   help="session-sticky KV retention: seconds a finished "
                        "session's committed blocks stay pinned so the next "
                        "turn prefills only the suffix (0 = off)")
    p.add_argument("--no-session-tiers", action="store_true",
                   help="skip staging expired session KV down the KVBM tier "
                        "ladder before unpinning")
    p.add_argument("--ring-prefill-threshold", type=int, default=0,
                   help="sp>1 only: min prompt tokens for ring prefill "
                        "(0 = cost-model break-even, -1 = never)")
    p.add_argument("--disk-kv-path", default=None, help="G3 disk KV tier directory")
    p.add_argument("--remote-kv-addr", default=None,
                   help="G4 remote block store host:port")
    p.add_argument("--tool-call-parser", default=None,
                   help="tool-call parser name (hermes, mistral, llama3_json, ...)")
    p.add_argument("--reasoning-parser", default=None,
                   help="reasoning parser name (basic, deepseek_r1, ...)")
    p.add_argument("--mm-image-tokens", type=int, default=0,
                   help="enable multimodal chat: run an in-process vision "
                        "encoder producing this many embedding tokens per "
                        "image (0 = multimodal off)")
    ns = p.parse_args(rest)
    ns.in_mode, ns.out_mode = in_mode, out_mode
    return ns


def build_local_engine(ns: argparse.Namespace) -> tuple[AsyncJaxEngine, EngineConfig]:
    # Hub repo ids resolve to a local snapshot; the SERVED model name
    # (ns.model, used for registration) keeps the user-given id.
    from dynamo_tpu.models.hub import resolve_model_path

    resolved = resolve_model_path(ns.model)
    if ns.tokenizer is None and resolved != ns.model:
        ns.tokenizer = resolved
    cfg = EngineConfig(
        model=resolved,
        max_batch_size=ns.max_batch_size,
        max_model_len=ns.max_model_len,
        block_size=ns.block_size,
        num_blocks=ns.num_blocks,
        tp=ns.tp,
        pp=ns.pp,
        dp=ns.dp,
        ep=ns.ep,
        sp=ns.sp,
        prefill_chunk=ns.prefill_chunk,
        itl_slo_ms=ns.itl_slo_ms,
        quantization=ns.quantization,
        kv_dtype=ns.kv_dtype,
        spec_ngram=ns.spec_ngram,
        spec_k=ns.spec_k,
        allow_random_weights=ns.allow_random_weights,
        host_kv_blocks=ns.host_kv_blocks,
        disk_kv_path=ns.disk_kv_path,
        remote_kv_addr=ns.remote_kv_addr,
        session_ttl=ns.session_ttl,
        session_tiers=not ns.no_session_tiers,
        ring_prefill_threshold=ns.ring_prefill_threshold,
    )
    from dynamo_tpu.engine.engine import build_engine

    return build_engine(cfg), cfg


async def run_http(ns: argparse.Namespace) -> None:
    engine, cfg = build_local_engine(ns)
    tok = load_tokenizer(ns.tokenizer or ns.model)
    image_encoder = None
    if ns.mm_image_tokens > 0:
        from dynamo_tpu.models.config import resolve_model_config
        from dynamo_tpu.models.vision import VisionConfig, VisionEncoder

        venc = VisionEncoder(VisionConfig(
            num_image_tokens=ns.mm_image_tokens,
            lm_hidden_size=resolve_model_config(cfg.model).hidden_size))
        loop = asyncio.get_event_loop()

        async def image_encoder(imgs: list[bytes]):
            out = await loop.run_in_executor(None, venc.encode, imgs)
            return [out[i] for i in range(len(imgs))]

    models = ModelManager()
    models.register(
        ns.model, tok, engine.generate,
        defaults=ModelDefaults(max_model_len=cfg.max_model_len, default_max_tokens=ns.max_tokens),
        stats=engine.stats,
        tool_parser=ns.tool_call_parser,
        reasoning_parser=ns.reasoning_parser,
        embed=engine.embed,
        image_encoder=image_encoder,
    )
    svc = HttpService(models)
    # Single-process launch: the engine lives here, so its perf-counter
    # family belongs on this /metrics (workers do the same in
    # components/worker.py).
    from dynamo_tpu.obs.profiler import install_perf_metrics
    install_perf_metrics(svc.metrics)
    # The scheduling ledger (dynamo_sched_*) likewise mirrors onto the
    # single-process /metrics endpoint.
    from dynamo_tpu.obs.sched_ledger import install_sched_metrics
    install_sched_metrics(svc.metrics)
    # The memory ledger (dynamo_mem_*) too — occupancy waterfall, leak
    # audit, TTX forecast (obs/mem_ledger.py).
    from dynamo_tpu.obs.mem_ledger import install_mem_metrics
    install_mem_metrics(svc.metrics)
    if ns.session_ttl > 0:
        from dynamo_tpu.engine.session import install_session_metrics

        # Session retention feeds dynamo_session_* (engine/session.py).
        install_session_metrics(svc.metrics)
    if ns.sp > 1:
        from dynamo_tpu.obs.ring_prefill import install_ring_prefill_metrics

        # Ring-vs-chunked arbitration feeds dynamo_ring_prefill_*.
        install_ring_prefill_metrics(svc.metrics)
    if cfg.warmup_mode != "off":
        from dynamo_tpu.obs.compile_ledger import install_compile_metrics

        # Compile ledger feeds dynamo_xla_compile_* (obs/compile_ledger.py).
        install_compile_metrics(svc.metrics)
    await svc.start(ns.host, ns.port)
    log.info("serving %s on http://%s:%d/v1", ns.model, ns.host, svc.port)
    # SIGTERM/SIGINT end the server with exit code 0; an engine that a
    # device error stopped (AsyncJaxEngine.fatal) ends it non-zero.
    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        asyncio.get_running_loop().add_signal_handler(sig, stop.set)
    try:
        while engine.fatal is None and not stop.is_set():
            await asyncio.sleep(0.2)
    finally:
        await svc.stop()
        await engine.shutdown()
    if engine.fatal is not None:
        raise SystemExit(f"engine stopped on a device error: {engine.fatal}")


async def run_text(ns: argparse.Namespace) -> None:
    engine, cfg = build_local_engine(ns)
    tok = load_tokenizer(ns.tokenizer or ns.model)
    print(f"dynamo_tpu REPL — model={ns.model} (ctrl-d to exit)")
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except (EOFError, KeyboardInterrupt):
            break
        req = PreprocessedRequest(
            token_ids=tok.encode(tok.apply_chat_template([{"role": "user", "content": line}]), add_bos=True),
            stop_conditions=StopConditions(max_tokens=ns.max_tokens),
            sampling_options=SamplingOptions(temperature=0.7),
            eos_token_ids=[tok.eos_id],
        )
        stream = DecodeStream(tok)
        async for out in engine.generate(req):
            for t in out.token_ids:
                sys.stdout.write(stream.step(t))
                sys.stdout.flush()
        sys.stdout.write(stream.flush() + "\n")
    await engine.shutdown()


async def run_batch(ns: argparse.Namespace) -> None:
    """Batch mode: JSONL of {"prompt": ...} → JSONL of completions."""
    engine, cfg = build_local_engine(ns)
    tok = load_tokenizer(ns.tokenizer or ns.model)

    async def one(line: str) -> dict:
        obj = json.loads(line)
        req = PreprocessedRequest(
            token_ids=tok.encode(obj["prompt"], add_bos=True),
            stop_conditions=StopConditions(max_tokens=obj.get("max_tokens", ns.max_tokens)),
            sampling_options=SamplingOptions(temperature=obj.get("temperature", 0.0)),
            eos_token_ids=[tok.eos_id],
        )
        toks: list[int] = []
        async for out in engine.generate(req):
            toks.extend(out.token_ids)
        return {"prompt": obj["prompt"], "text": tok.decode(toks), "tokens": len(toks)}

    src = open(ns.input_jsonl) if ns.input_jsonl else sys.stdin
    lines = [ln for ln in src.read().splitlines() if ln.strip()]
    results = await asyncio.gather(*(one(ln) for ln in lines))
    for r in results:
        print(json.dumps(r))
    await engine.shutdown()


def main() -> None:
    configure_logging()
    ns = parse_args()
    if ns.out_mode not in ("jax",):
        raise SystemExit(f"unknown out={ns.out_mode} (supported: jax)")
    if ns.in_mode == "http":
        asyncio.run(run_http(ns))
    elif ns.in_mode == "text":
        asyncio.run(run_text(ns))
    elif ns.in_mode == "batch":
        asyncio.run(run_batch(ns))
    else:
        raise SystemExit(f"unknown in={ns.in_mode} (supported: http, text, batch)")


if __name__ == "__main__":
    main()
