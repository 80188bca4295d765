"""Worker process: serve an engine (jax | mocker) on the distributed runtime.

Fills the role of the reference's engine worker components
(reference: components/src/dynamo/vllm/main.py init flow + mocker/main.py):
connect runtime → build engine with KV-event publishing → register model
card → serve_endpoint → publish metrics. ``python -m dynamo_tpu.components.worker``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import signal

from dynamo_tpu import chaos
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
)
from dynamo_tpu.router.publisher import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.runtime.protocols import MODEL_PREFIX
from dynamo_tpu.runtime.runtime import DistributedRuntime, RequestContext
from dynamo_tpu.utils.config import EngineConfig, RuntimeConfig
from dynamo_tpu.utils.logging import configure_logging, get_logger

log = get_logger("worker")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser("dynamo-worker")
    p.add_argument("--engine", choices=["jax", "mocker"], default="jax")
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--coordinator", default=None)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--tool-call-parser", default=None)
    p.add_argument("--reasoning-parser", default=None)
    p.add_argument("--num-blocks", type=int, default=0)
    p.add_argument("--max-batch-size", type=int, default=32)
    p.add_argument("--max-model-len", type=int, default=8192)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (layer blocks sharded over 'pipe')")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel replicas within ONE engine ('data' axis)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel shards ('expert' axis; MoE models)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel shards ('seq' axis; ring attention)")
    p.add_argument("--allow-random-weights", action="store_true",
                   help="serve RANDOM weights when the model path has no "
                        "loadable safetensors (tests/benches only)")
    p.add_argument("--spec-ngram", type=int, default=0,
                   help="n-gram speculative decoding: propose continuations "
                        "of the trailing n-gram, verify in one pass "
                        "(greedy-exact; 0 = off)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max proposed tokens per verify step")
    p.add_argument("--prefill-chunk", type=int, default=512,
                   help="prefill chunk tokens per step; 0 = SLO-driven auto "
                        "sizing (largest per-QoS chunk keeping predicted "
                        "decode ITL inside --itl-slo-ms)")
    p.add_argument("--itl-slo-ms", type=float, default=50.0,
                   help="decode ITL SLO budget for --prefill-chunk 0 auto "
                        "sizing (interactive 1x, standard 2x, batch 4x)")
    p.add_argument("--quantization", choices=["none", "int8"], default="none",
                   help="weight-only quantization (int8: per-channel scales, "
                        "bf16 compute; halves decode HBM traffic)")
    p.add_argument("--kv-dtype", choices=["bfloat16", "int8", "int4"],
                   default="bfloat16",
                   help="paged KV cache storage dtype (int8: per-block-per-"
                        "head scales, in-kernel dequant; halves KV bytes so "
                        "auto-sizing fits ~2x the blocks; int4: packed "
                        "nibbles, quarter bytes / ~4x blocks, even head_dim)")
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
    p.add_argument("--stream-ckpt-blocks", type=int, default=0,
                   help="crash-consistent stream checkpoints: every N "
                        "committed decode blocks (and once at prefill "
                        "completion) flush the stream's KV + a resumable "
                        "record to the G4 remote store so a worker kill "
                        "costs at most one interval of recompute; cadence "
                        "QoS-degrades (interactive 1x, standard 2x, batch "
                        "4x). 0 = off; needs --remote-kv-addr")
    p.add_argument("--warmup-mode", choices=["off", "lazy", "full"],
                   default="lazy",
                   help="XLA compile ledger / AOT bucket warmup: off = no "
                        "ledger, lazy = record organic compiles against the "
                        "enumerated lattice, full = precompile the reachable "
                        "bucket lattice before the endpoint serves "
                        "(readiness waits for it)")
    p.add_argument("--warmup-deadline", type=float, default=120.0,
                   help="full-mode warmup wall-seconds budget; lattice "
                        "entries past the deadline stay cold and show as "
                        "warmup coverage < 1.0 (0 = unbounded)")
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--speedup-ratio", type=float, default=10.0, help="mocker only")
    p.add_argument("--vocab-size", type=int, default=32000,
                   help="mocker only: bound on synthesized token ids; values "
                        "<= 260 keep every id inside the ByteTokenizer's "
                        "byte range so completion text round-trips")
    p.add_argument("--no-kv-events", action="store_true")
    p.add_argument("--health-interval", type=float, default=5.0,
                   help="idle seconds before a health canary replays through "
                        "the handler (reference: health_check.rs); 0 disables")
    p.add_argument("--drain-deadline", type=float, default=30.0,
                   help="retirement: seconds in-flight streams get to finish "
                        "after SIGTERM / a planner drain request before "
                        "being force-stopped (runtime/drain.py)")
    p.add_argument("--drain-batch-grace", type=float, default=None,
                   help="retirement: seconds before batch-class streams are "
                        "early-stopped during a drain (default: half the "
                        "deadline)")
    p.add_argument("--wedgeable", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--chaos-plan", default=None,
                   help="enable deterministic fault injection: a ChaosPlan "
                        "YAML/JSON file path or inline JSON (docs/CHAOS.md); "
                        "equivalent to DYN_CHAOS_PLAN")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="override the chaos plan's seed (DYN_CHAOS_SEED)")
    p.add_argument("--host-kv-blocks", type=int, default=0, help="G2 host KV tier capacity")
    p.add_argument("--disk-kv-path", default=None, help="G3 disk KV tier directory")
    p.add_argument("--remote-kv-addr", default=None,
                   help="G4 remote block store host:port ('auto' = discover "
                        "via the coordinator)")
    p.add_argument("--global-prefix-cache", action="store_true",
                   help="fleet-wide prefix cache: publish committed prefix "
                        "blocks to the G4 remote store so cold workers can "
                        "import instead of recomputing (needs "
                        "--remote-kv-addr)")
    # Disaggregated serving (reference: vllm decode-first pattern).
    p.add_argument("--disagg", choices=["none", "prefill", "decode"], default="none")
    p.add_argument("--prefill-endpoint", default="dyn://dynamo.prefill.generate",
                   help="decode mode: where the prefill pool lives")
    p.add_argument("--prefill-router", choices=["kv", "round-robin"], default="kv",
                   help="decode mode: prefix-aware (KvPushRouter) or plain "
                        "round-robin dispatch over the prefill pool; use "
                        "round-robin when --prefill-endpoint points at a "
                        "standalone dynamo_tpu.components.router, which is "
                        "KV-aware itself")
    p.add_argument("--no-kv-stream", action="store_true",
                   help="disable chunk-streamed KV handoff on a prefill "
                        "worker (fall back to one staged transfer at end "
                        "of prefill)")
    p.add_argument("--kv-transfer-ttl", type=float, default=60.0,
                   help="seconds a KV transfer may sit without progress "
                        "(registration, wave, or pull) before its pins are "
                        "released")
    p.add_argument("--min-prefill-blocks", type=int, default=2,
                   help="decode mode: prompt blocks below which prefill stays local")
    # Multi-host engine (reference: lib/llm/src/engines.rs:29-44 MultiNodeConfig).
    p.add_argument("--multihost-group", default=None,
                   help="rendezvous group for multi-host ranks (default: "
                        "namespace.component; MUST differ across replicas "
                        "of one component)")
    p.add_argument("--num-nodes", type=int, default=1,
                   help="processes forming ONE SPMD engine (1 = single-host)")
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--leader-addr", default=None,
                   help="host:port of the rank-0 jax coordinator; followers "
                        "default to resolving it via the coordination service")
    return p.parse_args(argv)


def model_card(ns: argparse.Namespace, name: str) -> dict:
    """ModelDeploymentCard-equivalent (reference: lib/llm/src/model_card.rs:91)."""
    return {
        "name": name,
        "endpoint": f"{ns.namespace}.{ns.component}.{ns.endpoint}",
        "tokenizer": ns.tokenizer or ns.model,
        "block_size": ns.block_size,
        "max_model_len": ns.max_model_len,
        "kv_events": not ns.no_kv_events,
        "tool_call_parser": ns.tool_call_parser,
        "reasoning_parser": ns.reasoning_parser,
    }


async def amain(ns: argparse.Namespace) -> None:
    if ns.engine != "mocker":
        # Hub repo ids resolve to a local snapshot before anything else
        # consumes the model string (card tokenizer + engine weights). The
        # SERVED name stays the user-given id; only loading paths change.
        from dynamo_tpu.models.hub import resolve_model_path

        if ns.served_model_name is None:
            ns.served_model_name = ns.model
        ns.model = resolve_model_path(ns.model)
    if ns.chaos_plan is not None:
        # CLI mirror of DYN_CHAOS_PLAN/DYN_CHAOS_SEED (docs/CHAOS.md).
        chaos.configure(ns.chaos_plan, seed=ns.chaos_seed)
    cfg = RuntimeConfig.from_settings(coordinator_url=ns.coordinator)
    rt = await DistributedRuntime.create(cfg)
    assert rt.client is not None and rt.primary_lease is not None
    if chaos.enabled():
        from dynamo_tpu.chaos.metrics import install_chaos_metrics

        install_chaos_metrics(rt.metrics)

    # Multi-host SPMD engine: all ranks join one jax.distributed group and
    # form ONE global mesh; rank 0 serves, others replay its op stream
    # (reference: MultiNodeConfig, lib/llm/src/engines.rs:29-44).
    op_channel = None
    if ns.num_nodes > 1:
        if ns.engine != "jax":
            raise SystemExit("--num-nodes > 1 requires --engine jax")
        from dynamo_tpu.parallel import multihost as mh

        # Distinct multi-host replicas of one component must rendezvous in
        # distinct groups (leader-key collision otherwise) — recipes pass
        # --multihost-group per replica.
        group = ns.multihost_group or f"{ns.namespace}.{ns.component}"
        leader_addr = ns.leader_addr
        op_port = 0
        loop = asyncio.get_running_loop()
        if ns.node_rank == 0:
            # Bind the op channel FIRST (port 0 → OS-assigned and owned from
            # here on); only the jax coordinator port keeps a small
            # bind-probe window, since jax itself binds it later.
            op_channel = mh.LeaderOpChannel(0, ns.num_nodes - 1)
            op_port = op_channel.port
            if not leader_addr:
                import socket as _socket

                host = rt.advertise_address.rsplit(":", 1)[0]
                with _socket.socket() as s:
                    s.bind(("", 0))
                    leader_addr = f"{host}:{s.getsockname()[1]}"
            await mh.publish_leader_addr(rt.client, group, leader_addr,
                                         op_port, rt.primary_lease.id)
        elif not leader_addr:
            leader_addr, op_port = await mh.resolve_leader_addr(rt.client, group)
        else:
            # Explicit --leader-addr on a follower: the op port is still the
            # leader's OS-assigned one — it MUST come from the published
            # record (a worker leader never listens on the port+1
            # convention; guessing would dial a dead or unrelated port).
            _, op_port = await mh.resolve_leader_addr(rt.client, group,
                                                      timeout=120.0)
        mncfg = mh.MultiNodeConfig(ns.num_nodes, ns.node_rank, leader_addr,
                                   op_port=op_port)
        # Blocks until every rank joins the group.
        await loop.run_in_executor(None, mh.initialize_distributed, mncfg)

        if ns.node_rank != 0:
            # Follower: build the engine from the leader's hello, replay its
            # op stream until it disconnects. No endpoint, no model card, no
            # publishers — followers are invisible to routing.
            from dynamo_tpu.engine.engine import EngineCore

            host, port = leader_addr.rsplit(":", 1)[0], mncfg.resolved_op_port()
            sock = await loop.run_in_executor(None, mh.connect_to_leader, host, port)

            def core_factory(hello: dict) -> EngineCore:
                return EngineCore(mh.engine_config_from_hello(hello))

            log.info("follower rank %d replaying leader op stream", ns.node_rank)
            print(f"FOLLOWER_READY rank={ns.node_rank}", flush=True)
            await loop.run_in_executor(None, mh.follower_loop, core_factory, sock)
            await rt.shutdown()
            return

        await loop.run_in_executor(None, op_channel.accept_followers)

    if rt.status_server is not None:
        # NotReady until the endpoint actually serves — model loading can
        # take minutes and a readiness probe must not pass before it.
        rt.status_server.ready = False

    publisher = None
    if not ns.no_kv_events:
        publisher = KvEventPublisher(
            rt.client, ns.namespace, ns.component, worker_id=rt.instance_id)
        publisher.start()
    sink = publisher.sink if publisher else None

    # Resolve the G4 remote store address once, for either engine kind.
    remote_kv = ns.remote_kv_addr
    if remote_kv == "auto":
        from dynamo_tpu.kvbm.remote import discover_store

        remote_kv = await discover_store(rt.client)
        if remote_kv is None:
            log.warning("--remote-kv-addr auto: no store advertised; "
                        "continuing without a G4 tier")
    if ns.host_kv_blocks or ns.disk_kv_path or remote_kv:
        from dynamo_tpu.kvbm.metrics import install_prefix_cache_metrics

        # KVBM tiers feed dynamo_prefix_cache_* (kvbm/metrics.py); re-home
        # the singleton so /metrics exposes hit/import/publish counters.
        install_prefix_cache_metrics(rt.metrics)
    if ns.session_ttl > 0:
        from dynamo_tpu.engine.session import install_session_metrics

        # Session retention feeds dynamo_session_* (engine/session.py).
        install_session_metrics(rt.metrics)
    if ns.stream_ckpt_blocks > 0:
        from dynamo_tpu.kvbm.stream_ckpt import install_stream_ckpt_metrics

        # Crash checkpoints feed dynamo_stream_ckpt_* (kvbm/stream_ckpt.py).
        install_stream_ckpt_metrics(rt.metrics)
    if ns.sp > 1:
        from dynamo_tpu.obs.ring_prefill import install_ring_prefill_metrics

        # Ring-vs-chunked arbitration feeds dynamo_ring_prefill_*.
        install_ring_prefill_metrics(rt.metrics)
    if ns.warmup_mode != "off":
        from dynamo_tpu.obs.compile_ledger import install_compile_metrics

        # Compile ledger feeds dynamo_xla_compile_* (obs/compile_ledger.py).
        # Installed for BOTH engine kinds — the mocker mirrors the ledger
        # device-free so fleet rollups see identical series either way.
        install_compile_metrics(rt.metrics)
    from dynamo_tpu.obs.sched_ledger import install_sched_metrics

    # Scheduling ledger feeds dynamo_sched_* (goodput, padding waste, HOL
    # stalls — obs/sched_ledger.py). Also both engine kinds: the mocker
    # mirrors step records device-free, so the fleet aggregator's
    # decode_stall SLI evaluates in chaos scenarios without a TPU.
    install_sched_metrics(rt.metrics)
    from dynamo_tpu.obs.mem_ledger import install_mem_metrics

    # Memory ledger feeds dynamo_mem_* (occupancy waterfall, pin-leak
    # audit, TTX forecast — obs/mem_ledger.py). Both engine kinds: the
    # mocker mirrors pins/forecast device-free, so the fleet kv_headroom
    # SLI and chaos orphan assertions evaluate without a TPU.
    install_mem_metrics(rt.metrics)

    follower_shards: list[dict] = []
    if ns.engine == "mocker":
        from dynamo_tpu.mocker.engine import MockEngine, MockEngineArgs

        engine = MockEngine(MockEngineArgs(
            num_blocks=ns.num_blocks or 512,
            block_size=ns.block_size,
            max_batch_size=ns.max_batch_size,
            max_model_len=ns.max_model_len,
            speedup_ratio=ns.speedup_ratio,
            vocab_size=ns.vocab_size,
            remote_kv_addr=remote_kv,
            global_prefix_cache=ns.global_prefix_cache,
            session_ttl=ns.session_ttl,
            stream_ckpt_blocks=ns.stream_ckpt_blocks,
            warmup_mode=ns.warmup_mode,
        ), event_sink=sink)
        stats_fn = engine.stats
    else:
        from dynamo_tpu.engine.engine import build_engine
        from dynamo_tpu.obs.profiler import install_perf_metrics

        # JAX engines feed the dynamo_engine_perf_* family (MFU, HBM-BW
        # utilization, roofline fraction — obs/profiler.py); re-home the
        # singleton into the runtime registry so /metrics exposes it.
        install_perf_metrics(rt.metrics)

        # Engine construction (param init, cache alloc) blocks for seconds —
        # run off-loop so the lease keep-alive keeps ticking.
        loop = asyncio.get_running_loop()
        engine = await loop.run_in_executor(None, lambda: build_engine(EngineConfig(
            model=ns.model,
            block_size=ns.block_size,
            num_blocks=ns.num_blocks,
            max_batch_size=ns.max_batch_size,
            max_model_len=ns.max_model_len,
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
            remote_kv_addr=remote_kv,
            global_prefix_cache=ns.global_prefix_cache,
            session_ttl=ns.session_ttl,
            session_tiers=not ns.no_session_tiers,
            ring_prefill_threshold=ns.ring_prefill_threshold,
            stream_ckpt_blocks=ns.stream_ckpt_blocks,
            warmup_mode=ns.warmup_mode,
            warmup_deadline=ns.warmup_deadline,
        ), event_sink=sink,
            op_sink=op_channel.broadcast if op_channel is not None else None))
        stats_fn = engine.stats
        if op_channel is not None:
            # Ship the leader-resolved engine essentials (num_blocks above
            # all) so follower schedulers can never diverge on capacity.
            import dataclasses as _dc

            from dynamo_tpu.parallel import multihost as mh

            resolved = _dc.replace(engine.core.engine_cfg,
                                   num_blocks=engine.core.runner.spec.num_blocks)
            hello = mh.leader_hello(resolved)
            # Prefill ranks each serve their cache shard of staged KV
            # transfers; the role rides the hello so followers bind their
            # shard servers and ack the addresses back (follower_loop).
            hello["disagg_role"] = ns.disagg
            op_channel.broadcast(hello)
            infos = await loop.run_in_executor(None, op_channel.wait_ready)
            follower_shards = [
                {"addr": i["shard_addr"], "box": i["shard_box"]}
                for i in infos if "shard_addr" in i]

    if ns.warmup_mode != "off":
        # AOT bucket warmup (obs/compile_ledger.py). Runs BEFORE ep.serve,
        # so readiness (flipped only after serve) already implies the
        # lattice is warm and routers never route onto a cold-bucket
        # worker. In lazy mode this is a no-op beyond publishing the plan;
        # in full mode it blocks for up to --warmup-deadline seconds. On a
        # multi-host engine this sits after wait_ready, so followers are
        # already replaying the op stream when warmup dispatches land.
        core = getattr(engine, "core", None)
        if core is not None and hasattr(core, "warmup"):
            warm = await asyncio.get_running_loop().run_in_executor(
                None, core.warmup)
        else:
            warm = engine.warmup() if hasattr(engine, "warmup") else None
        if warm:
            log.info("bucket warmup: %s", warm)

    if ns.disagg != "none" and ns.engine != "jax":
        raise SystemExit("--disagg requires --engine jax (KV handoff needs a real cache)")

    kv_source = None
    if ns.disagg != "none":
        from dynamo_tpu.disagg.metrics import install_kv_metrics

        install_kv_metrics(rt.metrics)
    if ns.disagg == "prefill":
        from dynamo_tpu.disagg.handlers import PrefillHandler
        from dynamo_tpu.disagg.source import KvTransferSource

        # shards[0] = this (leader) rank's server — started inside the
        # source — plus every follower rank's (ready-ack addresses); a
        # decode engine of any topology pulls its own box slices from them.
        kv_source = KvTransferSource(
            engine, ttl_s=ns.kv_transfer_ttl,
            advertise_host=rt.advertise_address.rsplit(":", 1)[0],
            extra_shards=follower_shards)
        kv_source.start()
        prefill = PrefillHandler(engine, kv_source, block_size=ns.block_size,
                                 stream=not ns.no_kv_stream)
        handler = prefill.generate
    elif ns.disagg == "decode":
        from dynamo_tpu.disagg.handlers import DisaggDecodeHandler
        from dynamo_tpu.runtime.client import EndpointClient, PushRouter
        from dynamo_tpu.runtime.protocols import EndpointId

        prefill_client = await EndpointClient.create(
            rt, EndpointId.parse(ns.prefill_endpoint))
        if ns.prefill_router == "kv":
            # Prefix-aware prefill dispatch: repeated prefixes land on the
            # prefill worker already holding their KV (reference routes
            # disagg prefill through the standalone KV router,
            # components/src/dynamo/router/__main__.py:30-120 — here the
            # router brain rides inside the decode worker).
            from dynamo_tpu.router.kv_router import KvPushRouter, KvRouterConfig

            # Each decode worker is one replica of the prefill-router
            # fleet: share load predictions (SyncedActiveSequences) so
            # concurrent decode workers don't make load-blind correlated
            # placements, and leave snapshot dumping to standalone routers
            # (N decode workers re-putting the full index every cycle would
            # race each other for no benefit).
            kv_prefill_router = await KvPushRouter.create(
                prefill_client, KvRouterConfig(
                    block_size=ns.block_size, sync_replicas=True,
                    snapshot_interval_s=0.0))

            async def prefill_call(payload, request_id):
                async for item in kv_prefill_router.generate(payload):
                    yield item
        else:
            prefill_router = PushRouter(prefill_client)

            async def prefill_call(payload, request_id):
                async for item in prefill_router.generate(payload, request_id):
                    yield item

        decode = DisaggDecodeHandler(
            engine, prefill_call, block_size=ns.block_size,
            min_prefill_blocks=ns.min_prefill_blocks)
        handler = decode.generate
    else:
        async def handler(payload: dict, ctx: RequestContext):
            req = PreprocessedRequest.from_dict(payload)
            # QoS deadline rides the request annotations; stamping it on the
            # ctx makes every is_cancelled() poll double as deadline
            # enforcement, and an already-expired request short-circuits
            # before the engine sees it.
            from dynamo_tpu.qos.deadline import deadline_of
            from dynamo_tpu.obs.tracer import get_tracer, trace_context_of

            ctx.deadline_ts = ctx.deadline_ts or deadline_of(req.annotations)
            if ctx.is_expired():
                yield LLMEngineOutput(
                    finish_reason=FinishReason.CANCELLED).to_dict()
                return
            # Tracing: open a dispatch span under the wire traceparent and,
            # on the FINAL delta, ship every span this process closed for
            # the trace back to the frontend (LLMEngineOutput.spans) so one
            # /debug/traces endpoint shows the cross-process timeline.
            tr = get_tracer("worker")
            tctx = trace_context_of(req.annotations)
            span = tr.start_span("worker.dispatch", ctx=tctx,
                                 request_id=req.request_id,
                                 model=req.model) if tctx else None
            async for out in engine.generate(req):
                if ctx.is_cancelled():
                    if span is not None:
                        tr.end_span(span, status="cancelled")
                    return
                d = out.to_dict()
                if out.finish_reason is not None and span is not None:
                    tr.end_span(
                        span,
                        status="error" if out.error else "ok",
                        finish_reason=str(out.finish_reason))
                    d["spans"] = [
                        s.to_dict()
                        for s in tr.recorder.spans_for(tctx.trace_id)]
                yield d

    if ns.wedgeable and ns.engine == "mocker":
        # Test hook: a control payload wedges/unwedges the mock engine's
        # step loop so e2e tests can exercise canary-driven NotReady.
        inner_handler = handler

        async def handler(payload: dict, ctx: RequestContext):  # noqa: F811
            if isinstance(payload, dict) and "__wedge__" in payload:
                engine.wedged = bool(payload["__wedge__"])
                yield {"token_ids": [], "finish_reason": "stop"}
                return
            async for item in inner_handler(payload, ctx):
                yield item

    if chaos.enabled():
        # Fault point covering EVERY dispatch path (agg, prefill, decode,
        # wedgeable) — wrapped here, under the health monitor, so canaries
        # exercise the same injected failures real traffic does. Only built
        # when a plan is active: the disabled path adds no generator layer.
        chaos_inner = handler

        async def handler(payload: dict, ctx: RequestContext):  # noqa: F811
            await chaos.ainject(
                "worker.dispatch", endpoint=ns.endpoint,
                request_id=payload.get("request_id")
                if isinstance(payload, dict) else None)
            async for item in chaos_inner(payload, ctx):
                yield item

    # Health canaries (reference: lib/runtime/src/health_check.rs:20-36):
    # replay a tiny generate through the SAME handler when idle; a wedged
    # engine flips ready=False in the published metrics and the KV router
    # stops sending traffic until a canary succeeds again.
    monitor = None
    if ns.health_interval > 0:
        from dynamo_tpu.runtime.health import (
            EndpointHealthMonitor,
            HealthCheckConfig,
            default_canary_payload,
            install_health_metrics,
        )

        install_health_metrics(rt.metrics)
        monitor = EndpointHealthMonitor(handler, HealthCheckConfig(
            payload=default_canary_payload(),
            idle_interval_s=ns.health_interval,
            timeout_s=max(ns.health_interval, 5.0),
        ))
        handler = monitor.handler
        base_stats = stats_fn

        def stats_fn():  # noqa: F811
            return {**base_stats(), "ready": monitor.ready}

    # While draining, published stats advertise NotReady so routers with a
    # stale membership view stop picking this worker even before the
    # instance-key DELETE propagates (kv_router health gating).
    drain_state = {"draining": False}
    inner_stats = stats_fn

    def stats_fn():  # noqa: F811
        s = dict(inner_stats())
        if drain_state["draining"]:
            s["ready"] = False
            s["draining"] = True
        return s

    ep = rt.namespace(ns.namespace).component(ns.component).endpoint(ns.endpoint)
    await ep.serve(handler)
    if monitor is not None:
        monitor.start()
    if rt.status_server is not None:
        rt.status_server.ready = True
        rt.status_server.add_provider("engine", stats_fn)
        if monitor is not None:
            # k8s readiness mirrors the canary state (reference: the system
            # status server consumes SystemHealth the same way).
            rt.status_server.set_ready_fn(lambda: monitor.ready)
        # Fleet aggregator discovery: publish this worker's status-server
        # /metrics under the coordinator's metrics prefix (lease-bound).
        await rt.advertise_metrics("worker")

    metrics_pub = WorkerMetricsPublisher(
        rt.client, ns.namespace, ns.component, rt.instance_id, stats_fn)
    metrics_pub.start()

    name = ns.served_model_name or ns.model
    if ns.disagg != "prefill":
        # Prefill workers are internal capacity — only decode/agg workers
        # publish a model card for the frontend to discover.
        async def put_card() -> None:
            await rt.client.put(
                f"{MODEL_PREFIX}/{name}/{rt.instance_id:016x}",
                json.dumps(model_card(ns, name)).encode(),
                lease_id=rt.primary_lease.id)

        await put_card()
        # A coordinator restart loses the card with the lease — re-declare
        # it whenever the runtime re-registers this worker.
        rt.on_reconnect(put_card)
    log.info("worker ready: engine=%s model=%s disagg=%s instance=%x",
             ns.engine, name, ns.disagg, rt.instance_id)

    # -- retirement (runtime/drain.py) ---------------------------------
    # First SIGTERM/SIGINT starts a graceful drain: membership out, bounded
    # run-down, session-KV evacuation. A SECOND signal aborts the drain
    # (skip waiting + evacuation, bounded fast exit). A planner drain
    # request on the coordinator key starts the same protocol with its own
    # reason/deadline.
    from dynamo_tpu.runtime.drain import (
        DrainRequest,
        WorkerDrainer,
        drain_key,
        drain_status_key,
        install_drain_metrics,
    )

    install_drain_metrics(rt.metrics)
    stop = asyncio.Event()
    abort = asyncio.Event()
    drain_req = DrainRequest(reason="signal")
    loop = asyncio.get_running_loop()

    def on_signal() -> None:
        if not stop.is_set():
            stop.set()
        else:
            log.warning("second signal: aborting drain, fast exit")
            abort.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, on_signal)
    # Said only now: whoever reads this line may signal at once (the
    # planner's connector retires a replica by SIGTERM), and a signal that
    # lands before the handlers are in kills the process where it should
    # drain it (seen under load: exit code -15 for 0, PR 56).
    print(f"WORKER_READY instance={rt.instance_id:016x}", flush=True)

    async def watch_drain_key() -> None:
        key = drain_key(ns.namespace, rt.instance_id)
        while True:
            try:
                raw = await rt.client.get(key)
            except Exception:
                raw = None  # coordinator unreachable; signals still work
            if raw is not None:
                try:
                    req = DrainRequest.from_bytes(raw)
                except Exception:
                    req = DrainRequest(reason="planner")
                drain_req.reason = req.reason or "planner"
                drain_req.deadline_s = req.deadline_s
                stop.set()
                return
            await asyncio.sleep(0.5)

    watcher = asyncio.create_task(watch_drain_key())
    await stop.wait()
    watcher.cancel()

    async def deregister() -> None:
        drain_state["draining"] = True
        await rt.deregister()
        if ns.disagg != "prefill":
            try:
                await asyncio.wait_for(rt.client.delete(
                    f"{MODEL_PREFIX}/{name}/{rt.instance_id:016x}"), 3.0)
            except Exception:
                log.warning("model card delete failed; lease expiry will")

    drainer = WorkerDrainer(
        inflight=lambda: rt.inflight_streams,
        deregister=deregister,
        evacuate=getattr(engine, "evacuate_sessions", None),
        abort_batch=(lambda: engine.abort_class("batch"))
        if hasattr(engine, "abort_class") else None,
        abort_all=(lambda: engine.abort_class(None))
        if hasattr(engine, "abort_class") else None,
        abort_event=abort,
        deadline_s=ns.drain_deadline,
        batch_grace_s=ns.drain_batch_grace,
    )
    report = await drainer.drain(reason=drain_req.reason,
                                 deadline_s=drain_req.deadline_s)
    if monitor is not None:
        await monitor.stop()
    if op_channel is not None:
        op_channel.close()  # followers see EOF and drain
    # Final snapshot: the retired worker's LAST published stats show it
    # idle/NotReady (aggregate views would otherwise keep its stale busy
    # numbers forever), then the terminal drain report lands on the
    # non-lease-bound status key for the planner to read post-exit.
    await metrics_pub.publish_once()
    await metrics_pub.stop()
    # The terminal report carries the engine's exit-time occupancy: routers
    # forget deregistered workers, so this line (and the status key) is the
    # only place a leak in a RETIRED worker stays observable.
    terminal = report.to_dict()
    try:
        final = dict(stats_fn())
        terminal["final_kv_usage"] = float(final.get("kv_usage", 0.0) or 0.0)
        terminal["final_num_running"] = int(final.get("num_running", 0) or 0)
    except Exception:
        pass
    try:
        await asyncio.wait_for(rt.client.put(
            drain_status_key(ns.namespace, rt.instance_id),
            json.dumps(terminal).encode()), 3.0)
    except Exception:
        log.warning("drain status publish failed (coordinator unreachable?)")
    if kv_source is not None:
        await kv_source.stop()
    if publisher:
        await publisher.stop()
    await rt.shutdown()
    print(f"WORKER_DRAINED {json.dumps(terminal)}", flush=True)


def main() -> None:
    configure_logging()
    asyncio.run(amain(parse_args()))


if __name__ == "__main__":
    main()
