"""Layered runtime configuration.

Mirrors the reference's figment-layered ``RuntimeConfig``
(reference: lib/runtime/src/config.rs) — values resolve, in order of
precedence: explicit kwargs > ``DYN_*`` environment variables > config file
(TOML-like JSON/YAML) > defaults.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

ENV_PREFIX = "DYN_"


def _coerce(value: str, typ: type) -> Any:
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    return value


@dataclass
class RuntimeConfig:
    """Process-level runtime settings (reference: lib/runtime/src/config.rs)."""

    # Worker threads for the compute pool (reference: compute/pool.rs).
    num_worker_threads: int = 0  # 0 = os.cpu_count()
    # Coordination service address (our consolidated etcd/NATS equivalent).
    coordinator_url: str = "tcp://127.0.0.1:6650"
    # Namespace this process operates in.
    namespace: str = "dynamo"
    # System status server (health/metrics) — reference: system_status_server.rs.
    system_enabled: bool = False
    system_port: int = 0  # 0 = ephemeral
    # Logging.
    log_level: str = "info"
    log_jsonl: bool = False
    # Request plane.
    request_timeout_s: float = 600.0
    # Primary lease TTL (liveness). Generous enough that a long GIL-holding
    # XLA trace/compile can't starve the keep-alive loop into lease expiry.
    lease_ttl_s: float = 20.0
    # Graceful shutdown drain deadline.
    drain_timeout_s: float = 30.0
    # Scheduling-policy bound on concurrently-executing handler streams
    # (excess CALLs queue; reference: tracker.rs semaphore policies).
    max_handler_streams: int = 1024

    @classmethod
    def from_settings(cls, path: str | os.PathLike | None = None, **overrides: Any) -> "RuntimeConfig":
        """Build config from defaults < file < DYN_* env < explicit overrides."""
        values: dict[str, Any] = {}
        candidate = path or os.environ.get(ENV_PREFIX + "CONFIG")
        if candidate and Path(candidate).exists():
            text = Path(candidate).read_text()
            try:
                values.update(json.loads(text))
            except json.JSONDecodeError:
                try:
                    import yaml

                    values.update(yaml.safe_load(text) or {})
                except Exception as exc:  # pragma: no cover - malformed config
                    raise ValueError(f"could not parse config file {candidate}") from exc
        fields = {f.name: f for f in dataclasses.fields(cls)}
        for name, f in fields.items():
            env_key = ENV_PREFIX + name.upper()
            if env_key in os.environ:
                values[name] = _coerce(os.environ[env_key], f.type if isinstance(f.type, type) else type(f.default))
        values.update({k: v for k, v in overrides.items() if v is not None})
        values = {k: v for k, v in values.items() if k in fields}
        return cls(**values)


@dataclass
class EngineConfig:
    """JAX engine settings (fills the role of vLLM EngineArgs in the reference;
    reference pass-through: components/src/dynamo/vllm/args.py)."""

    model: str = "tiny-llama"           # model name or local path
    tokenizer: str | None = None          # defaults to model path
    dtype: str = "bfloat16"
    block_size: int = 16                  # KV cache tokens per block
    num_blocks: int = 0                   # 0 = auto-size from HBM budget
    max_batch_size: int = 64
    max_model_len: int = 8192
    max_tokens_per_step: int = 8192       # prefill token budget per step
    # Chunked-prefill bucket. 0 = auto: costmodel.auto_prefill_chunk picks
    # the largest chunk whose predicted mixed-step time keeps decode ITL
    # inside itl_slo_ms (resolved to a concrete cap at engine construction
    # so bucket enumeration and warmup see real shapes).
    prefill_chunk: int = 512
    decode_bucket: tuple[int, ...] = (8, 16, 32, 64)
    # Decode inter-token-latency SLO budget (milliseconds) that
    # costmodel.auto_prefill_chunk sizes chunks against when
    # prefill_chunk=0. Per-QoS ladder scales it: interactive 1x,
    # standard 2x, batch 4x.
    itl_slo_ms: float = 50.0
    # Mesh axes sizes; 1 = unsharded. (data, pipe, seq, model, expert)
    dp: int = 1
    pp: int = 1
    tp: int = 1
    ep: int = 1
    sp: int = 1
    # pp>1: microbatches interleaved across stage blocks per dispatch
    # (models/llama.forward_pp). 0 = auto (2*pp); shapes that don't divide
    # fall back to the sequential pipeline.
    pp_microbatches: int = 0
    # Weight-only quantization (models/quant.py): "none" | "int8".
    # int8 halves decode's HBM traffic (per-out-channel scales, bf16
    # compute on the MXU) — the roofline-doubling lever for the
    # bandwidth-bound decode metric.
    quantization: str = "none"
    # KV-cache storage dtype (engine/cache.py): "bfloat16" (store at model
    # precision — the default) | "int8" (symmetric per-block-per-kv-head
    # quantization: payload + f32 scale sidecar) | "int4" (same scale
    # pytree, two signed nibbles packed per byte along head_dim — needs an
    # even head_dim). int8 halves the paged cache's bytes_per_block and
    # int4 quarters it, so auto-sizing fits ~2x/~4x the blocks in the same
    # HBM budget and decode's KV reads move 1/2 / 1/4 the bytes; dequant
    # (and int4 nibble unpack) folds into the paged-attention kernel's
    # per-block matmuls.
    kv_dtype: str = "bfloat16"
    enable_prefix_caching: bool = True
    kv_event_publishing: bool = True
    # KVBM tiers (reference: lib/llm/src/block_manager.rs CacheLevel):
    # G2 host arena capacity in blocks (0 = disabled) and optional G3 disk
    # tier (path + byte budget). Device-evicted committed blocks write back
    # to host, host spills to disk, prompts onboard from either.
    host_kv_blocks: int = 0
    disk_kv_path: str | None = None
    disk_kv_bytes: int = 1 << 30
    # G4 remote block store ("host:port" of a RemoteBlockServer); chained
    # after host/disk in the offload cascade.
    remote_kv_addr: str | None = None
    # Fleet-wide prefix cache: publish committed prefix blocks to the G4
    # remote store PROACTIVELY (publish-on-commit, kvbm/offload.py) so a
    # cold worker can import a shared prefix another worker computed
    # instead of recomputing it. Requires remote_kv_addr; the import side
    # (admission-time onboard) is always on when tiers exist.
    global_prefix_cache: bool = False
    # N-gram speculative decoding (engine/spec.py): 0 = off; n>0 proposes
    # continuations of the trailing n-gram, verified k at a time in one
    # forward pass. Greedy-exact.
    spec_ngram: int = 0
    spec_k: int = 4
    seed: int = 0
    # A checkpoint PATH without loadable weights fails engine construction
    # unless this is set — a typo'd path must not silently serve garbage.
    # (Named presets always random-init; they exist for tests/benches.)
    allow_random_weights: bool = False
    # Attention implementation: "auto" (pallas on TPU, dense elsewhere),
    # "dense", "pallas", or "pallas_interpret" (CPU-testable kernel path).
    attn_impl: str = "auto"
    # Session-sticky KV retention (engine/session.py): when a stream with a
    # session.id annotation finishes, its committed KV blocks stay pinned
    # on device for this many seconds (leader-stamped step clock) so turn
    # N+1 prefills only the new suffix. 0 = retention off.
    session_ttl: float = 0.0
    # On TTL expiry or pool pressure, stage a retained session's blocks
    # down the KVBM tier ladder (host→disk) before unpinning, so a later
    # turn can re-import them even after device eviction. False drops the
    # pins to plain LRU without the write-through.
    session_tiers: bool = True
    # AOT bucket warmup / compile ledger (obs/compile_ledger.py):
    # "off" disables the XLA compile ledger entirely (zero per-dispatch
    # overhead), "lazy" records organic compiles against the enumerated
    # bucket lattice (the default — full observability, no precompiles),
    # "full" precompiles the reachable lattice at startup so no serving
    # request ever pays a cold-bucket trace+compile stall (worker
    # readiness waits for it).
    warmup_mode: str = "lazy"
    # Wall-seconds budget for full-mode warmup; lattice entries past the
    # deadline stay cold and show up as coverage < 1.0. 0 = unbounded.
    warmup_deadline: float = 120.0
    # Context-parallel ring prefill (sp>1 meshes, ops/ring_attention.py):
    # minimum prompt tokens before a fresh prompt prefills as ONE
    # seq-sharded ring chunk instead of the chunked sequential path.
    # 0 = auto (ring-vs-chunked break-even from obs/costmodel.py),
    # N>0 = explicit token threshold, -1 = never (ring path fully off —
    # the engine behaves exactly like an sp=1 chunked engine).
    ring_prefill_threshold: int = 0
    # Crash-consistent stream checkpoints (kvbm/stream_ckpt.py): every
    # this-many committed decode blocks (and once at prefill completion)
    # an in-flight stream's newly committed KV blocks plus a resumable
    # StreamCheckpoint record flush to the shared G4 remote store, so an
    # unplanned worker kill costs at most one interval of recompute. The
    # cadence is QoS-degraded (interactive 1x, standard 2x, batch 4x).
    # 0 = off. Requires remote_kv_addr; single-host engines only (the
    # multi-host drain path still covers planned exits).
    stream_ckpt_blocks: int = 0

    def mesh_shape(self) -> dict[str, int]:
        return {"data": self.dp, "pipe": self.pp, "model": self.tp,
                "expert": self.ep, "seq": self.sp}
