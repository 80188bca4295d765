"""The first-party JAX engine: model runner + engine core + async facade.

Fills the role vLLM's AsyncLLM plays under the reference framework
(reference worker wrapper: components/src/dynamo/vllm/main.py,
handlers.py) — but the engine itself is ours, TPU-first:

- ``ModelRunner``: owns params, paged KV cache, and per-slot sampling state
  on device; compiles one XLA program per (batch, chunk, blocktable) bucket;
  cache/state buffers are donated so steps update in place.
- ``EngineCore``: synchronous scheduler + step loop (directly testable).
- ``AsyncJaxEngine``: thread-hosted step loop bridging to asyncio streams —
  the object a worker process serves via serve_endpoint.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import itertools
import json
import math
import queue as thread_queue
import threading
import time
from pathlib import Path
from dataclasses import dataclass, field
import functools
from functools import partial
from typing import TYPE_CHECKING, Any, AsyncIterator, Callable

if TYPE_CHECKING:
    from dynamo_tpu.kvbm.offload import OffloadManager

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu import chaos
from dynamo_tpu.engine import device
from dynamo_tpu.engine.cache import (
    KVCacheSpec,
    abstract_cache,
    abstract_caches,
    allocate_cache,
    cache_payload,
    cache_sharding,
    register_device_tier,
)
from dynamo_tpu.engine.prefix_pool import PrefixPool
from dynamo_tpu.engine.program_store import (
    ProgramStore,
    program_key,
    runtime_facts,
)
from dynamo_tpu.engine.sampling import (
    SamplingState,
    greedy_sample as _greedy_sample,
    record_tokens,
    sample,
)
from dynamo_tpu.engine.scheduler import Phase, PrefillWork, Scheduler, Seq, StepPlan
from dynamo_tpu.engine.session import SessionStore, get_session_metrics
from dynamo_tpu.kvbm.stream_ckpt import (
    CKPT_DRAWS_KEY,
    CKPT_GENERATED_KEY,
    build_ckpt_record,
    get_stream_ckpt_metrics,
)
from dynamo_tpu.models import llama, mamba
from dynamo_tpu.models.config import ModelConfig, resolve_model_config
from dynamo_tpu.obs.compile_ledger import (
    WARMUP_MODES,
    BucketSig,
    attends_tokens,
    enumerate_buckets,
    get_compile_ledger,
    pack_rows,
    sig_for_rows,
    token_bucket,
    writes_blocks,
)
from dynamo_tpu.obs.profiler import (
    LoopClock,
    StepPerfProfiler,
    loop_iteration,
    loop_phase,
    phase as _perf_phase,
    phase_table,
    phase_table_path,
    register_phase_source,
)
from dynamo_tpu.obs.mem_ledger import get_mem_ledger, live_ids_of
from dynamo_tpu.obs.sched_ledger import (
    SSM_COUNTS,
    GapStamps,
    HolStall,
    get_sched_ledger,
    recurrent_and_cross,
    step_class,
    step_counts,
    step_geometry,
)
from dynamo_tpu.obs.tracer import get_tracer, trace_context_of
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.router.events import KvCacheEvent
from dynamo_tpu.utils.config import EngineConfig
from dynamo_tpu.utils.logging import get_logger

log = get_logger("engine")


def _step_tokens(b: int, t: int, sp_prefill: bool) -> int:
    """The [N, H] a (b, t) step program runs its dense layers over: its
    token bucket, or the whole rectangle where ring prefill shards it
    over "seq"."""
    return b * t if sp_prefill else token_bucket("mixed", b, t)


def _runs(sizes: list[int]) -> list[tuple[int, int]]:
    """(first index, size) of consecutive runs of the given sizes."""
    return list(zip(itertools.accumulate(sizes, initial=0), sizes))


def _recurrent_engine_config(ec: EngineConfig) -> EngineConfig:
    """``ec`` for a model with recurrent layers (models/mamba.py): what
    keeps or moves a sequence's cache as blocks alone has nothing to resume
    from without the state at the blocks' end, so prefix matching is off
    (no block is committed for reuse, ``PrefixPool.match_prefix`` gives
    nothing; session retention, which claims its blocks through it, falls
    back to recomputing the prompt) and the paths that would move blocks
    are refused, each by its option."""
    refused = {
        "spec_ngram": (ec.spec_ngram > 0,
                       "a rejected draft would need the state rolled back"),
        "tp": (ec.tp > 1, "the state and the mixer are not sharded"),
        "pp": (ec.pp > 1, "the state pool is not divided over stages"),
        "sp": (ec.sp > 1, "the scan does not run over a 'seq' axis"),
        "ep": (ec.ep > 1, "the experts' exchange is not wired to a pattern"),
        "kv_dtype": (ec.kv_dtype in ("int8", "int4"),
                     "a quantized cache beside a float32 state is not "
                     "implemented"),
        "host_kv_blocks / disk_kv_path / remote_kv_addr": (
            ec.host_kv_blocks > 0 or bool(ec.disk_kv_path)
            or bool(ec.remote_kv_addr),
            "offloaded blocks come back without the state at their end"),
        "stream_ckpt_blocks": (
            ec.stream_ckpt_blocks > 0,
            "a checkpoint of blocks cannot resume the state"),
    }
    for option, (hit, why) in refused.items():
        if hit:
            raise ValueError(
                f"{option} is refused for a model with recurrent layers "
                f"({ec.model!r}): {why}")
    if ec.enable_prefix_caching:
        log.info("prefix matching is off for %s: a model with recurrent "
                 "layers cannot reuse a prefix's blocks without the state "
                 "at their end, which is not kept", ec.model)
    return dataclasses.replace(ec, enable_prefix_caching=False)


def _latent_engine_config(ec: EngineConfig) -> None:
    """Refuse, each by its option, what is not implemented over a latent
    (MLA) cache (engine/cache.py: one pool of rows, no K and V by head):
    the meshes that divide heads, layers or the sequence, a quantized pool,
    and the tiers and checkpoints that copy a K block and a V block by
    name (dynamo_tpu.kvbm)."""
    refused = {
        "tp": (ec.tp > 1, "the one row a token has no head axis to divide"),
        "pp": (ec.pp > 1, "the latent pool is not divided over stages"),
        "sp": (ec.sp > 1, "ring prefill exchanges keys and values by head"),
        "kv_dtype": (ec.kv_dtype in ("int8", "int4"),
                     "a quantized latent pool is not implemented"),
        "host_kv_blocks / disk_kv_path / remote_kv_addr": (
            ec.host_kv_blocks > 0 or bool(ec.disk_kv_path)
            or bool(ec.remote_kv_addr),
            "the offload tiers hold a K block and a V block"),
        "stream_ckpt_blocks": (
            ec.stream_ckpt_blocks > 0,
            "a checkpoint holds a K block and a V block"),
    }
    for option, (hit, why) in refused.items():
        if hit:
            raise ValueError(
                f"{option} is refused for a model with a latent (MLA) cache "
                f"({ec.model!r}): {why}")


def _moves_blocks_alone(op: Callable) -> Callable:
    """An ``EngineCore`` operation that hands a sequence's cache on as
    blocks (disaggregated transfer in either direction): refused for a
    model with recurrent layers, whose sequence is not resumable from
    blocks without its state."""
    @functools.wraps(op)
    def guarded(self, *args, **kwargs):
        if self.model_cfg.latent:
            raise ValueError(
                f"{op.__name__} is refused for a model with a latent (MLA) "
                f"cache ({self.engine_cfg.model!r}): the transfer's wire "
                "format is a K block and a V block; the prompt has to be "
                "recomputed where the sequence runs")
        if self.model_cfg.has_ssm:
            raise ValueError(
                f"{op.__name__} is refused for a model with recurrent "
                f"layers ({self.engine_cfg.model!r}): its blocks cannot "
                "resume a sequence without the state at their end; the "
                "prompt has to be recomputed where the sequence runs")
        return op(self, *args, **kwargs)
    return guarded


def _named(fn: Callable, name: str) -> Callable:
    """Give the function about to be jitted a name that says what it is and
    which bucket: the device trace's ``XLA Modules`` line then reads
    ``jit_<name>(...)`` and decode, mixed and verify steps can be told
    apart. (The persistent compile cache keys on it too.)"""
    fn.__name__ = fn.__qualname__ = name
    return fn


_NO_PHASE = contextlib.nullcontext()

# The packed inputs of a step program: what a step's rows send to the device,
# as one array (two where a row samples) in place of one placement a field.
#   ints   int32   [b, 5 + t + nblk]  q_start, q_len, slot, top_k, flags (bit 0
#                                     do_sample, bit 1 from_slot), then the
#                                     row's t tokens, then its nblk block ids
#   floats float32 [b, 5]             temp, top_p, fp, pp, rp: only for a
#                                     program that reads them (not fast_greedy)
# ``pack_step_inputs`` and ``unpack_step_inputs`` are the one definition of it.
_ROW_INTS = 5


def pack_step_inputs(tokens, q_start, q_len, bt, slots, temp, top_k, top_p,
                     fp, pp, rp, do_sample, from_slot, *, greedy: bool):
    """A step's per-row numpy arrays as its packed inputs ``(ints,)``, or
    ``(ints, floats)`` for a program that is not ``greedy`` (a
    ``fast_greedy`` program reads no sampling option and has no such
    parameter)."""
    b, t = tokens.shape
    ints = np.empty((b, _ROW_INTS + t + bt.shape[1]), np.int32)
    flags = np.asarray(do_sample, np.int32) | (
        np.asarray(from_slot, np.int32) << 1)
    for col, x in enumerate((q_start, q_len, slots, top_k, flags)):
        ints[:, col] = x
    ints[:, _ROW_INTS:_ROW_INTS + t] = tokens
    ints[:, _ROW_INTS + t:] = bt
    if greedy:
        return (ints,)
    return ints, np.stack([temp, top_p, fp, pp, rp], axis=1, dtype=np.float32,
                          casting="same_kind")


def unpack_step_inputs(t: int, ints, floats=None) -> tuple:
    """The thirteen per-row fields of a step's packed inputs (a step of
    ``t`` tokens a row), in the order ``pack_step_inputs`` takes them and in
    the dtypes it was given: static slices, of numpy arrays or inside a
    traced program alike. The five sampling options are None where the
    program has no ``floats``."""
    q_start, q_len, slots, top_k, flags = (ints[:, c] for c in range(_ROW_INTS))
    tokens = ints[:, _ROW_INTS:_ROW_INTS + t]
    bt = ints[:, _ROW_INTS + t:]
    temp, top_p, fp, pp, rp = (
        (None,) * 5 if floats is None else (floats[:, c] for c in range(5)))
    return (tokens, q_start, q_len, bt, slots, temp, top_k, top_p, fp, pp, rp,
            (flags & 1) != 0, (flags & 2) != 0)


def _padding_rows(b: int, t: int, nblk: int) -> tuple:
    """The thirteen per-row numpy arrays of a (b, t, nblk) step with no live
    row, in ``pack_step_inputs``' order: no tokens, temperature 0 and the
    neutral penalties (greedy-compatible), nothing sampled. What a step's
    fill starts from."""
    z, i32, f32 = np.zeros, np.int32, np.float32
    return (z((b, t), i32), z(b, i32), z(b, i32), z((b, nblk), i32),
            z(b, i32), z(b, f32), z(b, i32), np.ones(b, f32), z(b, f32),
            z(b, f32), np.ones(b, f32), z(b, bool), z(b, bool))

# What a step's ``engine.record`` span carries of its one count
# (obs/sched_ledger.py step_counts), and of the routed layers' device counts:
# what chipbench/layers/step_work_counts.py prices, and no more.
_RECORD_SPAN_COUNTS = (
    "programs", "live_tokens", "logit_rows", "attn_q_ctx", "kv_blocks_walked")
_RECORD_SPAN_MOE = ("moe_layer_steps", "moe_rows", "moe_experts_touched")
_RECORD_SPAN_SSM = ("ssm_layer_steps", "ssm_live_tokens",
                    "ssm_scanned_positions", "ssm_state_rows",
                    "ssm_scan_rows", "ssm_scan_positions")
_RECORD_SPAN_CROSS = ("cross_tokens", "kv_blocks_walked_shared")


@jax.jit
def _advance_key_data(data: jax.Array, n: jax.Array) -> jax.Array:
    """Key data after ``n`` sampler draws from ``data`` — replays
    sampling.sample()'s per-draw chain (``new_key = split(key)[0]``) in one
    fori_loop, so checkpoint resume restores a mid-stream PRNG state with a
    single tiny dispatch (``n`` is a traced operand: one compile serves
    every resume depth)."""
    key = jax.random.wrap_key_data(data)
    key = lax.fori_loop(0, n, lambda _, k: jax.random.split(k)[0], key)
    return jax.random.key_data(key)


def _derived_seed(request_id: str) -> int:
    """Stable per-request sampler seed for requests that set none. Making
    every stream's key a pure function of (seed, draws) is what lets a
    checkpoint resume restore sampler state exactly — including for
    unseeded requests, whose resume re-derives this same value from the
    (unchanged) request id."""
    import hashlib

    return int.from_bytes(
        hashlib.sha256(request_id.encode()).digest()[:4], "big")


@dataclass
class EngineMetrics:
    """Engine-side stats published to the router/planner
    (reference: ForwardPassMetrics, lib/llm/src/kv_router/publisher.rs:686)."""

    num_steps: int = 0
    # Host-to-device placements of step inputs (``ModelRunner.dispatch``):
    # one a greedy step program, two where a row samples, and the multimodal
    # pair and the logit mask where a step carries them.
    placed_inputs: int = 0
    num_prefill_tokens: int = 0
    num_decode_tokens: int = 0
    num_requests_finished: int = 0
    num_preemptions: int = 0
    prefix_hit_blocks: int = 0
    prefix_lookup_blocks: int = 0
    # Speculative decoding (reference surface: SpecDecodeStats in
    # ForwardPassMetrics): proposed = tokens offered for verification,
    # accepted = proposals that matched the true greedy path.
    spec_proposed: int = 0
    spec_accepted: int = 0
    # QoS: requests cancelled because their deadline passed (either while
    # waiting — before any prefill — or mid-decode via the stop check).
    deadline_cancelled: int = 0
    # Session turns that resumed from a drain-evacuated remote record
    # (pull-to-warm after another worker retired, runtime/drain.py).
    session_remote_resumes: int = 0
    # Streams resumed warm from a crash checkpoint (kvbm/stream_ckpt.py):
    # the migration operator replays the stream on a survivor with the
    # stream_ckpt.* annotations stamped.
    stream_ckpt_resumes: int = 0
    # KV-cache footprint (set once at engine construction): total device
    # bytes of the paged cache and whether int8 KV quantization is on —
    # exported as dynamo_engine_kv_cache_bytes / dynamo_engine_kv_quant_enabled.
    kv_cache_bytes: int = 0
    kv_quant_enabled: bool = False
    # Per-device shape of one of K or V, [L, NB, BS, KH, D] with KH divided
    # by tp (set once): what a reader of a device trace looks for to find
    # the operations that move the cache.
    kv_cache_shape: tuple[int, ...] = ()
    # A routed model on one chip (moe_impl "held"; set once):
    # experts held, the router's width, experts a token, and the matrices'
    # shapes a reader of a device trace finds the routed layer's operations
    # by (one expert stack's, the router's, the shared expert's).
    moe: dict | None = None
    # A model with recurrent layers (models/mamba.py; set once): the state
    # pool's layers, slots, the two leaves' shapes and dtypes, the bytes of
    # one sequence's state in one layer, and that prefix matching is off.
    ssm: dict | None = None
    # A model of latent attention (set once): the cache's kind, a row's
    # stored and useful width, its bytes a token over the layers; and what
    # its chunk rows carried (cumulative): the prefill chunks of several
    # tokens that ran, and the context under them, each chunk's last
    # position + 1 (``stats()["attn"]``).
    attn: dict | None = None
    attn_chunk_rows: int = 0
    attn_chunk_ctx_tokens: int = 0
    # The shapes that price a step's counts (set once;
    # obs/costmodel.py step_shapes): per kind of layer the parameters a
    # program reads whatever its rows, one expert's, the head's,
    # bytes_per_param, the bytes of a KV block of one layer.
    step_shapes: dict | None = None
    # The pool as sized (set once): its blocks, one block's bytes on each
    # device (K and V), and the bytes a step holds for each block of the
    # pool BEYOND the pool itself, from XLA's buffer assignment of the
    # widest step (ModelRunner._fit_pool; None where the pool was given and
    # nothing was probed). ~0 says the step updates the cache in place; a
    # block's worth or more says it copies the pool.
    kv_pool_blocks: int = 0
    kv_block_bytes: int = 0
    kv_step_copy_bytes_per_block: float | None = None
    # Time to first token in parts, summed over the sequences whose first
    # token has been posted (each once, a re-prefilled one too): arrival at
    # generate() -> add_request (the inbox), arrival -> the first plan that
    # carried a chunk of it (so the inbox wait is inside), that plan ->
    # first token posted.
    ttft_count: int = 0
    ttft_inbox_s: float = 0.0
    ttft_queue_s: float = 0.0
    ttft_prefill_s: float = 0.0

    def snapshot(self, sched: Scheduler, pool: PrefixPool) -> dict:
        return {
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_quant_enabled": self.kv_quant_enabled,
            "kv_cache_shape": list(self.kv_cache_shape),
            **({"moe": self.moe} if self.moe else {}),
            # Beside the pool's shapes, its rows that a sequence holds now.
            **({"ssm": {**self.ssm, "slots_in_use": sched.slots_in_use}}
               if self.ssm else {}),
            **({"attn": {**self.attn, "chunk_rows": self.attn_chunk_rows,
                         "chunk_ctx_tokens": self.attn_chunk_ctx_tokens}}
               if self.attn else {}),
            **({"step_shapes": self.step_shapes} if self.step_shapes else {}),
            "kv_pool_blocks": self.kv_pool_blocks,
            "kv_block_bytes": self.kv_block_bytes,
            "kv_step_copy_bytes_per_block": self.kv_step_copy_bytes_per_block,
            "ttft_count": self.ttft_count,
            "ttft_inbox_s": self.ttft_inbox_s,
            "ttft_queue_s": self.ttft_queue_s,
            "ttft_prefill_s": self.ttft_prefill_s,
            "num_waiting": sched.num_waiting,
            "num_running": sched.num_running,
            "kv_usage": pool.usage,
            "kv_total_blocks": pool.num_blocks,
            "num_steps": self.num_steps,
            "placed_inputs": self.placed_inputs,
            "prefill_tokens": self.num_prefill_tokens,
            "decode_tokens": self.num_decode_tokens,
            "requests_finished": self.num_requests_finished,
            "preemptions": self.num_preemptions,
            "prefix_hit_rate": self.prefix_hit_blocks / max(self.prefix_lookup_blocks, 1),
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "deadline_cancelled": self.deadline_cancelled,
            "session_remote_resumes": self.session_remote_resumes,
            "stream_ckpt_resumes": self.stream_ckpt_resumes,
        }


@dataclass
class PendingStep:
    """A dispatched-but-unmaterialized engine step: per batch,
    (signature of the program dispatched, rows, sample_rows, device
    tokens, device logprobs). For a "verify" batch the third entry is
    the rows' proposal chunks."""

    batches: list[tuple[BucketSig, list, list, Any, Any]] = field(default_factory=list)
    # The step's ordinal (what ``num_steps`` counts): rides the spans a
    # reader joins by it, the step's programs, its wait and its record.
    step: int = 0
    # Beside each batch: the name its program was built under
    # (``BucketSig.program``), as the device trace shows it less the hash.
    programs: list[str] = field(default_factory=list)
    # Beside each batch: the device int32 [3] of its routed layers' counts
    # (models/moe.py held_rows), None where the program has none.
    moe: list = field(default_factory=list)
    # Scheduling-ledger context captured at plan time (token-budget
    # utilization, HOL victim list) — consumed by _record_step at
    # finalize and by outputs_posted. None when DYN_SCHED_LEDGER=0.
    sched: Any = None
    # How many of the step batches' rows, counted from the first, are
    # decode/guided rows; the rest are prefill chunks. Captured at plan
    # time because prefill_target() moves as finalize appends tokens, so
    # a finalize-time re-derivation would misclassify. A step whose chunks
    # overflow one token bucket is several batches
    # (compile_ledger.pack_rows): the decode rows lead the first of them.
    dec_rows: int = 0


class ModelRunner:
    """Device-state owner + bucketed compiled step functions."""

    def __init__(
        self,
        cfg: ModelConfig,
        engine_cfg: EngineConfig,
        mesh=None,
        params=None,
        rng_seed: int = 0,
        program_dir: "str | Path | None" = None,
    ):
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.mesh = mesh
        # Replicated placement for host-built step inputs and sampling state.
        # On a mesh this makes every array an explicit global array — required
        # under multi-host jax (each process holds the full replicated value),
        # and a no-op-equivalent on one host.
        from jax.sharding import NamedSharding, PartitionSpec

        self._repl = (NamedSharding(mesh, PartitionSpec())
                      if mesh is not None else None)
        if params is not None:
            self.params = params
        else:
            from dynamo_tpu.models.loader import has_weights, load_params

            if engine_cfg.model.endswith(".gguf"):
                from dynamo_tpu.models.gguf import load_params_gguf

                _, self.params = load_params_gguf(engine_cfg.model, mesh=mesh)
            elif has_weights(engine_cfg.model):
                self.params = load_params(cfg, engine_cfg.model, mesh=mesh)
            else:
                from dynamo_tpu.models.config import MODEL_PRESETS

                if engine_cfg.model not in MODEL_PRESETS:
                    # A real model PATH without safetensors (typo, or a
                    # .bin-only snapshot): serving random weights would look
                    # like a working server producing garbage. Fail fast
                    # unless explicitly allowed (reference contrast: vLLM
                    # refuses unloadable checkpoints the same way).
                    if not engine_cfg.allow_random_weights:
                        raise ValueError(
                            f"{engine_cfg.model!r} has no *.safetensors "
                            "weights to load; convert the checkpoint, fix "
                            "the path, or pass --allow-random-weights to "
                            "serve RANDOM weights (tests/benches only)")
                    log.warning(
                        "%s has no *.safetensors weights: engine will serve "
                        "RANDOM weights (--allow-random-weights)",
                        engine_cfg.model)
                if mesh is None:
                    self.params = llama.init_params(
                        cfg, jax.random.key(rng_seed))
                else:
                    # On a mesh, one jitted program with the parameter
                    # shardings as its out_shardings: every device builds
                    # its own shard and nothing else. Built eagerly, each
                    # leaf appears whole — in float32 first — on the
                    # default device, which for a model meant to be spread
                    # over a mesh is more than that device holds. The seed
                    # is closed over, so on a multi-host mesh every process
                    # runs the same input-free program.
                    from dynamo_tpu.parallel.mesh import param_shardings

                    self.params = jax.jit(
                        lambda: llama.init_params(
                            cfg, jax.random.key(rng_seed)),
                        out_shardings=param_shardings(
                            llama.param_logical_axes(cfg), mesh))()
        if mesh is not None:
            # Explicitly place params per their logical-axis rules: on one
            # host this pins the TP/EP layout (instead of leaving GSPMD to
            # re-shard uncommitted arrays per bucket); on multi-host it is
            # mandatory — every process must contribute its shard of the
            # global param arrays. Leaves already placed (the loader's, the
            # sharded init's) pass through untouched: global_put returns
            # correctly-sharded arrays as-is.
            from dynamo_tpu.parallel.mesh import shard_params

            self.params = shard_params(
                self.params, llama.param_logical_axes(cfg), mesh)
        if engine_cfg.quantization == "int8":
            # After placement: the elementwise quantize preserves the mesh
            # sharding, so TP/EP layouts carry over (models/quant.py).
            # (The value itself was validated with the other config checks
            # in EngineCore, before any weight IO.)
            from dynamo_tpu.models.quant import quantize_params_int8

            self.params = quantize_params_int8(self.params, cfg)
        from dynamo_tpu.ops.paged_attention import select_attn_impl

        self.attn_impl = select_attn_impl(engine_cfg.attn_impl)
        self.max_nblk = -(-engine_cfg.max_model_len // engine_cfg.block_size)
        self._check_kernel_fits()
        maxb = engine_cfg.max_batch_size
        # Row maxb is the trash row: padding/non-sampling rows write their
        # sampling-state updates there so real slots are never clobbered by
        # duplicate scatter indices and PRNG keys only advance on real samples.
        self.counts = self._place(jnp.zeros((maxb + 1, cfg.vocab_size), jnp.int32))
        base = jax.random.split(jax.random.key(engine_cfg.seed), maxb + 1)
        self.keys = self._place(jax.vmap(jax.random.key_data)(base).astype(jnp.uint32))
        # Per-slot latest sampled token, ON DEVICE: lets the next decode step
        # consume this step's token without a host round-trip — the core of
        # the pipelined (host/device-overlapped) step loop. Row maxb = trash.
        self.slot_toks = self._place(jnp.zeros((maxb + 1,), jnp.int32))
        self._step_fns: dict[tuple[int, int, int], Callable] = {}
        # The compiled step programs an earlier start left under
        # ``program_dir`` (engine/program_store.py): ``step_fn`` looks there
        # before it builds one, and a program built here is written there.
        # None where there is no persistent compile cache to keep them
        # beside (the CPU backend), unless a test names a directory.
        self._store = ProgramStore(program_dir) if program_dir else None
        self._facts: tuple | None = None     # ``_program_facts``, read once
        # Compile ledger (obs/compile_ledger.py): every cache miss below is
        # a trace+compile that blocks the engine-core thread; the ledger
        # times it, attributes the victim request, and feeds warmup
        # coverage. Disabled (warmup_mode=off) the gate is one bool read.
        self._ledger = get_compile_ledger()
        # What it records of each program's build beside its seconds: the
        # layer bodies a program of this model holds and the distinct among
        # them, which are what its build traces and lowers
        # (``LayerPlan.bodies``; read off the plan, no count inside a trace).
        bodies = cfg.layer_plan.bodies
        self._bodies = (len(bodies), len(set(bodies)))
        # Loop-phase seconds (obs/profiler.py): the runner adds
        # engine.compile around a step program built inside serving;
        # EngineCore shares this clock for the rest of the loop.
        self.loop_clock = LoopClock()
        # Arrays ``dispatch`` has placed (EngineMetrics.placed_inputs).
        self.placed_inputs = 0
        # The pool comes last: everything else that lives on the device is
        # resident by now, so what memory_stats() calls free really is.
        self.spec = KVCacheSpec.for_model(
            cfg, engine_cfg.num_blocks or 1, engine_cfg.block_size,
            kv_dtype=engine_cfg.kv_dtype)
        # The second kind of cache, for a model with recurrent layers
        # (models/mamba.py): a row a slot and a trash row, as the sampling
        # state has; allocated with the KV pool, whose sizing counts it.
        self.ssm: dict | None = None
        # What _fit_pool measured (stats()["kv_step_copy_bytes_per_block"]);
        # None while nothing was probed: a given pool, the CPU backend.
        self.step_copy_bytes_per_block: float | None = None
        if not engine_cfg.num_blocks:
            self.spec = dataclasses.replace(
                self.spec, num_blocks=self._auto_num_blocks())
        self.cache_k, self.cache_v = allocate_cache(self.spec, mesh)
        if cfg.has_ssm:
            self.ssm = mamba.zeros_state(cfg, maxb)
        # Context-parallel ring prefill gate (ops/ring_attention.py promoted
        # to a serving mode): None = ring off (sp=1 mesh, or the knob set to
        # -1); otherwise the minimum prompt tokens before a fresh
        # full-prompt batch rides the seq-sharded ring path. 0 = auto — the
        # cost model's ring-vs-chunked break-even for this model on this
        # device (obs/costmodel.py).
        self.ring_threshold: int | None = None
        sp = mesh.shape.get("seq", 1) if mesh is not None else 1
        if sp > 1 and engine_cfg.ring_prefill_threshold >= 0:
            if engine_cfg.ring_prefill_threshold > 0:
                self.ring_threshold = engine_cfg.ring_prefill_threshold
            else:
                from dynamo_tpu.obs.costmodel import (
                    hw_spec_for,
                    ring_prefill_break_even_tokens,
                )

                self.ring_threshold = ring_prefill_break_even_tokens(
                    cfg, hw_spec_for(jax.devices()[0].device_kind), sp=sp,
                    chunk=engine_cfg.prefill_chunk,
                    block_size=engine_cfg.block_size,
                    kv_dtype=engine_cfg.kv_dtype,
                    quantization=engine_cfg.quantization,
                    max_tokens=engine_cfg.max_model_len)
            from dynamo_tpu.obs.ring_prefill import get_ring_prefill_metrics

            get_ring_prefill_metrics().threshold_tokens.set(
                float(self.ring_threshold))
            log.info("ring prefill engaged: sp=%d threshold=%d tokens%s",
                     sp, self.ring_threshold,
                     "" if engine_cfg.ring_prefill_threshold
                     else " (cost-model auto)")

    @property
    def moe_impl(self) -> str:
        """The routed layer's formulation (models/moe.py): across an
        "expert" axis the dropless sharded one; on one chip the grouped one
        (``held_rows`` over the layers' whole expert stack), which computes
        the rows routed to the experts held here and counts them, whether
        the chip holds a share of the published experts or all of them. No
        engine serves the all-experts form (llama.moe_mlp: E/k times the
        work); it is what the tests hold the grouped ones to. A model with
        no routed layer reads "dense" and its programs return no counts."""
        if self.engine_cfg.ep > 1:
            return "ep"
        return "held" if self.cfg.is_moe else "dense"

    def _place(self, x):
        """Replicate onto the mesh (global array) or leave as-is off-mesh."""
        if self._repl is None:
            return jnp.asarray(x)
        from dynamo_tpu.parallel.mesh import global_put

        return global_put(x, self._repl)

    def _check_kernel_fits(self) -> None:
        """Refuse at construction what the kernel cannot serve, instead of
        at the first request that reaches it. On a TPU: a mesh the kernel
        does not divide (the model code would otherwise swap in the dense
        gather path, models/llama.py forward), and scalar-prefetch operands
        — the block table, and a quantized pool's scale sidecars — that
        exceed SMEM."""
        if self.attn_impl != "pallas" or jax.default_backend() != "tpu":
            return
        from dynamo_tpu.ops.paged_attention import (
            SMEM_USABLE_BYTES,
            scalar_prefetch_bytes,
        )

        cfg, ec = self.cfg, self.engine_cfg
        shape = self.mesh.shape if self.mesh is not None else {}
        tp, dp = shape.get("model", 1), shape.get("data", 1)
        rows = sorted({s.b for s in enumerate_buckets(ec)})
        if tp > 1 and cfg.num_kv_heads % tp:
            raise ValueError(
                f"num_kv_heads={cfg.num_kv_heads} does not divide tp={tp}: "
                "the paged-attention kernel cannot be sharded over this "
                "mesh, and the dense gather path is not an acceptable "
                "substitute on a TPU")
        if tp > 1 and any(b % dp for b in rows):
            raise ValueError(
                f"batch buckets {[b for b in rows if b % dp]} do not divide "
                f"dp={dp}: those steps would leave the paged-attention "
                "kernel for the dense gather path")
        quant = ec.kv_dtype in ("int8", "int4")
        if quant and not ec.num_blocks:
            raise ValueError(
                f"kv_dtype={ec.kv_dtype} needs an explicit --num-blocks on a "
                "TPU: its scale sidecars ride SMEM, which bounds the pool to "
                "~1,000 blocks, far below what device memory would suggest")
        table = scalar_prefetch_bytes(batch=rows[-1], nblk=self.max_nblk)
        need = scalar_prefetch_bytes(
            batch=rows[-1], nblk=self.max_nblk,
            num_blocks=ec.num_blocks if quant else 0,
            kv_heads=cfg.num_kv_heads // tp)
        if need > SMEM_USABLE_BYTES:
            raise ValueError(
                f"the paged-attention kernel's scalar-prefetch operands need "
                f"{need} B of SMEM and {SMEM_USABLE_BYTES} B are usable: the "
                f"[{rows[-1]}, {self.max_nblk}] block table takes {table} B"
                + (f" and the kv_dtype={ec.kv_dtype} scale sidecars of "
                   f"{ec.num_blocks} blocks take {need - table} B (512 B a "
                   "block for K and for V); pass a smaller --num-blocks or "
                   "use kv_dtype=bfloat16" if quant else
                   "; lower max_model_len or max_batch_size"))

    #: Pool when auto-sizing on the CPU backend, which reports no memory
    #: statistics: enough for the tests and local work it is there for.
    _CPU_POOL_BLOCKS = 512
    #: Share of a device's memory the sizing leaves alone: compiled programs
    #: live there too (3–17 MB of code a bucket, hundreds of buckets in the
    #: lattice), and the allocator needs slack for fragmentation.
    _POOL_HEADROOM = 0.10

    def _auto_num_blocks(self) -> int:
        """Size the device KV pool from what the devices have free, so that
        the widest step the server can reach still fits beside it."""
        ec = self.engine_cfg
        cap = (ec.max_model_len // ec.block_size) * ec.max_batch_size + 1
        if jax.default_backend() == "cpu":
            return min(self._CPU_POOL_BLOCKS, cap)
        devices = (self.mesh.local_devices if self.mesh is not None
                   else jax.local_devices()[:1])
        stats = [d.memory_stats() for d in devices]
        if not all(st and "bytes_limit" in st for st in stats):
            raise RuntimeError(
                f"{devices[0].device_kind} reports no memory statistics; "
                "pass --num-blocks explicitly")
        free = min(st["bytes_limit"] - st["bytes_in_use"] for st in stats)
        headroom = int(self._POOL_HEADROOM
                       * min(st["bytes_limit"] for st in stats))
        log.info("kv pool sizing: %.2f GB free on each device, %.2f GB kept "
                 "back", free / 1e9, headroom / 1e9)
        return min(self._fit_pool(free - headroom), cap)

    def _fit_pool(self, budget: int) -> int:
        """The largest pool with which the widest reachable step takes no
        more than ``budget`` bytes of a device beyond what is resident.

        What a block costs inside a step is measured, not assumed. The
        widest bucket is lowered against an abstract cache at two pool
        sizes and compiled; XLA's own buffer assignment gives the bytes per
        block (the block plus whatever copies of it the step holds) and the
        bytes that do not depend on the pool (activations, which grow with
        the bucket), per device. The cache is donated and carried whole
        through the layer loop, written and read in place (models/llama.py
        ``_run_layers``), so the copies are 0 B a block and a block costs
        its own bytes (a step that holds a second K and V pays 2.5x that:
        PERF.md section 6). The copies measured here go to ``stats()`` as
        ``kv_step_copy_bytes_per_block``: a change to how the cache passes
        through the step shows there, and changes the pool with it."""
        ec = self.engine_cfg
        sig = self._widest_bucket()
        t0 = time.perf_counter()
        if self.cfg.has_ssm:
            # The state pool is resident beside the blocks, whatever their
            # number (the probe below hands it in as an argument).
            state = mamba.state_bytes(self.cfg, ec.max_batch_size)
            log.info("kv pool sizing: %.2f GB of recurrent state for %d "
                     "slots", state / 1e9, ec.max_batch_size)
            budget -= state
        # Probe near where the answer lies: over its whole range the curve
        # is not a line (small pools are assigned differently), close to
        # the answer it is. With one copy a block the budget would hold
        # ``bound`` blocks and the answer lies below that; a program that
        # does not fit the device does not compile, so start from half the
        # bound and a quarter, and halve again if even that is refused.
        n1 = max(budget // (2 * self._block_bytes_per_device()) // 2,
                 2 * self.max_nblk)
        while True:
            n0 = n1 // 2
            try:
                (peak0, extra0), (peak1, extra1) = (
                    self._probe_step_memory(sig, n) for n in (n0, n1))
                break
            except jax.errors.JaxRuntimeError:
                if n0 <= self.max_nblk:
                    raise
                log.info("kv pool sizing: a pool of %d blocks does not "
                         "compile beside the widest step; halving", n1)
                n1 = n0
        per_block = (peak1 - peak0) / (n1 - n0)
        copies = (extra1 - extra0) / (n1 - n0)
        fixed = extra0 - copies * n0
        n = int((budget - fixed) // per_block)
        self.step_copy_bytes_per_block = copies
        log.info(
            "kv pool sizing: widest bucket B=%d T=%d NBLK=%d needs %.2f GB "
            "beside the pool and %.0f B a block on each device (%.0f B the "
            "block, %.0f B its copies inside the step) -> %d blocks in "
            "%.2f GB, probed at %d and %d blocks in %.1fs",
            sig.b, sig.t, sig.nblk, fixed / 1e9, per_block,
            per_block - copies, copies, n, budget / 1e9, n0, n1,
            time.perf_counter() - t0)
        if n <= self.max_nblk:
            raise RuntimeError(
                f"no room for a KV pool that holds one {ec.max_model_len}-"
                f"token sequence ({self.max_nblk + 1} blocks) in "
                f"{budget / 1e9:.2f} GB: the widest step (B={sig.b} "
                f"T={sig.t}) needs {fixed / 1e9:.2f} GB beside the pool and "
                f"{per_block:.0f} B a block; lower max_batch_size, "
                "prefill_chunk or max_model_len")
        return n

    def _widest_bucket(self) -> BucketSig:
        """The reachable bucket with the most tokens through the dense
        layers (then the largest rectangle in attention, then the widest
        block table): the step whose activations are largest."""
        return max(enumerate_buckets(self.engine_cfg),
                   key=lambda s: (s.n, s.b * s.t, s.nblk))

    def _block_bytes_per_device(self) -> int:
        """One block's bytes on each device (K and V, or the one latent
        pool), by the cache's own sharding."""
        one = abstract_cache(
            dataclasses.replace(self.spec, num_blocks=1), self.mesh)
        return self.spec.pools * sum(
            math.prod(leaf.shape if self.mesh is None
                      else leaf.sharding.shard_shape(leaf.shape))
            * leaf.dtype.itemsize for leaf in jax.tree.leaves(one))

    def _probe_step_memory(self, sig: BucketSig,
                           num_blocks: int) -> tuple[int, int]:
        """Per-device (peak bytes, bytes beyond the arguments) of the step
        program for ``sig`` over a pool of ``num_blocks``, from XLA's buffer
        assignment. Nothing is allocated: the cache is abstract. The greedy
        variant stands for both (measured ahead-of-time: the sampling
        variant's temporaries are no larger, and it compiles 5x slower)."""
        caches = abstract_caches(
            dataclasses.replace(self.spec, num_blocks=num_blocks), self.mesh)
        maxb = self.engine_cfg.max_batch_size
        ssm = ({"ssm": mamba.state_shapes(self.cfg, maxb)}
               if self.cfg.has_ssm else {})
        args = (self.params, *caches, self.counts, self.keys,
                self.slot_toks,
                *self._padding_inputs(sig.b, sig.t, sig.nblk, True))
        # The answer is kept in the program store under the probe's own
        # arguments: a warm start then lowers nothing here either (two
        # lowerings, 5 s of a hybrid cell's warm start).
        name, key = sig.program() + "_memory", None
        if self._store is not None:
            key = program_key(name, (args, ssm), self._program_facts())
            try:
                peak, extra = map(int, json.loads(self._store.read(name, key)))
                return peak, extra
            except (ValueError, TypeError):
                pass    # no entry, or a damaged one: measured and written
        mem = self._build_step_fn(sig.b, sig.t, sig.nblk, fast_greedy=True) \
            .lower(*args, **ssm).compile().memory_analysis()
        extra = (mem.temp_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes)
        # (the state pool is _fit_pool's to count, not the step's own)
        pool = mamba.state_bytes(self.cfg, maxb) if ssm else 0
        out = mem.argument_size_in_bytes - pool + extra, extra
        if key is not None:
            self._store.write(name, key, json.dumps(out).encode())
        return out

    def _ssm_kw(self) -> dict:
        """The state pool as a step program takes it: by keyword, and only
        where the model has one."""
        return {"ssm": self.ssm} if self.ssm is not None else {}

    def _carried(self) -> tuple:
        """The device state a step program takes before its inputs, as
        ``_run_step`` hands it in."""
        return (self.params, self.cache_k, self.cache_v, self.counts,
                self.keys, self.slot_toks)

    def _run_step(self, fn, inputs) -> tuple:
        """Call a step program on the device state it carries (K, V, the
        sampling state and, where the model has one, the recurrent state's
        pool, all donated) and keep what it hands back in their place;
        returns the rest of its outputs (tokens, logprobs, counts)."""
        ssm = self._ssm_kw()
        (self.cache_k, self.cache_v, self.counts, self.keys,
         self.slot_toks, *rest) = fn(
            self.params, self.cache_k, self.cache_v, self.counts,
            self.keys, self.slot_toks, *inputs, **ssm)
        if ssm:
            self.ssm, *rest = rest
        return tuple(rest)

    def _padding_inputs(self, b: int, t: int, nblk: int,
                        greedy: bool) -> tuple:
        """The packed inputs of a (b, t, nblk) step, all padding and placed:
        q_len=0 rows compute nothing meaningful and do_sample=False routes
        sampling-state writes to the trash row. One array for a ``greedy``
        (``fast_greedy``) program, two for one that reads sampling options."""
        return tuple(self._place(x) for x in pack_step_inputs(
            *_padding_rows(b, t, nblk), greedy=greedy))

    # ------------------------------------------------------------------
    def _build_step_fn(self, b: int, t: int, nblk: int, sp_prefill: bool = False,
                       fast_greedy: bool = False, mm: bool = False,
                       masked: bool = False):
        """The jitted step program of one bucket. After the device state it
        carries (params, K, V, counts, keys, slot_toks; all but the first
        donated) it takes the packed inputs (``pack_step_inputs``: one int32
        array, and a float32 one unless ``fast_greedy``), which it cuts apart
        itself, then the multimodal pair and the logit mask where the
        program has them, and the recurrent state's pool by keyword.

        The same function still lowers from the thirteen per-row arrays
        passed one by one (``unpack_step_inputs``' order) in place of the
        packed ones: a different argument count is a different trace of the
        same body. That convention is kept for one caller,
        ``chipbench/aot_check.py compile_bucket`` (the benchmark's file, and
        the tests that go through it); the serving path only ever makes the
        packed trace. It goes when that file asks the runner for its
        abstract inputs (ROADMAP.md, benchmark debts)."""
        cfg = self.cfg
        trash_row = self.engine_cfg.max_batch_size

        attn_impl = self.attn_impl
        moe_impl = self.moe_impl
        mesh = self.mesh
        pp_micro = self.engine_cfg.pp_microbatches
        # dispatch() sends no batch with more live tokens than this
        # (EngineCore cuts steps by pack_rows).
        n_tok = _step_tokens(b, t, sp_prefill)

        has_ssm = cfg.has_ssm
        name = self._step_program(b, t, nblk, sp_prefill, fast_greedy, mm,
                                  masked)

        def step(params, ck, cv, counts, keys, slot_toks, *inputs, ssm=None):
            # (ssm: the recurrent state's pool, by keyword and donated by
            # name, where the model has one; every other model's program
            # has the arguments it always had.)
            n_rest = 2 * mm + masked
            per_row, rest = inputs[:len(inputs) - n_rest], \
                list(inputs[len(inputs) - n_rest:])
            with _perf_phase("layout"):
                if len(per_row) != 13:     # packed: what the serving path sends
                    if len(per_row) != (1 if fast_greedy else 2):
                        raise TypeError(
                            f"{name} takes {1 if fast_greedy else 2} packed "
                            f"per-row inputs, got {len(per_row)}")
                    per_row = unpack_step_inputs(t, *per_row)
                (tokens, q_start, q_len, bt, slots, temp, top_k, top_p, fp,
                 pp, rp, do_sample, from_slot) = per_row
                # Device-fed decode input: rows whose previous token was
                # sampled by an in-flight step read it from slot_toks
                # instead of the host tokens (which hold 0 for them) — XLA's
                # execution order guarantees the producing step has run.
                first = jnp.where(from_slot, slot_toks[slots], tokens[:, 0])
                tokens = tokens.at[:, 0].set(first)
            emb_override = rest.pop(0) if mm else None
            emb_mask = rest.pop(0) if mm else None
            logit_mask = rest.pop(0) if masked else None
            state = {}
            if has_ssm:
                # A row's state is its slot's row of the pool; a padded
                # row (no live token) reads and writes the trash row.
                state = {"ssm": ssm,
                         "ssm_slots": jnp.where(q_len > 0, slots, trash_row)}
            hidden, ck, cv, *moe = llama.forward(
                params, cfg, tokens, q_start, q_len, bt, ck, cv,
                attn_impl=attn_impl, moe_impl=moe_impl,
                mesh=mesh, sp_prefill=sp_prefill,
                embed_override=emb_override,
                embed_mask=emb_mask,
                pp_microbatches=pp_micro,
                num_tokens=n_tok, moe_counts=moe_impl == "held", **state)
            if has_ssm:
                ssm, *moe = moe
            with _perf_phase("logits"):
                logits = llama.logits_from_hidden(
                    params, cfg, hidden).astype(jnp.float32)
                if masked:
                    # Structured output (engine/guided.py): the grammar's
                    # per-row allow-mask, additive in log space. The model
                    # program is untouched — only the sampling input shifts.
                    logits = logits + logit_mask
            with _perf_phase("sampling"):
                write_slots = jnp.where(do_sample, slots, trash_row)
                if fast_greedy:
                    # Whole batch greedy + penalty-free (host-verified at
                    # dispatch): argmax over raw logits is bit-identical to
                    # the general path and skips its PRNG, penalty-count
                    # gathers, and sorted top-k/p masking — the per-step
                    # vocab-sized traffic that isn't the model itself.
                    toks, lps = _greedy_sample(logits)
                else:
                    st = SamplingState(
                        temperature=temp, top_k=top_k, top_p=top_p,
                        frequency_penalty=fp, presence_penalty=pp,
                        repetition_penalty=rp,
                        keys=keys[slots], token_counts=counts[slots],
                    )
                    toks, lps, new_keys = sample(logits, st)
                    new_counts = record_tokens(st.token_counts, toks, do_sample)
                    # Only sampling rows persist state; others write to trash.
                    counts = counts.at[write_slots].set(new_counts)
                    keys = keys.at[write_slots].set(new_keys)
                slot_toks = slot_toks.at[write_slots].set(toks)
            # (*moe: the routed layers' counts under moe_impl="held", a
            # last output that a program without them does not have.)
            return (ck, cv, counts, keys, slot_toks,
                    *((ssm,) if has_ssm else ()), toks, lps, *moe)

        by_name = {"donate_argnames": ("ssm",)} if has_ssm else {}
        return jax.jit(_named(step, name), donate_argnums=(1, 2, 3, 4, 5),
                       **by_name, **self._jit_shardings())

    def _step_program(self, b: int, t: int, nblk: int, sp_prefill: bool,
                      fast_greedy: bool, mm: bool, masked: bool) -> str:
        """The name of the step program of a ``_step_fns`` key, from the one
        place that names programs (``BucketSig.program``)."""
        return BucketSig(
            "decode" if t == 1 else "mixed", b, t, nblk, fast_greedy,
            self.engine_cfg.kv_dtype or "bfloat16").program(
                sp_prefill=sp_prefill, mm=mm, masked=masked)

    def _jit_shardings(self) -> dict:
        """Pin step-output shardings on a mesh: cache keeps its TP layout;
        sampling state and sampled tokens come back fully replicated so the
        host can materialize them on EVERY process (multi-host finalize) and
        the next dispatch feeds them straight back without resharding."""
        if self.mesh is None:
            return {}
        repl, cache = self._repl, cache_sharding(self.spec, self.mesh)
        cache_v = None if self.spec.latent else cache
        return {"out_shardings": (cache, cache_v, repl, repl, repl)
                # (the recurrent state's pool: every device holds it whole)
                + ((repl,) if self.cfg.has_ssm else ()) + (repl, repl)
                + ((repl,) if self.moe_impl == "held" else ())}

    def step_fn(self, b: int, t: int, nblk: int, sp_prefill: bool = False,
                fast_greedy: bool = False, mm: bool = False,
                masked: bool = False):
        """The step program of one bucket, built once a runner: loaded from
        the program store where an earlier start left it there (a
        ``jax.stages.Compiled``, called as the jitted function is), else
        the jitted function, which traces, lowers and compiles (or fetches
        from the persistent cache) inside its first call; the caller hands
        that call's inputs to ``_keep_program`` afterwards."""
        key = (b, t, nblk, sp_prefill, fast_greedy, mm, masked)
        if key not in self._step_fns:
            fn = self._load_program(key)
            if fn is None:
                log.info("compiling step fn B=%d T=%d NBLK=%d sp_prefill=%s "
                         "greedy=%s mm=%s masked=%s", b, t, nblk, sp_prefill,
                         fast_greedy, mm, masked)
                fn = self._build_step_fn(
                    b, t, nblk, sp_prefill, fast_greedy, mm, masked)
            self._step_fns[key] = fn
        return self._step_fns[key]

    # -- the program store (engine/program_store.py) --------------------
    def _program_facts(self) -> tuple:
        """What a step program of this runner is built from beside its
        bucket and its arguments: the source (the store's code digest), the
        resolved model, every field of the resolved ``EngineConfig`` but
        ``seed`` (which decides values of arguments and nothing of a
        program; a field no program closes over costs a miss when it
        changes, one left out would cost a wrong program), the
        implementations the runner resolved, the mesh with its devices, how
        host-built inputs are placed, and the process (``runtime_facts``).
        Read once."""
        if self._facts is None:
            ec = dataclasses.asdict(self.engine_cfg)
            del ec["seed"]
            mesh = self.mesh
            self._facts = (
                self._store.digest, repr(self.cfg), sorted(ec.items()),
                self.attn_impl, self.moe_impl,
                None if mesh is None else (
                    mesh.axis_names, mesh.devices.shape,
                    [(d.id, d.device_kind) for d in mesh.devices.flat]),
                repr(self._repl), runtime_facts())
        return self._facts

    def _padding_extras(self, b: int, t: int, mm: bool, masked: bool) -> tuple:
        """What a step program takes behind its packed inputs, as numpy
        zeros: the multimodal pair, the logit mask."""
        extra = ((np.zeros((b, t, self.cfg.hidden_size), np.float32),
                  np.zeros((b, t), bool)) if mm else ())
        if masked:
            extra += (np.zeros((b, self.cfg.vocab_size), np.float32),)
        return extra

    def _program_key(self, key: tuple) -> tuple[str, str]:
        """(name, store key) of the step program of a ``_step_fns`` key: the
        shape, dtype and sharding of every argument of the serving call,
        read off the live arrays (the parameters, both caches with the pool
        ``_fit_pool`` chose, the sampling state, the recurrent pool) and off
        the bucket's padding inputs, with ``_program_facts``. No trace."""
        b, t, nblk, _sp, greedy, mm, masked = key
        name = self._step_program(*key)
        args = (*self._carried(),
                *pack_step_inputs(*_padding_rows(b, t, nblk), greedy=greedy),
                *self._padding_extras(b, t, mm, masked))
        return name, program_key(name, (args, self._ssm_kw()),
                                 self._program_facts())

    def _built(self, key: tuple) -> dict:
        """What the compile ledger records of how the program of a
        ``_step_fns`` key came to be: the layer bodies it holds and those
        its build traced (none where it was loaded), and whether it was
        loaded from the store."""
        loaded = self._was_loaded(key)
        return {"bodies": (self._bodies[0], 0) if loaded else self._bodies,
                "loaded": loaded}

    def _was_loaded(self, key: tuple) -> bool:
        """Whether the program of a ``_step_fns`` key came from the store:
        it is then the loaded executable itself, not a jitted function."""
        return isinstance(self._step_fns.get(key), jax.stages.Compiled)

    def _load_program(self, key: tuple):
        """The step program of ``key`` from the store, or None."""
        if self._store is None:
            return None
        devices = (list(self.mesh.devices.flat) if self.mesh is not None
                   else list(self.counts.devices()))
        t0 = time.perf_counter()
        name, store_key = self._program_key(key)
        fn = self._store.load(name, store_key, devices)
        if fn is not None:
            log.info("step fn %s loaded from the program store in %.3fs",
                     name, time.perf_counter() - t0)
        return fn

    def _keep_program(self, key: tuple, inputs) -> None:
        """Write the step program of ``key`` to the store, after its first
        call (on ``inputs``) built it the ordinary way: lowered again from
        the live arrays, which jit answers from what that call left it
        (the executable is the one it runs), then serialised. Nothing for a
        program that came from the store, or where there is none."""
        if self._store is None or self._was_loaded(key):
            return
        t0 = time.perf_counter()
        name, store_key = self._program_key(key)
        try:
            compiled = self._step_fns[key].lower(
                *self._carried(), *inputs, **self._ssm_kw()).compile()
        except Exception:
            log.warning("step fn %s not kept", name, exc_info=True)
            return
        t1 = time.perf_counter()
        self._store.save(name, store_key, compiled)
        log.info("step fn %s kept in the program store in %.3fs (%.3fs of "
                 "it the lowering jit holds)", name,
                 time.perf_counter() - t0, t1 - t0)

    def phase_tables(self, programs=None) -> dict[str, dict[str, str]]:
        """``{program: {instruction: innermost phase}}`` of the step
        programs built so far (of those named in ``programs``, where given),
        by obs/profiler.py ``phase_table`` from each one's compiled text: a
        program loaded from the store has its own; a jitted one is lowered
        again with padding inputs beside the live state, which jit answers
        from what it holds for the serving call. Tens of milliseconds a
        program: for after a traced run (``AsyncJaxEngine.shutdown``) or an
        operator's question (``/debug/phases``), not for the serving path. A
        program that does not lower again (a live engine donated the cache
        under it) is left out, with a warning: ask again."""
        out: dict[str, dict[str, str]] = {}
        for key in list(self._step_fns):
            if isinstance(key[0], str):
                continue                  # a verify or an embed program
            name = "jit_" + self._step_program(*key)
            if programs is not None and name not in programs:
                continue
            b, t, nblk, _sp, greedy, mm, masked = key
            fn = self._step_fns[key]
            try:
                # The arrays themselves, as the serving call hands them
                # in: the lowering and the executable are then the ones jit
                # holds already (0.05 s a program on the chip; from shapes
                # with the same shardings it is another module, a compile
                # of its own: 14 s for a routed chunk step, PERF.md).
                text = (fn if self._was_loaded(key) else fn.lower(
                    *self._carried(),
                    *self._padding_inputs(b, t, nblk, greedy),
                    *map(self._place, self._padding_extras(b, t, mm, masked)),
                    **self._ssm_kw()).compile()).as_text()
            except Exception:
                log.warning("no phase table for %s", name, exc_info=True)
                continue
            out[name] = phase_table(text)
        return out

    def used_fast_greedy(self) -> bool:
        """Whether any compiled step so far took the argmax-only greedy
        variant — THE accessor for the compile-cache key layout (step keys
        are (b, t, nblk, sp, fast_greedy, mm, masked); 'verify'/'embed'
        entries are string-prefixed and excluded)."""
        return any(not isinstance(k[0], str) and k[4]
                   for k in self._step_fns)

    def reset_slot(self, slot: int, seed: int | None, *, advance: int = 0,
                   resume_tokens: "list[int] | None" = None) -> None:
        """Initialize a seq's persistent sampling state. ``advance`` replays
        that many sampler draws on the fresh key (sample()'s split chain is
        a pure function of (seed, draws), so a checkpoint-resumed stream's
        n+1'th draw is bit-identical to the unkilled run's);
        ``resume_tokens`` rebuilds the penalty counts
        from the already-generated ledger riding the resume prompt."""
        self.counts = self.counts.at[slot].set(0)
        if resume_tokens:
            toks = jnp.asarray(resume_tokens, jnp.int32)
            self.counts = self.counts.at[slot, toks].add(1)
        if seed is not None:
            k = jax.random.key_data(jax.random.key(seed)).astype(jnp.uint32)
            if advance > 0:
                k = _advance_key_data(k, jnp.int32(advance)).astype(jnp.uint32)
            self.keys = self.keys.at[slot].set(k)

    def bucket_of(self, rows: list[tuple[Seq, int, int]],
                  verify: bool = False) -> BucketSig:
        """The program that serves ``rows``, from the one place that knows
        (``sig_for_rows``, obs/compile_ledger.py)."""
        # The batch's max KV coverage — NOT the max allocated table length:
        # every query/context position this step touches is < start +
        # length. The dense path gathers every table entry, so its table is
        # bucketed from this; the kernel walks only what a row holds and
        # takes one width (sig_for_rows decides).
        bsz = self.engine_cfg.block_size
        nblk_need = max(
            min(len(seq.block_ids), -(-(start + length) // bsz))
            for seq, start, length in rows)
        return sig_for_rows(
            "verify" if verify else "mixed", len(rows),
            max(length for _, _, length in rows), nblk_need, self.engine_cfg)

    def dispatch(
        self,
        rows: list[tuple[Seq, int, int]],  # (seq, start, length) per row
        sample_rows: list[bool],
        masks: list | None = None,  # per-row bool[V] allow-masks (guided)
        *, step: int = 0,
    ) -> tuple[BucketSig, str, jax.Array, jax.Array, "jax.Array | None"]:
        """Enqueue one bucketed step on the device WITHOUT blocking; returns
        the signature of the program it ran, the program's name and device
        arrays (tokens [B], logprobs likewise, and the routed layers' counts
        int32 [3] or None) still being computed. ``step`` (the step's
        ordinal) rides the ``engine.program`` span around the call with the
        program's name, which holds its bucket: what a reader joins the
        device's ``XLA Modules`` event to.
        Inside it the host's work has three parts, ``engine.dispatch.fill``
        (the packed inputs, in numpy), ``.place`` (host to device: one
        array a greedy step, two where a row samples, counted in
        ``placed_inputs``) and ``.launch`` (the jitted call). The caller overlaps host
        work (scheduling, output assembly for earlier steps) with the
        device, then materializes via ``np.asarray``. A batch whose longest
        row is one token is the decode program; anything else is the ragged
        mixed program (decode rows packed with prefill-chunk rows): rows
        bucket over the decode ladder, t over the prefill chunk ladder.

        The program runs its dense layers over a token bucket N that
        follows from the (b, t) picked here (``token_bucket``); the rows
        hold no more live tokens than that, which the caller sees to by
        cutting a step with ``pack_rows``."""
        span = jax.profiler.TraceAnnotation("engine.program")
        clock = self.loop_clock
        with span:
            with loop_phase(clock, "engine.dispatch.fill"):
                sig, sp_prefill, arrays, mm, masked = self._fill_inputs(
                    rows, sample_rows, masks)
            kind, b, t, nblk = sig.kind, sig.b, sig.t, sig.nblk
            fast_greedy = sig.greedy
            led = self._ledger
            n_tok = _step_tokens(b, t, sp_prefill)
            live = sum(length for _, _, length in rows)
            if live > n_tok:
                raise ValueError(
                    f"{live} live tokens in a {kind} batch whose "
                    f"bucket (b={b}, t={t}) holds {n_tok}: cut it with "
                    "pack_rows")
            key = (b, t, nblk, sp_prefill, fast_greedy, mm, masked)
            miss = key not in self._step_fns
            cold = led.enabled and miss
            program = "jit_" + sig.program(sp_prefill=sp_prefill, mm=mm,
                                           masked=masked)
            if span.is_enabled():    # a profiler session is recording
                span.set_metadata(step=step, program=program)
            with loop_phase(clock, "engine.dispatch.place"):
                inputs = [self._place(x) for x in arrays]
            self.placed_inputs += len(inputs)
            if cold:
                # A miss pays for its program inside the phase below: the
                # store's load in step_fn, or jit's trace+compile, which
                # is lazy, INSIDE the first call (only execution stays
                # async); then the write to the store. Timing the three
                # measures the engine-thread stall.
                led.mark_inflight(True)
                t_compile = time.perf_counter()
            with loop_phase(clock, "engine.dispatch.launch"), \
                    self._compile_phase(miss, kind, b, t, nblk):
                toks, lps, *moe = self._run_step(self.step_fn(*key), inputs)
                if miss:
                    self._keep_program(key, inputs)
            if cold:
                dt = time.perf_counter() - t_compile
                led.mark_inflight(False)
                led.record(
                    sig, dt, **self._built(key),
                    trace_ctx=next((s.trace_ctx for s, _, _ in rows
                                    if s.trace_ctx is not None), None))
        return sig, program, toks, lps, (moe[0] if moe else None)

    def _fill_inputs(self, rows, sample_rows, masks):
        """The numpy inputs of the step program that serves ``rows``, filled
        row by row and packed (``engine.dispatch.fill``): the program's
        signature (``greedy`` as the rows turned out), whether it is a ring
        prefill, the packed inputs (``pack_step_inputs``: one array, two
        where a row samples) with the multimodal pair and the logit mask
        behind them where the step has them, and whether it has them."""
        t_max = max(length for _, _, length in rows)
        sig = self.bucket_of(rows)
        b, t, nblk = sig.b, sig.t, sig.nblk
        # Sequence-parallel prefill: a batch of fresh full-prompt chunks
        # (every row starts at 0) on a seq>1 mesh rides ring attention —
        # but only past the ring-vs-chunked threshold (explicit knob or
        # cost-model break-even, resolved in __init__). Shorter prompts
        # take the dense path: identical program to an sp=1 engine, so
        # staying below threshold costs zero extra ops.
        sp_capable = (
            t > 1
            and self.mesh is not None
            and self.mesh.shape.get("seq", 1) > 1
            and all(start == 0 for _, start, _ in rows)
        )
        sp_prefill = (
            sp_capable
            and self.ring_threshold is not None
            and t_max >= self.ring_threshold
        )
        if t > 1 and self.ring_threshold is not None:
            from dynamo_tpu.obs.ring_prefill import get_ring_prefill_metrics

            rpm = get_ring_prefill_metrics()
            if sp_prefill:
                rpm.invocations.inc()
                rpm.tokens.inc(sum(length for _, _, length in rows))
            else:
                rpm.bypassed.inc()

        masked = masks is not None and any(m is not None for m in masks)
        per_row = _padding_rows(b, t, nblk)
        (tokens, q_start, q_len, bt, slots, temp, top_k, top_p, fp, pp, rp,
         do_sample, from_slot) = per_row
        fast_greedy = True  # padding rows (temp 0, rp 1) are greedy-compatible

        for i, (seq, start, length) in enumerate(rows):
            # Decode rows only (start at/after the prefill target): a
            # length-1 resume-prefill chunk must read its host token, not
            # the in-flight sampled one.
            if (seq.inflight_samples > 0 and length == 1
                    and start >= seq.prefill_target()):
                # The input token was sampled by a still-in-flight step; the
                # compiled step reads it from slot_toks on device.
                from_slot[i] = True
            else:
                chunk = seq.tokens[start : start + length]
                tokens[i, : len(chunk)] = chunk
            q_start[i] = start
            q_len[i] = length
            ids = seq.block_ids[:nblk]  # beyond-coverage blocks never read
            bt[i, : len(ids)] = ids
            slots[i] = max(seq.slot, 0)
            so = seq.req.sampling_options
            temp[i] = so.temperature if so.temperature is not None else 1.0
            top_k[i] = so.top_k or 0
            top_p[i] = so.top_p if so.top_p is not None else 1.0
            fp[i] = so.frequency_penalty or 0.0
            pp[i] = so.presence_penalty or 0.0
            rp[i] = so.repetition_penalty or 1.0
            do_sample[i] = sample_rows[i]
            if temp[i] > 0.0 or fp[i] != 0.0 or pp[i] != 0.0 or rp[i] != 1.0:
                fast_greedy = False

        # Multimodal: chunks intersecting an embedding span carry the
        # encoder outputs for those positions. NOT gated on t>1 — a
        # length-1 prefill tail (chunk budget, prefix-cache hit leaving one
        # token) can land inside a span, and serving the placeholder
        # embedding there would poison the digest-keyed prefix cache.
        # Decode rows start at/after the prompt end, so they never
        # intersect and mm stays False for them naturally.
        emb_override = None
        for i, (seq, start, length) in enumerate(rows):
            if not seq.mm_spans or start >= seq.mm_end:
                continue  # decode rows skip the span scan with one compare
            for pos, emb in seq.mm_spans:
                lo = max(pos, start)
                hi = min(pos + emb.shape[0], start + length)
                if lo >= hi:
                    continue
                if emb_override is None:
                    emb_override = np.zeros(
                        (b, t, self.cfg.hidden_size), np.float32)
                    emb_mask = np.zeros((b, t), bool)
                emb_override[i, lo - start:hi - start] = \
                    emb[lo - pos:hi - pos]
                emb_mask[i, lo - start:hi - start] = True
        mm = emb_override is not None

        if masked:
            fast_greedy = False
            logit_mask = np.zeros((b, self.cfg.vocab_size), np.float32)
            for i, m in enumerate(masks):
                if m is not None:
                    logit_mask[i, ~m] = -1e30
        if not fast_greedy:
            sig = dataclasses.replace(sig, greedy=False)
        arrays = list(pack_step_inputs(*per_row, greedy=fast_greedy))
        if mm:
            arrays += [emb_override, emb_mask]
        if masked:
            arrays.append(logit_mask)
        return sig, sp_prefill, arrays, mm, masked

    def _compile_phase(self, miss: bool, kind: str, b: int, t: int,
                       nblk: int):
        """``engine.compile`` around the first call of a step program the
        serving path had to build (jit compiles inside that call); nothing
        around a call that hits."""
        if not miss:
            return _NO_PHASE
        return loop_phase(self.loop_clock, "engine.compile", kind=kind,
                          b=b, t=t, nblk=nblk)

    # -- speculative verify --------------------------------------------
    def _build_verify_fn(self, b: int, t: int, nblk: int):
        """One forward over a [B, t] chunk of (current token + proposed
        continuation), returning the ARGMAX token and its logprob at EVERY
        position — the speculative-decoding verify step (engine/spec.py).
        Greedy-only by contract (callers gate on greedy+penalty-free rows),
        so no sampling state is read or written; KV for all positions is
        written (rejected positions are overwritten by later true tokens)."""
        cfg = self.cfg
        attn_impl = self.attn_impl
        moe_impl = self.moe_impl
        mesh = self.mesh

        def verify(params, ck, cv, tokens, q_start, q_len, bt):
            hidden, ck, cv = llama.forward(
                params, cfg, tokens, q_start, q_len, bt, ck, cv,
                attn_impl=attn_impl, moe_impl=moe_impl, mesh=mesh,
                return_all_hidden=True)
            logits = llama.logits_from_hidden(params, cfg, hidden).astype(jnp.float32)
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # [B, t]
            lps = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                      toks[..., None], axis=-1)[..., 0]
            return ck, cv, toks, lps

        kw = {}
        if self.mesh is not None:
            repl, cache = self._repl, cache_sharding(self.spec, self.mesh)
            kw["out_shardings"] = (
                cache, None if self.spec.latent else cache, repl, repl)
        name = BucketSig("verify", b, t, nblk, True, "").program()
        return jax.jit(_named(verify, name), donate_argnums=(1, 2), **kw)

    def dispatch_verify(self, rows: list[tuple[Seq, int, int]],
                        chunks: list[list[int]], *, step: int = 0
                        ) -> tuple[BucketSig, jax.Array, jax.Array]:
        """Enqueue one verify step; chunk tokens are EXPLICIT (the proposals
        are not in seq.tokens yet) and each row's length is its chunk's.
        Returns the signature and ([B, t] argmax tokens, lps). One
        ``engine.program`` span, as ``dispatch`` has."""
        sig = self.bucket_of(rows, verify=True)
        b, t, nblk = sig.b, sig.t, sig.nblk
        with jax.profiler.TraceAnnotation(
                "engine.program", step=step, program="jit_" + sig.program()):
            return self._dispatch_verify(sig, rows, chunks)

    def _dispatch_verify(self, sig: BucketSig, rows, chunks):
        b, t, nblk = sig.b, sig.t, sig.nblk

        tokens = np.zeros((b, t), np.int32)
        q_start = np.zeros((b,), np.int32)
        q_len = np.zeros((b,), np.int32)
        bt = np.zeros((b, nblk), np.int32)
        for i, (seq, start, length) in enumerate(rows):
            tokens[i, : len(chunks[i])] = chunks[i]
            q_start[i] = start
            q_len[i] = len(chunks[i])
            ids = seq.block_ids[:nblk]
            bt[i, : len(ids)] = ids

        key = ("verify", b, t, nblk)
        led = self._ledger
        miss = key not in self._step_fns
        cold = led.enabled and miss
        if miss:
            log.info("compiling verify fn B=%d T=%d NBLK=%d", b, t, nblk)
            self._step_fns[key] = self._build_verify_fn(b, t, nblk)
        fn = self._step_fns[key]
        place = self._place
        if cold:
            led.mark_inflight(True)
            t_compile = time.perf_counter()
        with self._compile_phase(miss, "verify", b, t, nblk):
            self.cache_k, self.cache_v, toks, lps = fn(
                self.params, self.cache_k, self.cache_v,
                place(tokens), place(q_start), place(q_len), place(bt))
        if cold:
            dt = time.perf_counter() - t_compile
            led.mark_inflight(False)
            led.record(
                sig, dt, bodies=self._bodies,
                trace_ctx=next((s.trace_ctx for s, _, _ in rows
                                if s.trace_ctx is not None), None))
        return sig, toks, lps

    # -- embeddings ----------------------------------------------------
    def _build_embed_fn(self, b: int, t: int):
        """Prefill-only forward returning the final-norm hidden state at the
        last prompt token (the /v1/embeddings pooling; reference route:
        lib/llm/src/http/service/openai.rs:1132). Uses a TRANSIENT cache
        built inside the jit — embedding calls never touch (or contend with)
        the serving KV pool."""
        cfg = self.cfg
        ec = self.engine_cfg
        nblk = -(-t // ec.block_size) + 1

        def embed(params, tokens, q_len):
            shape = (cfg.attn_layers, nblk + 1, ec.block_size,
                     cfg.cache_kv_heads, cfg.cache_head_dim)
            ck = jnp.zeros(shape, jnp.dtype(cfg.dtype))
            cv = None if cfg.latent else jnp.zeros(shape, jnp.dtype(cfg.dtype))
            bt = jnp.tile(jnp.arange(1, nblk + 1, dtype=jnp.int32)[None, :],
                          (tokens.shape[0], 1))
            q_start = jnp.zeros((tokens.shape[0],), jnp.int32)
            state = {}
            if cfg.has_ssm:    # transient too: a row a sequence, from zeros
                state = {"ssm": mamba.zeros_state(cfg, tokens.shape[0]),
                         "ssm_slots": jnp.arange(tokens.shape[0])}
            hidden, *_ = llama.forward(
                params, cfg, tokens, q_start, q_len, bt, ck, cv,
                attn_impl="dense", mesh=self.mesh, **state)
            return hidden.astype(jnp.float32)

        kw = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            kw["out_shardings"] = NamedSharding(self.mesh, P())
        name = BucketSig("embed", b, t, 0, True, "").program()
        return jax.jit(_named(embed, name), **kw)

    def embed(self, token_lists: list[list[int]]) -> np.ndarray:
        """Embed a batch of token sequences → [N, H] float32 (last-token
        pooled, final-norm space)."""
        out = np.zeros((len(token_lists), self.cfg.hidden_size), np.float32)
        t_max = max(len(ts) for ts in token_lists)
        if t_max > self.engine_cfg.max_model_len:
            raise ValueError(
                f"embedding input of {t_max} tokens exceeds max_model_len="
                f"{self.engine_cfg.max_model_len}")
        sig = sig_for_rows("embed", len(token_lists), t_max, 0,
                           self.engine_cfg)
        b, t = sig.b, sig.t
        key = ("embed", b, t, 0, 0)
        led = self._ledger
        miss = key not in self._step_fns
        cold = led.enabled and miss
        if miss:
            log.info("compiling embed fn B=%d T=%d", b, t)
            self._step_fns[key] = self._build_embed_fn(b, t)
        fn = self._step_fns[key]
        tokens = np.zeros((b, t), np.int32)
        q_len = np.zeros((b,), np.int32)
        for i, ts in enumerate(token_lists):
            tokens[i, : len(ts)] = ts
            q_len[i] = len(ts)
        if cold:
            led.mark_inflight(True)
            t_compile = time.perf_counter()
        with self._compile_phase(miss, "embed", b, t, 0):
            hidden = np.asarray(fn(self.params, self._place(tokens),
                                   self._place(q_len)))
        if cold:
            led.mark_inflight(False)
            led.record(sig, time.perf_counter() - t_compile,
                       bodies=self._bodies)
        out[:] = hidden[: len(token_lists)]
        return out

    # -- AOT bucket warmup ---------------------------------------------
    def warmup(self, sigs: list[BucketSig], deadline_s: float = 0.0) -> dict:
        """Precompile the enumerated bucket lattice (obs/compile_ledger.py)
        by executing each program once with padding inputs: q_len=0 rows
        compute nothing meaningful, do_sample=False routes sampling-state
        writes to the trash row, and KV writes land in pool block 0 —
        which every real prefill rewrites before anything reads it. jit
        caches executables per call signature, so this mints exactly the
        cache entries serving dispatches would otherwise compile lazily
        (and the ledger's inventory ends equal to the enumeration).
        ``deadline_s`` bounds the total wall (0 = unbounded); lattice
        entries past the deadline stay cold and count against coverage."""
        led = self._ledger
        t0 = time.perf_counter()
        compiled = cached = failed = skipped = 0
        for sig in sigs:
            if deadline_s > 0 and time.perf_counter() - t0 >= deadline_s:
                skipped += 1
                continue
            try:
                hit = self._warm_one(sig)
            except Exception:
                log.warning("warmup compile failed for %s", sig,
                            exc_info=True)
                failed += 1
                continue
            cached += 1 if hit else 0
            compiled += 0 if hit else 1
        summary = {"compiled": compiled, "cached": cached, "failed": failed,
                   "deadline_skipped": skipped,
                   "seconds": round(time.perf_counter() - t0, 3),
                   "coverage": round(led.coverage(), 4)}
        log.info("bucket warmup: %s", summary)
        return summary

    def _warm_one(self, sig: BucketSig) -> bool:
        """Compile+execute one bucket signature with padding inputs.
        Returns True when the program was already cached (no compile)."""
        b, t, nblk = sig.b, sig.t, sig.nblk
        place = self._place
        t0 = time.perf_counter()
        if sig.kind == "embed":
            key = ("embed", b, t, 0, 0)
            if key in self._step_fns:
                return True
            self._step_fns[key] = self._build_embed_fn(b, t)
            np.asarray(self._step_fns[key](
                self.params, place(np.zeros((b, t), np.int32)),
                place(np.zeros((b,), np.int32))))
        elif sig.kind == "verify":
            key = ("verify", b, t, nblk)
            if key in self._step_fns:
                return True
            self._step_fns[key] = self._build_verify_fn(b, t, nblk)
            self.cache_k, self.cache_v, toks, _lps = self._step_fns[key](
                self.params, self.cache_k, self.cache_v,
                place(np.zeros((b, t), np.int32)),
                place(np.zeros((b,), np.int32)),
                place(np.zeros((b,), np.int32)),
                place(np.zeros((b, nblk), np.int32)))
            np.asarray(toks)
        else:
            key = (b, t, nblk, False, sig.greedy, False, False)
            if key in self._step_fns:
                return True
            inputs = self._padding_inputs(b, t, nblk, sig.greedy)
            toks, *_rest = self._run_step(self.step_fn(*key), inputs)
            np.asarray(toks)
            self._keep_program(key, inputs)
        self._ledger.record(sig, time.perf_counter() - t0, source="warmup",
                            **self._built(key))
        return False


class EngineCore:
    """Synchronous engine: scheduler + runner + output assembly."""

    def __init__(
        self,
        engine_cfg: EngineConfig,
        mesh=None,
        params=None,
        event_sink: Callable[[KvCacheEvent], None] | None = None,
        program_dir: "str | Path | None" = None,
    ):
        # Before the first jit on every path that builds an engine.
        device.require_backend(jax.default_backend(),
                               jax.config.jax_platforms)
        compile_cache_dir = device.configure_compile_cache()
        # The compiled step programs are kept beside the compile cache
        # (engine/program_store.py), so exactly where there is one; a test
        # names a directory of its own. (One process: a program of a mesh
        # that spans processes has not been shown to load in each of them.)
        if (program_dir is None and compile_cache_dir
                and jax.process_count() == 1):
            program_dir = Path(compile_cache_dir) / "programs"
        if engine_cfg.sp > 1 and engine_cfg.ring_prefill_threshold >= 0 and (
            engine_cfg.prefill_chunk < engine_cfg.max_model_len
            or engine_cfg.max_tokens_per_step < engine_cfg.max_model_len
        ):
            # Sequence-parallel engines prefill whole prompts as ONE
            # seq-sharded chunk (ring attention needs the chunk to be the
            # entire context); chunking — whether by prefill_chunk or by the
            # scheduler's per-step token budget — would push later chunks
            # (start != 0) onto the dense path and waste the sp axis. Copy
            # the config rather than mutating the caller's.
            log.info(
                "sp=%d: raising prefill_chunk %d and max_tokens_per_step %d -> "
                "max_model_len %d", engine_cfg.sp, engine_cfg.prefill_chunk,
                engine_cfg.max_tokens_per_step, engine_cfg.max_model_len)
            engine_cfg = dataclasses.replace(
                engine_cfg,
                prefill_chunk=max(engine_cfg.prefill_chunk, engine_cfg.max_model_len),
                max_tokens_per_step=max(engine_cfg.max_tokens_per_step,
                                        engine_cfg.max_model_len),
            )
        self.engine_cfg = engine_cfg
        if engine_cfg.spec_ngram > 0 and engine_cfg.pp > 1:
            raise ValueError("spec_ngram requires pp=1 (forward_pp has "
                             "no all-positions output)")
        if engine_cfg.pp > 1 and (engine_cfg.tp > 1 or engine_cfg.ep > 1
                                  or engine_cfg.sp > 1):
            raise ValueError(
                "pp>1 currently composes only with dp; tp/ep/sp must be 1 "
                "(the PP stage block is not head/expert/sequence-sharded — "
                "see models/llama.forward_pp)")
        if engine_cfg.quantization not in ("none", "", "int8"):
            # Validate here, before any weight IO — a typo must fail in
            # milliseconds, not after loading/sharding a 70B checkpoint.
            raise ValueError(
                f"unknown quantization {engine_cfg.quantization!r} "
                "(supported: none, int8)")
        if engine_cfg.kv_dtype not in ("bfloat16", "", "int8", "int4"):
            raise ValueError(
                f"unknown kv_dtype {engine_cfg.kv_dtype!r} "
                "(supported: bfloat16 [model-precision cache], int8, int4)")
        if engine_cfg.warmup_mode not in WARMUP_MODES:
            raise ValueError(
                f"unknown warmup_mode {engine_cfg.warmup_mode!r} "
                f"(supported: {', '.join(WARMUP_MODES)})")
        if engine_cfg.warmup_deadline < 0:
            raise ValueError(
                f"warmup_deadline must be >= 0 (0 = unbounded), "
                f"got {engine_cfg.warmup_deadline}")
        # Compile ledger gate (obs/compile_ledger.py): configured before
        # the runner exists so every compile this engine ever mints is
        # governed by the same mode; the enumerated lattice doubles as the
        # coverage denominator in lazy mode (grows organically) and the
        # precompile worklist in full mode (EngineCore.warmup).
        get_compile_ledger().configure(engine_cfg.warmup_mode)
        # Scheduling ledger gate (obs/sched_ledger.py): re-read the
        # DYN_SCHED_LEDGER env at engine construction so tests flipping
        # the env see the gate they set.
        self.sched_led = get_sched_ledger()
        self.sched_led.configure()
        self.model_cfg = resolve_model_config(engine_cfg.model)
        if engine_cfg.kv_dtype == "int4" and self.model_cfg.head_dim % 2:
            raise ValueError(
                f"kv_dtype=int4 packs two nibbles per byte along head_dim and "
                f"needs it even; model {engine_cfg.model!r} has head_dim="
                f"{self.model_cfg.head_dim}")
        mc = self.model_cfg
        if mc.latent:
            _latent_engine_config(engine_cfg)
        if mc.holds_share and engine_cfg.ep > 1:
            raise ValueError(
                f"model {engine_cfg.model!r} holds {mc.num_experts} of "
                f"{mc.router_width} routed experts: that is one chip's share "
                f"of an expert-parallel deployment, and ep={engine_cfg.ep} "
                "would divide it again; give the whole model to ep > 1")
        if engine_cfg.pp > 1 and mc.layer_plan.split != (0, 1, mc.num_layers, 0):
            raise ValueError(
                "pipeline stages take equal stacks of identical layers: a "
                "model with leading layers or a period of several (sliding "
                "and full attention, one mixer a layer) cannot run at "
                f"pp={engine_cfg.pp}")
        if mc.sliding_window and engine_cfg.sp > 1:
            raise ValueError("ring prefill has no window: a model with "
                             f"sliding layers cannot run at sp={engine_cfg.sp}")
        if mc.has_ssm:
            engine_cfg = self.engine_cfg = _recurrent_engine_config(
                engine_cfg)
        # Each attention layer's window, the routed and the recurrent
        # layers' counts: what the one count of a step's work needs
        # (obs/sched_ledger.py step_counts).
        self._windows = mc.attn_windows
        self._routed_layers = mc.layers_of("E")
        self._recurrent_and_cross = recurrent_and_cross(mc)
        self._ssm_layers = self._recurrent_and_cross["ssm_layers"]
        # Whether a program of n tokens streams its experts: the routed
        # layer's own predicate at this model's expert shape.
        from dynamo_tpu.models.moe import streams_experts

        expert = (mc.hidden_size, mc.expert_store_width,
                  jnp.dtype(mc.dtype).itemsize)
        self._streams_experts = lambda n: streams_experts(
            n, *expert, self.runner.mesh, 3 if mc.expert_gated else 2)
        # SLO-driven chunk sizing (prefill_chunk=0 = auto): resolve to
        # concrete per-QoS chunks BEFORE bucket enumeration and the
        # scheduler read the config — the prefill t ladder, warmup plan
        # and per-step token budget all key off ec.prefill_chunk, so auto
        # must not leave a 0 behind. The cap is the batch class's chunk
        # (largest SLO budget); interactive/standard refine downward
        # per-seq inside the scheduler.
        from dynamo_tpu.obs import costmodel as cm
        self._hw = cm.hw_spec_for(jax.devices()[0].device_kind)
        if engine_cfg.prefill_chunk <= 0:
            ladder_cap = min(engine_cfg.max_model_len,
                             engine_cfg.max_tokens_per_step)
            self.chunk_by_qos = {
                qos: cm.auto_prefill_chunk(
                    self.model_cfg, self._hw,
                    itl_slo_s=engine_cfg.itl_slo_ms / 1e3,
                    decode_rows=engine_cfg.max_batch_size,
                    decode_kv_len=max(engine_cfg.max_model_len // 2,
                                      engine_cfg.block_size),
                    block_size=engine_cfg.block_size,
                    max_chunk=ladder_cap,
                    kv_dtype=engine_cfg.kv_dtype or "bfloat16",
                    quantization=engine_cfg.quantization or "none",
                    qos_class=qos)
                for qos in cm.QOS_ITL_SLO_SCALE}
            resolved = max(self.chunk_by_qos.values())
            log.info("auto prefill chunk (itl_slo=%.1fms): %s -> cap %d",
                     engine_cfg.itl_slo_ms, self.chunk_by_qos, resolved)
            engine_cfg = dataclasses.replace(engine_cfg, prefill_chunk=resolved)
            self.engine_cfg = engine_cfg
        else:
            self.chunk_by_qos = {qos: engine_cfg.prefill_chunk
                                 for qos in cm.QOS_ITL_SLO_SCALE}
        self.sched_led.set_prefill_chunks(self.chunk_by_qos)
        # "auto" likewise leaves nothing behind: where attention is the
        # kernel a step's block table has ONE width (sig_for_rows reads the
        # resolved name), so the lattice, the warm-up and dispatch() agree.
        from dynamo_tpu.ops.paged_attention import select_attn_impl

        engine_cfg = dataclasses.replace(
            engine_cfg, attn_impl=select_attn_impl(engine_cfg.attn_impl))
        self.engine_cfg = engine_cfg
        if mesh is None and any(v != 1 for v in engine_cfg.mesh_shape().values()):
            mesh = make_mesh(MeshConfig(dp=engine_cfg.dp, pp=engine_cfg.pp,
                                        sp=engine_cfg.sp, tp=engine_cfg.tp,
                                        ep=engine_cfg.ep))
        self.runner = ModelRunner(self.model_cfg, engine_cfg, mesh=mesh, params=params,
                                  rng_seed=engine_cfg.seed,
                                  program_dir=program_dir)
        # What is running, said once here and again in stats(): the smoke
        # and the benchmark read it instead of touching JAX themselves.
        spec = self.runner.spec
        self.device_info = device.describe(
            self.runner.mesh, attn_impl=self.runner.attn_impl,
            pool_blocks=spec.num_blocks,
            pool_bytes=spec.num_blocks * spec.bytes_per_block(),
            compile_cache_dir=compile_cache_dir)
        shown = dict(self.device_info, mesh=",".join(
            f"{a}={n}" for a, n in self.device_info["mesh"].items()) or "none")
        log.info("engine on %s",
                 " ".join(f"{k}={v}" for k, v in shown.items()))
        if engine_cfg.warmup_mode != "off":
            # Publish the reachable lattice so coverage is meaningful even
            # before (or without) a full warmup — a lazy engine's coverage
            # gauge climbs as traffic mints buckets.
            get_compile_ledger().set_plan(enumerate_buckets(engine_cfg))
        self.pool = PrefixPool(
            self.runner.spec.num_blocks,
            engine_cfg.block_size,
            event_sink=event_sink,
            enable_prefix_caching=engine_cfg.enable_prefix_caching,
        )
        self.sched = Scheduler(
            pool=self.pool,
            max_batch_size=engine_cfg.max_batch_size,
            prefill_chunk=engine_cfg.prefill_chunk,
            max_model_len=engine_cfg.max_model_len,
            max_tokens_per_step=engine_cfg.max_tokens_per_step,
            spec_lookahead=(engine_cfg.spec_k if engine_cfg.spec_ngram > 0
                            else 0),
            chunk_by_qos=self.chunk_by_qos,
        )
        # Session-sticky KV retention (engine/session.py): finished streams
        # carrying a session.id keep their committed blocks pinned so the
        # next turn prefills only the suffix. Needs prefix caching — the
        # retained chain is claimed through the normal admission-time
        # match_prefix, which is also how avoided tokens get MEASURED.
        self.sessions: SessionStore | None = None
        if engine_cfg.session_ttl > 0 and engine_cfg.enable_prefix_caching:
            self.sessions = SessionStore(self.pool,
                                         ttl=engine_cfg.session_ttl)
        payload = cache_payload(self.runner.cache_k)
        self.metrics = EngineMetrics(
            kv_cache_bytes=(self.runner.spec.bytes_per_block()
                            * self.runner.spec.num_blocks),
            kv_quant_enabled=self.runner.spec.quantized,
            kv_cache_shape=tuple(
                payload.sharding.shard_shape(payload.shape)),
            kv_pool_blocks=self.runner.spec.num_blocks,
            kv_block_bytes=self.runner._block_bytes_per_device(),
            kv_step_copy_bytes_per_block=self.runner.step_copy_bytes_per_block,
            moe=self._moe_facts(),
            ssm=self._ssm_facts(),
            attn=self._attn_facts(),
            step_shapes=cm.step_shapes(
                mc, block_size=engine_cfg.block_size,
                kv_dtype=engine_cfg.kv_dtype or "bfloat16",
                quantization=engine_cfg.quantization or "none",
                devices=self.runner.mesh.size if self.runner.mesh else 1),
        )
        # The step programs that ran while a profiler session was open:
        # their phase tables are built when the engine shuts down
        # (AsyncJaxEngine.shutdown), and for nobody else.
        self.traced_programs: set[str] = set()
        register_phase_source(self.runner.phase_tables)
        # The engine thread's loop phases (obs/profiler.py loop_phase):
        # always-on seconds per phase, and profiler spans at the same
        # boundaries. The runner times its serve-path compiles into it.
        self.loop_clock = self.runner.loop_clock
        # (t_arrival, t_added, t_first_plan) of the sequences whose first
        # token the last finalize emitted; whoever hands the outputs on
        # closes them (outputs_posted).
        self._first_tokens: list[tuple[float, float, float]] = []
        # The gap ledger's side of a finalize (obs/sched_ledger.py
        # GapStamps), and the step's ledger record, which gains the gap.
        self._gap = GapStamps()
        self._sched_rec = None
        # Hardware counters: analytic FLOPs/bytes + MFU/BW-util per step
        # (obs/profiler.py). DYN_PERF_PROFILE=0 turns the whole thing into
        # a no-op dict lookup per step.
        self.perf = StepPerfProfiler(self.model_cfg, engine_cfg,
                                     shapes=self.metrics.step_shapes)
        self._seqs: dict[str, Seq] = {}
        self.default_eos: list[int] = []
        # Tracing: decode spans rotate every N generated tokens — one span
        # (one allocation) per N steps, never per token (obs/tracer.py).
        import os as _os
        self._trace_stride = max(
            int(_os.environ.get("DYN_TRACE_DECODE_STRIDE", "32")), 1)
        self._trace_last_preempt = 0
        # Deadline clock for the current step window. On multi-host engines
        # the leader stamps it over the op stream so every rank evaluates
        # deadline expiry against the SAME instant — per-rank wall clocks
        # would let ranks disagree on a cancellation and diverge.
        self._step_now: float | None = None
        # Structured output: token-id → text table + tokenizer EOS, built
        # lazily on the first guided request (engine/guided.py).
        self._guided_vocab: tuple[list[str], list[int]] | None = None
        self.kvbm: "OffloadManager | None" = None
        if (engine_cfg.host_kv_blocks > 0 or engine_cfg.disk_kv_path
                or engine_cfg.remote_kv_addr):
            from dynamo_tpu.kvbm.offload import OffloadManager
            from dynamo_tpu.kvbm.pools import DiskBlockPool, HostBlockPool

            # Multi-host engines: every rank runs this same construction in
            # SPMD lockstep (op-stream replay keeps decisions identical);
            # tiers then hold rank-LOCAL cache shards and extract/inject
            # touch only addressable memory (kvbm/distributed.py — the
            # reference's KvbmLeader/KvbmWorker split without the control
            # channel, distributed/leader.rs:126, worker.rs:143).
            transfer = None
            tier_spec, fp = self.runner.spec, engine_cfg.model
            disk_path = engine_cfg.disk_kv_path
            if jax.process_count() > 1:
                from dynamo_tpu.kvbm.distributed import (
                    ShardedBlockTransferEngine,
                    local_block_spec,
                )

                transfer = ShardedBlockTransferEngine(self.runner.mesh)
                tier_spec, shard_fp = local_block_spec(
                    self.runner.spec, self.runner.cache_k)
                fp = f"{engine_cfg.model}|{shard_fp}"
                if disk_path:
                    # Per-rank subdir: ranks colocated on one filesystem
                    # must not fight over one MANIFEST/arena.
                    disk_path = str(Path(disk_path) /
                                    f"rank{jax.process_index()}")
            # Cascade G2 host → G3 disk → G4 remote: each tier spills its
            # LRU victims to the next, lookups walk the chain top-down.
            remote = None
            if engine_cfg.remote_kv_addr:
                from dynamo_tpu.kvbm.remote import RemoteBlockPool

                remote = RemoteBlockPool(tier_spec, engine_cfg.remote_kv_addr,
                                         fingerprint=fp)
            disk = (DiskBlockPool(tier_spec, disk_path,
                                  engine_cfg.disk_kv_bytes,
                                  fingerprint=fp,
                                  overflow=remote)
                    if disk_path else None)
            tiers: list = []
            if engine_cfg.host_kv_blocks > 0:
                tiers.append(HostBlockPool(tier_spec, engine_cfg.host_kv_blocks,
                                           overflow=disk or remote))
            if disk is not None:
                tiers.append(disk)
            if remote is not None:
                tiers.append(remote)
            self.kvbm = OffloadManager(
                self.runner, self.pool, tiers, transfer=transfer,
                # The shared G4 store can't guarantee rank-identical
                # hit/miss (cross-engine LRU, connection hiccups), so
                # multi-host onboard plans are voted down to the mesh-wide
                # minimum (OffloadManager.vote_plans) instead of refused.
                vote_plans=(jax.process_count() > 1
                            and bool(engine_cfg.remote_kv_addr)),
                # Fleet-wide prefix cache: committed blocks publish to the
                # shared G4 store as they form, not only on eviction.
                publish_tier=(remote if engine_cfg.global_prefix_cache
                              else None),
                # Stream checkpoints park in the same shared store. Direct
                # remote writes are single-host only (same rule as
                # evacuate_sessions: a rank's KV shard in the SHARED store
                # would corrupt cross-worker reads); multi-host ranks all
                # see ckpt_tier=None, so enqueue stays rank-identical.
                ckpt_tier=(remote
                           if (engine_cfg.stream_ckpt_blocks > 0
                               and jax.process_count() == 1)
                           else None))
        # Memory & capacity ledger (obs/mem_ledger.py): re-read the
        # DYN_MEM_LEDGER env at construction (same contract as the sched
        # ledger above), publish this engine's device pool as the G1 tier
        # row, register every KVBM tier's occupancy callback, and hand the
        # audit a live-id source so orphaned pins reconcile against what
        # this engine actually holds. Tier callbacks and the live source
        # are pulled only at snapshot/audit time, never on the step path.
        self.mem_led = get_mem_ledger()
        self.mem_led.configure()
        register_device_tier(self.pool, self.runner.spec)
        if self.kvbm is not None:
            for tier in self.kvbm.tiers:
                self.mem_led.register_tier(tier.name, tier.occupancy)
        self._mem_source_key = f"engine:{id(self):x}"
        self.mem_led.register_live_source(self._mem_source_key,
                                          self._mem_live_ids)

    def _mem_live_ids(self) -> dict:
        """Per-owner-class live ids for the mem-ledger leak audit. A pin
        tagged under any class but absent from the matching set here is an
        orphan — a reference the engine no longer knows about."""
        staged = getattr(self, "_staged_pins", {})
        return live_ids_of(
            streams=self._seqs.keys(),
            sessions=(self.sessions.session_ids()
                      if self.sessions is not None else ()),
            **(self.kvbm.queue_live_ids() if self.kvbm is not None else {}),
            staging=staged.keys(),
        )

    def _guided_pieces(self) -> tuple[list[str], list[int]]:
        if self._guided_vocab is None:
            from dynamo_tpu.tokenizer import guided_vocab, load_tokenizer

            tok = load_tokenizer(self.engine_cfg.model)
            pieces = guided_vocab(tok, self.runner.cfg.vocab_size)
            eos = getattr(tok, "eos_id", None)
            self._guided_vocab = (pieces, [eos] if eos is not None else [])
        return self._guided_vocab

    # ------------------------------------------------------------------
    def warmup(self) -> dict:
        """AOT bucket warmup (obs/compile_ledger.py). Runs BEFORE the
        engine serves (the worker calls it between construction and
        readiness, on the thread that will become the engine-core owner's
        predecessor — no step loop is running yet, so device state has one
        owner throughout). ``off``/``lazy`` return immediately; ``full``
        precompiles the enumerated lattice under ``warmup_deadline``."""
        ec = self.engine_cfg
        led = get_compile_ledger()
        out: dict = {"mode": ec.warmup_mode,
                     "coverage": round(led.coverage(), 4)}
        if ec.warmup_mode != "off" and led.plan is not None:
            out["buckets"] = len(led.plan)
        if ec.warmup_mode == "full":
            out.update(self.runner.warmup(
                sorted(led.plan or enumerate_buckets(ec),
                       key=lambda s: (s.kind, s.b, s.t, s.nblk, s.greedy)),
                deadline_s=ec.warmup_deadline))
        return out

    # ------------------------------------------------------------------
    def add_request(self, req: PreprocessedRequest,
                    now: float | None = None,
                    arrival: tuple[float, float] | None = None,
                    ) -> LLMEngineOutput | None:
        """Queue a request; returns an immediate error output if rejected.
        `now` pins the deadline-expiry clock (multi-host replay passes the
        leader's timestamp so all ranks make the same admit decision).
        ``arrival`` is when the request reached ``generate()``, as
        ``(perf_counter, time.time)`` of one instant; without it the
        request arrives now. It starts the request's time to first token
        and its ``engine.queue`` span, and no decision reads it."""
        t_added = time.perf_counter()
        t_arrival, wall_arrival = arrival or (t_added, time.time())
        if not req.token_ids:
            return LLMEngineOutput(
                finish_reason=FinishReason.ERROR, error="empty prompt (no token_ids)"
            )
        from dynamo_tpu.qos.deadline import deadline_of, expired

        if expired(deadline_of(getattr(req, "annotations", None)), now):
            # Already past deadline: never enters the scheduler, so no
            # prefill compute is ever dispatched for it.
            self.metrics.deadline_cancelled += 1
            return LLMEngineOutput(finish_reason=FinishReason.CANCELLED)
        seq = Seq(req=req, block_size=self.engine_cfg.block_size,
                  t_arrival=t_arrival, t_added=t_added)
        if req.sampling_options.guided_json is not None:
            from dynamo_tpu.engine.guided import TokenMasker

            pieces, tok_eos = self._guided_pieces()
            eos_ids = list(req.eos_token_ids or self.default_eos or tok_eos)
            seq.guided = TokenMasker(pieces, eos_ids,
                                     req.sampling_options.guided_json)
        if req.mm_embeddings:
            if self.engine_cfg.sp > 1 or self.engine_cfg.pp > 1:
                return LLMEngineOutput(
                    finish_reason=FinishReason.ERROR,
                    error="multimodal requests require sp=1 and pp=1 "
                          "(the ring/pipeline prefill paths have no "
                          "embedding-override input yet)")
            from dynamo_tpu.protocols.common import tensor_from_wire

            try:
                seq.mm_spans = [(int(s["pos"]), tensor_from_wire(s))
                                for s in req.mm_embeddings]
            except Exception as exc:  # noqa: BLE001 - malformed client input
                return LLMEngineOutput(
                    finish_reason=FinishReason.ERROR,
                    error=f"bad mm_embeddings payload: {exc}")
            H = self.model_cfg.hidden_size
            for pos, emb in seq.mm_spans:
                if (emb.ndim != 2 or emb.shape[1] != H or pos < 0
                        or pos + emb.shape[0] > len(req.token_ids)):
                    return LLMEngineOutput(
                        finish_reason=FinishReason.ERROR,
                        error=f"mm span (pos={pos}, shape={emb.shape}) out of "
                              f"range for prompt len {len(req.token_ids)} / "
                              f"hidden {H}")
            seq.mm_end = max(pos + emb.shape[0] for pos, emb in seq.mm_spans)
        self.sched.add(seq)
        if seq.phase is Phase.FINISHED:  # rejected (too long for model or pool)
            return LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                error=f"prompt of {seq.prompt_len} tokens exceeds capacity "
                      f"(max_model_len={self.engine_cfg.max_model_len}, "
                      f"usable_kv_blocks={self.pool.num_blocks - 1})",
            )
        self._seqs[req.request_id] = seq
        seq.trace_ctx = trace_context_of(getattr(req, "annotations", None))
        if seq.trace_ctx is not None:
            # Admission wait started when the request reached generate()
            # (its wait in the inbox is part of it); step_begin ends it when
            # the first prefill chunk is planned (engine.queue →
            # engine.prefill).
            seq.trace_span = get_tracer().start_span(
                "engine.queue", ctx=seq.trace_ctx, start=wall_arrival,
                request_id=req.request_id, model=req.model,
                prompt_tokens=seq.prompt_len, priority=seq.qos_priority)
        if self.sessions is not None and seq.session_id is not None:
            # Turn N+1 of a retained session: release the store's pins so
            # the chain parks in the matchable inactive pool; this seq's
            # admission-time match_prefix re-references it an instant later
            # (single-threaded core — nothing allocates in between). The
            # avoided-token count is MEASURED from that match in step_begin,
            # not taken from the entry.
            sm = get_session_metrics()
            sm.lookups.inc()
            if self.sessions.claim(seq.session_id, self._step_now) is not None:
                sm.hits.inc()
            else:
                # No local turn retained: a drained worker may have parked
                # the session in the remote store. A record hit means the
                # kvbm.onboard below pulls the evacuated chain back warm —
                # count it as a (remote) session hit for the chaos
                # invariants and the dynamo_session_* family.
                remote = self._remote_tier()
                if (remote is not None
                        and getattr(remote, "get_session", None) is not None
                        and remote.get_session(seq.session_id)):
                    sm.hits.inc()
                    sm.remote_resumes.inc()
                    self.metrics.session_remote_resumes += 1
        if self.kvbm is not None:
            # Same matchable cap as the scheduler: leave ≥1 prompt token to
            # compute so decode has last-position state. Onboarding is an
            # optimization — a corrupt tier entry must not take down the
            # engine-core thread (add_request runs outside step()'s guard).
            cap = (seq.prefill_target() - 1) // seq.block_size
            try:
                self.kvbm.onboard(seq.block_seq.sequence_hashes()[:cap])
            except Exception:
                log.exception("kvbm onboard failed; continuing without reuse")
        self.metrics.prefix_lookup_blocks += max(len(seq.tokens) // seq.block_size, 1)
        return None

    def abort(self, request_id: str) -> None:
        seq = self._seqs.get(request_id)
        if seq is None or seq.phase is Phase.FINISHED:
            return
        self._reap_stream_ckpt(seq)
        self._trace_finish(seq, FinishReason.CANCELLED)
        self.sched.finish(seq, FinishReason.CANCELLED)

    def has_work(self) -> bool:
        return self.sched.has_work()

    # ------------------------------------------------------------------
    def _check_stop(self, seq: Seq, token: int) -> FinishReason | None:
        sc = seq.req.stop_conditions
        n_out = seq.num_output_tokens
        if seq.deadline_ts is not None:
            from dynamo_tpu.qos.deadline import expired

            if expired(seq.deadline_ts, self._step_now):
                # Mid-decode deadline: nobody is waiting for the rest of
                # this stream — stop burning decode steps on it.
                self.metrics.deadline_cancelled += 1
                return FinishReason.CANCELLED
        eos_ids = set(seq.req.eos_token_ids or self.default_eos)
        if token in (sc.stop_token_ids or []):
            return FinishReason.STOP
        if token in eos_ids and not sc.ignore_eos and (sc.min_tokens or 0) <= n_out:
            return FinishReason.STOP
        if sc.max_tokens is not None and n_out >= sc.max_tokens:
            return FinishReason.LENGTH
        if len(seq.tokens) >= self.engine_cfg.max_model_len:
            return FinishReason.LENGTH
        return None

    # -- crash-consistent stream checkpoints (kvbm/stream_ckpt.py) -------
    def _init_slot(self, seq: Seq) -> None:
        """Reset a seq's sampling slot — restoring mid-stream PRNG state
        and penalty counts when the request carries stream_ckpt.* resume
        annotations. Every stream gets a concrete seed (explicit or
        request-derived), so the key after n draws is a pure function of
        the request — the invariant that makes sampled resume
        bit-identical."""
        so = seq.req.sampling_options
        seed = so.seed if so.seed is not None else _derived_seed(
            seq.request_id)
        ann = getattr(seq.req, "annotations", None) or {}
        gen = int(ann.get(CKPT_GENERATED_KEY) or 0)
        if gen <= 0:
            self.runner.reset_slot(seq.slot, seed)
            return
        gen = min(gen, seq.prompt_len)
        self.runner.reset_slot(
            seq.slot, seed,
            advance=int(ann.get(CKPT_DRAWS_KEY) or gen),
            # The resume prompt's trailing ledger: rebuild the penalty
            # counts the crashed worker had accumulated.
            resume_tokens=seq.tokens[seq.prompt_len - gen:seq.prompt_len])

    def _ckpt_interval(self, seq: Seq) -> int:
        """Committed-block cadence for this seq, QoS-degraded from the
        --stream-ckpt-blocks base: interactive streams checkpoint at the
        configured interval, standard at 2x, batch at 4x — crash exposure
        is a latency-SLO product, and batch recompute is cheap relative to
        the store traffic it saves. 0 = checkpointing off."""
        base = self.engine_cfg.stream_ckpt_blocks
        if base <= 0:
            return 0
        if seq.qos_priority == "interactive":
            return base
        return base * (4 if seq.qos_priority == "batch" else 2)

    def _maybe_stream_ckpt(self, seq: Seq) -> None:
        """Enqueue a StreamCheckpoint when due: once at prefill completion
        (the first emit's commit), then every interval committed blocks.
        The decision reads only the commit stream + config, so multi-host
        ranks stay in lockstep (the enqueue itself no-ops there —
        ckpt_tier is single-host, see EngineCore.__init__)."""
        k = self._ckpt_interval(seq)
        if (k <= 0 or self.kvbm is None or self.kvbm.ckpt_tier is None
                or seq.committed_blocks <= 0):
            return
        if 0 <= seq.ckpt_blocks and seq.committed_blocks - seq.ckpt_blocks < k:
            return
        start = max(seq.ckpt_blocks, 0)
        hashes = seq.block_seq.sequence_hashes()[: seq.committed_blocks]
        pairs = list(zip(seq.block_ids[start:seq.committed_blocks],
                         hashes[start:]))
        generated = seq.tokens[seq.prompt_len:]
        so = seq.req.sampling_options
        seed = so.seed if so.seed is not None else _derived_seed(
            seq.request_id)
        # Threefry key data is just the seed's two 32-bit words — the
        # record carries the full PRNG state (key + draw counter) without
        # touching the device.
        record = build_ckpt_record(
            seq.request_id, generated, hashes,
            key_data=[(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
            draws=len(generated), seed=seed, prompt_tokens=seq.prompt_len)
        span = None
        if seq.trace_ctx is not None:
            span = get_tracer().start_span(
                "engine.ckpt", ctx=seq.trace_ctx, request_id=seq.request_id,
                blocks=len(pairs), generated=len(generated))
        self.kvbm.enqueue_stream_ckpt(seq.request_id, record, pairs)
        if span is not None:
            get_tracer().end_span(span)
        seq.ckpt_blocks = seq.committed_blocks

    def _reap_stream_ckpt(self, seq: Seq) -> None:
        """Finish-time reap: a finished stream (any reason) must not be
        resumable. Only streams that ever checkpointed pay the store
        round-trip."""
        if self.kvbm is not None and seq.ckpt_blocks >= 0:
            self.kvbm.delete_stream_ckpt(seq.request_id)

    def ckpt_lag_blocks(self) -> int:
        """Committed blocks of live streams not yet covered by a
        checkpoint — the fleet's crash exposure, exported as
        dynamo_stream_ckpt_lag_blocks."""
        return sum(max(s.committed_blocks - max(s.ckpt_blocks, 0), 0)
                   for s in list(self._seqs.values())
                   if s.phase is not Phase.FINISHED)

    def _moe_facts(self) -> dict | None:
        """``stats()["moe"]`` of a routed model on one chip
        (``moe_impl="held"``): a share of the experts or all of them."""
        if self.runner.moe_impl != "held":
            return None
        mc = self.model_cfg
        h, m = mc.hidden_size, mc.moe_intermediate_size
        sm = mc.shared_expert_width
        return {"experts_held": mc.num_experts,
                "router_width": mc.router_width,
                "experts_per_token": mc.num_experts_per_tok,
                "expert_act": mc.expert_act,
                # 3: gate, up and down; 2: an expert without a gate. Two
                # shapes either way: gate and up are of one.
                "expert_matrices": 3 if mc.expert_gated else 2,
                "router_input": mc.router_input,
                "routed_layers": self._routed_layers,
                "hidden_size": h, "expert_width": m,
                "bytes_per_param": jnp.dtype(mc.dtype).itemsize,
                # (as stored: ModelConfig.expert_store_width)
                "shapes": [[mc.num_experts, h, mc.expert_store_width],
                           [mc.num_experts, mc.expert_store_width, h],
                           [h, mc.router_width]]
                + ([[h, sm], [sm, h]] if sm else [])}

    def _attn_facts(self) -> dict | None:
        """``stats()["attn"]`` of a model of latent attention."""
        mc, spec = self.model_cfg, self.runner.spec
        if not mc.latent:
            return None
        return {"cache_kind": spec.kind, "pools": spec.pools,
                "row_stored": spec.head_dim, "row_useful": spec.row_width,
                "value_width": mc.kv_lora_rank,
                "bytes_per_token": spec.bytes_per_token(),
                "layers": spec.num_layers, "heads": mc.num_heads,
                "absorbed": "decode and chunk rows"}

    def _ssm_facts(self) -> dict | None:
        """``stats()["ssm"]`` of a model with recurrent layers."""
        mc = self.model_cfg
        if not mc.has_ssm:
            return None
        slots = self.engine_cfg.max_batch_size
        pool = mamba.state_shapes(mc, slots)
        # A Mamba-1 mixer's state is [N, d], a channel where a Mamba-2 head
        # has a [P, N] block: d heads of size 1, and its ``dt`` is d wide.
        one = bool(mc.mamba_inner)
        heads, head_dim = ((mc.mamba_inner, 1) if one
                           else (mc.mamba_num_heads, mc.mamba_head_dim))
        return {"layers": self._ssm_layers, "slots": slots,
                "recurrence": "mamba1" if one else "mamba2",
                "shapes": {k: list(v.shape) for k, v in pool.items()},
                "dtypes": {k: str(v.dtype) for k, v in pool.items()},
                "slot_layer_bytes": mamba.slot_layer_bytes(mc),
                "pool_bytes": mamba.state_bytes(mc, slots),
                # per live token of a layer: z, xBC and dt in, y out
                "token_bytes": (2 * mc.ssm_inner + mc.ssm_conv_dim + heads)
                * jnp.dtype(mc.dtype).itemsize,
                "heads": heads, "head_dim": head_dim,
                "state_size": mc.ssm_state_size, "groups": mc.ssm_groups,
                "conv_kernel": mc.conv_kernel, "conv_dim": mc.ssm_conv_dim,
                "chunk": mc.ssm_chunk,
                "prefix_matching": "off: a prefix's blocks are useless "
                "without the state at their end, which is not kept"}

    def step_begin(self) -> "PendingStep | None":
        """Plan one engine step and DISPATCH it to the device without
        blocking on results. Host-side state is advanced speculatively
        (positions, block growth — everything value-independent), so the
        caller may plan+dispatch the NEXT step while this one computes:
        the sampled tokens stay on device (slot_toks) and feed the next
        decode step directly. Value-dependent effects (token append, hash
        commit, stop conditions) happen in :meth:`step_finalize`, which
        lags by however many steps the caller keeps in flight.

        This is the host/device overlap the reference-class engines get
        from async scheduling — expressed TPU-style: the host never waits
        to build step N+1, and a finished/stopped stream costs at most one
        speculative row, discarded at finalize.
        """
        with loop_phase(self.loop_clock, "engine.plan"):
            plan = self._plan_step()
        if plan is None:
            return None
        # The step's ordinal rides the spans a reader joins by it: each
        # program's ``engine.program`` inside this one (ModelRunner.dispatch),
        # the step's ``engine.finalize.wait`` and its ``engine.record``.
        with loop_phase(self.loop_clock, "engine.dispatch"):
            pending = self._dispatch_plan(plan, self.metrics.num_steps)
        self.metrics.placed_inputs = self.runner.placed_inputs
        if self.sched_led.enabled:
            with loop_phase(self.loop_clock, "engine.plan"):
                pending.sched = self._sched_context(plan)
        return pending

    def _plan_step(self) -> "StepPlan | None":
        """The scheduling half of :meth:`step_begin`: session sweep, plan,
        write-back of evicted blocks, plan-time accounting. None when there
        is nothing to dispatch."""
        if self.sessions is not None:
            self._session_sweep()
        plan = self.sched.plan()
        if self.kvbm is not None:
            # Write back blocks evicted during planning before their slots
            # are rewritten by this step's KV scatter (batched: one bucketed
            # transfer instead of per-eviction round-trips).
            self.kvbm.flush_pending()
        self.metrics.num_preemptions = self.sched.preemption_count
        if plan.empty:
            return None
        self.metrics.num_steps += 1
        self._trace_plan(plan)
        if self.sessions is not None:
            # Avoided-token accounting: the blocks a session turn did NOT
            # recompute are exactly its admission-time prefix hit — a
            # measured quantity, counted once per seq on its first planned
            # chunk.
            for w in plan.prefill:
                seq = w.seq
                if seq.session_id is not None and not seq.session_counted:
                    seq.session_counted = True
                    if seq.prefix_hit_blocks:
                        get_session_metrics().avoided_tokens.inc(
                            seq.prefix_hit_blocks * seq.block_size)
        # Checkpoint-resume accounting mirrors the session pattern: the
        # recompute a crash actually cost is the resume prompt MINUS what
        # the admission onboard pulled back warm — measured once, on the
        # first planned chunk.
        for w in plan.prefill:
            seq = w.seq
            if seq.ckpt_counted:
                continue
            seq.ckpt_counted = True
            # The same once-per-sequence instant ends the queue part of its
            # time to first token (a re-prefill does not move it).
            seq.t_first_plan = time.perf_counter()
            ann = getattr(seq.req, "annotations", None) or {}
            if int(ann.get(CKPT_GENERATED_KEY) or 0) > 0:
                self.metrics.stream_ckpt_resumes += 1
                sm = get_stream_ckpt_metrics()
                sm.resumes.inc(1)
                sm.resume_recomputed_tokens.inc(max(
                    seq.prefill_target()
                    - seq.prefix_hit_blocks * seq.block_size, 0))
        return plan

    def _dispatch_plan(self, plan: StepPlan, step: int = 0) -> "PendingStep":
        """The device half of :meth:`step_begin`: slot init, batch
        building, input prep and the jitted call(s)."""
        fresh = [seq for seq in [w.seq for w in plan.prefill] + plan.decode
                 if not seq.slot_initialized and seq.slot >= 0]
        if fresh:
            # reset_slot's eager programs, a few a new sequence.
            with loop_phase(self.loop_clock, "engine.dispatch.reset"):
                for seq in fresh:
                    self._init_slot(seq)
                    seq.slot_initialized = True

        # A plan with chunks packs its decode rows and the chunks into ONE
        # ragged "mixed" program, whose dense layers run over the rows'
        # live tokens and whose attention runs over the [B, T] rows — see
        # the scheduler module docstring. A batch whose chunks hold more
        # tokens than its program's token bucket goes out as several
        # programs, below.
        pending = PendingStep(step=step)
        batches: list[tuple[list, list[bool], list | None]] = []
        decode_seqs = plan.decode
        guided_rows: list = []
        if any(s.guided is not None for s in decode_seqs):
            rest = []
            for s in decode_seqs:
                if s.guided is None:
                    rest.append(s)
                elif s.inflight_samples == 0:
                    # Unpipelined by design: the mask for token t needs
                    # token t-1 materialized on the host.
                    guided_rows.append((s, s.num_computed, 1))
                # else: pause this cycle until the in-flight token lands
            decode_seqs = rest
        if self.engine_cfg.spec_ngram > 0 and decode_seqs:
            verify_rows, verify_chunks, decode_seqs = self._plan_verify(decode_seqs)
            if verify_rows:
                sig, toks, lps = self.runner.dispatch_verify(
                    verify_rows, verify_chunks, step=step)
                for seq, start, length in verify_rows:
                    seq.num_computed = start + length
                    seq.inflight_samples += 1
                    seq.verify_inflight = True
                pending.batches.append(
                    (sig, verify_rows, verify_chunks, toks, lps))
                pending.programs.append("jit_" + sig.program())
                pending.moe.append(None)
        pf_rows, pf_sample_rows, pf_masks = [], [], None
        if plan.prefill:
            pf_rows = [(w.seq, w.start, w.length) for w in plan.prefill]
            # Sample only on the chunk completing a *fresh* prompt; a
            # preempt-resumed seq already holds its next token (the resume
            # prefill just rebuilds KV) so sampling would duplicate output.
            pf_sample_rows = [
                w.start + w.length >= w.seq.prefill_target()
                and len(w.seq.tokens) == w.seq.prompt_len
                for w in plan.prefill
            ]
            if any(w.seq.guided is not None and s for w, s in
                   zip(plan.prefill, pf_sample_rows)):
                # The FIRST sampled token must already obey the grammar.
                pf_masks = [
                    w.seq.guided.mask()
                    if (w.seq.guided is not None and pf_sample_rows[i])
                    else None
                    for i, w in enumerate(plan.prefill)]
        pending.dec_rows = len(decode_seqs) + len(guided_rows)
        if pf_rows:
            # One ragged launch: decode rows, guided decode rows (their
            # masks join per-row), then the prefill chunks. dispatch()
            # gives a degenerate all-length-1 batch the decode program.
            rows = ([(s, s.num_computed, 1) for s in decode_seqs]
                    + guided_rows + pf_rows)
            sample_rows = [True] * pending.dec_rows + pf_sample_rows
            masks = None
            if guided_rows or pf_masks is not None:
                masks = ([None] * len(decode_seqs)
                         + [s.guided.mask() for s, _, _ in guided_rows]
                         + (pf_masks if pf_masks is not None
                            else [None] * len(pf_rows)))
            batches.append((rows, sample_rows, masks))
        else:
            if decode_seqs:
                rows = [(s, s.num_computed, 1) for s in decode_seqs]
                batches.append((rows, [True] * len(rows), None))
            if guided_rows:
                batches.append((guided_rows, [True] * len(guided_rows),
                                [s.guided.mask() for s, _, _ in guided_rows]))

        ec = self.engine_cfg
        for rows, sample_rows, b_masks in batches:
            for lo, k in _runs(pack_rows([r[2] for r in rows], ec)):
                run, samples = rows[lo:lo + k], sample_rows[lo:lo + k]
                sig, program, toks, lps, moe = self.runner.dispatch(
                    run, samples, masks=b_masks and b_masks[lo:lo + k],
                    step=step)
                # Value-independent bookkeeping, done at dispatch so the
                # next plan() sees advanced positions. Token metrics count
                # at finalize, so discarded speculative rows don't inflate
                # them.
                for (seq, start, length), sampled in zip(run, samples):
                    seq.num_computed = start + length
                    if sampled:
                        seq.inflight_samples += 1
                pending.batches.append((sig, run, samples, toks, lps))
                pending.programs.append(program)
                pending.moe.append(moe)
        return pending

    def _sched_context(self, plan: StepPlan) -> dict:
        """Scheduling-ledger context of a dispatched plan (token-budget
        utilization, HOL victims): ``_record_step`` reads the first,
        ``outputs_posted`` files the second with the gap it measures.
        Nothing is priced here."""
        used = len(plan.decode) + sum(w.length for w in plan.prefill)
        hol = None
        if plan.prefill and plan.decode:
            # Every decode-ready stream in this step waits out the
            # prefill work before its token materializes; the culprit
            # is the request contributing the largest chunk.
            culprit = max(plan.prefill, key=lambda w: w.length)
            hol = HolStall(
                culprit=culprit.seq.request_id,
                culprit_tokens=sum(w.length for w in plan.prefill),
                victims=[(s.trace_ctx, s.request_id, s.qos_priority)
                         for s in plan.decode])
        return {
            "budget_util": used / max(self.sched.max_tokens_per_step, 1),
            "hol": hol,
        }

    def _trace_plan(self, plan: StepPlan) -> None:
        """Advance per-seq phase spans from the step plan. Spans are
        observational only — multi-host ranks may record different wall
        times but never make different decisions off them. Untraced seqs
        (no obs.traceparent annotation) cost one None check here."""
        tr = None
        for w in plan.prefill:
            s = w.seq
            sp = s.trace_span
            if s.trace_ctx is None or (sp is not None
                                       and sp.name == "engine.prefill"):
                continue  # untraced, or a later chunk of the same prefill
            tr = tr or get_tracer()
            if sp is not None:
                # queue→prefill admit, or a preempt-resume out of decode.
                extra = ({"tokens": s.trace_tokens}
                         if sp.name == "engine.decode" and s.trace_tokens
                         else {})
                tr.end_span(sp, prefix_hit_blocks=s.prefix_hit_blocks,
                            **extra)
            s.trace_span = tr.start_span(
                "engine.prefill", ctx=s.trace_ctx, request_id=s.request_id,
                prompt_tokens=s.prompt_len,
                prefix_hit_blocks=s.prefix_hit_blocks)
            s.trace_tokens = 0
        for s in plan.decode:
            if s.trace_ctx is None:
                continue
            sp = s.trace_span
            if sp is not None and sp.name == "engine.decode":
                s.trace_tokens += 1
                if s.trace_tokens >= self._trace_stride:
                    tr = tr or get_tracer()
                    tr.end_span(sp, tokens=s.trace_tokens,
                                batch=len(plan.decode))
                    s.trace_span = tr.start_span(
                        "engine.decode", ctx=s.trace_ctx,
                        request_id=s.request_id)
                    s.trace_tokens = 0
                continue
            tr = tr or get_tracer()
            if sp is not None:  # prefill complete: decode begins
                tr.end_span(sp)
            s.trace_span = tr.start_span(
                "engine.decode", ctx=s.trace_ctx, request_id=s.request_id,
                batch=len(plan.decode))
            s.trace_tokens = 1

    def _trace_finish(self, seq: Seq, reason: FinishReason | None) -> None:
        sp = seq.trace_span
        if sp is None:
            return
        seq.trace_span = None
        status = "ok"
        if reason is FinishReason.CANCELLED:
            status = "cancelled"
        elif reason is FinishReason.ERROR:
            status = "error"
        attrs: dict = {"finish_reason": str(reason) if reason else "",
                       "output_tokens": seq.num_output_tokens}
        if sp.name == "engine.decode" and seq.trace_tokens:
            attrs["tokens"] = seq.trace_tokens
        get_tracer().end_span(sp, status=status, **attrs)

    def _record_step(self, t0: float, pending: "PendingStep",
                     moe: list | None = None, span=None) -> None:
        """Always-on step profile: one ring append per engine step. ``moe``:
        the step's routed-layer counts (layer steps, rows, experts touched,
        largest groups, streamed layer steps), where its programs gave any.
        The step's rows are walked once, here (``step_counts``): the profiler prices that count,
        the scheduling ledger files it, and ``span`` (the step's
        ``engine.record``) carries it with the device's counts, for a reader
        of a trace to join to the step's programs by ``step``."""
        counts = step_counts(pending.batches, self.engine_cfg.block_size,
                             self._windows, dec_rows=pending.dec_rows,
                             attn_tokens=attends_tokens(self.engine_cfg),
                             # (a latent row is written by the scatter)
                             block_writes=writes_blocks(self.engine_cfg)
                             and not self.model_cfg.latent,
                             **self._recurrent_and_cross)
        self.metrics.attn_chunk_rows += counts["chunk_rows"]
        self.metrics.attn_chunk_ctx_tokens += counts["chunk_ctx_tokens"]
        ssm = (tuple(counts[k] for k in SSM_COUNTS) if self._ssm_layers
               else None)
        pc = self.sched.preemption_count
        wall = time.perf_counter() - t0
        perf = self.perf.measure(counts, wall, moe)
        get_tracer().recorder.steps.record(
            time.time(), wall,
            num_prefill=counts["prefill_rows"],
            num_decode=counts["decode_rows"],
            num_waiting=self.sched.num_waiting,
            num_preempted=pc - self._trace_last_preempt,
            occupancy=(self.sched.num_running
                       / max(self.engine_cfg.max_batch_size, 1)),
            **perf)
        self._trace_last_preempt = pc
        # (one static call, ~60 ns: is a profiler session recording?)
        if span is not None and jax.profiler.TraceAnnotation.is_enabled():
            span.set(**{k: counts[k] for k in _RECORD_SPAN_COUNTS},
                     **(dict(zip(_RECORD_SPAN_MOE, moe)) if moe else {}),
                     **({k: counts[k] for k in _RECORD_SPAN_SSM}
                        if ssm else {}),
                     **({k: counts[k] for k in _RECORD_SPAN_CROSS}
                        if counts["cross_tokens"] else {}))
            self.traced_programs.update(pending.programs)
        if self.sched_led.enabled:
            info = pending.sched or {}
            # (the step's HOL victims are filed with its gap: outputs_posted)
            self._sched_rec = self.sched_led.record_step(
                wall_s=wall,
                budget_util=info.get("budget_util", 0.0),
                queue_depths=self.sched.waiting.depths(),
                moe=moe and tuple(moe), ssm=ssm,
                **step_geometry(self.model_cfg, self.engine_cfg,
                                pending.batches, counts=counts, moe=moe,
                                shapes=self.metrics.step_shapes,
                                live_cost=self.perf.last_cost))
        if self.mem_led.enabled:
            # Capacity forecast + leak audit cadence ride the step clock:
            # free-pool observations feed the per-QoS EWMA consumption
            # rates behind dynamo_mem_ttx_seconds, and maybe_audit is a
            # no-op until audit_interval_s has elapsed.
            self.mem_led.observe_device(
                free=self.pool.num_free_raw,
                cached=self.pool.num_inactive,
                total=self.pool.num_blocks - 1)
            self.mem_led.observe_free(self.pool.num_free, now=time.time())
            self.mem_led.maybe_audit(time.time())
        self.loop_clock.publish()

    def _plan_verify(self, decode_seqs: list
                     ) -> tuple[list, list[list[int]], list]:
        """Partition decode seqs into speculative-verify rows and plain
        decode. A seq verifies when it is greedy + penalty-free (verify is
        argmax-exact only then), its last token is host-known (no in-flight
        device-fed sample), and the n-gram proposer finds a continuation
        (engine/spec.py).

        Pipelined entry: under the overlapped step loop a decode seq's last
        token is ALWAYS still in flight at plan time — so when the known
        prefix already shows a repetition signal (a proposal exists even
        without the pending token), the seq PAUSES one plan cycle (dropped
        from this step) so its token materializes and the next plan can
        verify. The bubble costs one cycle; an accepted run repays it with
        up to spec_k+1 tokens. No signal → plain pipelined decode, no
        bubble."""
        from dynamo_tpu.engine.spec import greedy_eligible, propose

        ec = self.engine_cfg
        verify_rows, verify_chunks, plain = [], [], []
        for seq in decode_seqs:
            if seq.guided is not None or not greedy_eligible(seq.req.sampling_options):
                plain.append(seq)
                continue
            # cap proposals to stay inside the model context
            k = min(ec.spec_k, ec.max_model_len - 1 - seq.num_computed)
            proposal = propose(seq.tokens, ec.spec_ngram, k) if k > 0 else []
            if seq.inflight_samples > 0:
                if not proposal:
                    plain.append(seq)   # no signal: stay fully pipelined
                # else: pause this cycle (dispatch nothing for this seq)
                continue
            if not proposal:
                plain.append(seq)
                continue
            start = seq.num_computed
            chunk = [seq.tokens[start], *proposal]
            verify_rows.append((seq, start, len(chunk)))
            verify_chunks.append(chunk)
            self.metrics.spec_proposed += len(proposal)
        return verify_rows, verify_chunks, plain

    def _emit_and_finish(self, seq, candidates: list[int], lps_row,
                         outputs: dict[str, LLMEngineOutput],
                         count_decode: bool) -> int:
        """THE finalize tail, shared by step and verify batches so
        the greedy-equivalence guarantee can't drift between them: append
        candidate tokens until a stop fires, commit blocks, transfer
        prefix-hit stats, assemble the output, run finish bookkeeping.
        Returns the number of tokens emitted."""
        emitted: list[int] = []
        reason = None
        if seq.t_first_plan and candidates:
            # Its first first-token: cleared here, so it counts once.
            self._first_tokens.append(
                (seq.t_arrival, seq.t_added, seq.t_first_plan))
            seq.t_first_plan = 0.0
        if self._gap.step and candidates:
            self._gap.stamp(seq)
        for token in candidates:
            seq.tokens.append(token)
            seq.block_seq.append(token)
            emitted.append(token)
            if seq.guided is not None:
                seq.guided.advance(token)
            reason = self._check_stop(seq, token)
            if reason is not None:
                break
        if count_decode:
            self.metrics.num_decode_tokens += len(emitted)
        self.sched.commit_computed_blocks(seq)
        if reason is None:
            # Checkpoint cadence rides the commit stream: first at prefill
            # completion (this seq's first emit), then every interval
            # committed blocks. Finishing streams skip straight to the reap.
            self._maybe_stream_ckpt(seq)
        if seq.prefix_hit_blocks:
            self.metrics.prefix_hit_blocks += seq.prefix_hit_blocks
            seq.prefix_hit_blocks = 0
        per_tok = [float(x) for x in lps_row[: len(emitted)]]
        out = LLMEngineOutput(
            token_ids=emitted,
            cum_log_probs=sum(per_tok),
            log_probs=per_tok,
        )
        if reason is not None:
            out.finish_reason = reason
            self._reap_stream_ckpt(seq)
            if (self.sessions is not None and seq.session_id is not None
                    and reason in (FinishReason.STOP, FinishReason.LENGTH)):
                # Retain BEFORE sched.finish releases the seq's refs: the
                # session pin increfs the committed chain while it is still
                # active, so there is no instant where turn N's KV is
                # evictable. Cancelled/errored streams never retain.
                self._retain_session(seq)
            self._trace_finish(seq, reason)
            self.sched.finish(seq, reason)
            self.metrics.num_requests_finished += 1
            del self._seqs[seq.request_id]
        outputs[seq.request_id] = out
        return len(emitted)

    def step_finalize(self, pending: "PendingStep") -> dict[str, LLMEngineOutput]:
        """Materialize a dispatched step's tokens and apply value-dependent
        effects: append tokens, commit full blocks (hash chain), evaluate
        stop conditions, assemble per-request outputs."""
        t0 = time.perf_counter()
        clock = self.loop_clock
        outputs: dict[str, LLMEngineOutput] = {}
        dec_left = pending.dec_rows
        moe = None
        self._gap.step = pending.step if self.sched_led.enabled else 0
        waited = clock.seconds["engine.finalize.wait"]
        for (sig, rows, sample_rows, toks_dev, lps_dev), moe_dev in zip(
                pending.batches, pending.moe, strict=True):
            with loop_phase(clock, "engine.finalize.wait", step=pending.step):
                # The host blocks here until the device has run the step.
                toks = np.asarray(toks_dev)
                lps = np.asarray(lps_dev)
                if moe_dev is not None:
                    # The same program's output: it is there with the tokens.
                    moe = (moe or [0, 0, 0, 0, 0])
                    moe[0] += self._routed_layers
                    for i, x in enumerate(np.asarray(moe_dev)):
                        moe[i + 1] += int(x)
                    if self._streams_experts(sig.n):
                        moe[4] += self._routed_layers
            with loop_phase(clock, "engine.finalize.host"):
                if sig.kind == "verify":
                    self._finalize_verify(rows, sample_rows, toks, lps,
                                          outputs)
                    continue
                self._finalize_batch(rows, sample_rows, toks, lps,
                                     dec_left, outputs)
            dec_left = max(dec_left - len(rows), 0)
        self._gap.wait_s = clock.seconds["engine.finalize.wait"] - waited
        with loop_phase(clock, "engine.record", step=pending.step) as span:
            self._record_step(t0, pending, moe, span)
        if self.kvbm is not None and not self.sched.has_work():
            # Engine going idle: this finalize's commits would otherwise sit
            # in the publish-on-commit queue until the next step_begin —
            # which may be a long time away on a drained worker.
            with loop_phase(clock, "engine.finalize.host"):
                self.kvbm.drain_publish()
        return outputs

    def outputs_posted(self, pending: "PendingStep | None" = None,
                       span=None, now: float | None = None) -> None:
        """The outputs of the last finalize (``pending``'s; None: there was
        none) have been handed on. One ``perf_counter()`` (``now``, where
        the caller took it as it handed them on) closes

        - the time to first token of the sequences whose first token was
          among them (``EngineMetrics.ttft_*``): a sequence counts once, at
          its first first-token, however often it is preempted and
          re-prefilled;
        - the token gap of every other sequence the step posted to: the
          seconds since the step that posted to it before (``_emit_and_finish``
          stamped which), filed in the scheduling ledger under the class of
          the programs that ran and their widest row bucket, beside the
          seconds this thread was blocked on the device for the step
          (``SchedLedger.record_post``), with the step's HOL victims, whose
          stall is that gap less the decode mean of their row bucket.

        ``span`` (the ``engine.post`` phase) carries the gap while a
        profiler session records."""
        if now is None:
            now = time.perf_counter()
        if self._first_tokens:
            m = self.metrics
            for t_arrival, t_added, t_first_plan in self._first_tokens:
                m.ttft_count += 1
                m.ttft_inbox_s += t_added - t_arrival
                m.ttft_queue_s += t_first_plan - t_arrival
                m.ttft_prefill_s += now - t_first_plan
            self._first_tokens.clear()
        gap = self._gap
        if pending is None or gap.step != pending.step:
            return          # no finalize before this post, or the ledger off
        period, rows, odd = gap.close(now)
        cls, b = step_class(pending.batches)
        hol = (pending.sched or {}).get("hol")
        self.sched_led.record_post(
            self._sched_rec, cls=cls, b=b, period_s=period, rows=rows,
            odd_gaps=odd, wait_s=gap.wait_s, hol=hol,
            hol_b=sig_for_rows("decode", len(hol.victims), 1, 0,
                               self.engine_cfg).b if hol else 0)
        self._sched_rec = None
        if span is not None and jax.profiler.TraceAnnotation.is_enabled():
            span.set(gap_ms=round(period * 1e3, 3), gap_rows=rows + len(odd),
                     cls=cls)

    def _finalize_batch(self, rows, sample_rows, toks, lps, dec_rows: int,
                        outputs: dict[str, LLMEngineOutput]) -> None:
        """Apply one step batch's materialized tokens (host arrays [B],
        padded to the bucket). The first ``dec_rows`` rows are decode
        rows, the rest prefill chunks."""
        for i, (seq, start, length) in enumerate(rows):
            if seq.phase is Phase.FINISHED:
                # Finished (stop/abort) while this step was in flight:
                # its speculative row is discarded.
                continue
            # The split was captured at plan time.
            decode_row = i < dec_rows
            if not decode_row:
                self.metrics.num_prefill_tokens += length
            if sample_rows[i]:
                seq.inflight_samples -= 1
            if not sample_rows[i]:
                # Intermediate prefill chunk: no token emitted. (A seq
                # preempted while in flight is WAITING with num_computed
                # reset to 0 — commit is then a no-op.)
                self.sched.commit_computed_blocks(seq)
                continue
            self._emit_and_finish(
                seq, [int(toks[i])], lps[i:i + 1], outputs,
                count_decode=decode_row)

    def _finalize_verify(self, rows, chunks, toks, lps,
                         outputs: dict[str, LLMEngineOutput]) -> None:
        """Accept/rollback a speculative verify step (engine/spec.py).

        Position j's argmax is on the true greedy path iff every earlier
        proposal matched; accepted tokens append exactly as decode tokens
        would have, the rest of the chunk rolls back (its KV is stale but
        unreachable — later true tokens overwrite those positions)."""
        from dynamo_tpu.engine.spec import accept

        for i, (seq, start, length) in enumerate(rows):
            seq.verify_inflight = False
            if seq.phase is Phase.FINISHED:
                continue  # finished (abort) while in flight: discard
            seq.inflight_samples -= 1
            emitted_all = accept(chunks[i], [int(x) for x in toks[i, :length]])
            # Untouched-state check: a preemption while in flight reset
            # num_computed — leave its bookkeeping alone, discard the step.
            in_flight_intact = seq.num_computed == start + length
            if in_flight_intact:
                # Roll back to the KV-valid bound BEFORE the commit inside
                # _emit_and_finish: KV at position start+j was computed from
                # input chunk[j], which is a true token only for
                # j < len(emitted_all). With the optimistic start+length
                # still in place, a rejection landing on a block boundary
                # would commit a block whose last slot holds KV from the
                # rejected proposal token — poisoning the shared prefix pool
                # for every later request (and G2+ offloads) with that chain.
                # (A stop firing mid-candidates finishes the seq inside
                # _emit_and_finish, so no tighter post-call restore is
                # needed: a live seq always emits all of emitted_all.)
                seq.num_computed = start + len(emitted_all)
            n_emitted = self._emit_and_finish(
                seq, emitted_all, lps[i], outputs, count_decode=True)
            self.metrics.spec_accepted += max(n_emitted - 1, 0)

    def set_step_time(self, now: float | None) -> None:
        """Pin the deadline clock for the next step window (op-stream
        replay passes the leader's timestamp; see _step_now)."""
        self._step_now = now

    def has_expired_waiting(self, now: float | None = None) -> bool:
        from dynamo_tpu.qos.deadline import expired

        return any(expired(s.deadline_ts, now) for s in self.sched.waiting)

    def reap_expired(self, now: float | None = None) -> dict[str, LLMEngineOutput]:
        """Cancel WAITING seqs whose deadline passed and emit their terminal
        outputs. Waiting seqs never flow through step batches, so without an
        explicit reap an expired queued request would only die on admission."""
        outs: dict[str, LLMEngineOutput] = {}
        for seq in self.sched.expire_waiting(now):
            self._seqs.pop(seq.request_id, None)
            self.metrics.deadline_cancelled += 1
            self._trace_finish(seq, FinishReason.CANCELLED)
            outs[seq.request_id] = LLMEngineOutput(finish_reason=FinishReason.CANCELLED)
        return outs

    # -- session-sticky KV retention (engine/session.py) ----------------
    def _retain_session(self, seq: Seq) -> None:
        """Pin a finishing stream's committed chain under its session id."""
        hashes = seq.block_seq.sequence_hashes()[: seq.committed_blocks]
        self.sessions.retain(seq.session_id, hashes, self._step_now)
        # Capacity cap: LRU sessions demote (not just drop) so a later turn
        # can still re-import from the KVBM ladder.
        while len(self.sessions) > self.sessions.max_sessions:
            popped = self.sessions.pop_oldest()
            if popped is None:  # pragma: no cover - len()>0 guarantees one
                break
            self._demote_session(*popped)

    def _session_sweep(self) -> None:
        """TTL + pool-pressure valve, run before each plan().

        TTL expiry uses the leader-stamped step clock, so multi-host ranks
        release the same sessions on the same step. The pressure valve
        mirrors the admission watermark: while the head-of-line waiting seq
        cannot admit because session pins hold the pool, release the oldest
        sessions first — retained turns must never starve live traffic.
        """
        for sid, entry in self.sessions.pop_expired(self._step_now):
            self._demote_session(sid, entry)
        sched = self.sched
        while len(self.sessions) and sched.waiting:
            head = sched.waiting[0]
            need = head.blocks_needed(len(head.tokens))
            if need + len(sched.running) <= self.pool.num_free:
                break
            if need + len(sched.running) > (self.pool.num_free
                                            + self.sessions.pinned_blocks):
                break  # releasing every pin still wouldn't admit; keep them
            popped = self.sessions.pop_oldest()
            if popped is None:  # pragma: no cover - len() checked above
                break
            self._demote_session(*popped)

    def _demote_session(self, session_id: str, entry) -> None:
        """Release a retained entry's pins, first write-staging the chain
        down the KVBM tier ladder (host→disk→remote) when session_tiers is
        on — so a post-eviction turn re-imports instead of recomputing."""
        sm = get_session_metrics()
        sm.expired.inc()
        if (self.engine_cfg.session_tiers and self.kvbm is not None
                and entry.pinned):
            try:
                staged = self.kvbm.stage_blocks(
                    list(zip(entry.pinned, entry.seq_hashes)))
                sm.demoted_blocks.inc(staged)
            except Exception:
                log.exception("session %s: tier demotion failed; releasing "
                              "pins to LRU", session_id)
        if self.mem_led.enabled and entry.pinned:
            self.mem_led.record_churn("device", "session_demote",
                                      len(entry.pinned))
        self.pool.release(entry.pinned)
        entry.pinned = []

    def _remote_tier(self):
        """The shared remote tier in the KVBM ladder, or None."""
        if self.kvbm is None:
            return None
        for tier in self.kvbm.tiers:
            if getattr(tier, "name", "") == "remote":
                return tier
        return None

    def evacuate_sessions(self, _args: dict | None = None) -> dict:
        """Drain step 4 (runtime/drain.py): push every retained session's
        device chain plus a resumable record to the shared remote store,
        then release the pins — turn N+1 on a surviving worker pulls the
        chain back warm instead of recomputing. Engine-core thread only
        (CORE_OPS "session_evacuate"). Multi-host engines fall back to the
        tier-ladder demotion: each rank holds only its KV shard, and a
        shard written to the SHARED store would corrupt cross-worker reads.
        """
        out = {"sessions": 0, "blocks": 0, "bytes": 0}
        if self.sessions is None:
            return out
        remote = self._remote_tier()
        direct = remote is not None and jax.process_count() == 1
        while True:
            popped = self.sessions.pop_oldest()
            if popped is None:
                break
            sid, entry = popped
            try:
                if direct and entry.pinned:
                    blocks = self.transfer.extract(
                        self.runner.cache_k, self.runner.cache_v, entry.pinned)
                    for h, block in zip(entry.seq_hashes, blocks):
                        remote.put(h, block)
                        out["blocks"] += 1
                        out["bytes"] += int(getattr(block, "nbytes", 0))
                    if remote.put_session(sid, list(entry.seq_hashes),
                                          entry.tokens):
                        out["sessions"] += 1
                elif (self.kvbm is not None and entry.pinned):
                    # No direct path: stage down the local ladder so at least
                    # a restart of THIS worker re-imports instead of
                    # recomputing. No resumable record — survivors can't
                    # reach these blocks.
                    self.kvbm.stage_blocks(
                        list(zip(entry.pinned, entry.seq_hashes)))
            except Exception:
                log.exception("session %s evacuation failed; its blocks fall "
                              "to the LRU", sid)
            self.pool.release(entry.pinned)
            entry.pinned = []
        return out

    def abort_class(self, priority: str | None = None) -> list[str]:
        """Abort every live request of one QoS class (None = all) — the
        drain run-down's early-stop valve (runtime/drain.py abort_batch /
        abort_all). Returns the aborted request ids so the async wrapper
        can emit their terminal CANCELLED outputs."""
        rids = [rid for rid, seq in self._seqs.items()
                if seq.phase is not Phase.FINISHED
                and (priority is None or seq.qos_priority == priority)]
        for rid in rids:
            self.abort(rid)
        return rids

    def step(self) -> dict[str, LLMEngineOutput]:
        """Run one engine step synchronously; returns per-request deltas."""
        now = time.time()
        self.set_step_time(now)
        outs = self.reap_expired(now)
        pending = self.step_begin()
        if pending is not None:
            outs.update(self.step_finalize(pending))
            self.outputs_posted(pending)
        return outs

    # -- disagg / KV-transfer primitives (engine-core thread only) ---------
    @property
    def transfer(self):
        if self.kvbm is not None:  # share jit caches with the offload path
            return self.kvbm.transfer
        if getattr(self, "_transfer", None) is None:
            if jax.process_count() > 1:
                # Multi-host cache arrays span processes: extract/inject must
                # stay shard-local (a plain np.asarray of the global array
                # would need non-addressable shards).
                from dynamo_tpu.kvbm.distributed import ShardedBlockTransferEngine

                self._transfer = ShardedBlockTransferEngine(self.runner.mesh)
            else:
                from dynamo_tpu.kvbm.transfer import BlockTransferEngine

                self._transfer = BlockTransferEngine()
        return self._transfer

    @_moves_blocks_alone
    def export_blocks(self, seq_hashes: list[int]) -> list[tuple[int, int | None, np.ndarray]]:
        """Gather the device-resident prefix of a hash chain off the device.
        The prefill side of disaggregated serving (reference: the NIXL
        kv_transfer_params handoff, components/src/dynamo/vllm/handlers.py)."""
        ids, kept = [], []
        parent: int | None = None
        for h in seq_hashes:
            bid = self.pool.block_for_hash(h)
            if bid is None:
                break
            ids.append(bid)
            kept.append((h, parent))
            parent = h
        if not ids:
            return []
        blocks = self.transfer.extract(self.runner.cache_k, self.runner.cache_v, ids)
        return [(h, par, data) for (h, par), data in zip(kept, blocks)]

    @_moves_blocks_alone
    def import_blocks(self, plan: list[tuple[int, int | None, np.ndarray]],
                      span_attrs: dict | None = None) -> int:
        """Inject externally-received blocks as matchable cache entries —
        the decode side of disaggregated serving. Hashes already on device
        are skipped (and MRU-protected)."""
        from dynamo_tpu.kvbm.offload import inject_and_commit, plan_onboard

        by_hash = {h: data for h, _, data in plan}
        filtered = plan_onboard(self.pool, [h for h, _, _ in plan], by_hash.get)
        flush = self.kvbm.flush_pending if self.kvbm is not None else None
        return inject_and_commit(self.runner, self.pool, self.transfer, filtered,
                                 flush=flush, span_attrs=span_attrs)

    def pin_blocks(self, seq_hashes: list[int]) -> list[int]:
        """Incref the device-resident prefix of a chain so it survives until
        a pending transfer pulls it; pair with unpin_blocks."""
        return self.pool.match_prefix(seq_hashes)

    def unpin_blocks(self, block_ids: list[int]) -> None:
        self.pool.release(block_ids)

    # -- sharded disagg handoff (named ops — replayable on multi-host) -----
    # These bodies run on EVERY rank of a multi-host engine via the op
    # stream (parallel/multihost.py), in SPMD lockstep: pool decisions are
    # deterministic, device work is the same XLA program everywhere, and
    # each rank touches only its addressable cache shard
    # (disagg/sharded.py module docstring has the full design).

    @property
    def staging(self):
        if getattr(self, "_staging", None) is None:
            from dynamo_tpu.disagg.sharded import StagingStore

            self._staging = StagingStore()
            self._staged_pins: dict[str, list[int]] = {}
        return self._staging

    def my_box(self) -> tuple[int, int, int, int]:
        """This rank's (layer, head) extents of the global cache."""
        from dynamo_tpu.kvbm.distributed import local_box

        starts, stops = local_box(cache_payload(self.runner.cache_k))
        return (starts[0], stops[0], starts[3], stops[3])

    def start_shard_server(self, advertise_host: str, on_release=None) -> str:
        """Start (once) the per-rank shard server serving staged KV; returns
        the address to advertise in kv_transfer_params. Thread-safe to call
        off the engine-core thread: it only binds a socket and reads the
        (lock-guarded) staging store."""
        if getattr(self, "_shard_server", None) is None:
            from dynamo_tpu.disagg.sharded import ShardServer

            self._shard_server = ShardServer(self.staging, on_release=on_release)
        return f"{advertise_host}:{self._shard_server.port}"

    @staticmethod
    def _vote_min(n: int) -> int:
        from dynamo_tpu.parallel.multihost import vote_min

        return vote_min(n)

    @_moves_blocks_alone
    def stage_export(self, xfer_id: str, seq_hashes: list[int]) -> int:
        """Pin the device-resident prefix of a chain and stage this rank's
        cache shard of it to host memory; returns hashes covered. The pin
        holds until release_export, the staging until then too — pulls are
        served from host memory, never re-touching device state.

        Multi-host: the covered count is voted down to the mesh-wide
        minimum (0 if any rank's extract failed), and pins beyond it are
        released — so pin state, staged hash lists, and therefore every
        future eviction decision stay rank-identical."""
        touch = self.staging  # ensure _staged_pins exists on every path
        block_ids = self.pool.match_prefix(seq_hashes)
        data = None
        try:
            if block_ids:
                # Sharded staging box-slices 6-d float data (disagg/
                # sharded.py) — quantized caches stage dequantized blocks;
                # the importer requantizes at its inject boundary.
                blocks = self.transfer.extract(
                    self.runner.cache_k, self.runner.cache_v, block_ids,
                    dequant=self.runner.spec.quantized)
                data = np.stack(blocks)
        except Exception as exc:  # noqa: BLE001 — vote handles divergence
            log.warning("stage_export extract failed: %s", exc)
            data = None
        n = self._vote_min(len(block_ids) if data is not None else 0)
        if n < len(block_ids):
            self.pool.release(block_ids[n:])
            block_ids = block_ids[:n]
        if n == 0:
            return 0
        covered = seq_hashes[:n]
        parents: list[int | None] = [None, *covered[:-1]]
        touch.fill(xfer_id, covered, parents, data[:n], self.my_box())
        self._staged_pins[xfer_id] = block_ids
        if self.mem_led.enabled:
            self.mem_led.pin("staging", xfer_id, len(block_ids))
        return n

    def release_export(self, xfer_id: str) -> None:
        """Unpin + unstage one transfer — final ack AND mid-stream abort.
        For a still-streaming transfer this also tears down the stream
        state, so pins already shipped, staged-but-unpulled, and
        not-yet-staged waves all release together (later kv_stage_wave ops
        for this id become no-ops)."""
        st = getattr(self, "_streams_by_xid", {}).pop(xfer_id, None)
        if st is not None:
            self._stream_exports.pop(st.request_id, None)
        self.staging.drop(xfer_id)
        ids = self._staged_pins.pop(xfer_id, None)
        if ids is not None and self.mem_led.enabled:
            self.mem_led.unpin("staging", xfer_id)
        if ids:
            self.pool.release(ids)

    # -- streamed (wave-granular) export ------------------------------
    # The prefill side of the chunk-streamed handoff: kv_stream_begin
    # declares the full expected chain once, the leader's step loop emits
    # one kv_stage_wave exec op after each finalize that commits new
    # blocks (AsyncJaxEngine._run), and kv_stream_end votes + trims. All
    # three are replayed ops, so pins/staging stay rank-identical; the
    # per-wave extract failure of a single rank is absorbed by pinning
    # regardless and voting the covered count down at stream end.

    def _ensure_streams(self) -> None:
        if getattr(self, "_stream_exports", None) is None:
            self._stream_exports: dict[str, _StreamExport] = {}
            self._streams_by_xid: dict[str, _StreamExport] = {}

    @_moves_blocks_alone
    def stream_begin(self, xfer_id: str, request_id: str,
                     seq_hashes: list[int]) -> int:
        """Open a streamed export for ``request_id``'s chain. No device
        work — the staging entry just declares the expected hashes so
        early pulls can wait on waves."""
        touch = self.staging  # ensure _staged_pins exists on every path
        self._ensure_streams()
        st = _StreamExport(xfer_id=xfer_id, request_id=request_id,
                           hashes=list(seq_hashes))
        self._stream_exports[request_id] = st
        self._streams_by_xid[xfer_id] = st
        self._staged_pins.setdefault(xfer_id, [])
        parents: list[int | None] = [None, *st.hashes[:-1]]
        touch.begin(xfer_id, st.hashes, parents, self.my_box(),
                    str(jnp.dtype(self.runner.spec.dtype)))
        return len(st.hashes)

    def stream_wave_targets(self) -> list[tuple[str, int, int]]:
        """Leader-side wave detection (engine-core thread, after
        step_finalize): chains whose committed-block prefix grew past what
        has been staged. Also caches each stream's Seq while it is still
        registered, so the final wave (committed by the finalize that
        finishes the request) is still visible after _seqs drops it."""
        streams = getattr(self, "_stream_exports", None)
        if not streams:
            return []
        out: list[tuple[str, int, int]] = []
        for rid, st in list(streams.items()):
            if st.seq is None:
                st.seq = self._seqs.get(rid)
            if st.seq is None:
                continue
            avail = min(st.seq.committed_blocks, len(st.hashes))
            if avail > st.requested:
                out.append((st.xfer_id, st.requested, avail))
                st.requested = avail
        return out

    def stage_wave(self, xfer_id: str, start: int, stop: int) -> int:
        """Stage blocks [start, stop) of a streamed chain: pin the new
        wave, extract this rank's shard slice, append to staging. NO vote
        here — pin decisions derive from pool state (rank-identical by
        replay); a local extract failure freezes this rank's staged count
        and stream_end's vote trims everyone to the minimum. Returns the
        blocks staged so far on this rank."""
        st = getattr(self, "_streams_by_xid", {}).get(xfer_id)
        if st is None:  # released/aborted while the op was in flight
            return 0
        stop = min(stop, len(st.hashes))
        if stop <= start:
            return st.staged
        # Pin [start, stop) without double-pinning earlier waves:
        # match_prefix increfs the whole resident prefix, so drop the refs
        # below start. The committed prefix can't shrink between the
        # finalize that committed it and this op (no allocate in between),
        # so len(ids) == stop on every rank in the healthy case.
        ids = self.pool.match_prefix(st.hashes[:stop])
        if start:
            self.pool.release(ids[:start])
        keep = ids[start:]
        self._staged_pins.setdefault(xfer_id, []).extend(keep)
        if keep and self.mem_led.enabled:
            self.mem_led.pin("staging", xfer_id, len(keep))
        if st.failed:
            return st.staged
        if len(ids) < stop:
            log.warning("stage_wave %s: only %d/%d blocks resident; "
                        "freezing stream", xfer_id, len(ids), stop)
            st.failed = True
            return st.staged
        try:
            blocks = self.transfer.extract(
                self.runner.cache_k, self.runner.cache_v, keep,
                dequant=self.runner.spec.quantized,
                span_attrs={"phase": "stage", "xfer_id": xfer_id,
                            "start": start, "stop": stop})
            data = np.stack(blocks)
        except Exception as exc:  # noqa: BLE001 — stream_end's vote trims
            log.warning("stage_wave extract failed: %s", exc)
            st.failed = True
            return st.staged
        if st.staged != start or not self.staging.append(xfer_id, start, data):
            st.failed = True
            return st.staged
        st.staged = stop
        from dynamo_tpu.disagg.metrics import get_kv_metrics

        get_kv_metrics().record_wave("stage", int(data.nbytes))
        return st.staged

    def stream_end(self, xfer_id: str) -> int:
        """Close a streamed export: vote the mesh-wide minimum staged
        count, trim pins/staging beyond it, mark the staging entry
        complete. Returns the covered (pullable) block count."""
        st = getattr(self, "_streams_by_xid", {}).pop(xfer_id, None)
        if st is None:
            return 0
        self._stream_exports.pop(st.request_id, None)
        covered = self._vote_min(st.staged)
        pins = self._staged_pins.get(xfer_id, [])
        if len(pins) > covered:
            if self.mem_led.enabled:
                self.mem_led.unpin("staging", xfer_id, len(pins) - covered)
            self.pool.release(pins[covered:])
            self._staged_pins[xfer_id] = pins[:covered]
        self.staging.finalize(xfer_id, covered)
        return covered

    def _fetch_local(self, params: dict, start: int | None = None,
                     stop: int | None = None, clients: dict | None = None):
        """The network half of a pull: fetch + assemble this rank's box
        (the window [start, stop) of the chain; the whole transfer when
        stop is None). Touches no engine state — safe off the core thread.
        ``clients`` is a per-transfer addr→ShardClient cache so wave pulls
        reuse connections. Returns (hashes, parents, local_blocks) or None
        on any failure."""
        from dynamo_tpu.disagg.sharded import (
            ShardClient,
            assemble_local,
            box_intersection,
        )

        spec = self.runner.spec
        box = self.my_box()
        pieces: list[tuple[np.ndarray, tuple[int, int, int, int]]] = []
        hashes: list[int] = []
        parents: list[int | None] = []
        try:
            for sh in params.get("shards", []):
                inter = box_intersection(box, tuple(sh["box"]))
                if inter is None:
                    continue
                if clients is not None:
                    client = clients.get(sh["addr"])
                    if client is None:
                        client = clients[sh["addr"]] = ShardClient(sh["addr"])
                    h, p, flat, got = client.fetch(params["xfer_id"], inter,
                                                   start, stop)
                else:
                    client = ShardClient(sh["addr"], retries=2)
                    try:
                        h, p, flat, got = client.fetch(params["xfer_id"],
                                                       inter, start, stop)
                    finally:
                        client.close()
                if hashes and len(h) != len(hashes):
                    # Shards answered different windows (a partial serve
                    # racing finalize-trim) — the slices no longer tile.
                    raise RuntimeError(
                        f"shard windows diverge: {len(h)} vs {len(hashes)}")
                hashes, parents = h, p  # identical across shards (one chain)
                pieces.append((flat, got))
            local = (assemble_local(box, pieces, len(hashes), spec.block_size,
                                    spec.head_dim, jnp.dtype(spec.dtype))
                     if hashes else None)
        except Exception as exc:  # noqa: BLE001 — nondeterministic IO
            log.warning("shard pull failed: %s", exc)
            return None
        return (hashes, parents, local) if local is not None else None

    def _pull_state(self, xfer_id: str) -> dict:
        if not hasattr(self, "_pulls"):
            self._pulls: dict[str, dict] = {}
        return self._pulls.setdefault(
            xfer_id, {"clients": {}, "waves": {}, "last": None})

    @_moves_blocks_alone
    def prefetch_remote(self, params: dict, start: int | None = None,
                        stop: int | None = None, tail: bool = False) -> None:
        """Start the pull's network half on a background thread so engine
        steps keep running while bytes move; import_remote joins it. As a
        replayed op, every rank overlaps ITS fetch with ITS serving — the
        op order stays identical, only the waiting moves off the step
        path. Wave pulls ([start, stop) windows) of one transfer chain on
        a single thread lineage so the per-shard connections are reused
        without cross-thread sharing."""
        state = self._pull_state(params["xfer_id"])
        prev = state["last"]
        slot: dict = {}

        def run() -> None:
            if prev is not None:
                prev["thread"].join()
            with get_tracer().span("kv.transfer", phase="pull",
                                   xfer_id=params["xfer_id"],
                                   start=start if start is not None else 0,
                                   stop=stop if stop is not None else -1,
                                   tail=tail) as sp:
                result = self._fetch_local(params, start, stop,
                                           state["clients"])
                if result is not None:
                    sp.attrs["bytes"] = int(result[2].nbytes)
                    sp.attrs["blocks"] = len(result[0])
            slot["result"] = result

        t = threading.Thread(target=run, name="kv-prefetch", daemon=True)
        slot["thread"] = t
        state["waves"][(start, stop)] = slot
        state["last"] = slot
        t.start()

    @_moves_blocks_alone
    def import_remote(self, params: dict, start: int | None = None,
                      stop: int | None = None, final: bool = True) -> int:
        """Join the prefetch (or fetch inline), vote, and inject one
        window of the chain. On a multi-host engine every rank runs this
        as a replayed op; the mesh-wide vote makes fetch failure
        all-or-nothing so per-rank pool state can never diverge (divergent
        pools would mean divergent XLA programs → hung collectives).
        Returns blocks injected, or -1 when the pull failed on some rank
        (no state was mutated anywhere). ``final`` closes the transfer's
        pull state (shard connections) afterwards."""
        state = self._pull_state(params["xfer_id"])
        slot = state["waves"].pop((start, stop), None)
        if slot is not None:
            slot["thread"].join()
            fetched = slot["result"]
        else:
            fetched = self._fetch_local(params, start, stop, state["clients"])
        failed = self._vote_min(1 if fetched is not None else 0) == 0
        if failed:
            self.close_pull(params["xfer_id"])
            return -1
        hashes, parents, local = fetched
        plan = [(h, par, local[i])
                for i, (h, par) in enumerate(zip(hashes, parents))]
        n = self.import_blocks(
            plan, span_attrs={"phase": "import", "xfer_id": params["xfer_id"],
                              "start": start if start is not None else 0,
                              "stop": stop if stop is not None else len(hashes)})
        from dynamo_tpu.disagg.metrics import get_kv_metrics

        get_kv_metrics().record_wave("pull", int(local.nbytes))
        log.info("pulled %d KV blocks for box %s (injected %d)",
                 len(plan), self.my_box(), n)
        if final:
            self.close_pull(params["xfer_id"])
        return n

    def close_pull(self, xfer_id: str) -> None:
        """Tear down a transfer's pull state: close per-shard connections
        and drop pending wave results. Closing the sockets first makes any
        in-flight fetch thread fail fast, so the join is bounded."""
        state = getattr(self, "_pulls", {}).pop(xfer_id, None)
        if state is None:
            return
        for client in state["clients"].values():
            client.close()
        last = state["last"]
        if last is not None and last["thread"].is_alive():
            last["thread"].join(timeout=5.0)

    def run_op(self, name: str, args: dict):
        """Execute one named core op — the replayable subset of run_in_core
        (every rank of a multi-host engine runs the same op with the same
        args, so unlike a closure it CAN ride the op stream)."""
        return CORE_OPS[name](self, args)

    def embed(self, token_lists: list[list[int]]) -> "np.ndarray":
        """Last-token-pooled embeddings (engine-core thread only)."""
        return self.runner.embed(token_lists)

    def fail_all(self, error: str) -> list[str]:
        """Abort every in-flight request (engine-fatal path). Returns the
        request ids that were failed so callers can notify their streams."""
        rids = list(self._seqs)
        for rid in rids:
            self.abort(rid)
        self._seqs.clear()
        if self.sessions is not None:
            # Retained pins must not outlive the requests that made them —
            # a failed engine's pool is rebuilt from scratch anyway.
            self.sessions.release_all()
        return rids


@dataclass
class _StreamExport:
    """Per-request state of a streamed (wave-granular) KV export.

    ``requested`` is leader-only bookkeeping (how far wave detection has
    emitted ops); ``staged`` is this rank's locally-staged prefix, voted
    down to the mesh minimum at stream_end. ``seq`` is cached by the
    leader's wave detection so the final wave — committed by the finalize
    that also finishes the request — is still observable after the seq
    leaves ``_seqs``."""

    xfer_id: str
    request_id: str
    hashes: list[int]
    seq: "Seq | None" = None
    requested: int = 0
    staged: int = 0
    failed: bool = False


# The replayable core-op registry: names + msgpack-able args only, so a
# multi-host leader can broadcast them on the op stream and followers
# replay them in lockstep (the closure-based run_in_core can't cross
# process boundaries and stays single-host-only).
CORE_OPS: dict[str, Callable[["EngineCore", dict], Any]] = {
    "kv_stage": lambda core, a: core.stage_export(a["xfer_id"], a["hashes"]),
    "kv_release": lambda core, a: core.release_export(a["xfer_id"]),
    "kv_prefetch": lambda core, a: core.prefetch_remote(a["params"]),
    "kv_import": lambda core, a: core.import_remote(a["params"]),
    # Streamed (wave-granular) handoff — see EngineCore.stream_begin.
    "kv_stream_begin": lambda core, a: core.stream_begin(
        a["xfer_id"], a["request_id"], a["hashes"]),
    "kv_stage_wave": lambda core, a: core.stage_wave(
        a["xfer_id"], a["start"], a["stop"]),
    "kv_stream_end": lambda core, a: core.stream_end(a["xfer_id"]),
    "kv_prefetch_wave": lambda core, a: core.prefetch_remote(
        a["params"], a["start"], a["stop"], a.get("tail", False)),
    "kv_import_wave": lambda core, a: core.import_remote(
        a["params"], a["start"], a["stop"], a.get("final", False)),
    "kv_pull_abort": lambda core, a: core.close_pull(a["xfer_id"]),
    # Drain-aware retirement (runtime/drain.py): evacuate retained
    # sessions to the remote store; early-stop a QoS class's streams.
    "session_evacuate": lambda core, a: core.evacuate_sessions(a),
    "qos_abort_class": lambda core, a: core.abort_class(
        a.get("priority") if a else None),
}


class OpChannelDown(RuntimeError):
    """The multi-host op broadcast channel failed — the engine cannot
    continue (a rank's devices would be missing from every collective)."""


class AsyncJaxEngine:
    """Async facade: background step-loop thread + asyncio output streams.

    This is what a worker process serves via ``serve_endpoint`` — the analog
    of vLLM's AsyncLLM under the reference (components/src/dynamo/vllm/
    handlers.py generate())."""

    def __init__(self, core: EngineCore, op_sink: Callable[[dict], None] | None = None):
        self.core = core
        # Multi-host leader hook (parallel/multihost.py): every state-
        # changing op is broadcast to follower ranks BEFORE being applied
        # locally, so their engine state machines replay identically.
        self._op_sink = op_sink
        self._channel_down = False
        # Set when the device or the compiler refused a step: the loop has
        # stopped, generate() answers with this error, and the launcher
        # (launch/run.py) exits non-zero on it.
        self.fatal: Exception | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._inbox: thread_queue.Queue = thread_queue.Queue()
        self._streams: dict[str, asyncio.Queue] = {}
        self._wake = threading.Event()
        self._stop = False
        self._thread = threading.Thread(target=self._run, name="engine-core", daemon=True)
        self._started = False

    def start(self) -> None:
        if not self._started:
            self._loop = asyncio.get_running_loop()
            self._thread.start()
            self._started = True

    async def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        if self._started:
            await asyncio.get_running_loop().run_in_executor(None, self._thread.join, 5.0)
        if self.core.traced_programs and not self._thread.is_alive():
            # A profiler session was open while this engine served: leave
            # the phase tables of the programs it ran then where a reader
            # of that trace, in this process, looks for them.
            await asyncio.get_running_loop().run_in_executor(
                None, self.write_phase_tables)
        # A dead engine must not keep vouching for its pins: drop its
        # live-id source so anything it leaked surfaces in the next audit.
        self.core.mem_led.unregister_live_source(self.core._mem_source_key)

    def write_phase_tables(self) -> "Path | None":
        """``{program: {instruction: phase}}`` of the step programs that ran
        under a profiler session (``EngineCore.traced_programs``), as JSON
        at obs/profiler.py ``phase_table_path()`` (found by the process
        id). After the last request: the engine thread has ended. None, and
        a warning, where it cannot be done: a trace without its tables is
        still a trace."""
        t0 = time.perf_counter()
        try:
            tables = self.core.runner.phase_tables(self.core.traced_programs)
            path = phase_table_path()
            path.write_text(json.dumps(tables))
        except Exception:
            log.warning("phase tables not written", exc_info=True)
            return None
        log.info("phase tables of %d programs at %s in %.1f s", len(tables),
                 path, time.perf_counter() - t0)
        return path

    def _emit_op(self, op: dict) -> None:
        """Broadcast one op to follower ranks; a failed broadcast is fatal
        for the whole multi-host engine (its devices leave the collective
        group), so stop the loop and surface OpChannelDown."""
        if self._op_sink is None:
            return
        try:
            self._op_sink(op)
        except Exception as exc:
            log.exception("op-channel broadcast failed; stopping engine loop")
            self._channel_down = True
            self._stop = True
            raise OpChannelDown(str(exc)) from exc

    def _stage_stream_waves(self) -> None:
        """After each finalize: stage newly-committed prefill chunks of any
        open streamed exports as kv_stage_wave ops. Broadcast-then-apply
        like every state-changing op, and emitted at a fixed point of the
        loop (right after step_finalize), so followers replay the wave at
        the identical op-stream position — pool pins stay rank-identical.
        The overlap comes for free: the NEXT chunk's device step is already
        dispatched (pipelined step_begin) while this host-side extract+
        stage runs."""
        for xid, start, stop in self.core.stream_wave_targets():
            self._emit_op({"op": "exec", "name": "kv_stage_wave",
                           "args": {"xfer_id": xid, "start": start,
                                    "stop": stop}})
            staged = self.core.run_op(
                "kv_stage_wave", {"xfer_id": xid, "start": start, "stop": stop})
            listener = getattr(self.core, "_stream_listener", None)
            if listener is not None and staged:
                try:
                    listener(xid, staged)
                except Exception:  # noqa: BLE001 — advisory only
                    log.exception("stream wave listener failed")

    # ------------------------------------------------------------------
    def _run(self) -> None:
        # Pipelined step loop: keep ONE step in flight. Each iteration plans
        # and dispatches step N+1 BEFORE blocking on step N's tokens, so host
        # work (scheduling, numpy prep, output assembly, SSE handoff) runs
        # while the device computes — the overlap reference-class engines get
        # from async scheduling (see EngineCore.step_begin). The loop's
        # phases are cut by ``loop_phase`` (obs/profiler.py LOOP_PHASES):
        # idle wait, inbox and post here; plan, dispatch, finalize and
        # record inside step_begin / step_finalize. What of an iteration's
        # wall no phase holds is ``loop["engine.unphased"]``
        # (``loop_iteration``).
        pending: PendingStep | None = None
        clock = self.core.loop_clock
        while not self._stop:
            with loop_iteration(clock):
                if not self._inbox.empty():
                    with loop_phase(clock, "engine.inbox"):
                        self._drain_inbox()
                if self._channel_down:
                    # Op channel died mid-drain: fail everything in flight
                    # (checked before the idle-continue so an idle engine still
                    # reports the failure to its streams).
                    self.core.fail_all("multi-host op channel down")
                    for rid in list(self._streams):
                        self._post(rid, LLMEngineOutput(
                            finish_reason=FinishReason.ERROR,
                            error="multi-host op channel down"))
                    break
                if not self.core.has_work() and pending is None:
                    # One span for the whole wait, however many timeouts it
                    # takes, so that a gap of the device trace is covered by
                    # one host span. Only the inbox (or shutdown) can give this
                    # thread work, so that is all the wait looks at.
                    with loop_phase(clock, "engine.idle_wait"):
                        while self._inbox.empty() and not self._stop:
                            self._wake.wait(timeout=0.05)
                            self._wake.clear()
                    continue
                try:
                    # Chaos: inside the try so an error-kind injection exercises
                    # the engine-fatal path (fail_all + drain), and a delay is a
                    # straggling device step.
                    chaos.inject("engine.step")
                    t_step = time.time()
                    if self.core.has_expired_waiting(t_step):
                        # Broadcast-then-apply, like every state-changing op:
                        # followers reap the same seqs at the same instant.
                        self._emit_op({"op": "reap", "now": t_step})
                        for rid, out in self.core.reap_expired(t_step).items():
                            self._post(rid, out)
                    self._emit_op({"op": "step", "now": t_step})
                    self.core.set_step_time(t_step)
                    nxt = self.core.step_begin() if self.core.has_work() else None
                    done = pending
                    outputs = (self.core.step_finalize(done)
                               if done is not None else {})
                    pending = nxt
                    # ``step`` joins the span to the step's wait and record.
                    with loop_phase(clock, "engine.post",
                                    step=done.step if done else 0) as span:
                        self.core.outputs_posted(
                            done, span, now=self._post_step(outputs))
                        self._stage_stream_waves()
                except Exception as exc:
                    # Engine-fatal: fail + drain all in-flight state so the loop
                    # doesn't spin hot retrying the same failing step.
                    log.exception("engine step failed; failing all in-flight requests")
                    pending = None
                    self.core.fail_all(str(exc))
                    if self._op_sink is not None and not isinstance(exc, OpChannelDown):
                        # Followers must mirror the wipe or their replayed state
                        # machines diverge from ours. (If the channel itself died,
                        # _stop is already set and there is no one to tell.)
                        try:
                            self._emit_op({"op": "fail_all", "error": str(exc)})
                        except OpChannelDown:
                            pass
                    if isinstance(exc, jax.errors.JaxRuntimeError):
                        # Out of device memory, a kernel that does not lower: the
                        # next request to reach this bucket fails the same way.
                        # Stop, rather than answer every request with an error
                        # from a process that looks healthy and exits 0. Set
                        # before the streams are told, so that generate() either
                        # is among them or sees it.
                        log.error("device error is fatal: engine stopped serving")
                        self.fatal = exc
                    for rid in list(self._streams):
                        self._post(rid, LLMEngineOutput(finish_reason=FinishReason.ERROR, error=str(exc)))
                    if self.fatal is not None:
                        break
                    continue

    def _drain_inbox(self) -> None:
        """Apply everything that has arrived: requests (admission, prefix
        match), aborts, core ops. Stops early when the op channel dies."""
        while True:
            try:
                kind, payload = self._inbox.get_nowait()
            except thread_queue.Empty:
                break
            if kind == "add":
                req, arrival = payload
                # The admit timestamp rides the op so follower ranks
                # evaluate deadline expiry at the leader's instant. The
                # arrival stamps do not: they are this process's clocks.
                t_add = time.time()
                try:
                    self._emit_op({"op": "add", "req": req.to_dict(),
                                   "now": t_add})
                except OpChannelDown as exc:
                    self._post(req.request_id, LLMEngineOutput(
                        finish_reason=FinishReason.ERROR, error=str(exc)))
                    break
                err = self.core.add_request(req, now=t_add, arrival=arrival)
                if err is not None:
                    self._post(req.request_id, err)
            elif kind == "abort":
                try:
                    self._emit_op({"op": "abort", "rid": payload})
                except OpChannelDown:
                    break  # _stop is set; streams fail below
                self.core.abort(payload)
                self._post(payload, LLMEngineOutput(finish_reason=FinishReason.CANCELLED))
            elif kind == "exec_op":
                # Named core op (CORE_OPS): broadcast first so followers
                # replay it at the same point in the stream, then run
                # locally. This is how disagg KV staging/import composes
                # with multi-host engines.
                name, args, fut, fut_loop = payload
                try:
                    self._emit_op({"op": "exec", "name": name, "args": args})
                except OpChannelDown as exc:
                    fut_loop.call_soon_threadsafe(self._resolve, fut, None, exc)
                    break
                try:
                    result, exc = self.core.run_op(name, args), None
                except Exception as e:
                    result, exc = None, e
                try:
                    fut_loop.call_soon_threadsafe(self._resolve, fut, result, exc)
                except RuntimeError:
                    log.warning("exec_op result dropped: caller loop closed")
            elif kind == "exec" and self._op_sink is not None:
                # Closure-based core access can't ride the op stream —
                # running it would desync the followers' SPMD programs.
                # Refuse loudly; use run_op (named ops) instead.
                fn, fut, fut_loop = payload
                exc = RuntimeError(
                    "run_in_core is not supported on a multi-host leader; "
                    "use run_op with a registered named op")
                try:
                    fut_loop.call_soon_threadsafe(self._resolve, fut, None, exc)
                except RuntimeError:
                    pass
            elif kind == "exec":
                # Arbitrary core access (KV export/import/pin for disagg)
                # marshaled onto this thread — the only thread allowed to
                # touch device state. The future resolves on the loop it
                # was created on (the caller's), which may differ from
                # self._loop — cross-loop set_result is not thread-safe.
                fn, fut, fut_loop = payload
                try:
                    result, exc = fn(self.core), None
                except Exception as e:
                    result, exc = None, e
                try:
                    fut_loop.call_soon_threadsafe(self._resolve, fut, result, exc)
                except RuntimeError:
                    # Caller's loop closed before we resolved (e.g. a
                    # cancelled asyncio.run): the future's owner is gone;
                    # dropping the result must not kill this thread.
                    log.warning("exec result dropped: caller loop closed")

    @staticmethod
    def _resolve(fut: asyncio.Future, result, exc: Exception | None) -> None:
        if fut.cancelled():
            return
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)

    async def run_in_core(self, fn: Callable[[EngineCore], Any]) -> Any:
        """Run ``fn(core)`` on the engine-core thread and await its result."""
        self.start()
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("exec", (fn, fut, loop)))
        self._wake.set()
        return await fut

    async def run_op(self, name: str, args: dict) -> Any:
        """Run a registered named core op (CORE_OPS) on the engine-core
        thread. On a multi-host leader the op is broadcast to followers
        first — this is the multi-host-safe replacement for run_in_core."""
        self.start()
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("exec_op", (name, args, fut, loop)))
        self._wake.set()
        return await fut

    def _post(self, rid: str, out: LLMEngineOutput) -> None:
        loop, q = self._loop, self._streams.get(rid)
        if loop is None or q is None:
            return
        loop.call_soon_threadsafe(q.put_nowait, out)

    def _post_step(self, outputs: dict[str, LLMEngineOutput]) -> float:
        """Hand a step's outputs to their streams' queues on the event
        loop, and return the one ``perf_counter()`` of it, taken as the
        last of them goes. That last hand-off carries the stamp: its
        callback runs behind the step's other ``put_nowait``s and files
        the seconds since (``stats()["gaps"]["handover"]``), what the loop
        adds before a consumer can take the step's tokens. No call of its
        own: one more wake-up of the loop a step moved ``itl_p95_ms`` by
        more than a per cent on the chip (PERF.md, PR 59)."""
        loop, streams, last = self._loop, self._streams, None
        for rid, out in outputs.items():
            q = streams.get(rid)
            if q is None or loop is None:
                continue
            if last is not None:
                loop.call_soon_threadsafe(last[0].put_nowait, last[1])
            last = (q, out)
        now = time.perf_counter()
        if last is not None:
            if self.core.sched_led.enabled:
                loop.call_soon_threadsafe(self._put_last, *last, now)
            else:
                loop.call_soon_threadsafe(last[0].put_nowait, last[1])
        return now

    def _put_last(self, q: asyncio.Queue, out: LLMEngineOutput,
                  posted: float) -> None:
        q.put_nowait(out)
        self.core.sched_led.record_handover(time.perf_counter() - posted)

    # ------------------------------------------------------------------
    async def generate(self, req: PreprocessedRequest) -> AsyncIterator[LLMEngineOutput]:
        self.start()
        q: asyncio.Queue = asyncio.Queue()
        self._streams[req.request_id] = q
        if self.fatal is not None:
            q.put_nowait(LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                error=f"engine stopped: {self.fatal}"))
        # The arrival stamps (one instant on both clocks) ride beside the
        # request, not in it: a field of the request would go down the op
        # stream to follower ranks.
        self._inbox.put(("add", (req, (time.perf_counter(), time.time()))))
        self._wake.set()
        out: LLMEngineOutput | None = None
        try:
            while True:
                out = await q.get()
                yield out
                if out.finish_reason is not None:
                    break
        finally:
            self._streams.pop(req.request_id, None)
            if out is None or out.finish_reason is None:  # client bailed early
                self._inbox.put(("abort", req.request_id))
                self._wake.set()

    async def embed(self, token_lists: list[list[int]]) -> "np.ndarray":
        """Embeddings via the engine-core thread (serialized with steps —
        device state has one owner)."""
        return await self.run_in_core(lambda core: core.embed(token_lists))

    # -- drain-aware retirement (runtime/drain.py) ---------------------
    async def evacuate_sessions(self) -> dict:
        """Push retained session KV + resumable records to the remote
        store (multi-host-safe: rides the op stream)."""
        return await self.run_op("session_evacuate", {})

    async def abort_class(self, priority: str | None = None) -> int:
        """Early-stop every live stream of one QoS class (None = all),
        emitting their terminal CANCELLED outputs. Returns the count."""
        rids = await self.run_op("qos_abort_class", {"priority": priority})
        for rid in rids or []:
            self._post(rid, LLMEngineOutput(finish_reason=FinishReason.CANCELLED))
        return len(rids or [])

    @property
    def inflight(self) -> int:
        """Streams with a live output queue (drain run-down's gauge)."""
        return len(self._streams)

    def stats(self) -> dict:
        out = self.core.metrics.snapshot(self.core.sched, self.core.pool)
        out["device"] = self.core.device_info
        # Seconds per phase of the engine thread's loop (LOOP_PHASES).
        out["loop"] = self.core.loop_clock.snapshot()
        if self.core.kvbm is not None:
            out["kvbm"] = self.core.kvbm.snapshot()
            if self.core.kvbm.ckpt_tier is not None:
                # Crash exposure refreshes on the stats poll cadence — a
                # gauge read between polls shows the last sweep's value.
                get_stream_ckpt_metrics().lag_blocks.set(
                    float(self.core.ckpt_lag_blocks()))
        if self.core.sessions is not None:
            out["session"] = self.core.sessions.snapshot()
        led = get_compile_ledger()
        if led.enabled:
            # Warmup coverage + compile stalls ride the published stats so
            # the planner and /debug/fleet can see cold-bucket workers.
            out["compile"] = led.snapshot()
        sled = get_sched_ledger()
        if sled.enabled:
            # Goodput, padding waste, and stall attribution ride the same
            # stats channel (bench stamps, planner feed, /debug/fleet).
            out["sched"] = sled.snapshot()
            # Token gaps by the step that made them, and the hand-over to
            # the streams' loop: histograms, cumulative.
            out["gaps"] = sled.gaps_snapshot()
        mled = get_mem_ledger()
        if mled.enabled:
            # Tier occupancy, pin-owner totals, TTX posture, and the last
            # leak-audit verdict ride the same channel — chaos invariants
            # read orphan_pins from here (chaos/invariants.py).
            out["mem"] = mled.snapshot()
        return out


def build_engine(engine_cfg: EngineConfig, mesh=None, params=None,
                 event_sink=None, op_sink=None) -> AsyncJaxEngine:
    core = EngineCore(engine_cfg, mesh=mesh, params=params, event_sink=event_sink)
    return AsyncJaxEngine(core, op_sink=op_sink)
