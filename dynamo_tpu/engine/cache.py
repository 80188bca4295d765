"""Paged KV cache storage + device block allocator.

The device tier (G1) of the KV block story: cache tensors are
``[layers, num_blocks, block_size, kv_heads, head_dim]`` jax.Arrays, sharded
over the mesh "model" axis on kv_heads. Block 0 is reserved as the trash
block for padding writes (models/llama.py). Host/disk tiers and offload live
in dynamo_tpu.kvbm (reference: lib/llm/src/block_manager/). ``layers`` counts
the layers that have attention (``ModelConfig.attn_layers``, read off the
layer plan): a model whose layers are one mixer each
(``ModelConfig.hybrid_pattern``) keeps K and V for its attention layers
alone, and a model with recurrent mixers keeps a second kind of cache beside
this one, a pool of fixed-size state a sequence (models/mamba.py;
``ModelRunner.ssm``), for some layers or, where every layer has both mixers
(``ssm_beside_attention``), for every layer that is here too.

With ``kv_dtype="int8"`` each cache becomes a two-leaf pytree
``{"q": int8 payload [L, NB, BS, KH, D], "s": float32 scales [L, NB, KH]}``
— symmetric per-(layer, block, kv_head) quantization, mirroring the
``{"q", "so"}`` weight-quant idiom in models/llama.py. Everything downstream
(scan over layers, donation, shard_map in_specs) treats the cache as a
pytree, so the plain-array fast path is structurally unchanged.

``kv_dtype="int4"`` keeps the same pytree but packs two signed nibbles per
byte along head_dim: ``{"q": uint8 [L, NB, BS, KH, D//2], "s": f32}`` —
the uint8 payload dtype IS the packed-int4 marker everywhere downstream
(kernel, kvbm, scatter), so no third leaf or flag is needed. A block costs
~0.25x its bf16 bytes, so auto-sizing fits ~4x the blocks.

A model of latent attention (``ModelConfig.latent``: multi-head latent
attention) has ONE pool, not a K pool and a V pool: ``[layers, num_blocks,
block_size, 1, W]``, a token's row ``[c_kv | k_r | zeros]`` of
``latent_row`` values stored ``W`` wide (the next multiple of 128 lanes),
which every head reads; a token's value is its row's first ``kv_lora_rank``
values (models/llama.py ``_latent_attention``). Wherever the engine carries
"K and V" such a model carries ``(pool, None)``: None is an empty pytree, so
donation, shardings and the layer loop's carry take it as it is. A block is
sized, fitted and counted by that one array's bytes. A quantized latent pool
is refused here; the tiers and the wire that copy K and V by name
(dynamo_tpu.kvbm, disagg) are refused at the engine's construction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from dynamo_tpu.engine.errors import NoFreeBlocks
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import kv_cache_spec, kv_scale_spec

#: scales are float32 — 4 bytes per (layer, block, kv_head), k and v each
_SCALE_ITEMSIZE = 4


@dataclass
class KVCacheSpec:
    num_blocks: int
    block_size: int
    num_layers: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"
    #: "int8" / "int4" enable quantized storage; any other value means the
    #: cache is stored at ``dtype`` (model precision) exactly as before.
    kv_dtype: str = "bfloat16"
    #: one pool of latent rows (``ModelConfig.latent``), ``head_dim`` the
    #: stored row's width of which the first ``row_width`` values are the
    #: row; False: a K pool and a V pool
    latent: bool = False
    row_width: int = 0

    def __post_init__(self):
        if self.latent and self.quantized:
            raise ValueError(
                f"kv_dtype={self.kv_dtype} with a latent (MLA) cache: a "
                "quantized latent pool is not implemented (the row's rotary "
                "part and its latent would want scales of their own)")

    @classmethod
    def for_model(cls, cfg: ModelConfig, num_blocks: int, block_size: int,
                  kv_dtype: str = "bfloat16") -> "KVCacheSpec":
        return cls(
            num_blocks=num_blocks,
            block_size=block_size,
            # (a layer of another mixer has no K and V: models/mamba.py)
            num_layers=cfg.attn_layers,
            # (differential attention's pairs of heads are one head each)
            num_kv_heads=cfg.cache_kv_heads,
            head_dim=cfg.cache_head_dim,
            dtype=cfg.dtype,
            kv_dtype=kv_dtype,
            latent=cfg.latent,
            row_width=cfg.latent_row if cfg.latent else 0,
        )

    @property
    def pools(self) -> int:
        """Arrays of ``shape`` the cache is: K and V, or the one latent
        pool."""
        return 1 if self.latent else 2

    @property
    def kind(self) -> str:
        return "latent" if self.latent else "kv"

    def bytes_per_token(self) -> int:
        """A token's stored bytes over all layers (scales apart)."""
        return self.bytes_per_block() // self.block_size

    @property
    def quantized(self) -> bool:
        return self.kv_dtype in ("int8", "int4")

    @property
    def packed_int4(self) -> bool:
        return self.kv_dtype == "int4"

    @property
    def payload_dtype(self):
        """Storage dtype of the quantized payload leaf. uint8 is the packed
        int4 marker (two nibbles per byte); int8 means one byte per elem."""
        return jnp.uint8 if self.packed_int4 else jnp.int8

    @property
    def payload_head_dim(self) -> int:
        """Trailing payload dim: head_dim, halved when int4-packed."""
        if self.packed_int4:
            if self.head_dim % 2:
                raise ValueError(
                    f"kv_dtype=int4 needs an even head_dim, got {self.head_dim}")
            return self.head_dim // 2
        return self.head_dim

    @property
    def shape(self) -> tuple[int, int, int, int, int]:
        return (self.num_layers, self.num_blocks, self.block_size, self.num_kv_heads, self.head_dim)

    @property
    def payload_shape(self) -> tuple[int, int, int, int, int]:
        """Stored payload shape: == ``shape`` except int4 packs head_dim/2."""
        return (self.num_layers, self.num_blocks, self.block_size,
                self.num_kv_heads, self.payload_head_dim)

    @property
    def scale_shape(self) -> tuple[int, int, int]:
        """Quantization scale tensor [layers, blocks, kv_heads] (int8/int4)."""
        return (self.num_layers, self.num_blocks, self.num_kv_heads)

    def bytes_per_block(self) -> int:
        if self.quantized:
            payload = (2 * self.num_layers * self.block_size
                       * self.num_kv_heads * self.payload_head_dim)
            scales = 2 * self.num_layers * self.num_kv_heads * _SCALE_ITEMSIZE
            return payload + scales
        itemsize = jnp.dtype(self.dtype).itemsize
        # k + v (or the one latent pool), all layers
        return (self.pools * self.num_layers * self.block_size
                * self.num_kv_heads * self.head_dim * itemsize)


def register_device_tier(pool, spec: KVCacheSpec, *, name: str = "device") -> None:
    """Register the device (G1) block pool as a tier row in the memory
    ledger (obs/mem_ledger.py). ``pool`` is a PrefixPool; resident means
    referenced-or-cached — everything not on the raw free list (block 0,
    never handed out, is excluded). Byte math comes from
    :meth:`KVCacheSpec.bytes_per_block`, so quantized specs report their
    packed footprint. Pulled only at snapshot/audit time, never per-step."""
    from dynamo_tpu.obs.mem_ledger import get_mem_ledger

    bytes_per_block = spec.bytes_per_block()

    def _occupancy() -> tuple[int, int]:
        resident = pool.num_blocks - 1 - pool.num_free_raw
        return resident, resident * bytes_per_block

    get_mem_ledger().register_tier(name, _occupancy)


def _zeros(spec: KVCacheSpec):
    """One zeroed cache (K or V): a plain array, or the ``{"q", "s"}``
    pytree when ``spec.quantized``."""
    if spec.quantized:
        return {"q": jnp.zeros(spec.payload_shape, spec.payload_dtype),
                "s": jnp.zeros(spec.scale_shape, jnp.float32)}
    return jnp.zeros(spec.shape, jnp.dtype(spec.dtype))


def cache_sharding(spec: KVCacheSpec, mesh: Mesh | None):
    """Sharding of one cache on ``mesh``, shaped like the cache pytree
    (payload and scales each on their own spec); None off-mesh."""
    if mesh is None:
        return None
    payload = NamedSharding(mesh, kv_cache_spec())
    if spec.quantized:
        return {"q": payload, "s": NamedSharding(mesh, kv_scale_spec())}
    return payload


def allocate_cache(spec: KVCacheSpec, mesh: Mesh | None = None):
    """Allocate zeroed K and V caches (sharded if a mesh is given: each
    device then zeroes its own shard and never sees the whole). A latent
    spec's are ``(pool, None)``."""
    if mesh is None:
        return _zeros(spec), None if spec.latent else _zeros(spec)
    zeros = jax.jit(partial(_zeros, spec),
                    out_shardings=cache_sharding(spec, mesh))
    return zeros(), None if spec.latent else zeros()


def abstract_cache(spec: KVCacheSpec, mesh: Mesh | None = None):
    """Shape, dtype and sharding of one cache with nothing allocated — what
    the pool-sizing probe lowers the step program against
    (engine.ModelRunner._probe_step_memory)."""
    shapes = jax.eval_shape(partial(_zeros, spec))
    sharding = cache_sharding(spec, mesh)
    if sharding is None:
        return shapes
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, sharding)


def abstract_caches(spec: KVCacheSpec, mesh: Mesh | None = None) -> tuple:
    """:func:`abstract_cache` as a step program takes the cache: K and V,
    or ``(pool, None)`` of a latent spec."""
    one = abstract_cache(spec, mesh)
    return one, None if spec.latent else one


def cache_payload(cache) -> jax.Array:
    """The int8 payload leaf of a quantized cache, or the array itself —
    use wherever shard/box geometry of the [L, NB, BS, KH, D] tensor is
    needed without caring about quantization."""
    return cache["q"] if isinstance(cache, dict) else cache


@dataclass
class BlockAllocator:
    """Free-list allocator over device block ids. Block 0 (trash) is never
    handed out. Eviction/reuse decisions live above (kvbm); this is the raw
    device pool (reference: block_manager/pool)."""

    num_blocks: int
    _free: list[int] = field(default_factory=list)

    def __post_init__(self) -> None:
        self._free = list(range(self.num_blocks - 1, 0, -1))  # pop() yields 1,2,3..

    @property
    def num_free(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> list[int]:
        if n > len(self._free):
            raise NoFreeBlocks(f"need {n} blocks, {len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
        self._free.extend(blocks)
