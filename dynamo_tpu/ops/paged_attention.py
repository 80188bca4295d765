"""Pallas TPU paged-attention kernel (flash-style, block-table addressed).

The portable path in models/llama.py gathers the whole paged context into a
dense ``[B, S, KH, D]`` tensor in HBM before attending — correct, but it
materializes S=NBLK*BS rows per sequence and streams them twice. This kernel
instead walks the block table directly: for each (sequence, context block)
grid step, Pallas DMAs exactly one KV block ``[BS, KH, D]`` from HBM into
VMEM (double-buffered across grid steps via the index map) and folds it into
a running online softmax. No gathered context tensor ever exists.

Works for both prefill chunks (T>1 query tokens) and decode (T=1) with the
same causal position masking as the dense path. Numerical equivalence is
tested in tests/test_ops.py (interpret mode), which also compiles the kernel
ahead-of-time for the v5e with the installed libtpu; chip_smoke.py runs it on
the chip through the server.

Design notes (reference has no TPU analog; its one kernel is a CUDA block
copy, lib/llm/src/kernels/block_copy.cu — paged attention itself lives
inside vLLM/TRT-LLM, which we replace):
- grid = (B, NQ, NS, SPB): batch and q-chunk are parallel; the context-block
  walk is partitioned into NS splits of SPB blocks each (split-K flash
  decode). Within a split the block axis is sequential ("arbitrary"),
  carrying the online-softmax state in VMEM scratch (acc, row-max m, row-sum
  l) — one slab per kv head, re-initialized at each split's first step.
- num_splits=1 IS the sequential kernel: one split walks all blocks and
  normalizes in-kernel, exactly the pre-split-K code path. num_splits>1
  emits per-split partial ``(acc, m, l)`` state as float32 outputs and a
  small jnp combine (logsumexp-weighted merge) produces the final rows —
  long-context decode latency drops from O(NBLK) sequential grid steps to
  O(NBLK / NS).
- ragged early-exit: per-row used-block counts ride the scalar-prefetch
  channel; the K/V index maps clamp the context-block lookup at a row's last
  real block, so every grid step past it re-requests the same HBM block and
  Pallas elides the DMA (revisited block ⇒ no copy), while pl.when skips the
  matmuls. Batch cost is proportional to total context, not B × max_blocks.
  The same holds along a row's query chunks: the counts are per (row,
  query chunk), so a chunk past the row's live tokens walks nothing (a
  one-token row in a T=512 rectangle pays one chunk of eight), and a live
  chunk stops at the last block its own last position can see (the causal
  mask hides the rest). Both leave every live position's result as it
  was, bit for bit: a block whose every score is masked changes no running
  state. With one query chunk a row (decode) the counts are the row's own.
- block tables + positions are scalar-prefetched (PrefetchScalarGridSpec)
  so the K/V BlockSpec index maps can address HBM blocks by table lookup —
  the DMA pipeline chases the page table, the kernel body never sees HBM.
- the kernel takes the WHOLE cache ``[L, NB, BS, KH, Dp]`` and a layer
  index (one more scalar-prefetch operand): the K/V index map is
  ``(layer, table[b, j], 0, 0, 0)``, the same blocks from another base, so
  the model's layer loop never cuts a layer out of the cache for it
  (models/llama.py ``_layer``). A quantized pool's scales stay a per-layer
  ``[NB, KH]`` operand: SMEM must not grow with L.
- K/V blocks load ALL kv heads at once — block shape ``(1, BS, KH, Dp)``
  equals the array's trailing dims, which always satisfies Mosaic's tiling
  constraint (a per-head block ``(1, BS, 1, D)`` has a second-to-minor dim
  of 1 against KH=8 and does not lower). The kv-head loop is a static
  Python loop inside the kernel: KH small 2D matmuls on the MXU per block.
- q rows are pre-laid-out ``[B, KH, T*REP, D]`` (rep = query heads per kv
  head) outside the kernel so each head's queries are one contiguous 2D
  slab — one MXU matmul covers all query heads of the kv head.
- quantized caches: int8 payloads DMA at 1 byte/elem and the per-(block,
  kv-head) scale folds into the MXU results; packed int4 payloads (uint8,
  two nibbles per byte, trailing dim D/2 — engine/cache.py) additionally
  unpack in VMEM via integer shifts before the matmuls, so KV streams from
  HBM at half a byte per element.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
_SCRATCH_CAP_BYTES = 4 * 2**20  # online-softmax VMEM scratch budget

# Mosaic min-tile sublane count by dtype itemsize (lane is always 128):
# f32 → (8, 128), bf16 → (16, 128), int8/uint8/fp8 → (32, 128).
_MIN_SUBLANE = {4: 8, 2: 16, 1: 32}

#: int4 payloads clip to ±7 (not -8): symmetric range keeps dequant a pure
#: scale multiply, mirroring int8's ±127.
INT4_QMAX = 7.0


def _sublane(dtype) -> int:
    return _MIN_SUBLANE.get(jnp.dtype(dtype).itemsize, 8)


#: Scalar memory the kernel's prefetched operands may take on a TPU v5e:
#: the 1 MiB the compiler reports ("Used 1.00M of 1.00M smem") less 16 KiB
#: for what it keeps for itself (spill slots, ~3 KiB seen). Checked against
#: the compiler itself by tests/test_ops.py's ahead-of-time cases.
SMEM_USABLE_BYTES = (1 << 20) - (16 << 10)


def scalar_prefetch_bytes(*, batch: int, nblk: int, num_blocks: int = 0,
                          kv_heads: int = 0) -> int:
    """SMEM bytes of the kernel's scalar-prefetch operands. Each row of a
    2-D operand pads to whole 128-lane words (512 B): the ``[B, NBLK]``
    block table, three ``[B]`` vectors (one of them ``[B x query chunks]``
    for a prefill rectangle: 2 KB at most, inside the slack of
    ``SMEM_USABLE_BYTES``), the ``[1]`` layer index and, for a
    quantized cache (``num_blocks`` and ``kv_heads`` given), the two
    ``[NB, KH]`` float32 scale sidecars of ONE layer — which is what bounds
    a quantized pool to ~1,000 blocks, and the block table to ~4k blocks a
    row at 64 rows."""
    def row(n: int) -> int:
        return -(-n // 128) * 512

    return (batch * row(nblk) + 3 * row(batch) + row(1)
            + 2 * num_blocks * row(kv_heads))


# ---------------------------------------------------------------------------
# Packed int4
# ---------------------------------------------------------------------------

def pack_int4(vals: jax.Array) -> jax.Array:
    """Pack signed nibbles [-8..7] (any int dtype) into uint8 bytes along the
    trailing axis, split-half layout: byte j of a length-D/2 packed row holds
    element j in its low nibble and element j + D/2 in its high nibble. The
    split-half convention keeps unpack a cheap concat (no interleave) in the
    kernel's VMEM lane layout."""
    d = vals.shape[-1]
    if d % 2:
        raise ValueError(f"int4 packing needs an even trailing dim, got {d}")
    w = vals.astype(jnp.int32)
    lo = w[..., : d // 2] & 0xF
    hi = w[..., d // 2:] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: uint8 [..., D/2] → int32 [..., D] with
    sign-extended 4-bit values. Pure integer arithmetic (mask/shift/sub) so
    it lowers inside Pallas kernels and under interpret mode alike."""
    w = packed.astype(jnp.int32)
    lo = w & 0xF
    hi = (w >> 4) & 0xF
    # sign-extend 4-bit two's complement: x - 16 when bit 3 is set
    lo = lo - ((lo & 0x8) << 1)
    hi = hi - ((hi & 0x8) << 1)
    return jnp.concatenate([lo, hi], axis=-1)


# ---------------------------------------------------------------------------
# Split-K sizing
# ---------------------------------------------------------------------------

#: f32 per-split partial-state budget (acc + m + l outputs in HBM). The
#: split-K prefill gate: partial state scales with ns·R (R = T·rep query
#: rows), so a big prefill chunk that would emit hundreds of MB of state
#: stays sequential even when the grid underfills the cores.
_SPLIT_STATE_CAP_BYTES = 8 * 2**20


def resolve_num_splits(num_splits: int, *, nblk: int, batch: int,
                       q_chunks: int, q_tokens: int,
                       state_rows: int = 0, kv_heads: int = 0,
                       head_dim: int = 0) -> int:
    """Resolve a ``num_splits`` request to the split count actually used.

    0 ("auto") defers to the cost model's :func:`auto_num_splits`. Decode
    (q_tokens == 1) engages whenever the batch underfills the cores.
    Chunked prefill (q_tokens > 1) engages under the SAME underfill signal —
    ``batch × q_chunks`` grid programs vs core count — but only while the
    f32 per-split partial state (which scales with ns·R, unlike decode's
    R = rep) fits :data:`_SPLIT_STATE_CAP_BYTES`; callers that don't supply
    the state geometry (``state_rows``/``kv_heads``/``head_dim``) keep the
    conservative sequential walk. Explicit values are clamped to [1, nblk].
    """
    if num_splits <= 0:
        from dynamo_tpu.obs.costmodel import auto_num_splits

        want = auto_num_splits(nblk, batch=batch, q_chunks=q_chunks)
        if q_tokens != 1 and want > 1:
            if not (state_rows and kv_heads and head_dim):
                return 1
            bytes_per_split = (batch * kv_heads * state_rows
                               * (head_dim + 256) * 4)
            want = min(want, max(
                _SPLIT_STATE_CAP_BYTES // max(bytes_per_split, 1), 1))
        return max(1, min(want, nblk))
    return max(1, min(num_splits, nblk))


def _kernel(*refs, bs: int, kh: int, rep: int, spb: int, nq: int,
            quant: bool, int4: bool, split: bool):
    if quant:
        # Scales ride the scalar-prefetch channel with the block table, so
        # dequant needs no extra DMA: the int8/int4 block is widened
        # in-register and the per-(block, head) scale folds into the MXU
        # results.
        (bt_ref, qs_ref, kl_ref, ub_ref, ly_ref, ks_ref, vs_ref, *refs) = refs
    else:
        (bt_ref, qs_ref, kl_ref, ub_ref, ly_ref, *refs) = refs
        ks_ref = vs_ref = None
    if split:
        (q_ref, k_ref, v_ref, o_ref, mo_ref, lo_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref) = refs
    del ly_ref  # consumed by the index maps (layer), not the body
    b = pl.program_id(0)
    qi = pl.program_id(1)
    si = pl.program_id(2)
    jj = pl.program_id(3)
    g = si * spb + jj  # global context-block index

    @pl.when(jj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kl_ref[b]

    # Blocks this query chunk of the row can see (the same count clamps the
    # K/V DMAs in the index map): none past the row's context, none past
    # the chunk's own last position, none at all for a chunk of padding.
    @pl.when(g < ub_ref[b if nq == 1 else b * nq + qi])
    def _compute():
        r = q_ref.shape[2]  # rows in this q chunk (row = token*rep + q-head)
        # Causal/visibility mask is head-independent: [R, BS].
        row = lax.broadcasted_iota(jnp.int32, (r, bs), 0) + qi * r
        row_t = row // rep                                            # query token idx
        ctx = lax.broadcasted_iota(jnp.int32, (r, bs), 1) + g * bs    # context position
        q_pos = qs_ref[b] + row_t
        visible = (ctx <= q_pos) & (ctx < kv_len)

        if int4:
            # Unpack once per block for all kv heads: uint8 [BS, KH, D/2]
            # → f32 [BS, KH, D] signed nibbles, scales applied per head in
            # the matmul results below.
            k_wide = unpack_int4(k_ref[0]).astype(jnp.float32)
            v_wide = unpack_int4(v_ref[0]).astype(jnp.float32)

        for ki in range(kh):
            q = q_ref[0, ki].astype(jnp.float32)                      # [R, D]
            if int4:
                k = k_wide[:, ki]                                     # [BS, D]
                v = v_wide[:, ki]
            else:
                k = k_ref[0, :, ki].astype(jnp.float32)               # [BS, D]
                v = v_ref[0, :, ki].astype(jnp.float32)
            scores = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )                                                         # [R, BS]
            if quant:
                # Symmetric per-(block, head) scale: constant over the
                # contraction, so scaling the int matmul result is exact.
                scores = scores * ks_ref[bt_ref[b, g], ki]
            scores = jnp.where(visible, scores, NEG_INF)

            m_prev = m_ref[ki, :, :1]                                 # [R, 1]
            l_prev = l_ref[ki, :, :1]
            m_curr = jnp.max(scores, axis=1, keepdims=True)           # [R, 1]
            m_new = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)                               # [R, BS]
            p = jnp.where(visible, p, 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )                                                         # [R, D]
            if quant:
                pv = pv * vs_ref[bt_ref[b, g], ki]
            acc_ref[ki] = acc_ref[ki] * alpha + pv
            m_ref[ki] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[ki] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(jj == spb - 1)
    def _finish():
        if split:
            # Emit this split's raw flash state; the jnp combine outside the
            # kernel merges splits. Empty splits (every block past kv_len)
            # emit (m=NEG_INF, l=0, acc=0) and combine to zero weight.
            for ki in range(kh):
                o_ref[0, 0, ki] = acc_ref[ki]
                mo_ref[0, 0, ki] = m_ref[ki]
                lo_ref[0, 0, ki] = l_ref[ki]
        else:
            for ki in range(kh):
                l = l_ref[ki, :, :1]
                l = jnp.where(l == 0.0, 1.0, l)                       # all-masked rows → 0
                o_ref[0, ki] = (acc_ref[ki] / l).astype(o_ref.dtype)


def _combine_splits(o_p: jax.Array, m_p: jax.Array, l_p: jax.Array,
                    out_dtype) -> jax.Array:
    """Merge per-split flash state [B, NS, KH, R, ·] → final rows
    [B, KH, R, D]. Standard logsumexp-weighted combine; a row whose every
    split is empty (kv_len 0 / fully masked) has l_tot 0 and yields 0,
    matching the sequential kernel's guarded divide."""
    m = m_p[..., :1]                                      # [B,NS,KH,R,1]
    l = l_p[..., :1]
    m_tot = jnp.max(m, axis=1, keepdims=True)             # [B,1,KH,R,1]
    w = jnp.exp(m - m_tot)                                # [B,NS,KH,R,1]
    l_tot = jnp.sum(w * l, axis=1)                        # [B,KH,R,1]
    acc = jnp.sum(o_p * w, axis=1)                        # [B,KH,R,D]
    l_tot = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return (acc / l_tot).astype(out_dtype)


def _layer_stack(k_cache, v_cache, layer):
    """(k_cache, v_cache, int32 layer) with a leading layer axis: a single
    layer's cache (``layer`` None) is a one-layer stack, a free reshape."""
    if layer is None:
        k_cache, v_cache = jax.tree.map(lambda a: a[None], (k_cache, v_cache))
        layer = 0
    return k_cache, v_cache, jnp.asarray(layer, jnp.int32)


def paged_attention_kernel(
    q: jax.Array,             # [B, T, H, D]
    k_cache,                  # [L, NB, BS, KH, D] — or {"q": int8
                              #   [L,NB,BS,KH,D] | uint8 packed int4
                              #   [L,NB,BS,KH,D/2], "s": f32 [L, NB, KH]}
    v_cache,
    block_tables: jax.Array,  # [B, NBLK] int32
    q_start: jax.Array,       # [B] int32 first query position
    kv_lens: jax.Array,       # [B] int32 valid context length
    *,
    layer=None,               # int32 scalar (may be traced): which layer of
                              #   the cache; None = the cache IS one layer,
                              #   [NB, BS, KH, D]
    num_splits: int = 0,      # 0 = auto (cost model), 1 = sequential, N = forced
    interpret: bool = False,
) -> jax.Array:
    """Flash paged attention over layer ``layer`` of a block-table cache.
    Returns [B, T, H, D].

    The cache is only read, and only the blocks the tables name: the layer
    index rides the scalar-prefetch channel into the K/V index maps, so a
    caller that carries the whole cache through a loop hands it over as it
    is, without cutting the layer out.

    Quantized caches (``{"q", "s"}`` — engine/cache.py) DMA int8 blocks
    (half the HBM bytes of bf16) or packed-int4 blocks (a quarter — uint8
    payload, two nibbles per byte) and fold the per-(block, kv-head) dequant
    scale into the per-block MXU matmuls; no widened KV tensor ever exists
    in HBM.

    ``num_splits`` partitions each row's context-block walk across grid
    programs (split-K flash decode); per-row used-block counts clamp the KV
    index maps so ragged batches skip DMA + compute past each row's real
    context.
    """
    k_cache, v_cache, layer = _layer_stack(k_cache, v_cache, layer)
    quant = isinstance(k_cache, dict)
    int4 = False
    if quant:
        # The layer's scales, [NB, KH]: small, and what SMEM holds must not
        # grow with L.
        k_scale = k_cache["s"][layer].astype(jnp.float32)
        v_scale = v_cache["s"][layer].astype(jnp.float32)
        k_cache, v_cache = k_cache["q"], v_cache["q"]
        int4 = k_cache.dtype == jnp.uint8            # packed marker dtype
    b, t, h, d = q.shape
    _, nb, bs, kh, dp = k_cache.shape
    if int4 and dp * 2 != d:
        raise ValueError(
            f"packed int4 cache trailing dim {dp} != head_dim/2 ({d}//2)")
    nblk = block_tables.shape[1]
    rep = h // kh
    # [B, T, KH, REP, D] → [B, KH, T*REP, D]: one contiguous query slab per
    # kv head (row r ↔ query token r // rep, query head r % rep).
    qs = (q * (d ** -0.5)).reshape(b, t, kh, rep, d)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kh, t * rep, d)

    # Chunk the query rows (flash tiling) so the all-head softmax scratch
    # stays within a few MB of VMEM for long prefill chunks: scratch bytes =
    # KH * rchunk * (D + 256) * 4. Decode (T=1) always fits in one chunk, so
    # each KV block is still DMA'd exactly once per step on the hot path.
    r = t * rep
    rchunk = r
    # Halving stops while the chunk stays Mosaic-legal: a partial block's
    # second-to-minor dim must be a multiple of the dtype's min sublane
    # count (rchunk == r needs no divisibility — whole-axis blocks are
    # always legal). Better to overshoot the soft scratch cap than emit a
    # block shape the TPU refuses to lower.
    q_sub = _sublane(q.dtype)
    while (kh * rchunk * (d + 256) * 4 > _SCRATCH_CAP_BYTES
           and rchunk % 2 == 0 and rchunk > rep
           and (rchunk // 2) % q_sub == 0):
        rchunk //= 2
    nq = r // rchunk

    ns = resolve_num_splits(num_splits, nblk=nblk, batch=b, q_chunks=nq,
                            q_tokens=t, state_rows=r, kv_heads=kh,
                            head_dim=d)
    spb = -(-nblk // ns)  # context blocks walked per split
    split = ns > 1

    # Ragged early-exit: each query chunk of a row sees DMAs only up to
    # the last block it can see — past it the clamped index map re-requests
    # the same block and Pallas elides the copy (compute is pl.when-gated
    # on the same count). Chunk c holds rows c*rchunk.. of the row's slab,
    # row r being query token r // rep: it sees context up to its own last
    # position and within kv_len, and nothing if it starts at or past
    # kv_len (a row's live query tokens end there: the chunk is padding).
    # [B * NQ], row-major; with NQ == 1 that is the row's used blocks.
    qs32, kl32 = q_start.astype(jnp.int32), kv_lens.astype(jnp.int32)
    chunk = jnp.arange(nq, dtype=jnp.int32)
    first = qs32[:, None] + (chunk * rchunk) // rep
    last = qs32[:, None] + ((chunk + 1) * rchunk - 1) // rep
    seen = jnp.where(first < kl32[:, None],
                     jnp.minimum(kl32[:, None], last + 1), 0)
    used_blocks = jnp.clip((seen + bs - 1) // bs, 0, nblk).reshape(-1)

    # Index maps see all scalar-prefetch refs after the grid indices
    # (bt, q_start, kv_lens, used_blocks, layer[, k_scale, v_scale]).
    def qmap(bi, qi, si, jj, *_prefetch):
        return (bi, 0, qi, 0)

    def kvmap(bi, qi, si, jj, *prefetch):
        bt, ub, ly = prefetch[0], prefetch[3], prefetch[4]
        g = si * spb + jj
        used = ub[bi if nq == 1 else bi * nq + qi]
        clamped = jnp.minimum(g, jnp.maximum(used - 1, 0))
        return (ly[0], bt[bi, clamped], 0, 0, 0)

    def omap_split(bi, qi, si, jj, *_prefetch):
        return (bi, si, 0, qi, 0)

    scalars = (block_tables.astype(jnp.int32), qs32, kl32, used_blocks,
               layer.reshape(1))
    if quant:
        scalars = scalars + (k_scale, v_scale)

    if split:
        out_shape = (
            jax.ShapeDtypeStruct((b, ns, kh, r, d), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, kh, r, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, kh, r, 128), jnp.float32),
        )
        out_specs = (
            pl.BlockSpec((1, 1, kh, rchunk, d), omap_split),
            pl.BlockSpec((1, 1, kh, rchunk, 128), omap_split),
            pl.BlockSpec((1, 1, kh, rchunk, 128), omap_split),
        )
    else:
        out_shape = jax.ShapeDtypeStruct((b, kh, r, d), q.dtype)
        out_specs = pl.BlockSpec((1, kh, rchunk, d), qmap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, nq, ns, spb),
        in_specs=[
            pl.BlockSpec((1, kh, rchunk, d), qmap),
            # The layer axis is squeezed: the body sees (1, BS, KH, Dp).
            pl.BlockSpec((None, 1, bs, kh, dp), kvmap),
            pl.BlockSpec((None, 1, bs, kh, dp), kvmap),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kh, rchunk, d), jnp.float32),
            pltpu.VMEM((kh, rchunk, 128), jnp.float32),
            pltpu.VMEM((kh, rchunk, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, kh=kh, rep=rep, spb=spb, nq=nq,
                          quant=quant, int4=int4, split=split),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        # A name of its own in the device trace (the custom call would
        # otherwise take it from whatever scope encloses it).
        name="paged_attention_splitk" if split else "paged_attention",
    )(*scalars, qs, k_cache, v_cache)
    if split:
        out = _combine_splits(*out, out_dtype=q.dtype)
    # [B, KH, T*REP, D] → [B, T, H, D]
    return out.reshape(b, kh, t, rep, d).transpose(0, 2, 1, 3, 4).reshape(b, t, h, d)


def paged_attention_sharded(
    mesh,
    q: jax.Array,             # [B, T, H, D] — H sharded on "model"
    k_cache,                  # [L, NB, BS, KH, D] (KH on "model") or {"q","s"}
    v_cache,
    block_tables: jax.Array,  # [B, NBLK]
    q_start: jax.Array,       # [B]
    kv_lens: jax.Array,       # [B]
    *,
    layer=None,               # as paged_attention_kernel
    num_splits: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """TP-sharded paged attention: shard_map the kernel over the "model"
    (head) axis so each device runs the kernel on its local heads. Heads are
    fully parallel in attention, so no collective is needed — the psum for
    TP happens in the subsequent wo projection, inserted by GSPMD.

    Batch rides the "data" axis (size-1 no-op on pure-TP meshes).
    """
    k_cache, v_cache, layer = _layer_stack(k_cache, v_cache, layer)
    cache_spec = P(None, None, None, "model", None)
    if isinstance(k_cache, dict):
        # Quantized cache pytree: payload sharded on kv_heads, scales on
        # their matching head axis — each shard dequantizes its own heads.
        # Packed-int4 payloads shard identically (packing is along D).
        cache_spec = {"q": cache_spec, "s": P(None, None, "model")}

    def local(q, k_cache, v_cache, block_tables, q_start, kv_lens, layer):
        return paged_attention_kernel(
            q, k_cache, v_cache, block_tables, q_start, kv_lens, layer=layer,
            num_splits=num_splits, interpret=interpret)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data", None, "model", None),
            cache_spec,
            cache_spec,
            P("data", None),
            P("data"),
            P("data"),
            P(),
        ),
        out_specs=P("data", None, "model", None),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, block_tables.astype(jnp.int32),
              q_start.astype(jnp.int32), kv_lens.astype(jnp.int32), layer)


def select_attn_impl(requested: str = "auto") -> str:
    """Resolve the attention implementation name.

    "auto" → "pallas" on TPU, "dense" elsewhere. TP-sharded meshes use the
    shard_map-wrapped kernel (paged_attention_sharded). The interpreter is
    for CPU tests: on a TPU it would run the kernel's Python body on the
    host and call the result a kernel run, so it is refused there.
    """
    on_tpu = jax.default_backend() == "tpu"
    if requested == "auto":
        return "pallas" if on_tpu else "dense"
    if requested == "pallas_interpret" and on_tpu:
        raise ValueError(
            "attn_impl='pallas_interpret' is the CPU test path; on a TPU "
            "backend use 'pallas' (or 'auto')")
    return requested
