"""Pallas TPU paged-attention kernel (flash-style, block-table addressed).

The portable path in models/llama.py gathers the whole paged context into a
dense ``[B, S, KH, D]`` tensor in HBM before attending — correct, but it
materializes S=NBLK*BS rows per sequence and streams them twice. This kernel
instead walks the block table directly: for each (sequence, query chunk)
grid step it loops over groups of the KV blocks that chunk can see, copies a
group's blocks ``[BS, KH, D]`` from HBM into VMEM itself (one async copy a
block, by table lookup, one wait a group, the next group landing while this
one computes) and folds the group into a running online softmax. No gathered context tensor
ever exists.

Works for both prefill chunks (T>1 query tokens) and decode (T=1) with the
same causal position masking as the dense path. Numerical equivalence is
tested in tests/test_ops.py (interpret mode), which also compiles the kernel
ahead-of-time for the v5e with the installed libtpu; chip_smoke.py runs it on
the chip through the server.

Design notes (reference has no TPU analog; its one kernel is a CUDA block
copy, lib/llm/src/kernels/block_copy.cu — paged attention itself lives
inside vLLM/TRT-LLM, which we replace):
- grid = (B, NQ), both parallel (in order under the token-major entry,
  below): a row, a chunk of its query rows. There is
  no block axis, so the block table's width specialises nothing but an
  operand's shape (a step program is compiled for ONE width, the longest
  context the engine admits: obs/compile_ledger.py ``sig_for_rows``): the
  walk is a ``fori_loop`` inside the grid step, over groups of G blocks
  (``_group_blocks``: G*BS is 256 keys under a prefill chunk's rows and 512
  under a decode row's, a multiple of the MXU's 128 lanes), with a trip
  count read off the scalar prefetch. The online-softmax state (acc,
  row-max m, row-sum l) lives in VMEM scratch, one slab per kv head,
  initialized by each step that walks.
- the ragged walk: per-(row, query chunk) used-block counts ride the
  scalar-prefetch channel (``chunk_used_blocks``). A chunk walks
  ceil(used / G) groups: a padding row, or a query chunk past the row's
  live tokens (a one-token row in a T=512 rectangle has seven of eight),
  costs one grid step that writes zeros; a live chunk stops at the last
  block its own last position can see (the causal mask hides the rest), and
  a block past that costs nothing. The last group of a walk names the last
  used block again for the slots past it: their keys are masked by
  position, and the buffer never holds VMEM nobody wrote. Batch cost is
  proportional to total context, not B x max_blocks.
- one walk a (row, query chunk), no split of it over grid steps: a v5e has
  one core, every batch bucket fills the grid's parallel axes (the decode
  ladder starts at 8 rows), and a walk that lives inside one grid step has
  no steps to spread.
- the kernel takes the WHOLE cache ``[L, NB, BS, KH, Dp]`` where it lies in
  HBM (memory space ANY) and a layer index (one more scalar-prefetch
  operand): a block is copied from ``cache[layer, table[b, j]]``, so the
  model's layer loop never cuts a layer out of the cache for it
  (models/llama.py ``_attention``), and the cache is only read. A quantized
  pool's scales stay a per-layer ``[NB, KH]`` operand: SMEM must not grow
  with L.
- a block lands with ALL its kv heads, ``[BS, KH, Dp]`` as the pool holds
  it, into a ``[G*BS, KH, Dp]`` buffer (two of them for K, two for V), so
  one head's keys lie KH rows apart. For a bf16 pool the buffer is read as
  uint32 ``[G*BS*KH/2, Dp]``: one sublane-strided load brings heads 2j and
  2j+1 of every key in the two halves of a word, and a bf16 in the high
  half of a word is its float32. Other pools (float32 in tests, int8,
  packed int4) take the plain strided read. The kv-head loop is a static
  Python loop inside the group's body: per head one ``[R, D] x [D, G*BS]``
  and one ``[R, G*BS] x [G*BS, D]`` matmul on the MXU.
- precision: bf16 queries and keys go to the MXU as bf16 with a float32
  result (the products are exact, so the scores are those of a float32
  widening up to summation order); max, exp, sum, alpha and the accumulator
  are float32. The probabilities are float32 values when they enter P.V, and
  the MXU takes them in one bf16 pass: at default precision Mosaic rounds a
  float32 operand to bf16, so the product is bit for bit that of
  ``p.astype(bf16)`` and the values' own bf16 with a float32 result
  (tools/attn_bench.py's ``dot`` line, on the chip: PERF.md, PR 53). P.V is
  no multi-pass float32 product, and the vector units' work in a group is
  the de-interleave of the heads and the softmax.
- the walk's copies, and what the scalar core runs for them: a group's
  blocks are fetched by a loop of one K and one V copy a block (16 KB each
  at 4 KV heads), every copy signalling the semaphore of its buffer and
  slot by the bytes it moved, and the group is awaited by ONE wait a buffer
  for the slot's whole byte count (a DMA semaphore counts bytes; the wait's
  descriptor is the slot itself). What does not change from block to block
  (the row's place in the table, which is flat in SMEM so that it is one
  product; the run's first block; how far the used blocks reach into it) is
  worked out before the loop. The kernel is compiled with
  ``disable_bounds_checks``: the hardware checks of each copy's two ends
  were half of the loop (28 of 57 bundles a block; one loop trip is 18-22
  now: tools/kernel_bundles.py), and every address is in range by
  construction instead. **Every dynamic address the kernel forms, and why
  it is in range:**
  (1) the table read ``bt[row + start + min(i, reach)]``: ``row = b * NBLK``
  with ``b`` a grid index; a group is fetched only if it begins at a used
  block (``start <= last``, so ``reach >= 0``) and ``last < NBLK``
  (``chunk_used_blocks`` clips to the table's width), so the index lies in
  the row's own ``[0, NBLK)``;
  (2) a block copy's source ``cache[layer, id]``: the id is clamped into
  ``[0, NB - 1]`` after it is read, so a table that names no block of the
  pool reads the nearest block that exists, never past the pool (the
  quantized pool's ``scale[id, head]`` takes the same clamped id);
  ``layer`` is clamped into ``[0, L - 1]`` by the wrapper before it rides
  the scalar prefetch;
  (3) a block copy's target ``buf[slot, i * BS : (i + 1) * BS]``: ``slot``
  is ``g % 2`` or ``1 - g % 2`` and ``i < G`` is the loop's static bound;
  (4) the wait's ``buf[slot]`` and the semaphores ``[n, slot]``: the same
  ``slot``;
  (5) the reads of a landed group: ``buf[slot]`` whole, by head
  ``buf[slot, :, ki, :]`` with ``ki`` static, or as uint32 rows
  ``pl.ds(j, G * BS, stride=KH / 2)`` with ``j < KH / 2`` static: the last
  row is ``j + (G * BS - 1) * KH / 2 < G * BS * KH / 2``;
  (6) the token-major tile ``q[first : first + tile]`` (``_token_tile``'s
  load and store, the one write the kernel addresses itself): ``first`` is
  clamped into ``[0, N]`` and the array holds ``N + tile`` tokens, so the
  store stays inside the output whatever ``starts`` says;
  (7) the per-row and per-chunk scalars ``ub[chunk]``, ``fb[chunk]``,
  ``kl[b]``, ``qs[b]``, ``ts[b]``: grid indices. The output of a rectangle
  step is a block of the pipeline, addressed by Pallas.
- q rows are pre-laid-out ``[B, KH, T*REP, D]`` (rep = query heads per kv
  head) outside the kernel so each head's queries are one contiguous 2D
  slab — one MXU matmul covers all query heads of the kv head. That is a
  rectangle step's entry (every decode program, verify, the pipeline's
  microbatches), where the layout is a reshape.
- a packed step (N live tokens < B x T) has a second entry, token-major
  (``starts``): ``q [N, H, D]`` stays in HBM like the cache, a (row, query
  chunk) grid step copies its own tile of tokens from its row's first token
  on, builds the slabs in VMEM (``_token_tile``), runs the same walk and
  writes the tile's live tokens back over the queries they came from. The
  ``[B, T]`` rectangle of ``q`` and of the output, six or seven passes of
  B x T x H x D elements a layer around and inside the rectangle's kernel
  (8 x 512 positions for 513 live tokens), does not exist on that path, and
  a (row, chunk) without a live token moves nothing (PERF.md, PR 50). The
  grid's steps run in order there ("arbitrary"): a row's last tile runs
  into the next row's tokens.
- a latent pool (``v_cache`` None, ``v_width``: multi-head latent
  attention's one row a token, ``[L, NB, BS, 1, W]``, which every query head
  reads; models/llama.py ``_latent_attention``) is one more mode of the same
  walk, not a second kernel: one copy a block (there is no V pool), the
  group's buffer ``[G*BS, W]`` (the pool seen ``[L, NB, BS, W]``: its one
  head is no axis of the tiles, a block is ``BS x W`` bf16 in whole
  ``(16, 128)`` tiles), the scores over the whole stored row (the query
  carries zeros where the row is padding) and **V taken from K's tile**,
  its first ``v_width`` lanes (a multiple of 128: a lane-aligned slice of
  the value already loaded, no copy), so P.V and the accumulator are
  ``v_width`` wide. The table walk, the mask, the softmax state and every
  address are the ones above: no new dynamic address.
- quantized caches: int8 payloads copy at 1 byte/elem and go to the MXU as
  they are (exact in bf16); the per-(block, kv-head) scale multiplies that
  block's BS columns of the group's scores, and of its probabilities before
  P.V. Packed int4 payloads (uint8, two nibbles per byte, trailing dim D/2 —
  engine/cache.py) additionally unpack in VMEM via integer shifts before
  the matmuls, so KV streams from HBM at half a byte per element.
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
# A query chunk is as large as the 16 MiB of VMEM a kernel gets allow: its
# float32 softmax state (all kv heads), the query and output blocks the
# pipeline holds two of, and its float32 scores against a group of keys.
# At 8 kv heads x 128 that is 512 rows (12 MiB with the K/V buffers).
_SCRATCH_CAP_BYTES = 8 * 2**20  # online-softmax VMEM scratch budget
_CHUNK_ROWS = 512               # rows x _GROUP_KEYS float32 scores: 512 KiB
_GROUP_KEYS = 256       # keys a group of the walk holds under a prefill chunk
_GROUP_KEYS_WIDE = 512  # ... and under at most _FEW_ROWS query rows (decode)
_FEW_ROWS = 32

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
    """SMEM bytes of the kernel's scalar-prefetch operands: what the walk
    reads to find its blocks. Each row of a 2-D operand pads to whole
    128-lane words (512 B): the ``[B, NBLK]`` block table the copies look
    blocks up in, three ``[B]`` vectors (first query position, context
    length, and the used-block counts that set each walk's trip count:
    ``[B x query chunks]`` for a prefill rectangle, 2 KB at most, inside
    the slack of ``SMEM_USABLE_BYTES``), the ``[1]`` layer index and, for a
    quantized cache (``num_blocks`` and ``kv_heads`` given), the two
    ``[NB, KH]`` float32 scale sidecars of ONE layer — which is what bounds
    a quantized pool to ~1,000 blocks, and the block table to ~4k blocks a
    row at 64 rows. The K/V buffers and semaphores are VMEM, not here."""
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


def _i32(x: int):
    """A Python int as an int32 constant (lax primitives take no weak types)."""
    return jnp.int32(x)


def _cast(x: jax.Array, dtype) -> jax.Array:
    """``x`` as ``dtype``; an integer payload goes by float32 (Mosaic
    converts int8 to bf16 in those two steps)."""
    if x.dtype == dtype:
        return x
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = lax.convert_element_type(x, jnp.float32)
    return lax.convert_element_type(x, dtype)


def query_chunks(t: int, *, rep: int, kh: int, d: int,
                 q_dtype=jnp.bfloat16) -> tuple[int, int]:
    """(rows a query chunk holds, chunks a row) for ``t`` query tokens of
    ``rep`` query heads a kv head. The rows are chunked (flash tiling) so
    that what a grid step holds fits VMEM: the all-head softmax scratch,
    KH * rchunk * (D + 256) * 4 bytes, and a group's float32 scores,
    ``_CHUNK_ROWS`` rows at most. Every chunk of a row walks the row's
    context again, and a one-token row beside a prefill chunk pays its
    whole first chunk: 512 rows measured 5-14 % under 256 on a T=512
    chunk at depths 0-3,584 and 10 % over at T=128 (PERF.md, PR 35).
    Decode (T=1) always fits in one chunk."""
    r = t * rep
    rchunk = r
    # Halving stops while the chunk stays Mosaic-legal: a partial block's
    # second-to-minor dim must be a multiple of the dtype's min sublane
    # count (rchunk == r needs no divisibility — whole-axis blocks are
    # always legal), and a chunk holds whole tokens (every T the engine
    # buckets to, a power of two, halves that way). Better to overshoot the
    # soft scratch cap than emit a block shape the TPU refuses to lower.
    q_sub = _sublane(q_dtype)
    while ((kh * rchunk * (d + 256) * 4 > _SCRATCH_CAP_BYTES
            or rchunk > _CHUNK_ROWS)
           and rchunk % 2 == 0 and rchunk > rep
           and (rchunk // 2) % q_sub == 0 and (rchunk // 2) % rep == 0):
        rchunk //= 2
    return rchunk, r // rchunk


def chunk_used_blocks(q_start, kv_lens, *, nq: int, rchunk: int, rep: int,
                      bs: int, nblk: int):
    """``[B, NQ]`` int32: the blocks each query chunk of each row walks.
    Chunk c holds rows ``c*rchunk ..`` of the row's ``[T*rep]`` slab (row r
    is query token ``r // rep``): it sees the context up to its own last
    position and within ``kv_len``, and nothing if it starts at or past
    ``kv_len`` (the row's live tokens end there: the chunk is padding)."""
    chunk = jnp.arange(nq, dtype=jnp.int32)
    first = q_start[:, None] + (chunk * rchunk) // rep
    last = q_start[:, None] + ((chunk + 1) * rchunk - 1) // rep
    seen = jnp.where(first < kv_lens[:, None],
                     jnp.minimum(kv_lens[:, None], last + 1), 0)
    return jnp.clip((seen + bs - 1) // bs, 0, nblk)


def chunk_first_blocks(q_start, *, nq: int, rchunk: int, rep: int, bs: int,
                       window: int):
    """``[B, NQ]`` int32: under a window, the block at which each query
    chunk's walk begins: the one that holds the oldest key the chunk's
    first query token can see, position ``first - window + 1``. Blocks
    before it are out of every query's window in the chunk and are not
    copied; inside the walk the mask hides what the later queries no
    longer see."""
    chunk = jnp.arange(nq, dtype=jnp.int32)
    first = q_start[:, None] + (chunk * rchunk) // rep
    return jnp.maximum(first - (window - 1), 0) // bs


def _group_blocks(rows: int, bs: int, nblk: int) -> int:
    """Blocks a group of the walk holds, G: what one online-softmax update
    covers. ``G * bs`` keys is a multiple of the 128 lanes wherever the
    table is that long: ``_GROUP_KEYS_WIDE`` keys under a few query rows
    (decode: the group's float32 scores ``[rows, G*bs]`` are a vreg or
    two), ``_GROUP_KEYS`` under a prefill chunk's (256 rows x 256 keys of
    float32 is the whole register file)."""
    keys = _GROUP_KEYS_WIDE if rows <= _FEW_ROWS else _GROUP_KEYS
    return max(1, min(keys // bs, nblk))


def _kernel(*refs, bs: int, kh: int, rep: int, gb: int, nq: int, nblk: int,
            quant: bool, int4: bool, mm_dtype, window: int = 0,
            tile: int = 0, latent: bool = False):
    # A window (static, > 0) adds one scalar-prefetch operand, the block
    # each walk begins at, ahead of the others, and a lower bound in the
    # mask; with window == 0 nothing below is traced that was not before.
    if window:
        fb_ref, *refs = refs
    # The token-major entry (static ``tile`` > 0: the tokens a query chunk
    # holds) adds each row's first token, and takes ``q`` and the output as
    # one array of tokens in HBM: see ``_token_tile``.
    if tile:
        ts_ref, *refs = refs
    if quant:
        # Scales ride the scalar-prefetch channel with the block table, so
        # dequant needs no extra DMA: the int8/int4 payload goes to the MXU
        # as it is and the per-(block, head) scale multiplies that block's
        # columns of the group's scores and of its probabilities.
        (bt_ref, qs_ref, kl_ref, ub_ref, ly_ref, ks_ref, vs_ref, *refs) = refs
    else:
        (bt_ref, qs_ref, kl_ref, ub_ref, ly_ref, *refs) = refs
        ks_ref = vs_ref = None
    if latent:
        # One pool: no V operand and no V buffer (V is K's first lanes).
        refs = [*refs[:2], None, refs[2], refs[3], None, *refs[4:]]
    if tile:
        (q_hbm, k_hbm, v_hbm, o_hbm, kbuf, vbuf, sems, acc_ref, m_ref, l_ref,
         tok_ref, q_ref, tok_sems) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sems, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    # Rows in this q chunk: row = token*rep + q-head in a rectangle's slab,
    # q-head*tile + token in the slab built from a tile of tokens.
    r = q_ref.shape[2]
    gk = gb * bs                # keys a group holds
    nb = k_hbm.shape[1]         # blocks the pool holds

    # Blocks this query chunk of the row can see: none past the row's
    # context, none past the chunk's own last position, none at all for a
    # chunk of padding. The walk is over groups of ``gb`` of them: the trip
    # count is this chunk's own, whatever the table's width.
    chunk = b if nq == 1 else lax.add(lax.mul(b, _i32(nq)), qi)
    used = ub_ref[chunk]
    last = lax.max(lax.sub(used, _i32(1)), _i32(0))
    if window:
        # The walk is over the blocks [first, used): what lies before is
        # out of the window of every query in the chunk.
        first = lax.min(fb_ref[chunk], last)
        groups = lax.div(lax.add(lax.sub(used, first), _i32(gb - 1)), _i32(gb))
    else:
        groups = lax.div(lax.add(used, _i32(gb - 1)), _i32(gb))
    layer = ly_ref[0]
    kv_len = kl_ref[b]
    q_pos0 = qs_ref[b]

    row = lax.mul(b, _i32(nblk))    # the row's place in the flat table

    def block_ids(start):
        # The ids of the blocks from the row's block ``start`` on (None: its
        # first, which costs no equation), as a function of a block's place
        # i in that run. Past the
        # last used block the last one is named again: its keys are masked
        # by position, and what the buffer holds there is KV that was
        # written, never stale VMEM. What does not change with i is worked
        # out here, before the loop that asks: where the run begins in the
        # table, and how far the used blocks reach into it (>= 0: a group
        # that is fetched begins at a used block).
        at, reach = row, last
        if start is not None:
            at, reach = lax.add(row, start), lax.sub(last, start)

        def block_id(i):
            # ... and an id is held inside the pool, whatever the table
            # says: no hardware check stands behind the copies' addresses.
            return lax.clamp(_i32(0), bt_ref[lax.add(at, lax.min(i, reach))],
                             _i32(nb - 1))
        return block_id

    def fetch(start, slot):
        # The group of blocks from ``start`` on into buffer ``slot``: one
        # copy a block and buffer, each signalling its buffer's semaphore of
        # the slot by the bytes it moved.
        block_id = block_ids(start)

        def copy(i, c):
            blk = block_id(i)
            dst = pl.ds(lax.mul(i, _i32(bs)), bs)
            pltpu.make_async_copy(k_hbm.at[layer, blk], kbuf.at[slot, dst],
                                  sems.at[0, slot]).start()
            if not latent:
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, dst],
                                      sems.at[1, slot]).start()
            return c
        lax.fori_loop(0, gb, copy, 0)

    def land(slot):
        # One wait a buffer for the group's whole byte count: a DMA
        # semaphore counts bytes, and a wait takes its count from the
        # descriptor's shape, not from a source.
        for n, buf in enumerate((kbuf,) if latent else (kbuf, vbuf)):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[n, slot]).wait()

    def group(g, c):
        # (lax primitives all through the walk: a jnp call is a nested jit
        # to trace, and a step program is traced at every start.)
        slot = lax.rem(g, _i32(2))
        nxt = lax.add(g, _i32(1))
        # The group's first block among the row's, and its first key.
        start = lax.mul(g, _i32(gb))
        if window:
            start = lax.add(start, first)
        base = lax.mul(start, _i32(bs))

        @pl.when(lax.lt(nxt, groups))
        def _next():
            fetch(lax.add(start, _i32(gb)), lax.sub(_i32(1), slot))

        land(slot)

        # Causal/visibility mask is head-independent, [R, GK]: key c of the
        # group is seen by chunk row w (query token w // rep, or w % tile
        # where the slab is a tile's) if
        # base + c <= q_pos0 + (qi*r + w) // rep and base + c < kv_len.
        ctx = lax.broadcasted_iota(jnp.int32, (r, gk), 1)
        tok = (lax.rem if tile else lax.div)(
            lax.broadcasted_iota(jnp.int32, (r, gk), 0),
            lax.full((r, gk), tile or rep, jnp.int32))
        q_pos = lax.sub(lax.add(q_pos0, lax.mul(qi, _i32(r // rep))), base)
        visible = lax.bitwise_and(
            lax.le(lax.sub(ctx, tok), lax.broadcast(q_pos, (r, gk))),
            lax.lt(ctx, lax.broadcast(lax.sub(kv_len, base), (r, gk))))
        if window:
            # ... and by a sliding layer's query only if fewer than
            # ``window`` positions back: g*gk + c > q_pos - window.
            visible = lax.bitwise_and(visible, lax.gt(
                lax.sub(ctx, tok),
                lax.broadcast(lax.sub(q_pos, _i32(window)), (r, gk))))

        neg_inf = lax.full((r, gk), NEG_INF, jnp.float32)
        zeros = lax.full((r, gk), 0.0, jnp.float32)

        def cols(x, n):
            # [R, 1] -> [R, n]
            return lax.broadcast_in_dim(x, (r, n), (0, 1))

        if quant:
            col_blk = lax.div(lax.broadcasted_iota(jnp.int32, (1, gk), 1),
                              lax.full((1, gk), bs, jnp.int32))
            block_id = block_ids(start)

            def scale_row(s_ref, ki):
                # [1, GK]: block i's scale over its bs columns.
                def put(i, acc):
                    return jnp.where(col_blk == i, s_ref[block_id(i), ki],
                                     acc)
                return lax.fori_loop(0, gb, put,
                                     jnp.zeros((1, gk), jnp.float32))

        def head(ki, k, v):
            """Fold kv head ``ki``'s keys and values [GK, D] of this group
            into its running softmax."""
            q = _cast(q_ref[0, ki], mm_dtype)                         # [R, D]
            # bf16 x bf16 products are exact in float32: the scores the
            # float32 widening gave, up to the order of summation.
            scores = lax.dot_general(
                q, _cast(k, mm_dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)                   # [R, GK]
            if quant:
                # Symmetric per-(block, head) scale: constant over the
                # contraction, so scaling the int matmul result is exact.
                scores = scores * scale_row(ks_ref, ki)
            scores = lax.select(visible, scores, neg_inf)

            # The online softmax in lax primitives: a jnp call is a nested
            # jit to trace, which a step program pays at every start.
            m_prev = m_ref[ki, :, :1]                                 # [R, 1]
            l_prev = l_ref[ki, :, :1]
            m_curr = lax.expand_dims(lax.reduce_max(scores, (1,)), (1,))
            m_new = lax.max(m_prev, m_curr)
            alpha = lax.exp(lax.sub(m_prev, m_new))
            p = lax.exp(lax.sub(scores, cols(m_new, gk)))             # [R, GK]
            p = lax.select(visible, p, zeros)
            l_new = lax.add(lax.mul(alpha, l_prev),
                            lax.expand_dims(lax.reduce_sum(p, (1,)), (1,)))
            if quant:
                p = p * scale_row(vs_ref, ki)
            # Float32 operands at default precision: the MXU takes both in
            # one bf16 pass (the module's note on precision). A latent
            # tile's values are the keys' own lanes, bf16 as they landed:
            # the probabilities take that pass's rounding here, by name.
            if latent and v.dtype == jnp.bfloat16:
                p = lax.convert_element_type(p, jnp.bfloat16)
            else:
                v = _cast(v, jnp.float32)
            pv = lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)                   # [R, D]
            acc_ref[ki] = lax.add(
                lax.mul(acc_ref[ki], cols(alpha, acc_ref.shape[-1])), pv)
            m_ref[ki] = cols(m_new, m_ref.shape[-1])
            l_ref[ki] = cols(l_new, l_ref.shape[-1])

        # A block lands as it lies in the pool, [BS, KH, Dp], so one head's
        # rows are KH apart in the group's buffer.
        if latent:
            # The group's rows [GK, W] as they landed, and their first
            # lanes as the values.
            rows = kbuf[slot]
            head(0, rows, lax.slice_in_dim(rows, 0, acc_ref.shape[-1], axis=1))
        elif int4:
            # Unpack once per group for all kv heads: uint8 [GK, KH, D/2]
            # → signed nibbles [GK, KH, D].
            k_wide = unpack_int4(kbuf[slot]).astype(jnp.float32)
            v_wide = unpack_int4(vbuf[slot]).astype(jnp.float32)
            for ki in range(kh):
                head(ki, k_wide[:, ki], v_wide[:, ki])
        elif kbuf.dtype == jnp.bfloat16 and kh % 2 == 0:
            # bf16 packs two rows a 32-bit word, so as uint32 [GK*KH/2, Dp]
            # one strided load brings heads 2j (low half) and 2j+1 (high
            # half) of every key, eight keys a vreg; a bf16 in the high
            # half of a word IS its float32. The pairs are a loop that is
            # unrolled when the kernel is lowered: the body is traced once,
            # not KH/2 times (a step program is traced at every start), and
            # the heads still overlap on the device.
            kw, vw = (buf.at[slot].reshape(gk * kh, buf.shape[-1])
                      .bitcast(jnp.uint32) for buf in (kbuf, vbuf))
            sixteen = lax.full((gk, kbuf.shape[-1]), 16, jnp.uint32)
            hi = lax.full((gk, kbuf.shape[-1]), 0xFFFF0000, jnp.uint32)

            def pair(j, c):
                rows = pl.ds(j, gk, stride=kh // 2)
                k2, v2 = kw[rows, :], vw[rows, :]
                head(2 * j, pltpu.bitcast(lax.shift_left(k2, sixteen), jnp.float32),
                     pltpu.bitcast(lax.shift_left(v2, sixteen), jnp.float32))
                head(2 * j + 1, pltpu.bitcast(lax.bitwise_and(k2, hi), jnp.float32),
                     pltpu.bitcast(lax.bitwise_and(v2, hi), jnp.float32))
                return c
            lax.fori_loop(0, kh // 2, pair, 0, unroll=True)
        else:
            for ki in range(kh):
                head(ki, kbuf[slot, :, ki, :], vbuf[slot, :, ki, :])
        return c

    # Depth matters to set-up: a step program is traced at every start, and
    # an operation costs the more to trace the deeper it sits in nested
    # loops and conditionals (PERF.md, PR 34). So the walk's loop stands at
    # the kernel's top level (no trip for a dead step) and only the short
    # pieces around it are conditional.
    live = lax.gt(groups, _i32(0))
    if tile:
        load, spread, collect, store = _token_tile(
            q_hbm, o_hbm, tok_ref, q_ref, tok_sems,
            first=lax.add(ts_ref[b], lax.mul(qi, _i32(tile))),
            n_live=lax.sub(lax.sub(kv_len, q_pos0), lax.mul(qi, _i32(tile))))

    @pl.when(live)
    def _init():
        m_ref[:] = lax.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[:] = lax.full(l_ref.shape, 0.0, l_ref.dtype)
        acc_ref[:] = lax.full(acc_ref.shape, 0.0, acc_ref.dtype)
        if tile:
            load.start()
        fetch(first if window else None, _i32(0))
        if tile:
            load.wait()
            spread()

    lax.fori_loop(_i32(0), groups, group, 0)

    def result(ki):
        """The chunk's output of kv head ``ki`` [R, D] (a slice: of them
        all)."""
        l = l_ref[ki, :, :1]
        l = lax.select(lax.eq(l, lax.full(l.shape, 0.0, l.dtype)),
                       lax.full(l.shape, 1.0, l.dtype), l)    # all-masked rows → 0
        acc = acc_ref[ki]
        return lax.convert_element_type(
            lax.div(acc, lax.broadcast_in_dim(
                l, acc.shape, tuple(range(acc.ndim)))), q_ref.dtype)

    @pl.when(live)
    def _finish():
        if tile:
            collect(result(slice(None)))
            store.start()
            store.wait()
            return

        def one(ki, c):
            o_ref[0, ki] = result(ki)
            return c
        lax.fori_loop(0, kh, one, 0)

    if not tile:
        @pl.when(lax.eq(groups, _i32(0)))
        def _dead():
            # Nothing to walk (a padding row, a chunk of padding): the step
            # costs its grid slot and zeros. (Of a tile of tokens it costs
            # the slot: nothing is copied in and nothing written.)
            o_ref[...] = lax.full(o_ref.shape, 0.0, o_ref.dtype)


def _token_tile(q_hbm, o_hbm, tok_ref, q_ref, sems, *, first, n_live):
    """How a (row, query chunk) of the token-major entry gets its queries
    and leaves its output: ``q_hbm [N + tile, HP, D]`` holds the step's
    tokens packed row after row, the chunk's are the ``tile`` from ``first``
    on, of which ``n_live`` (or more: then all) are the row's own; the rest
    are a later row's or padding, computed against this row's context and
    never written. ``o_hbm`` is the same buffer (the call aliases them).

    Returns (load, spread, collect, store). ``load`` copies the tile into
    ``tok_ref [tile, HP, D]``; ``spread()`` lays it out as the walk reads
    it, one slab a kv head in ``q_ref [1, KH, rep * tile, D]``, row
    ``j * tile + i`` query head ``j`` of the kv head's ``rep``, token ``i``;
    ``collect(out)`` puts the walk's output ``[KH, rep * tile, D]`` back
    into ``tok_ref`` token-major, and ``store`` copies the tile over the
    tokens it came from: the live ones take their output, the others the
    ``q`` they held, so a later row still finds its queries there. That
    needs the grid's steps in order, which the call's
    ``dimension_semantics`` say.

    Both moves are one transpose of the tile's two leading axes, a handful
    of equations whatever the number of heads (a kernel body's length is
    set-up time: PERF.md, PR 49)."""
    tile, hp, d = tok_ref.shape
    _, kh, r, _ = q_ref.shape
    h = kh * r // tile
    # ``first`` comes from the caller's ``starts``: held to the tokens, so
    # that the tile lies inside the array (the tile of padding behind them
    # is there for the last one), whatever a row's start says.
    here = pl.ds(lax.clamp(_i32(0), first, _i32(q_hbm.shape[0] - tile)), tile)
    load = pltpu.make_async_copy(q_hbm.at[here], tok_ref, sems.at[0])
    store = pltpu.make_async_copy(tok_ref, o_hbm.at[here], sems.at[1])

    def spread():
        heads = lax.transpose(tok_ref[...], (1, 0, 2))       # [HP, tile, D]
        q_ref[0] = lax.reshape(lax.slice_in_dim(heads, 0, h), (kh, r, d))

    def collect(out):
        # (a latent walk's output is the values' width: zeros behind it)
        dv = out.shape[-1]
        heads = lax.pad(lax.reshape(out, (h, tile, dv)),
                        lax.full((), 0.0, out.dtype),
                        ((0, hp - h, 0), (0, 0, 0), (0, d - dv, 0)))
        mine = lax.lt(lax.broadcasted_iota(jnp.int32, tok_ref.shape, 0),
                      lax.broadcast(n_live, tok_ref.shape))
        tok_ref[...] = lax.select(mine, lax.transpose(heads, (1, 0, 2)),
                                  tok_ref[...])

    return load, spread, collect, store


def _layer_stack(k_cache, v_cache, layer):
    """(k_cache, v_cache, int32 layer) with a leading layer axis: a single
    layer's cache (``layer`` None) is a one-layer stack, a free reshape."""
    if layer is None:
        k_cache, v_cache = jax.tree.map(lambda a: a[None], (k_cache, v_cache))
        layer = 0
    return k_cache, v_cache, jnp.asarray(layer, jnp.int32)


def paged_attention_kernel(
    q: jax.Array,             # [B, T, H, D]; with ``starts``: [N, H, D]
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
    interpret: bool = False,
    window: int = 0,          # static; > 0: a sliding layer, query i sees
                              #   the keys j with i - j < window
    starts: jax.Array | None = None,  # [B] int32: token-major, each row's
                              #   first token among ``q``'s N
    t: int | None = None,     # ... and the row bucket T (static)
    scale: float | None = None,  # on q; None: the head's ``D ** -0.5``
                              #   (a caller whose D is not the model's
                              #   head: differential attention's pair view)
    v_width: int = 0,         # with ``v_cache`` None (a latent pool): the
                              #   lanes of a row that are its value
) -> jax.Array:
    """Flash paged attention over layer ``layer`` of a block-table cache.
    Returns [B, T, H, D].

    A latent pool (``v_cache`` None, ``k_cache [L, NB, BS, 1, W]``): every
    query head ``[.., W]`` scores against the one row a token and reads its
    first ``v_width`` lanes; the result is ``[.., H, v_width]``.

    Token-major (``starts`` given): ``q [N, H, D]`` holds the rows' live
    query tokens packed row after row, row ``i``'s ``kv_lens[i] -
    q_start[i]`` of them from ``starts[i]`` on, as a step's dense layers
    leave them, and the result is ``[N, H, D]`` in the same places (a token
    of no row keeps its scaled ``q``: finite, and read by nobody). No array
    of ``B x T`` positions exists on that path: a (row, query chunk) grid
    step copies its own tile of tokens from HBM, as it copies its KV blocks,
    and writes its tile back (``_token_tile``); one with no live token
    copies nothing. The walk, the scores and the sums are the rectangle's.

    Under a ``window`` the walk of a (row, query chunk) starts at the first
    block any of its queries can see (``chunk_first_blocks``, one more
    scalar-prefetch operand) and the mask gains the lower bound: a sliding
    layer's decode row copies ``window / BS + 1`` blocks, not its context.

    The cache is only read, and only the blocks the tables name: it stays
    in HBM and the kernel copies those blocks itself, the layer index
    riding the scalar-prefetch channel, so a caller that carries the whole
    cache through a loop hands it over as it is, without cutting the layer
    out.

    Quantized caches (``{"q", "s"}`` — engine/cache.py) copy int8 blocks
    (half the HBM bytes of bf16) or packed-int4 blocks (a quarter — uint8
    payload, two nibbles per byte) and fold the per-(block, kv-head) dequant
    scale into the group's MXU matmuls; no widened KV tensor ever exists
    in HBM.

    Per-(row, query chunk) used-block counts end each walk at the chunk's
    real context: the width of ``block_tables`` bounds what a row may hold
    and costs nothing past what it does hold.
    """
    k_cache, v_cache, layer = _layer_stack(k_cache, v_cache, layer)
    quant = isinstance(k_cache, dict)
    latent = v_cache is None
    if latent and (quant or window or not v_width or v_width % 128
                   or k_cache.shape[3] != 1 or v_width > k_cache.shape[4]):
        raise ValueError(
            "a latent pool is [L, NB, BS, 1, W] at the model's precision, "
            "walked whole (no quantized payload, no window) with v_width a "
            f"multiple of 128 within W: got {jax.tree.map(jnp.shape, k_cache)}"
            f", window {window}, v_width {v_width}")
    int4 = False
    # Held inside the cache: the kernel's copies are not checked.
    layers = (k_cache["q"] if quant else k_cache).shape[0]
    layer = lax.clamp(jnp.int32(0), layer, jnp.int32(layers - 1))
    if quant:
        # The layer's scales, [NB, KH]: small, and what SMEM holds must not
        # grow with L.
        k_scale = k_cache["s"][layer].astype(jnp.float32)
        v_scale = v_cache["s"][layer].astype(jnp.float32)
        k_cache, v_cache = k_cache["q"], v_cache["q"]
        int4 = k_cache.dtype == jnp.uint8            # packed marker dtype
    packed = starts is not None
    if packed:
        (n, h, d), b = q.shape, block_tables.shape[0]
    else:
        b, t, h, d = q.shape
    _, nb, bs, kh, dp = k_cache.shape
    dv = v_width if latent else d       # the output's width
    if latent:
        # The one head is no axis of a block's tiles: the same bytes.
        k_cache = k_cache.reshape(layers, nb, bs, dp)
    if int4 and dp * 2 != d:
        raise ValueError(
            f"packed int4 cache trailing dim {dp} != head_dim/2 ({d}//2)")
    nblk = block_tables.shape[1]
    rep = h // kh
    r = t * rep
    rchunk, nq = query_chunks(t, rep=rep, kh=kh, d=d, q_dtype=q.dtype)
    tile = rchunk // rep if packed else 0
    qs = q * (d ** -0.5 if scale is None else scale)
    if packed:
        # A tile read from a row's first token runs into the next row's
        # tokens and, on the last row, past N: a tile of padding behind
        # them. The heads fill whole sublane tiles (a slice of the array in
        # HBM is of whole tiles).
        qs = lax.pad(qs, lax.full((), 0.0, qs.dtype), (
            (0, tile, 0), (0, -h % _sublane(q.dtype), 0), (0, 0, 0)))
    else:
        # [B, T, KH, REP, D] → [B, KH, T*REP, D]: one contiguous query slab
        # per kv head (row r ↔ query token r // rep, query head r % rep).
        qs = qs.reshape(b, t, kh, rep, d)
        qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kh, t * rep, d)

    gb = _group_blocks(rchunk, bs, nblk)   # the walk is over groups of G blocks

    # Ragged walk: the blocks each query chunk of each row can see, [B * NQ]
    # row-major (with NQ == 1 the row's used blocks).
    qs32, kl32 = q_start.astype(jnp.int32), kv_lens.astype(jnp.int32)
    used_blocks = chunk_used_blocks(qs32, kl32, nq=nq, rchunk=rchunk, rep=rep,
                                    bs=bs, nblk=nblk).reshape(-1)

    # Index maps see all scalar-prefetch refs after the grid indices
    # (bt, q_start, kv_lens, used_blocks, layer[, k_scale, v_scale]).
    def qmap(bi, qi, *_prefetch):
        return (bi, 0, qi, 0)

    # (the table flat: a row's place in it is one product, worked out once
    # a group and not a tiled 2-D SMEM address a block)
    scalars = (block_tables.astype(jnp.int32).reshape(-1), qs32, kl32,
               used_blocks, layer.reshape(1))
    if quant:
        scalars = scalars + (k_scale, v_scale)
    if packed:
        scalars = (starts.astype(jnp.int32),) + scalars
    if window:
        scalars = (chunk_first_blocks(
            qs32, nq=nq, rchunk=rchunk, rep=rep, bs=bs,
            window=window).reshape(-1),) + scalars

    # bf16 operands go to the MXU as they are (int8 / int4 payloads are
    # exact in bf16); anything wider keeps float32 matmuls.
    narrow = quant or k_cache.dtype == jnp.bfloat16
    mm_dtype = jnp.bfloat16 if narrow and q.dtype == jnp.bfloat16 \
        else jnp.float32

    scratch_shapes = [
        *([pltpu.VMEM((2, gb * bs, dp), k_cache.dtype)] if latent else
          [pltpu.VMEM((2, gb * bs, kh, dp), k_cache.dtype),
           pltpu.VMEM((2, gb * bs, kh, dp), v_cache.dtype)]),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((kh, rchunk, dv), jnp.float32),
        pltpu.VMEM((kh, rchunk, 128), jnp.float32),
        pltpu.VMEM((kh, rchunk, 128), jnp.float32),
    ]
    # The queries and the output: a rectangle's are blocks of the pipeline,
    # [1, KH, rchunk, D] a grid step; the tokens stay in HBM like the cache
    # and a grid step copies its tile into scratch (the tile as it lies,
    # the slabs the walk reads, two semaphores).
    q_spec = pl.BlockSpec((1, kh, rchunk, d), qmap)
    if packed:
        q_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch_shapes += [
            pltpu.VMEM((tile,) + qs.shape[1:], q.dtype),
            pltpu.VMEM((1, kh, rchunk, d), q.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, nq),
        in_specs=[
            q_spec,
            # The cache stays where it is, [L, NB, BS, KH, Dp] in HBM: the
            # kernel copies the blocks the table names, a group at a time.
            *[pl.BlockSpec(memory_space=pl.ANY)] * (1 if latent else 2),
        ],
        out_specs=q_spec if packed or not latent else pl.BlockSpec(
            (1, kh, rchunk, dv), qmap),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, kh=kh, rep=rep, gb=gb, nq=nq,
                          nblk=nblk, quant=quant, int4=int4, mm_dtype=mm_dtype,
                          window=window, tile=tile, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            qs.shape if packed else (b, kh, r, dv), q.dtype),
        # The tokens' tiles overlap (a row's last one runs into the next
        # row's tokens) and are written over the queries they were read
        # from: the grid's steps run in order.
        input_output_aliases={len(scalars): 0} if packed else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(("arbitrary",) if packed
                                 else ("parallel",)) * 2,
            # Every address the kernel forms is in range by construction
            # (the module's docstring lists them), so the scalar core does
            # not check each copy's two ends before it issues it.
            disable_bounds_checks=True,
        ),
        interpret=interpret,
        # A name of its own in the device trace (the custom call would
        # otherwise take it from whatever scope encloses it).
        name="paged_attention",
    )(*scalars, qs, k_cache, *(() if latent else (v_cache,)))
    if packed:
        return lax.slice(out, (0, 0, 0), (n, h, dv))
    # [B, KH, T*REP, D] → [B, T, H, D]
    return out.reshape(b, kh, t, rep, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, dv)


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
    interpret: bool = False,
    window: int = 0,          # as paged_attention_kernel
    starts: jax.Array | None = None,  # as paged_attention_kernel: q is
    t: int | None = None,             #   [N, H, D], and so is the result
    scale: float | None = None,       # as paged_attention_kernel
) -> jax.Array:
    """TP-sharded paged attention: shard_map the kernel over the "model"
    (head) axis so each device runs the kernel on its local heads. Heads are
    fully parallel in attention, so no collective is needed — the psum for
    TP happens in the subsequent wo projection, inserted by GSPMD.

    Batch rides the "data" axis (size-1 no-op on pure-TP meshes). The
    token-major form has no batch axis to ride it: the caller keeps the
    rectangle where "data" splits the rows.
    """
    k_cache, v_cache, layer = _layer_stack(k_cache, v_cache, layer)
    cache_spec = P(None, None, None, "model", None)
    if isinstance(k_cache, dict):
        # Quantized cache pytree: payload sharded on kv_heads, scales on
        # their matching head axis — each shard dequantizes its own heads.
        # Packed-int4 payloads shard identically (packing is along D).
        cache_spec = {"q": cache_spec, "s": P(None, None, "model")}

    def local(q, k_cache, v_cache, block_tables, q_start, kv_lens, layer,
              *starts):
        return paged_attention_kernel(
            q, k_cache, v_cache, block_tables, q_start, kv_lens, layer=layer,
            interpret=interpret, window=window, t=t, scale=scale, **(
                {"starts": starts[0]} if starts else {}))

    q_spec = (P("data", None, "model", None) if starts is None
              else P(None, "model", None))
    rows = () if starts is None else (starts.astype(jnp.int32),)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            q_spec,
            cache_spec,
            cache_spec,
            P("data", None),
            P("data"),
            P("data"),
            P(),
        ) + (P(),) * len(rows),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, block_tables.astype(jnp.int32),
              q_start.astype(jnp.int32), kv_lens.astype(jnp.int32), layer,
              *rows)


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
