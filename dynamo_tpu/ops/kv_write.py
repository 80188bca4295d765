"""A packed step's keys and values into the paged cache, by blocks: one
Pallas call a layer whose scalar core issues HBM-to-HBM copies of runs of
consecutive slots, in place of one XLA scatter update a token.

``cache.at[layer, slot // BS, slot % BS].set(k)`` over a step's N tokens is N
updates of ``[KH, D]`` (2 KB in the 7B cut), and XLA's TPU scatter runs them
one after another, 0.13 us each: 138 us a layer for K and V at 520 tokens,
where this call takes 8 (tools/attn_bench.py ``write``; PERF.md, PR 60). The bytes are
block-shaped already: a row's live tokens lie end to end among the step's
tokens (``TokenLayout.starts``) at consecutive positions from ``q_start``
on, so their slots are runs of up to BS consecutive slots of one cache block
``[BS, KH, D]``, and a 512-token chunk is 32-34 block writes, not 512.

- One grid step: a loop over the B rows, inside it a loop over the cache
  blocks the row's positions touch (a grid step a row would cost more than
  a decode row's one copy). Row r owns the tokens ``starts[r] ..
  starts[r] + n`` (``n = kv_lens[r] - q_start[r]``) at positions
  ``q_start[r] ..``. ``k``, ``v`` ``[N, KH, D]`` and both pools
  ``[L, NB, BS, KH, D]`` stay in HBM (``pl.ANY``); the pools are aliased to
  the two results, so the call updates the carried buffers where they lie.
- A DMA's size is static, so a block's run of ``cnt`` tokens (BS for a
  whole block; fewer where a chunk starts or ends inside one, the usual
  case) goes as the copies of BS, BS/2, ... 1 tokens that ``cnt``'s bits
  name (``_pieces``: one conditional copy of K and of V a size, traced once
  for every block of every row; sizes past the step's N tokens are left
  out). A decode row beside a chunk is one copy of one token.
- Every copy signals ONE DMA semaphore by the bytes it moved, and the body
  waits once, after the last row's last copy is issued, for the bytes of all
  the rows' tokens: whole blocks' worth in a loop, the rest by its bits (a
  wait takes its count from its descriptor's shape: PERF.md, PR 53).
- A row with no live token and the bucket's padded tokens issue nothing: the
  trash block (block 0) is not written on this path.

**Every dynamic address the kernel forms, and why it is in range** (the call
is compiled with ``disable_bounds_checks``, as the attention kernel is):
(1) the per-row scalars ``starts[r]``, ``q_start[r]``, ``kv_lens[r]``: ``r``
is the row loop's index, ``< B``;
(2) the table read ``bt[r * NBLK + min(first + i, NBLK - 1)]``: ``first =
max(q_start[r], 0) // BS`` and ``i >= 0``, so the index lies in row r's own
``[0, NBLK)``; positions past the table's reach name its last block, as
``_positions_and_slots`` clips them;
(3) a copy's source ``k[src + at : src + at + p]``: a row's count ``n`` is
clamped into ``[0, N]`` and its first token ``src`` into ``[0, N - n]``;
inside a block ``at + p <= cnt`` and the blocks' counts sum to ``n``, so the
last token read is ``< src + n <= N``;
(4) a copy's target ``cache[layer, id, off + at : off + at + p]``: ``layer``
is clamped into ``[0, L - 1]`` by the wrapper, ``id`` into ``[0, NB - 1]``
after it is read (a table that names no block of the pool writes the nearest
one that exists, never past the pool), ``off < BS`` and ``cnt <= BS - off``,
so ``off + at + p <= BS``;
(5) the wait's descriptors ``cache[0, 0, 0 : p]``: static, ``p <= BS``.

The body is 121 equations whatever the shapes (``tests/test_kv_write.py``
holds the count: a kernel body's length is paid at every cold program
build).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


def _i32(x: int):
    return jnp.int32(x)


def _pieces(cnt, top: int, fn) -> None:
    """``fn(p, at)`` for each power of two ``p <= top`` among ``cnt``'s bits
    (``cnt < 2 top``), largest first, ``at`` the tokens the larger ones
    took."""
    p = top
    while p:
        at = lax.bitwise_and(cnt, _i32(~(2 * p - 1)))
        pl.when(lax.ne(lax.bitwise_and(cnt, _i32(p)), _i32(0)))(
            functools.partial(fn, p, at))
        p //= 2


def _kernel(ly_ref, ts_ref, qs_ref, kl_ref, bt_ref, k_hbm, v_hbm, _ck, _cv,
            ok_hbm, ov_hbm, sem, *, b: int, nblk: int):
    n_tok = k_hbm.shape[0]
    _, nb, bs, _, _ = ok_hbm.shape
    # (a run is at most a block and at most the step's tokens)
    top = 1 << (min(bs, n_tok).bit_length() - 1)
    layer = ly_ref[0]

    def row(r, total):
        pos = lax.max(qs_ref[r], _i32(0))
        n = lax.clamp(_i32(0), lax.sub(kl_ref[r], qs_ref[r]), _i32(n_tok))
        src = lax.clamp(_i32(0), ts_ref[r], lax.sub(_i32(n_tok), n))
        off0 = lax.rem(pos, _i32(bs))
        first = lax.add(lax.mul(r, _i32(nblk)), lax.div(pos, _i32(bs)))
        last = lax.add(lax.mul(r, _i32(nblk)), _i32(nblk - 1))
        # (n == 0: off0 + bs - 1 < 2 bs - 1, no block)
        blocks = lax.div(lax.add(lax.add(off0, n), _i32(bs - 1)), _i32(bs))
        blocks = lax.select(lax.gt(n, _i32(0)), blocks, _i32(0))

        def block(i, c):
            # The row's i-th block holds its tokens from ``done`` on, at
            # the block's offset ``off``: off0 in the first, 0 after it.
            done = lax.max(lax.sub(lax.mul(i, _i32(bs)), off0), _i32(0))
            off = lax.max(lax.sub(off0, lax.mul(i, _i32(bs))), _i32(0))
            cnt = lax.min(lax.sub(_i32(bs), off), lax.sub(n, done))
            blk = lax.clamp(_i32(0), bt_ref[lax.min(lax.add(first, i), last)],
                            _i32(nb - 1))
            tok = lax.add(src, done)

            def copy(p, at):
                here = pl.ds(lax.add(tok, at), p)
                there = pl.ds(lax.add(off, at), p)
                pltpu.make_async_copy(
                    k_hbm.at[here], ok_hbm.at[layer, blk, there], sem).start()
                pltpu.make_async_copy(
                    v_hbm.at[here], ov_hbm.at[layer, blk, there], sem).start()

            _pieces(cnt, top, copy)
            return c

        lax.fori_loop(_i32(0), blocks, block, 0)
        return lax.add(total, n)

    total = lax.fori_loop(0, b, row, _i32(0))

    def landed(p, _at=None):
        # K's and V's: the same bytes, one descriptor twice.
        some = ok_hbm.at[0, 0, pl.ds(0, p)]
        for _ in range(2):
            pltpu.make_async_copy(some, some, sem).wait()

    def whole(i, c):
        landed(bs)
        return c

    lax.fori_loop(_i32(0), lax.div(total, _i32(bs)), whole, 0)
    _pieces(lax.rem(total, _i32(bs)), bs // 2, landed)


def kv_write(k: jax.Array, v: jax.Array, cache_k: jax.Array,
             cache_v: jax.Array, block_tables: jax.Array, q_start: jax.Array,
             kv_lens: jax.Array, starts: jax.Array, *, layer,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Write a packed step's ``k`` and ``v`` ``[N, KH, D]`` into layer
    ``layer`` of the pools ``[L, NB, BS, KH, D]`` and return both pools: row
    r's ``kv_lens[r] - q_start[r]`` tokens, which lie from ``starts[r]`` on
    among the N, go to positions ``q_start[r] ..`` of the blocks
    ``block_tables[r]`` names. The live slots hold bit for bit what
    ``cache.at[layer, slot // BS, slot % BS].set`` leaves there; nothing
    else is written (no padded token, no trash block)."""
    n, kh, d = k.shape
    layers, nb, bs, ckh, cd = cache_k.shape
    if (ckh, cd) != (kh, d) or cache_v.shape != cache_k.shape \
            or v.shape != k.shape:
        raise ValueError(f"k/v {k.shape}/{v.shape} do not fit the pools "
                         f"{cache_k.shape}/{cache_v.shape}")
    if bs & (bs - 1):
        raise ValueError(f"block size {bs} is no power of two")
    b, nblk = block_tables.shape
    layer = lax.clamp(jnp.int32(0), jnp.asarray(layer, jnp.int32),
                      jnp.int32(layers - 1))
    scalars = (layer.reshape(1), starts.astype(jnp.int32),
               q_start.astype(jnp.int32), kv_lens.astype(jnp.int32),
               block_tables.astype(jnp.int32).reshape(-1))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, b=b, nblk=nblk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[hbm] * 4, out_specs=[hbm] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
                   jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype)],
        # operands count the prefetched scalars: the pools are the last two
        input_output_aliases={len(scalars) + 2: 0, len(scalars) + 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # every address is in range by construction (the docstring's
            # list), so no copy's two ends are checked before it is issued
            disable_bounds_checks=True),
        interpret=interpret,
        name="kv_write",
    )(*scalars, k.astype(cache_k.dtype), v.astype(cache_v.dtype),
      cache_k, cache_v))


def kv_write_sharded(mesh, k, v, cache_k, cache_v, block_tables, q_start,
                     kv_lens, starts, *, layer, interpret: bool = False):
    """:func:`kv_write` under "model": each shard writes its own KV heads
    into its own part of the pools, no collective (as
    ``paged_attention_sharded`` reads them)."""
    cache_spec = P(None, None, None, "model", None)
    tok_spec = P(None, "model", None)

    def local(k, v, ck, cv, bt, qs, kl, ts, layer):
        return kv_write(k, v, ck, cv, bt, qs, kl, ts, layer=layer,
                        interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, tok_spec, cache_spec, cache_spec, P(), P(), P(),
                  P(), P()),
        out_specs=(cache_spec, cache_spec), check_vma=False,
    )(k, v, cache_k, cache_v, block_tables, q_start, kv_lens, starts,
      jnp.asarray(layer, jnp.int32))
