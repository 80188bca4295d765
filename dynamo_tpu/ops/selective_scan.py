"""The Mamba-1 recurrence over the state pool, as one Pallas kernel.

    S_t[j, c] = exp(dt_t[c] A[j, c]) S_{t-1}[j, c] + dt_t[c] B_t[j] x_t[c]
    y_t[c]    = sum_j C_t[j] S_t[j, c] + D[c] x_t[c]

The decay differs by channel ``c`` and by state index ``j``, so the scan has
no matmul form (models/mamba.py ``_scan_blocks`` is Mamba-2's, one scalar a
head): it is elementwise work on a row's ``[N, d]`` state, one position
after the other, and at ``d`` 5,120 and ``N`` 16 the state is 327,680 bytes
of float32 that a decode row reads and writes for 5 operations and one
exponential an element. A decode step is its bytes.

**The channels lie on the lanes and the sublanes, the state index on
neither.** The pool keeps a slot-layer as ``[N, d / 128, 128]``; ``x``,
``dt`` and ``y`` are ``[tokens, d / 128, 128]``. A position's update is then
``N`` times the same few whole-register operations on ``[d / 128, 128]``
tiles, ``B_t[j]`` and ``C_t[j]`` rows of 128 equal lanes that a load spreads
down the sublanes, and ``y`` a running sum of registers: no operation crosses
a lane or a sublane, which is what held ops/ssm_update.py's first forms to
two thirds of their copies' time (PERF.md section 6, PR 49). It is also
what lets a row's positions be cut from the token-major arrays where they
lie: the token axis is no tiled dimension, so a copy may start at any token.

**The grid is (row, tile of the channels)**, and it moves the rows that
have live tokens and no other, as ops/ssm_update.py's does
(:func:`~dynamo_tpu.ops.ssm_update.live_steps`: the live rows first, a step
past their count names the last block again, which Pallas neither fetches
nor writes back, and the body does not run: a padded row costs a grid step
and no byte, and the trash row it names is never touched). A live step takes
the row's ``[N, tile]`` state by its slot as a block of the pipeline, zeros
in its place where the row starts a sequence (``q_start`` 0), and keeps it
in VMEM while a loop runs the row's positions: ``q_len`` of them, 1 in a
decode row and up to the chunk in a chunk row, ``BLOCK`` at a time, each
block's ``x``, ``dt``, ``B`` and ``C`` copied from the tokens behind the
row's first one and its ``y`` copied back there. A block's last positions
may be the next row's tokens or padding: they are read, not computed, and
their ``y`` is written as zeros, which the next row, later in the grid,
writes over with its own (the grid's steps run in order). The state goes
back where it came from (``input_output_aliases``).

``exp(dt A)`` is formed here from ``dt [tile]`` and ``A [N, tile]``. Every
product and sum is float32 on the vector unit; ``y`` leaves as the model's
type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.ssm_update import live_steps

LANES = 128

#: positions a copy brings of a row of several (a chunk program); a program
#: of one-token rows copies one
BLOCK = 8

#: scoped VMEM the kernel asks for. At ``[16, 5120]`` and one tile: the
#: state in and out, each twice (the pipeline holds the next beside the
#: current), 1.25 MiB; ``A`` twice 0.63 MiB; a block of ``x``, ``dt`` (float32)
#: and ``y`` 0.4 MiB, of ``B`` and ``C`` 0.13 MiB: under 3 MiB of the 16 asked.
VMEM_LIMIT_BYTES = 16 * 2**20


def state_shape(n_state: int, d: int) -> tuple[int, int, int]:
    """A slot-layer of the pool: ``[N, d / 128, 128]``."""
    if d % LANES:
        raise ValueError(f"mamba_inner {d} is no multiple of {LANES}: the "
                         "selective scan keeps the channels on whole lanes")
    return (n_state, d // LANES, LANES)


def _kernel(layer_ref, slot_ref, row_ref, n_ref, start_ref, len_ref,
            fresh_ref, s_ref, a_ref, d_ref, x_hbm, dt_hbm, b_hbm, c_hbm,
            _y_in, o_ref, y_hbm, xb, dtb, bb, cb, yb, sems, *, block: int):
    del layer_ref, slot_ref, _y_in
    n_state, tg, _ = s_ref.shape
    i, c = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        r = row_ref[i]
        first, count = start_ref[r], len_ref[r]
        o_ref[...] = jnp.where(fresh_ref[r] != 0, 0.0, s_ref[...])
        tile = pl.ds(c * tg, tg)

        def run_block(j, carry):
            at = first + j * block
            src = pl.ds(at, block)
            loads = [
                pltpu.make_async_copy(x_hbm.at[src, tile], xb, sems.at[0]),
                pltpu.make_async_copy(dt_hbm.at[src, tile], dtb, sems.at[1]),
                pltpu.make_async_copy(b_hbm.at[src], bb, sems.at[2]),
                pltpu.make_async_copy(c_hbm.at[src], cb, sems.at[3]),
            ]
            for cp in loads:
                cp.start()
            yb[...] = jnp.zeros_like(yb)
            for cp in loads:
                cp.wait()

            def position(p, carry):
                xv, dtv = xb[p], dtb[p]                     # [tg, 128]
                dx = dtv * xv
                y = d_ref[...] * xv
                for k in range(n_state):
                    s1 = (jnp.exp(dtv * a_ref[k]) * o_ref[k]
                          + dx * bb[p, pl.ds(k, 1), :])
                    o_ref[k] = s1
                    y = y + cb[p, pl.ds(k, 1), :] * s1
                yb[p] = y.astype(yb.dtype)
                return carry

            lax.fori_loop(0, jnp.minimum(block, count - j * block), position, 0)
            out = pltpu.make_async_copy(yb, y_hbm.at[src, tile], sems.at[4])
            out.start()
            out.wait()
            return carry

        lax.fori_loop(0, (count + block - 1) // block, run_block, 0)

    @pl.when((n_ref[0] == 0) & (i == 0))
    def _():
        o_ref[...] = s_ref[...]


def scan_reference(state, x, dt, bm, cm, neg_a, d_skip):
    """The recurrence itself in ``jax.numpy``, float32, over a rectangle:
    ``state [B, N, d]``, ``x`` / ``dt [B, T, d]`` (``dt`` 0 at a padded
    position: the identity), ``bm`` / ``cm [B, T, N]``, ``neg_a [N, d]``,
    ``d_skip [d]``. Returns (the state after position T - 1, y [B, T, d])."""
    def step(s, xs):
        xt, dtt, bt, ct = xs                       # [B, d] [B, d] [B, N] [B, N]
        s = (jnp.exp(dtt[:, None, :] * neg_a) * s
             + (dtt * xt)[:, None, :] * bt[:, :, None])
        return s, jnp.einsum("bn,bnd->bd", ct, s) + d_skip * xt

    state, ys = lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return state, jnp.moveaxis(ys, 0, 1)


def _scan_jnp(state, layer, slots, starts, q_len, fresh, x, dt, bm, cm, neg_a,
              d_skip, t: int, out_dtype):
    """:func:`selective_scan` without the kernel: the rows' ``[B, T]``
    rectangle gathered from the tokens, :func:`scan_reference`, and the live
    positions' ``y`` scattered back. What the CPU runs."""
    n, d = x.shape
    at = starts[:, None] + jnp.arange(t)[None, :]                  # [B, T]
    inside = jnp.arange(t)[None, :] < q_len[:, None]
    idx = jnp.clip(at, 0, n - 1)
    old = state[layer, slots]                                      # [B, N, g, 128]
    s0 = jnp.where(fresh[:, None, None], 0.0, old.reshape(len(slots), -1, d))
    s1, y = scan_reference(
        s0, x[idx], jnp.where(inside[..., None], dt[idx], 0.0), bm[idx],
        cm[idx], neg_a, d_skip)
    # (a row without live tokens, a padded one, keeps what its slot holds)
    state = state.at[layer, slots].set(jnp.where(
        (q_len > 0)[:, None, None, None], s1.reshape(old.shape), old))
    y_out = jnp.zeros((n, d), out_dtype).at[
        jnp.where(inside, at, n).reshape(-1)].set(
            y.reshape(-1, d).astype(out_dtype), mode="drop")
    return state, y_out


@functools.partial(jax.jit, static_argnames=("t", "impl", "tiles", "out_dtype"))
def selective_scan(state, layer, slots, starts, q_len, fresh, x, dt, bm, cm,
                   a_log, d_skip, *, t: int, impl: str = "pallas",
                   tiles: int = 1, out_dtype=jnp.bfloat16):
    """``state [M, S, N, d / 128, 128]`` float32, the pool, updated in place
    at ``(layer, slots[b])`` for each of the B rows with ``q_len[b] > 0`` and
    left as it is everywhere else. The tokens are the step's, row after row:
    ``x [tokens, d]`` (the convolution's output), ``dt [tokens, d]`` float32
    (after the softplus), ``bm`` / ``cm [tokens, N]``; row ``b``'s
    ``q_len[b]`` positions start at token ``starts[b]`` and ``t`` (static)
    bounds them; ``fresh [B]`` marks a row that starts from zeros. ``a_log
    [N, d]``, ``d_skip [d]`` float32. Returns (the pool, ``y [tokens, d]``
    of ``out_dtype``, zeros at a token of no row). ``impl`` "jnp": no kernel
    (the CPU's form); "pallas_interpret": the kernel, interpreted."""
    n, d = x.shape
    n_state = a_log.shape[0]
    neg_a = -jnp.exp(a_log.astype(jnp.float32))
    f32 = jnp.float32
    if impl == "jnp":
        return _scan_jnp(state, layer, slots, starts, q_len, fresh,
                         x.astype(f32), dt.astype(f32), bm.astype(f32),
                         cm.astype(f32), neg_a, d_skip.astype(f32), t,
                         out_dtype)
    b = slots.shape[0]
    g = d // LANES
    if g % tiles:
        raise ValueError(f"{tiles} tiles do not divide {g} groups of lanes")
    tg = g // tiles
    block = 1 if t == 1 else BLOCK
    rows, row_slots, count = live_steps(q_len > 0, slots)

    def tokens(v, width):          # [tokens, ...] -> [tokens + block, width, 128]
        return jnp.pad(v.astype(f32), ((0, block), (0, 0))).reshape(
            n + block, width, -1)

    def lanes(v):                  # [tokens, N] -> [tokens + block, N, 128]
        return jnp.broadcast_to(
            jnp.pad(v.astype(f32), ((0, block), (0, 0)))[:, :, None],
            (n + block, n_state, LANES))

    def slot(i, c, ly, sl, rw, cnt, *_):
        # (a step past the live rows names the last block again)
        return (ly[0], sl[i], 0, jnp.where(i < cnt[0], c, tiles - 1), 0)

    def whole(i, c, *_):
        return (0, c, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(b, tiles),
        in_specs=[
            pl.BlockSpec((None, None, n_state, tg, LANES), slot),
            pl.BlockSpec((n_state, tg, LANES), whole),
            pl.BlockSpec((tg, LANES), lambda i, c, *_: (c, 0)),
            hbm, hbm, hbm, hbm, hbm,
        ],
        out_specs=[pl.BlockSpec((None, None, n_state, tg, LANES), slot), hbm],
        scratch_shapes=[
            pltpu.VMEM((block, tg, LANES), f32),
            pltpu.VMEM((block, tg, LANES), f32),
            pltpu.VMEM((block, n_state, LANES), f32),
            pltpu.VMEM((block, n_state, LANES), f32),
            pltpu.VMEM((block, tg, LANES), out_dtype),
            pltpu.SemaphoreType.DMA((5,)),
        ],
    )
    state, y = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n + block, g, LANES), out_dtype)],
        # operands count the seven prefetched scalars: the pool is the
        # eighth, and ``y`` starts as the zeros handed in behind C
        input_output_aliases={7: 0, 14: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=impl == "pallas_interpret",
        name="selective_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row_slots, rows,
      count.reshape(1), starts.astype(jnp.int32), q_len.astype(jnp.int32),
      fresh.astype(jnp.int32), state, neg_a.reshape(n_state, g, LANES),
      d_skip.astype(f32).reshape(g, LANES), tokens(x, g), tokens(dt, g),
      lanes(bm), lanes(cm), jnp.zeros((n + block, g, LANES), out_dtype))
    return state, y[:n].reshape(n, d)
