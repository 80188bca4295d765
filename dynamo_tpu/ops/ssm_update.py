"""The one-token Mamba-2 recurrence over the state pool, as one Pallas kernel.

A decode row's state is 2 MB of float32 (``[H, P, N]`` = 64 x 64 x 128) that
a step reads, scales, adds an outer product to, reads out through ``C`` and
writes back: 5 FLOP a 8 bytes, nothing but its bytes. Written in
``jax.numpy`` the rows' states are gathered out of the pool (on a TPU a loop
of 2 MB copies), updated, and scattered back: three passes over them and a
tenth of the memory's bandwidth (PERF.md section 6, PR 45). Here the pool
stays where it is and the result goes back into the same buffer
(``input_output_aliases``).

    S' = a S + dx (x) B        y = S' C

per head, with ``a [B, H]`` the decay (0 for a row that starts from zeros),
``dx = dt x`` ``[B, H, P]``, ``B`` / ``C`` ``[B, G, N]`` shared by a group's
heads, all float32 and every product and sum in float32 on the vector unit.

**The grid moves the rows that have a one-token update and no other**
(PR 49). ``one [B]`` says which rows those are. Each of the B grid steps is
told a row and its slot by two prefetched lists (:func:`live_steps`): the
marked rows first, in their order, and past their count ``n``, which is
prefetched too, the last of them again. Step ``i < n`` takes the whole
slot-layer ``[H, P, N]`` of its slot by that index, as the paged-attention
kernel takes its blocks: one 2 MB block in and one out a step, large enough
for the copies to run at what the memory gives a read beside a write (72-76 %
of 819 GB/s on a v5e; the 256 KB blocks of a group of heads, one a grid step,
reached 50 %). A step ``i >= n`` names the block before it again: Pallas
fetches no block whose index did not change and writes none back, and the
body does not run, so a padded row or a chunk row costs a grid step's
~0.35 us and no byte. The trash row, which padded rows name, is neither read
nor written here. With no marked row at all, step 0 copies the one block it
was given back as it was, so the pool is left bit for bit. ``y`` of a row
that was not moved is 0.

Inside a step what varies along the state's sublanes (``P``) has to be
spread over its lanes (``N``): a group's ``dx``, and its ``a`` repeated
``P`` times, come in as rows ``[1, heads a group x P]``, are repeated down
128 sublanes (a load does that) and transposed, four 128 x 128 tiles each,
which gives every head's ``dx`` and ``a`` as lane-constant columns. (A lane
slice of a column tile broadcast over the lanes, one permute a vector
register, filled the cross-lane unit together with the sums over ``N`` and
held the kernel to 61-66 % where its copies alone reach 72-76: PERF.md
section 6, PR 49.) ``B`` and ``C`` are rows and broadcast over the sublanes.
``y`` is the sum over the lanes, a head a column of its group's
``[P, heads a group]`` tile.

The groups are a loop in the kernel and a group's eight heads are one block
``[hg, P, N]`` of a few equations, because **a step program lowers this
body once for every program it warms and set-up pays for each equation of
it**, ~3 ms an equation inside ``pl.when`` on the chip's host: all 64 heads
written out ran at the copies' time and made a cell's warm set-up 26 % longer,
eight written out in a loop 8-10 %; this form costs 6 us a call more than
either (``a`` takes the cross-lane unit too) and ~3 % of set-up (PERF.md
section 6, PR 49). The shapes are the published ones': ``N`` and ``heads a
group x P`` multiples of 128 on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: scoped VMEM the kernel asks for a 2 MiB slot-layer (``[64, 64, 128]``, the
#: shape it was written at): the slot-layer in and out, each twice (the
#: pipeline holds the next beside the current), are 8 MB; a group's spread
#: ``dx`` and ``a`` 256 KB each, the rest a few tiles. A larger slot-layer
#: asks for as many times more (:func:`vmem_limit_bytes`).
VMEM_LIMIT_BYTES = 16 * 2**20


def vmem_limit_bytes(heads: int, p: int, n: int) -> int:
    """The scoped VMEM for a slot-layer ``[heads, p, n]`` of float32: what
    the 2 MiB one is given, times the slot-layer's size over 2 MiB. At
    ``[32, 128, 256]`` (4 MiB: in and out twice are 16 MiB, a group's 16
    heads of temporaries 2 MiB each) that is 32 MiB of the v5e's 128."""
    return VMEM_LIMIT_BYTES * max(1, heads * p * n * 4 // (2 * 2**20))


def _kernel(layer_ref, slot_ref, row_ref, n_ref, s_ref, a_ref, dx_ref, b_ref,
            c_ref, o_ref, y_ref):
    del layer_ref, slot_ref, row_ref
    heads, p, n_state = s_ref.shape
    groups = b_ref.shape[0]
    hg = heads // groups

    def spread(ref, g):
        """A group's row ``[1, hg x P]`` down the sublanes and transposed:
        what varies along ``P`` constant over the lanes, ``[hg, P, N]``."""
        return jnp.broadcast_to(ref[g], (n_state, hg * p)).T.reshape(
            hg, p, n_state)

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        def group(g, carry):
            at = pl.ds(g * hg, hg)
            s1 = spread(a_ref, g) * s_ref[at] + spread(dx_ref, g) * b_ref[g]
            o_ref[at] = s1
            y = jnp.sum(s1 * c_ref[g], axis=2, keepdims=True)    # [hg, P, 1]
            for j in range(hg):
                y_ref[g, :, j:j + 1] = y[j]
            return carry

        lax.fori_loop(0, groups, group, 0)

    @pl.when((n_ref[0] == 0) & (pl.program_id(0) == 0))
    def _():
        o_ref[...] = s_ref[...]


def live_steps(one, slots):
    """What each of the B grid steps names: (the row ``[B]``, its slot
    ``[B]``, how many rows ``one [B]`` marks). The marked rows come first, in
    their order; a step past them names the last of them again (row 0 where
    there is none). By ranks and one-hot sums, not by a sort and gathers:
    ``[B, B]`` comparisons fuse into the step, those are operations of their
    own."""
    b = one.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    key = jnp.where(one, idx, idx + b)
    place = jnp.sum(key[None, :] < key[:, None], axis=1)     # each row's rank
    count = jnp.sum(one, dtype=jnp.int32)
    want = jnp.minimum(idx, jnp.maximum(count - 1, 0))       # each step's rank
    hit = place[:, None] == want[None, :]                    # [row, step]

    def pick(v):
        return jnp.sum(jnp.where(hit, v[:, None], 0), axis=0, dtype=jnp.int32)

    return pick(idx), pick(slots.astype(jnp.int32)), count


@functools.partial(jax.jit, static_argnames=("interpret",))
def update_rows(state, layer, slots, one, a, dx, bm, cm, *,
                interpret: bool = False):
    """``state [M, S, H, P, N]`` float32, the pool, updated in place at
    ``(layer, slots[b])`` for each of the B rows that ``one [B]`` marks and
    left as it is everywhere else; ``a [B, H]``, ``dx [B, H, P]``, ``bm`` /
    ``cm [B, G, N]``, all float32. Returns (the pool, ``y [B, H, P]``
    float32, 0 for a row that ``one`` does not mark)."""
    _m, _s, h, p, n = state.shape
    b, g = bm.shape[:2]
    hg = h // g
    rows, row_slots, count = live_steps(one, slots)

    def slot(i, ly, sl, rw, cnt):
        return (ly[0], sl[i], 0, 0, 0)

    def row(i, ly, sl, rw, cnt):
        return (rw[i], 0, 0, 0)

    def rows_of(v):         # [B, H, P] -> [B, G, 1, hg x P]: a group a row
        return v.reshape(b, g, 1, hg * p)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, None, h, p, n), slot),
            pl.BlockSpec((None, g, 1, hg * p), row),
            pl.BlockSpec((None, g, 1, hg * p), row),
            pl.BlockSpec((None, g, 1, n), row),
            pl.BlockSpec((None, g, 1, n), row),
        ],
        out_specs=[
            pl.BlockSpec((None, None, h, p, n), slot),
            pl.BlockSpec((None, g, p, hg), row),
        ],
    )
    state, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, g, p, hg), jnp.float32)],
        # operands count the four prefetched scalars: the pool is the fifth
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes(h, p, n)),
        interpret=interpret,
        name="ssm_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row_slots, rows,
      count.reshape(1), state,
      rows_of(jnp.broadcast_to(a[:, :, None], (b, h, p))), rows_of(dx),
      bm[:, :, None, :], cm[:, :, None, :])
    # (a row that was not moved has no block of ``y`` written)
    y = y.transpose(0, 1, 3, 2).reshape(b, h, p)
    return state, jnp.where(one[:, None, None], y, 0.0)
