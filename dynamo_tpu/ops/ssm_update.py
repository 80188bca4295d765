"""The one-token Mamba-2 recurrence over the state pool, as one Pallas kernel.

A decode row's state is 2 MB of float32 (``[H, P, N]`` = 64 x 64 x 128) that
a step reads, scales, adds an outer product to, reads out through ``C`` and
writes back: 5 FLOP a 8 bytes, nothing but its bytes. Written in
``jax.numpy`` the rows' states are gathered out of the pool (on a TPU a loop
of 2 MB copies), updated, and scattered back: three passes over them and a
tenth of the memory's bandwidth (PERF.md section 6, PR 45). Here the pool
stays where it is: a grid over (row, group of heads) takes each block
``[heads a group, P, N]`` of row ``slots[b]`` of layer ``layer`` by a
scalar-prefetched index, as the paged-attention kernel takes its blocks,
and the result goes back into the same buffer (``input_output_aliases``).

    S' = a S + dx (x) B        y = S' C

per head, with ``a [B, H]`` the decay (0 for a row that starts from zeros,
1 for a row this call must leave alone), ``dx = dt x`` ``[B, H, P]``,
``B`` / ``C`` ``[B, G, N]`` shared by a group's heads. What varies along the
state's sublanes (``P``) comes in as columns, ``[B, G, P, heads a group]``:
a head's column is a lane slice and broadcasts over the lanes (``N``);
``B`` and ``C`` are rows and broadcast over the sublanes. ``y`` leaves in
the same column layout.

Padded rows name the trash row, several at once: they read and write one
block in no order, with ``a = 1`` and ``dx = 0``, so it keeps what it held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: scoped VMEM the kernel asks for: a block in and out, each twice (the
#: pipeline holds the next beside the current), are 1 MB at the published
#: shape; the columns and rows beside them a few tiles.
VMEM_LIMIT_BYTES = 16 * 2**20


def _kernel(layer_ref, slots_ref, s_ref, a_ref, dx_ref, b_ref, c_ref,
            o_ref, y_ref):
    del layer_ref, slots_ref
    heads = s_ref.shape[0]
    b_row = b_ref[...]                                   # [1, N]
    c_row = c_ref[...]
    a_cols = a_ref[...]                                  # [P, heads]
    dx_cols = dx_ref[...]
    for j in range(heads):
        s1 = a_cols[:, j:j + 1] * s_ref[j] + dx_cols[:, j:j + 1] * b_row
        o_ref[j] = s1
        y_ref[:, j:j + 1] = jnp.sum(s1 * c_row, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def update_rows(state, layer, slots, a, dx, bm, cm, *, interpret: bool = False):
    """``state [M, S, H, P, N]`` float32, the pool, updated in place at
    ``(layer, slots[b])`` for each of the B rows; ``a [B, H]``,
    ``dx [B, H, P]``, ``bm`` / ``cm [B, G, N]``, all float32. Returns
    (the pool, ``y [B, H, P]`` float32)."""
    _m, _s, h, p, n = state.shape
    b, g = bm.shape[:2]
    hg = h // g

    def cols(v):            # [B, H, P] -> [B, G, P, hg]: a head a lane
        return v.reshape(b, g, hg, p).transpose(0, 1, 3, 2)

    a_cols = cols(jnp.broadcast_to(a[:, :, None], (b, h, p)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, g),
        in_specs=[
            pl.BlockSpec((None, None, hg, p, n),
                         lambda i, j, ly, sl: (ly[0], sl[i], j, 0, 0)),
            pl.BlockSpec((None, None, p, hg), lambda i, j, ly, sl: (i, j, 0, 0)),
            pl.BlockSpec((None, None, p, hg), lambda i, j, ly, sl: (i, j, 0, 0)),
            pl.BlockSpec((None, None, 1, n), lambda i, j, ly, sl: (i, j, 0, 0)),
            pl.BlockSpec((None, None, 1, n), lambda i, j, ly, sl: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hg, p, n),
                         lambda i, j, ly, sl: (ly[0], sl[i], j, 0, 0)),
            pl.BlockSpec((None, None, p, hg), lambda i, j, ly, sl: (i, j, 0, 0)),
        ],
    )
    state, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, g, p, hg), jnp.float32)],
        # operands count the two prefetched scalars: the pool is the third
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      state, a_cols, cols(dx), bm[:, :, None, :], cm[:, :, None, :])
    return state, y.transpose(0, 1, 3, 2).reshape(b, h, p)
