"""Pallas TPU kernel for the routed experts of a decode-sized step: the
touched experts' matrices (three, or two where an expert has no gate)
streamed once each, where they lie.

The grouped form (models/moe.py ``held_rows``: sort the (token, choice)
rows by expert, gather them, three ``lax.ragged_dot`` calls, scatter back)
pays by the group. A decode step has a few dozen rows in groups of one to
three over a ``[L x E]`` operand that is mostly empty groups, and XLA's
grouped matmul then reads its weights at 52-65 % of the chip's bandwidth
(PERF.md section 6, PR 41 and 44). This kernel computes ALL N rows against
every touched expert instead: no row is sorted, gathered or scattered, and
an expert costs its bytes.

Design notes (in the idiom of ops/paged_attention.py):
- outside the kernel, in XLA and tiny (:func:`stream_plan`): the combine
  matrix ``c[E_held, N]`` float32 (token n's weight for expert e; 0 where n
  did not choose e, is padding, or e is held elsewhere), the touched
  experts' ids compacted to the front of a static length
  ``G = min(N x k, E_held)``, and their number. No argsort of N x k keys.
- grid = (G,), sequential. The layer index, the ids and their number ride
  the scalar-prefetch channel; the block of ``w_gate`` / ``w_up`` /
  ``w_down`` at step g is ``(layer, ids[g])`` of the ``[L, E_held, ...]``
  stack as the parameters hold it, so no slab of a stack is cut or copied
  and the pipeline has expert g + 1 landing while g computes. Past the last
  touched expert the ids repeat it: a block whose index does not change is
  not fetched again, and the step does nothing.
- a live step is ``gate = x W_gate``, ``up = x W_up`` (float32 results),
  ``act(gate) * up`` rounded once to the weights' dtype as the down
  product's operand, ``x' W_down`` in float32, and
  ``out += c[ids[g]][:, None] * that`` into the ``[N, H]`` float32 output,
  whose block is the same at every step and so stays in VMEM over the
  grid. A row that did not choose the expert has the factor 0 and is
  selected away, not multiplied: what an expert makes of a row that is not
  its own need not be finite.
- precision: nothing is rounded that the grouped form does not round
  (``lax.ragged_dot`` hands gate, up and out back in the operands' dtype;
  here they stay float32 up to the one cast before the down product).
- VMEM: two of each of an expert's three matrices (the pipeline's double
  buffer) and the rows' blocks, :func:`vmem_bytes`; the kernel asks for
  ``VMEM_LIMIT_BYTES`` or, where an expert needs more, for what it needs, up
  to ``VMEM_MAX_BYTES``. An expert that does not fit so (K-EXAONE's 75.5 MB)
  is not this kernel's: models/moe.py ``streams_experts`` keeps it on the
  grouped form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The VMEM the kernel asks for (``vmem_limit_bytes``): 32 MiB of the v5e's
#: 128, twice the 16 MiB a kernel gets unasked.
VMEM_LIMIT_BYTES = 32 << 20
#: The most it asks for where an expert needs more than that: half of the
#: v5e's VMEM. An expert of two matrices ``[2688, 1920]`` is 20.6 MB, 42 MiB
#: with the pipeline's second buffer; the ask is then what it needs.
VMEM_MAX_BYTES = 64 << 20


def vmem_bytes(n: int, h: int, m: int, itemsize: int,
               matrices: int = 3) -> int:
    """VMEM the kernel holds at ``n`` rows of width ``h`` against experts
    of width ``m`` and ``matrices`` matrices (3: gate, up, down; 2: no
    gate): two of each block the pipeline moves (an expert's matrices, the
    rows, their float32 result, a column of the combine matrix padded to a
    lane tile) and the float32 values of one step (gate, up, their product;
    the down product, weighted, selected). An
    upper bound: the compiler took 23.41 MiB of these 23.6 at 16 rows of
    SmallThinker's (tests/test_ops.py compiles with no more than this)."""
    rows = -(-n // 16) * 16
    blocks = (matrices * h * m * itemsize
              + rows * (h * itemsize + h * 4 + 128 * 4))
    return 2 * blocks + rows * (3 * m + 3 * h) * 4


def vmem_ask(n: int, h: int, m: int, itemsize: int,
             matrices: int = 3) -> int:
    """What the kernel asks for (``vmem_limit_bytes``) where an expert needs
    more than ``VMEM_LIMIT_BYTES``: :func:`vmem_bytes`, and for a gated
    expert a thirty-second more. At 16 rows against gated experts of 1,024
    columns and up the compiler stages the two first products beside each
    other and takes 0.05-0.5 MiB over the arithmetic above (``[2048,
    1536]``: 37.54 MiB of 37.05, compiled for the described v5e, PR 61);
    an expert without a gate compiles inside it (``[2688, 1920]``)."""
    need = vmem_bytes(n, h, m, itemsize, matrices)
    return need + need // 32 if matrices == 3 else need


def stream_plan(topi, weights, live, held: int):
    """What the kernel walks, from a routing: (``c`` [held, N] float32, the
    combine matrix; ``ids`` [G] int32, the touched experts in ascending
    order, then the last of them again; ``n_touched`` int32 [1]; ``counts``
    int32 [3]: rows, experts touched, rows of the largest group, as
    ``held_rows`` counts them). ``topi`` [N, k] over the held range
    ``0 .. held - 1`` (what falls outside is held elsewhere), ``live`` [N]
    bool or None."""
    n, k = topi.shape
    here = (topi >= 0) & (topi < held)
    if live is not None:
        here = here & live[:, None]
    experts = jnp.arange(held, dtype=jnp.int32)
    hot = here[:, :, None] & (topi[:, :, None] == experts)        # [N, k, E]
    c = jnp.sum(jnp.where(hot, weights.astype(jnp.float32)[:, :, None], 0.0),
                axis=1).T                                         # [E, N]
    sizes = jnp.sum(hot, axis=(0, 1), dtype=jnp.int32)            # [E]
    touched = sizes > 0
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    # The (g+1)-th touched expert is the first whose running count passes
    # g: as many experts as have a count of at most g come before it.
    cum = jnp.cumsum(touched.astype(jnp.int32))
    g = jnp.arange(min(n * k, held), dtype=jnp.int32)
    ids = jnp.sum(cum[None, :] <= g[:, None], axis=1, dtype=jnp.int32)
    # (past the last touched expert the sum is ``held``: the last again)
    ids = jnp.minimum(ids, jnp.max(jnp.where(touched, experts, 0)))
    counts = jnp.stack([jnp.sum(sizes), n_touched, jnp.max(sizes)])
    return c, ids, n_touched.reshape(1), counts


def _kernel(ly_ref, ids_ref, nt_ref, x_ref, c_ref, *refs, act):
    """``refs``: an expert's matrices, ``w_gate``, ``w_up`` and ``w_down``
    or, for an expert without a gate, ``w_up`` and ``w_down``; then the
    output."""
    del ly_ref, ids_ref       # the index maps read them
    *w_refs, o_ref = refs
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(g < nt_ref[0])
    def _expert():
        x = x_ref[...]
        *first, wd_ref = w_refs
        if len(first) == 2:
            gate = jnp.dot(x, first[0][...], preferred_element_type=jnp.float32)
            up = jnp.dot(x, first[1][...], preferred_element_type=jnp.float32)
            hidden = act(gate) * up
        else:
            hidden = act(jnp.dot(x, first[0][...],
                                 preferred_element_type=jnp.float32))
        out = jnp.dot(hidden.astype(wd_ref.dtype), wd_ref[...],
                      preferred_element_type=jnp.float32)         # [N, H]
        w = c_ref[...]                                            # [N, 1]
        o_ref[...] += jnp.where(w != 0.0, w * out, 0.0)


def stream_rows(xt, topi, weights, w_gate, w_up, w_down, live=None,
                layer=None, act=jax.nn.silu, *, interpret: bool = False):
    """``held_rows`` (models/moe.py: the same arguments, the same results)
    by the kernel: ([N, H] float32, int32 [3] counts). ``w_*`` are one
    layer's slabs ``[E_held, H|M, M|H]`` or, with ``layer`` (an index, may
    be traced), the whole stack ``[L, E_held, ...]``, of which the kernel
    reads the touched experts of that layer and nothing else."""
    if layer is None:
        w_gate, w_up, w_down = (w if w is None else w[None]
                                for w in (w_gate, w_up, w_down))
        layer = 0
    n, h = xt.shape
    _, held, _, m = w_up.shape
    mats = [w for w in (w_gate, w_up, w_down) if w is not None]
    c, ids, n_touched, counts = stream_plan(topi, weights, live, held)

    def rows(g, *_prefetch):
        return (0, 0)

    def expert(g, ly, ids, nt):
        return (ly[0], ids[g], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ids.shape[0],),
        in_specs=[
            pl.BlockSpec((n, h), rows),
            pl.BlockSpec((None, n, 1), lambda g, ly, ids, nt: (ids[g], 0, 0)),
            *[pl.BlockSpec((None, None, *w.shape[2:]), expert) for w in mats],
        ],
        out_specs=pl.BlockSpec((n, h), rows),
    )
    y = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(VMEM_LIMIT_BYTES, vmem_ask(
                n, h, m, w_up.dtype.itemsize, len(mats))),
        ),
        interpret=interpret,
        name="moe_stream",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n_touched,
      xt.astype(w_up.dtype), c[:, :, None], *mats)
    return y, counts
