"""The Mamba-2 mixer over a pool of per-sequence state.

A second kind of cache beside the paged K and V (engine/cache.py): a
sequence's recurrent state does not grow with its length, so it is no list
of blocks but one row of a pool, ``{"state": float32 [M, slots + 1, H, P,
N], "conv": [M, slots + 1, (K - 1) x C]}`` for the model's M Mamba layers, named
by the slot the sequence holds from admission to its end (the sampling
state's slot, engine/scheduler.py ``Seq.slot``); the last row is the trash
row that a padded row of a step names (its convolution tail is written
there; the one-token update's kernel moves no state for it). The pool is
carried through the step and donated like K and V: a layer gathers its
rows' states ``[B, ...]``, and writes them back with one scatter on the
buffer itself. Nothing else in a step program has the pool's shape.

A row whose ``q_start`` is 0 starts from zeros: the program decides that,
no host call clears a slot, and a sequence that is preempted and recomputed
from its first token is right by construction.

The mixer (``transformers``' ``modeling_nemotron_h.py``; d = H x P heads
times head size, G groups of state size N, c = d + 2 G N, kernel K):

    [z | xBC | dt] = u W_in                       widths d, c, H
    xBC_t <- silu(b + sum_k w[k] * xBC_{t-K+1+k})  depthwise, causal
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt_t = softplus(dt_t + dt_bias); a_t = exp(dt_t A), A = -exp(A_log)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t[g(h)];  y_t = S_t C_t[g(h)] + D x_t
    y <- RMSNorm_groups(y * silu(z)) * w;  out = y W_out

In a step the two projections, the convolution, the gate and its norm run
over the ``[N, H]`` live tokens like every other matmul; the recurrence runs
over rows, a padded position made the identity by ``dt = 0`` (``a = 1``,
nothing added). A row of one token is the one-token recurrence, every such
row at once and the pool updated in place by one kernel
(ops/ssm_update.py); a row of several, a chunk of a prompt, the blocked form
(:func:`_scan_blocks`), one row at a time, which starts from the row's stored
state and leaves the state after the row's last live token, so a long prompt
carries its state from chunk to chunk.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.obs.profiler import phase

#: the leaves of a Mamba layer under ``params["layers"]``, stacked ``[M, ...]``
LEAVES = ("ssm_norm", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
          "ssm_A_log", "ssm_D", "ssm_gate_norm", "ssm_out")
#: a Mamba-1 layer's (:func:`mixer1`): no norm on the gated output; a
#: LayerNorm's bias, the projection to delta | B | C and delta's to dt
LEAVES1 = ("ssm_norm", "ssm_norm_b", "ssm_in", "ssm_conv_w", "ssm_conv_b",
           "ssm_x", "ssm_dt", "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_out")


def state_shapes(cfg: ModelConfig, slots: int) -> dict[str, jax.ShapeDtypeStruct]:
    """Shape and type of the state pool's two leaves for ``slots``
    sequences (and a trash row), with nothing allocated."""
    m = cfg.layers_of("M") + cfg.layers_of("S")
    if cfg.mamba_inner:     # Mamba-1: [N, d] a slot-layer, d on the lanes
        from dynamo_tpu.ops.selective_scan import state_shape

        own = state_shape(cfg.ssm_state_size, cfg.mamba_inner)
    else:
        own = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
    return {
        "state": jax.ShapeDtypeStruct(
            (m, slots + 1, *own), jnp.dtype(cfg.ssm_state_dtype)),
        # a row's K - 1 last inputs, [K - 1, C] flattened: with K - 1 = 3
        # as a dimension of its own the chip keeps the pool in another
        # order than the program and converts all of it, both ways, a step
        "conv": jax.ShapeDtypeStruct(
            (m, slots + 1, (cfg.conv_kernel - 1) * cfg.ssm_conv_dim),
            jnp.dtype(cfg.dtype)),
    }


def zeros_state(cfg: ModelConfig, slots: int) -> dict[str, jax.Array]:
    return {k: jnp.zeros(s.shape, s.dtype)
            for k, s in state_shapes(cfg, slots).items()}


def state_bytes(cfg: ModelConfig, slots: int) -> int:
    """Bytes of the pool for ``slots`` sequences and the trash row."""
    return sum(math.prod(s.shape) * s.dtype.itemsize
               for s in state_shapes(cfg, slots).values())


def slot_layer_bytes(cfg: ModelConfig) -> int:
    """Bytes of one sequence's state in one Mamba layer (both leaves)."""
    return sum(math.prod(s.shape[2:]) * s.dtype.itemsize
               for s in state_shapes(cfg, 0).values())


def init_layers(cfg: ModelConfig, dense, key: jax.Array, m: int,
                own_norm: bool = True) -> dict:
    """The ``[M, ...]`` stacks of ``m`` Mamba layers (without ``ssm_norm``
    where the mixer reads another's norm: ``own_norm`` false). The matrices
    are drawn as every other matrix is (``dense(key, shape, fan_in)``, the
    two projections by their leaf's name); ``dt_bias``,
    ``A_log`` and ``D`` as the published initialisation draws them (dt
    log-uniform in [time_step_min, time_step_max], floored, and ``dt_bias``
    its inverse softplus; ``A`` uniform in [1, 16]; ``D`` ones): a normal
    draw of ``A_log`` gives a state that vanishes in a token or never
    decays."""
    h, d, c = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_conv_dim
    heads, kk = cfg.mamba_num_heads, cfg.conv_kernel
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 6))
    lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(next(k), (m, heads), jnp.float32) * (hi - lo) + lo),
        cfg.time_step_floor)
    return {
        **({"ssm_norm": jnp.ones((m, h), dt)} if own_norm else {}),
        "ssm_in": dense(next(k), (m, h, d + c + heads), h, leaf="ssm_in"),
        "ssm_conv_w": dense(next(k), (m, kk, c), kk),
        "ssm_conv_b": jnp.zeros((m, c), dt),
        # softplus^-1(dt) = dt + log(-expm1(-dt))
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_A_log": jnp.log(jax.random.uniform(
            next(k), (m, heads), jnp.float32, 1.0, 16.0)),
        "ssm_D": jnp.ones((m, heads), jnp.float32),
        "ssm_gate_norm": jnp.ones((m, d), dt),
        "ssm_out": dense(next(k), (m, d, h), d, leaf="ssm_out"),
    }


def init_layers1(cfg: ModelConfig, dense, key: jax.Array, m: int) -> dict:
    """The ``[M, ...]`` stacks of ``m`` Mamba-1 layers, without their norm
    (llama.py ``_init_sambay`` draws the norms). The matrices as every other
    matrix; ``dt_bias`` the inverse softplus of a dt drawn log-uniform in
    [time_step_min, time_step_max], floored; ``A[j, c] = j + 1`` for every
    channel (S4D-real, the published start; ``A_log`` is ``[N, d]``, the
    channels last); ``D`` and the convolution's bias drawn around 1 and 0
    with a spread (a leaf at exactly its start is one a comparison cannot
    see)."""
    h, d, ns, r = (cfg.hidden_size, cfg.ssm_inner, cfg.ssm_state_size,
                   cfg.mamba_dt_rank)
    kk, dt = cfg.conv_kernel, jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 8))
    lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(next(k), (m, d), jnp.float32) * (hi - lo) + lo),
        cfg.time_step_floor)
    return {
        "ssm_in": dense(next(k), (m, h, 2 * d), h),
        "ssm_conv_w": dense(next(k), (m, kk, d), kk),
        "ssm_conv_b": (0.1 * jax.random.normal(next(k), (m, d))).astype(dt),
        "ssm_x": dense(next(k), (m, d, r + 2 * ns), d),
        "ssm_dt": dense(next(k), (m, r, d), r),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, ns + 1, dtype=jnp.float32))[None, :, None], (m, ns, d)) + 0.0,
        "ssm_D": 1.0 + 0.1 * jax.random.normal(next(k), (m, d), jnp.float32),
        "ssm_out": dense(next(k), (m, d, h), d),
    }


def logical_axes(own_norm: bool = True) -> dict:
    """No leaf of a Mamba layer is divided over a mesh (tp, pp, sp and ep
    above 1 are refused for a model with recurrent layers)."""
    wide = {"ssm_in": 3, "ssm_conv_w": 3, "ssm_out": 3}
    return {k: ("layers",) + (None,) * (wide.get(k, 2) - 1) for k in LEAVES
            if own_norm or k != "ssm_norm"}


def _conv(cfg: ModelConfig, lp, xbc, tail, tok_row, tok_off, starts, q_len):
    """The causal depthwise convolution over the step's tokens.
    ``xbc [N, C]`` token-major, ``tail [B, K-1, C]`` each row's last K-1
    inputs before this step (zeros at a sequence's start); ``tok_row`` /
    ``tok_off [N]`` name a token's row and its place in it, ``starts [B]``
    a row's first token. A token's input ``lag`` places back is the token
    that far before it where its row has one, else the row's tail. Returns
    (silu(conv) [N, C], the new tails: each row's last K-1 inputs up to its
    last live one)."""
    kk = cfg.conv_kernel
    n = xbc.shape[0]
    w = lp["ssm_conv_w"].astype(jnp.float32)                      # [K, C]
    out = (lp["ssm_conv_b"].astype(jnp.float32)
           + w[kk - 1] * xbc.astype(jnp.float32))
    for lag in range(1, kk):
        before = tail[tok_row, jnp.clip(kk - 1 + tok_off - lag, 0, kk - 2)]
        src = jnp.where((tok_off >= lag)[:, None],
                        jnp.roll(xbc, lag, axis=0), before.astype(xbc.dtype))
        out = out + w[kk - 1 - lag] * src.astype(jnp.float32)
    new = []
    for k in range(kk - 1):
        # Place k of the new tail is input q_len - (K-1) + k of the row's
        # new ones or, where that is before them, of the old tail.
        pos = q_len - (kk - 1) + k
        old = jnp.take_along_axis(
            tail, jnp.clip(kk - 1 + pos, 0, kk - 2)[:, None, None], axis=1)[:, 0]
        new.append(jnp.where((pos >= 0)[:, None],
                             xbc[jnp.clip(starts + pos, 0, n - 1)],
                             old.astype(xbc.dtype)))
    return jax.nn.silu(out).astype(xbc.dtype), jnp.stack(new, axis=1)


def _scan_one(state, a, dx, bm, cm):
    """The one-token recurrence on the rows' own states. ``state
    [B, H, P, N]``, ``a [B, H]`` the decay, ``dx [B, H, P]`` (``dt x``),
    ``bm`` / ``cm [B, G, N]``, all float32. Returns (y [B, H, P] without
    the D term, the new state)."""
    rep = a.shape[1] // bm.shape[1]
    s1 = (a[:, :, None, None] * state
          + dx[..., None] * jnp.repeat(bm, rep, axis=1)[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", s1, jnp.repeat(cm, rep, axis=1))
    return y, s1


def _scan_blocks(x, bm, cm, dt, a_log, state, block: int):
    """The blocked form of the same recurrence over T positions a row.
    x [B,T,H,P], bm / cm [B,T,G,N], dt [B,T,H] float32 (0 at a padded
    position), state [B,H,P,N] float32: the row's state before its first
    position. Within a block of ``block`` positions
    ``y = ((C B^T) * L)(dt x)`` with ``L[i, j] = exp(s_i - s_j)`` for
    ``i >= j``, ``s`` the running sum of ``dt A``, plus what the state that
    entered the block gives, ``exp(s_i) C_i S``; between blocks
    ``S <- exp(s_last) S + sum_j exp(s_last - s_j) dt_j x_j (x) B_j``. The
    blocks run in a ``lax.scan`` that carries the state. Returns
    (y [B,T,H,P] float32 without the D term, the state after position
    T - 1)."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    q = min(block, t)
    nb = -(-t // q)
    pad = nb * q - t
    if pad:
        x, bm, cm, dt = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                         for v in (x, bm, cm, dt))
    rep = h // g
    neg_a = -jnp.exp(a_log)                                       # [H]

    def blocks(v):      # [B, nb*q, ...] -> [nb, B, q, ...]
        return jnp.moveaxis(v.reshape(b, nb, q, *v.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((q, q), bool))

    def body(s0, xs):
        xq, bq, cq, dtq = xs                     # [B,q,H,P] [B,q,G,N] .. [B,q,H]
        s = jnp.cumsum(dtq * neg_a, axis=1).transpose(0, 2, 1)    # [B, H, q]
        # (masked before the exp: above the diagonal s_i - s_j is positive)
        decay = jnp.exp(jnp.where(lower, s[..., :, None] - s[..., None, :],
                                  -jnp.inf))                      # [B,H,q,q]
        cb = jnp.einsum("bign,bjgn->bgij", cq, bq,
                        preferred_element_type=jnp.float32)       # [B,G,q,q]
        w = (jnp.repeat(cb, rep, axis=1) * decay).astype(xq.dtype)
        dx = dtq[..., None] * xq.astype(jnp.float32)              # [B,q,H,P]
        y = jnp.einsum("bhij,bjhp->bihp", w, dx.astype(xq.dtype),
                       preferred_element_type=jnp.float32)
        # what the state that entered the block gives
        ch = jnp.repeat(cq.astype(jnp.float32), rep, axis=2)      # [B,q,H,N]
        y = y + jnp.exp(s).transpose(0, 2, 1)[..., None] * jnp.einsum(
            "bqhn,bhpn->bqhp", ch, s0)
        # the block's own contribution to the state that leaves it
        to_end = jnp.exp(s[..., -1:] - s).transpose(0, 2, 1)      # [B, q, H]
        bh = jnp.repeat(bq.astype(jnp.float32), rep, axis=2)      # [B,q,H,N]
        add = jnp.einsum("bqhp,bqhn->bhpn", dx * to_end[..., None], bh)
        s1 = jnp.exp(s[..., -1])[:, :, None, None] * s0 + add
        return s1, y

    state, ys = lax.scan(body, state, tuple(map(blocks, (x, bm, cm, dt))))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nb * q, h, p)[:, :t]
    return y, state


def _token_rows(lay, n: int):
    """(row [N], place in its row [N], each row's first token [B]) of a
    step's tokens, for either form of ``TokenLayout``."""
    if lay.tok_row is None:       # the rectangle, row-major
        i = jnp.arange(n, dtype=jnp.int32)
        return i // lay.t, i % lay.t, jnp.arange(lay.b, dtype=jnp.int32) * lay.t
    return lay.tok_row, lay.tok_off, lay.row_tok[:, 0]


def _update_rows(state, layer, slots, one, a, dx, bm, cm, impl: str):
    """The one-token recurrence on the pool, in place, of those of B rows
    that ``one`` marks: the kernel (ops/ssm_update.py), which moves those
    rows' state and no other's, or, for ``impl`` "jnp", a gather of all B,
    ``_scan_one``'s arithmetic (the identity for the others: ``a`` 1, ``dx``
    0) and a scatter."""
    if impl != "jnp":
        from dynamo_tpu.ops.ssm_update import update_rows

        # (an int32 whether the layer is a number, in the leading group, or
        # the scan's: one trace and one lowering of the kernel a program)
        return update_rows(state, jnp.asarray(layer, jnp.int32), slots, one,
                           a, dx, bm, cm,
                           interpret=impl == "pallas_interpret")
    y, s1 = _scan_one(state[layer, slots], a, dx, bm, cm)
    return state.at[layer, slots].set(s1), y


def mixer(cfg: ModelConfig, lp, layer, u, ssm, *, lay, slots, q_start, q_len,
          live, impl: str = "jnp"):
    """One Mamba-2 mixer over a step's tokens. ``u [N, H]`` is the normed
    state, ``ssm`` the whole state pool and ``layer`` this layer's place
    among the Mamba layers (may be traced), ``slots [B]`` each row's row of
    the pool (a padded row: the trash row), ``q_start`` / ``q_len [B]`` as
    in the step, ``live [N]`` which tokens are. Returns (out [N, H], the
    pool with this layer's rows updated in place).

    Everything runs over the N tokens but the recurrence itself, which runs
    over rows: a row of one token (every row of a decode program, the
    decode rows of a mixed one) by the one-token update of all B rows at
    once (``impl``: the kernel of ops/ssm_update.py, interpreted or not, or
    "jnp"); a row of several tokens, a chunk of a prompt, by the blocked
    scan over its T positions, one such row at a time in a loop over the
    rows that skips the others, so a mixed step pays for the chunks it has
    and not for a ``[B, T]`` rectangle."""
    d, c, heads = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.mamba_num_heads
    g, ns, p = cfg.ssm_groups, cfg.ssm_state_size, cfg.mamba_head_dim
    n, b, t = u.shape[0], lay.b, lay.t
    # (a multiplier of 1 is no operation of the program)
    if cfg.ssm_in_multiplier != 1.0:
        u = u * cfg.ssm_in_multiplier
    with phase("ssm_proj"):
        zxd = lax.optimization_barrier(u @ lp["ssm_in"])          # [N, d+c+H]
        if cfg.ssm_multipliers:     # one a slice: z, x, B, C, dt
            zxd = zxd * cfg.ssm_column_multipliers.astype(zxd.dtype)
        z, xbc, dt = zxd[:, :d], zxd[:, d:d + c], zxd[:, d + c:]
    tok_row, tok_off, starts = _token_rows(lay, n)
    fresh = q_start == 0
    with phase("ssm_conv"):
        tail = jnp.where(fresh[:, None], 0, ssm["conv"][layer, slots])
        xbc, tail = _conv(cfg, lp, xbc, tail.reshape(b, -1, c), tok_row,
                          tok_off, starts, q_len)
        conv = ssm["conv"].at[layer, slots].set(tail.reshape(b, -1))
    with phase("ssm_scan"):
        dt = jnp.where(live[:, None], jax.nn.softplus(
            dt.astype(jnp.float32) + lp["ssm_dt_bias"]), 0.0)      # [N, H]
        x = xbc[:, :d].reshape(n, heads, p)
        bm = xbc[:, d:d + g * ns].reshape(n, g, ns)
        cm = xbc[:, d + g * ns:].reshape(n, g, ns)
        neg_a = -jnp.exp(lp["ssm_A_log"])
        # Rows of one token, all at once. A row of none or of several is
        # left as it is here (a = 1, nothing added; the kernel passes it by).
        one = q_len == 1
        dt1 = jnp.where(one[:, None], dt[starts], 0.0)             # [B, H]
        a1 = jnp.where((one & fresh)[:, None], 0.0, jnp.exp(dt1 * neg_a))
        state, y1 = _update_rows(
            ssm["state"], layer, slots, one, a1,
            dt1[:, :, None] * x[starts].astype(jnp.float32),
            bm[starts].astype(jnp.float32), cm[starts].astype(jnp.float32),
            impl)
        if t == 1:
            y = y1                                                 # N == B
        else:
            # Rows of several tokens, one at a time, each from its stored
            # state to the state after its last live token. A row's T
            # positions are cut from the tokens behind its first (padded so
            # that the cut fits); what lies past its live ones is another
            # row's or padding, made the identity by dt = 0, and its part
            # of ``y`` is overwritten by the rows behind it.
            def padded(v):
                return jnp.pad(v, ((0, t),) + ((0, 0),) * (v.ndim - 1))

            xp, bp, cp, dtp = map(padded, (x, bm, cm, dt))
            inside = jnp.arange(t)

            def chunk(row, carry):
                state, y = carry
                at = starts[row]
                cut = lambda v: lax.dynamic_slice_in_dim(v, at, t)[None]
                dtr = jnp.where(inside[:, None] < q_len[row], cut(dtp)[0], 0.0)
                s0 = jnp.where(fresh[row], 0.0, lax.dynamic_slice(
                    state, (layer, slots[row], 0, 0, 0),
                    (1, 1, heads, p, ns))[0])
                yr, s1 = _scan_blocks(cut(xp), cut(bp), cut(cp), dtr[None],
                                      lp["ssm_A_log"], s0, cfg.ssm_chunk)
                state = lax.dynamic_update_slice(
                    state, s1[None], (layer, slots[row], 0, 0, 0))
                return state, lax.dynamic_update_slice_in_dim(y, yr[0], at, 0)

            state, y = lax.fori_loop(
                0, b, lambda row, carry: lax.cond(
                    q_len[row] > 1, chunk, lambda _r, cr: cr, row, carry),
                (state, jnp.zeros((n + t, heads, p), jnp.float32)))
            y = y[:n].at[jnp.where(one, starts, n)].set(y1, mode="drop")
        y = y + lp["ssm_D"][:, None] * x.astype(jnp.float32)       # [N, H, P]
        # The gate first, then the norm over each group's channels.
        y = y.reshape(n, d) * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(n, g, d // g)
        yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + cfg.rms_norm_eps)
        y = (yg.reshape(n, d).astype(u.dtype) * lp["ssm_gate_norm"])
    with phase("ssm_proj"):
        out = y @ lp["ssm_out"]
    if cfg.ssm_out_multiplier != 1.0:
        out = out * cfg.ssm_out_multiplier
    return out, {"state": state, "conv": conv}


def mixer1(cfg: ModelConfig, lp, layer, u, ssm, *, lay, slots, q_start, q_len,
           live, impl: str = "jnp"):
    """One Mamba-1 mixer over a step's tokens (``transformers``'
    ``modeling_mamba.py``; d the inner width, N the state size, R dt's rank):

        [x | z] = u W_in;  x <- silu(conv_causal_K(x) + b)
        [delta | B | C] = x W_x;  dt = softplus(delta W_dt + b_dt)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        out = (y * silu(z)) W_out

    with ``A = -exp(A_log)`` of ``[N, d]``: a decay a channel and a state
    index, so none of :func:`mixer`'s forms computes it. The pool, the slots,
    the convolution and its tail, the zero start at ``q_start`` 0 and the
    trash row are :func:`mixer`'s; the recurrence is one kernel over every
    row of the step, of one position or of a chunk's
    (ops/selective_scan.py; ``impl`` "jnp": the same recurrence without it).
    Arguments as :func:`mixer`'s. Returns (out [N, H], the pool, ``y [N, d]``
    before the gate, which SambaY's memory units read)."""
    from dynamo_tpu.ops.selective_scan import selective_scan

    d, ns, r = cfg.ssm_inner, cfg.ssm_state_size, cfg.mamba_dt_rank
    n, b = u.shape[0], lay.b
    with phase("ssm_proj"):
        xz = lax.optimization_barrier(u @ lp["ssm_in"])           # [N, 2 d]
        x, z = xz[:, :d], xz[:, d:]
    tok_row, tok_off, starts = _token_rows(lay, n)
    fresh = q_start == 0
    with phase("ssm_conv"):
        tail = jnp.where(fresh[:, None], 0, ssm["conv"][layer, slots])
        x, tail = _conv(cfg, lp, x, tail.reshape(b, -1, d), tok_row, tok_off,
                        starts, q_len)
        conv = ssm["conv"].at[layer, slots].set(tail.reshape(b, -1))
    with phase("ssm_proj"):
        dbc = x @ lp["ssm_x"]                                     # [N, R + 2 N]
        dt = jax.nn.softplus((dbc[:, :r] @ lp["ssm_dt"]).astype(jnp.float32)
                             + lp["ssm_dt_bias"])                 # [N, d]
    with phase("ssm_scan"):
        state, y = selective_scan(
            ssm["state"], jnp.asarray(layer, jnp.int32), slots, starts, q_len,
            fresh, x, dt, dbc[:, r:r + ns], dbc[:, r + ns:], lp["ssm_A_log"],
            lp["ssm_D"], t=lay.t, impl=impl, out_dtype=u.dtype)
        gated = (y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
    with phase("ssm_proj"):
        out = gated @ lp["ssm_out"]
    return out, {"state": state, "conv": conv}, y
