"""The plain reference of ``phi-4-mini-flash-reasoning``: the forward pass of
Phi-4-mini-flash-reasoning (``model_type: "phi4flash"``; SambaY,
arXiv:2507.06607, with differential attention, arXiv:2410.05258) in
straightforward ``jax.numpy``, float32 activations, highest matmul
precision, no cache, no kernel, no batching, one layer at a time and the
head in blocks so that it fits beside the engine's pools.

With ``n = num_hidden_layers``, ``LN`` LayerNorm with weight and bias (eps
``layer_norm_eps``), every layer ``i`` is

    h <- h + mix_i(LN(h));   h <- h + (silu(g) * p) W_down,  [g | p] = LN(h) W_gate_up

and after the last layer ``logits = LN(h) embed^T`` (the head is the
embedding's table, no bias). No layer carries a position. ``mix_i`` is

- ``i`` even, ``i <= n/2``, **Mamba-1** (d = mamba_expand x hidden_size, N =
  mamba_d_state, K = mamba_d_conv, R = mamba_dt_rank):
      [x | z] = u W_in;  x_t <- silu(b + sum_k w[k] x_{t-K+1+k})   causal, zeros before
      [delta | B | C] = x W_x;  dt = softplus(delta W_dt + b_dt);  A = -exp(A_log)
      S_t[c, j] = exp(dt_t[c] A[c, j]) S_{t-1}[c, j] + dt_t[c] B_t[j] x_t[c]
      y_t[c] = sum_j C_t[j] S_t[c, j] + D[c] x_t[c];   out = (y * silu(z)) W_out
  Layer ``n/2``'s ``y``, before the gate, is the memory ``m``.
- ``i`` odd, ``i < n/2``, **differential attention** over the last
  ``sliding_window`` positions; ``i = n/2 + 1`` the same over all of them.
  ``[q | k | v] = u W_qkv + b``; heads of ``D = hidden_size /
  num_attention_heads`` in adjacent pairs: ``q1, q2`` the even and odd Q
  heads, ``k1, k2`` / ``v1, v2`` the even and odd KV heads, Q pair ``p``
  reading KV pair ``p // (Q heads a KV head)``;
      A1 = softmax(q1 k1^T / sqrt(D)) [v1 | v2];  A2 = softmax(q2 k2^T / sqrt(D)) [v1 | v2]
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 i)
      out = concat_p(RMS_{2D}(A1 - lambda A2; w) (1 - lambda_init)) W_o + b_o
- ``i`` even, ``i > n/2``, **gated memory unit**: ``out = (m * silu(u W_1)) W_2``,
  ``m`` at the same token.
- ``i`` odd, ``i > n/2 + 1``, **cross attention**: ``q = u W_q + b`` alone,
  the same differential form with the layer's own lambdas and norm, over
  layer ``n/2 + 1``'s keys and values, causal, no window.

**Nothing is skipped and nothing is shared**: the recurrence runs token by
token (a ``lax.scan`` over positions), the two softmaxes and their
difference are written as above (no pair view, no zero halves), and every
layer runs over every token (the program runs the cross-decoder for the
tokens whose logits it takes).

Departures: weights are the engine's leaves widened to float32 (bf16
matrices; ``A_log``, ``dt_bias``, ``D`` and the lambda vectors are float32
already); a padded position (``pad_to``) is masked in attention (by a large
finite number: one past the window of every live key sees nothing, and its
row stays finite) and, being behind the live ones, reaches no live position
through the causal convolution or the recurrence. Assumed (``about.json``): the Mamba-1 sizes,
the biases, ``lambda_init`` by the layer's index.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's
layout. Under ``layers``, a stack a kind of mixer, in the layers' order:
Mamba ``ssm_norm(_b)``, ``ssm_in [*, H, 2d]``, ``ssm_conv_w [*, K, d]``,
``ssm_conv_b``, ``ssm_x [*, d, R + 2N]``, ``ssm_dt [*, R, d]``, ``ssm_dt_bias``,
``ssm_A_log [*, N, d]``, ``ssm_D``, ``ssm_out``; attention ``attn_norm(_b)``,
``wq``, ``wk``, ``wv``, ``wo``, ``bq``, ``bk``, ``bv``, ``bo``,
``diff_lq1``, ``diff_lk1``, ``diff_lq2``, ``diff_lk2``, ``diff_norm``; cross
attention the same names behind ``x_`` (no ``wk``, ``wv``, ``bk``, ``bv``);
memory units ``gmu_norm(_b)``, ``gmu_in``, ``gmu_out``; and one stack of all
``n`` MLPs, ``mlp_norm(_b)``, ``w_gate``, ``w_up``, ``w_down``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w) + _f32(b)


@partial(jax.jit, static_argnames=("kernel", "n_state", "rank"))
def _mamba(u, lp, *, kernel, n_state, rank):
    """One Mamba-1 mixer over the sequence ``u [T, H]``. Returns (out, y:
    the scan's output before the gate)."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        xz = u @ _f32(lp["ssm_in"])
        d = xz.shape[1] // 2
        x, z = xz[:, :d], xz[:, d:]
        w = _f32(lp["ssm_conv_w"])                                 # [K, d]
        padded = jnp.pad(x, ((kernel - 1, 0), (0, 0)))
        x = jax.nn.silu(_f32(lp["ssm_conv_b"]) + sum(
            w[k] * padded[k:k + t] for k in range(kernel)))
        dbc = x @ _f32(lp["ssm_x"])
        delta, bm, cm = (dbc[:, :rank], dbc[:, rank:rank + n_state],
                         dbc[:, rank + n_state:])
        dt = jax.nn.softplus(delta @ _f32(lp["ssm_dt"]) + lp["ssm_dt_bias"])
        neg_a = -jnp.exp(_f32(lp["ssm_A_log"]))                    # [N, d]

        def step(s, xs):
            xt, dtt, bt, ct = xs                        # [d] [d] [N] [N]
            s = jnp.exp(dtt * neg_a) * s + (dtt * xt) * bt[:, None]
            return s, ct @ s

        _, y = jax.lax.scan(step, jnp.zeros((n_state, d), jnp.float32),
                            (x, dt, bm, cm))
        y = y + lp["ssm_D"] * x
        return (y * jax.nn.silu(z)) @ _f32(lp["ssm_out"]), y


@partial(jax.jit, static_argnames=("n_heads", "n_kv"))
def _keys_values(u, lp, *, n_heads, n_kv):
    """(k, v) ``[T, n_kv, D]`` of an attention layer."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        d = lp["wk"].shape[1] // n_kv
        k = (u @ _f32(lp["wk"]) + _f32(lp["bk"])).reshape(t, n_kv, d)
        v = (u @ _f32(lp["wv"]) + _f32(lp["bv"])).reshape(t, n_kv, d)
        return k, v


@partial(jax.jit, static_argnames=("n_heads", "window"))
def _diff_attention(u, k, v, lp, n_valid, lambda_init, eps, *, n_heads,
                    window):
    """Differential attention of the queries of ``u [T, H]`` over the keys
    and values ``k``, ``v [T, n_kv, D]`` (the layer's own, or another
    layer's), a pair of Q heads at a time."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        d = lp["wq"].shape[1] // n_heads     # hidden_size / heads as published
        n_kv = k.shape[1]
        per_kv = n_heads // n_kv
        q = (u @ _f32(lp["wq"]) + _f32(lp["bq"])).reshape(t, n_heads, d)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) & (j < n_valid)
        if window:
            seen = seen & (i - j < window)
        lam = (jnp.exp(jnp.sum(lp["diff_lq1"] * lp["diff_lk1"]))
               - jnp.exp(jnp.sum(lp["diff_lq2"] * lp["diff_lk2"]))
               + lambda_init)

        def softmax_of(qh, kh):
            # (a finite mask: a padded position past the window sees no key
            # at all, and a row of -inf would be NaN, which a masked value
            # times zero carries into every live position above it)
            s = jnp.where(seen, (qh @ kh.T) / math.sqrt(d), -1e30)
            return jax.nn.softmax(s, axis=-1)

        def pair(p):
            kv = p // per_kv               # the KV pair this Q pair reads
            q1, q2 = q[:, 2 * p], q[:, 2 * p + 1]
            k1, k2 = k[:, 2 * kv], k[:, 2 * kv + 1]
            v12 = jnp.concatenate([v[:, 2 * kv], v[:, 2 * kv + 1]], axis=-1)
            a1 = softmax_of(q1, k1) @ v12                          # [T, 2D]
            a2 = softmax_of(q2, k2) @ v12
            x = a1 - lam * a2
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps) * _f32(lp["diff_norm"])
            return x * (1.0 - lambda_init)

        out = jax.lax.map(pair, jnp.arange(n_heads // 2))          # [P, T, 2D]
        out = jnp.moveaxis(out, 0, 1).reshape(t, n_heads * d)
        return out @ _f32(lp["wo"]) + _f32(lp["bo"])


@jax.jit
def _memory_unit(u, m, lp):
    with jax.default_matmul_precision("highest"):
        return (m * jax.nn.silu(u @ _f32(lp["gmu_in"]))) @ _f32(lp["gmu_out"])


def _blocks(width: int, most: int) -> int:
    """The fewest equal blocks of ``width`` of at most ``most`` columns."""
    n = -(-width // most)
    while width % n:
        n += 1
    return n


@jax.jit
def _mlp(v, w_gate, w_up, w_down, layer):
    """``(silu(v Wg) * (v Wu)) Wd`` with layer ``layer`` of the stacks, a
    block of the intermediate width at a time."""
    with jax.default_matmul_precision("highest"):
        width = w_gate.shape[2]
        n = _blocks(width, 2048)
        cols = width // n

        def block(acc, j):
            g = _f32(jax.lax.dynamic_slice(
                w_gate, (layer, 0, j * cols), (1, w_gate.shape[1], cols))[0])
            p = _f32(jax.lax.dynamic_slice(
                w_up, (layer, 0, j * cols), (1, w_up.shape[1], cols))[0])
            dn = _f32(jax.lax.dynamic_slice(
                w_down, (layer, j * cols, 0), (1, cols, w_down.shape[2]))[0])
            return acc + (jax.nn.silu(v @ g) * (v @ p)) @ dn, None

        return jax.lax.scan(block, jnp.zeros_like(v), jnp.arange(n))[0]


@jax.jit
def _head(hid, w, b, embed, eps):
    """The final LayerNorm and the tied head, a block of the vocabulary's
    rows at a time."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(hid, w, b, eps)
        vocab = embed.shape[0]
        n = _blocks(vocab, 16384)
        rows = vocab // n
        out = jax.lax.map(lambda j: x @ _f32(jax.lax.dynamic_slice_in_dim(
            embed, j * rows, rows, 0)).T, jnp.arange(n))           # [n, T, rows]
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab)


def logits_at(params, model: dict, tokens: list[int], positions: list[int],
              pad_to: int = 0) -> np.ndarray:
    """Float32 logits [len(positions), vocab] after the tokens at
    ``positions`` of the sequence ``tokens``; ``pad_to`` pads the sequence
    (masked) so that several lengths share one compiled program."""
    if model.get("model_type") != "phi4flash":
        raise ValueError("this reference is Phi-4-mini-flash's (SambaY)")
    if model.get("mb_per_layer", 2) != 2:
        raise ValueError("this reference has a Mamba mixer in every second "
                         "layer")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    depth, hidden = model["num_hidden_layers"], model["hidden_size"]
    half = depth // 2
    eps = float(model["layer_norm_eps"])
    n_heads, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    mamba = dict(kernel=model.get("mamba_d_conv", 4),
                 n_state=model.get("mamba_d_state", 16),
                 rank=model.get("mamba_dt_rank") or -(-hidden // 16))
    layers = params["layers"]

    def stack(prefix, names, at):
        """Place ``at`` of a kind's leaves, under the names the functions
        above read (the cross layers' without their ``x_``)."""
        own = "" if prefix == "x_" else prefix
        return {own + name: layers[prefix + name][at] for name in names}

    norm = lambda x, name, at: _layer_norm(
        x, layers[name][at], layers[name + "_b"][at], eps)
    lambdas = ("diff_lq1", "diff_lk1", "diff_lq2", "diff_lk2", "diff_norm")
    h = _f32(params["embed"][jnp.asarray(ids)])
    seen = dict.fromkeys("SAGX", 0)
    memory = shared = None
    for i in range(depth):
        lambda_init = jnp.float32(0.8 - 0.6 * math.exp(-0.3 * i))
        if i % 2 == 0 and i <= half:                       # Mamba-1
            at = seen["S"]
            seen["S"] += 1
            mix, y = _mamba(norm(h, "ssm_norm", at), stack(
                "ssm_", ("in", "conv_w", "conv_b", "x", "dt", "dt_bias",
                         "A_log", "D", "out"), at), **mamba)
            if i == half:
                memory = y
        elif i % 2 and i <= half + 1:                      # attention
            at = seen["A"]
            seen["A"] += 1
            lp = stack("", ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
                            *lambdas), at)
            u = norm(h, "attn_norm", at)
            window = model["sliding_window"] if i < half else 0
            k, v = _keys_values(u, lp, n_heads=n_heads, n_kv=n_kv)
            if i == half + 1:
                shared = (k, v)
            mix = _diff_attention(u, k, v, lp, jnp.int32(n), lambda_init,
                                  jnp.float32(eps), n_heads=n_heads,
                                  window=window)
        elif i % 2 == 0:                                   # gated memory unit
            at = seen["G"]
            seen["G"] += 1
            mix = _memory_unit(norm(h, "gmu_norm", at), memory,
                               stack("gmu_", ("in", "out"), at))
        else:                                              # cross attention
            at = seen["X"]
            seen["X"] += 1
            lp = stack("x_", ("wq", "wo", "bq", "bo", *lambdas), at)
            mix = _diff_attention(norm(h, "x_attn_norm", at), *shared, lp,
                                  jnp.int32(n), lambda_init, jnp.float32(eps),
                                  n_heads=n_heads, window=0)
        h = h + mix
        h = h + _mlp(norm(h, "mlp_norm", i), layers["w_gate"], layers["w_up"],
                     layers["w_down"], jnp.int32(i))
    return np.asarray(_head(h[jnp.asarray(positions)], params["final_norm"],
                            params["final_norm_b"], params["embed"],
                            jnp.float32(eps)))
