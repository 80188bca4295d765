"""The plain reference of ``falcon-h1-34b-l6``: Falcon-H1's forward pass
(``model_type: "falcon_h1"``, written from the keys of the published
``config.json``) in straightforward ``jax.numpy``, float32 activations,
highest matmul precision, no cache, no kernel, no batching, one layer at a
time so that it fits beside the engine's pools.

Every block is the same (``RMS_w(z) = z / sqrt(mean(z^2) + eps) * w``, eps
``rms_norm_eps``; no projection has a bias; multipliers by their keys, each
on the activation where the equation has it, none folded into a matrix):

    h0   = embed[token] * embedding_multiplier
    u    = RMS_{input_layernorm}(h)
    # attention: num_attention_heads query heads over num_key_value_heads KV
    # heads of head_dim, rotate-half rotary on the whole head, theta
    # rope_theta, causal softmax at 1 / sqrt(head_dim), no QK norm
    q, k, v = (u * attention_in_multiplier) Wq, Wk, Wv;  k = k * key_multiplier
    a    = softmax(rope(q) rope(k)^T / sqrt(head_dim)) v Wo * attention_out_multiplier
    # Mamba-2: d = mamba_d_ssm = mamba_n_heads x mamba_d_head, G =
    # mamba_n_groups, N = mamba_d_state, c = d + 2 G N, K = mamba_d_conv
    [z | x | B | C | dt] = ((u * ssm_in_multiplier) W_in) * ssm_multipliers[0..4]
    xBC_t = silu(b + sum_k w[k] xBC_{t-K+1+k})     depthwise, causal, zeros before
    dt_t = softplus(dt_t + dt_bias); A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)];  y_t = S_t C_t[g(h)] + D x_t
    y    = RMS_groups(y * silu(z)) * w            (the gate first, then the norm:
                                                   mamba_norm_before_gate false)
    s    = y W_out * ssm_out_multiplier
    h    = h + a + s                              # one residual, two branches
    v    = RMS_{pre_ff_layernorm}(h)
    h    = h + (silu(v Wg * mlp_multipliers[0]) * (v Wu)) Wd * mlp_multipliers[1]
    logits = RMS_{final_layernorm}(h) W_head * lm_head_multiplier

**The recurrence is computed token by token** (a ``lax.scan`` over
positions, a group of heads at a time): independent of the program's blocked
form and of its one-token kernel.

Departures: weights are the engine's bf16 leaves widened to float32, not a
float32 master copy (``A_log``, ``dt_bias`` and ``D`` are float32 leaves
already); a padded position (``pad_to``) is masked in attention and, being
behind the live ones, reaches no live position through the causal
convolution or the recurrence. Assumed (``about.json``): the order of
``ssm_multipliers`` is the in-projection's own, z, x, B, C, dt.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's layout:
under ``layers`` one stack ``[L, ...]`` a leaf, ``attn_norm``
(``input_layernorm``), ``wq``, ``wk``, ``wv``, ``wo``; ``ssm_in [L, H, d + c +
heads]``, ``ssm_conv_w [L, K, c]``, ``ssm_conv_b``, ``ssm_dt_bias``,
``ssm_A_log``, ``ssm_D``, ``ssm_gate_norm``, ``ssm_out [L, d, H]``; ``mlp_norm``
(``pre_ff_layernorm``), ``w_gate``, ``w_up``, ``w_down``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, pos, theta):
    """x [T, H, D]; rotate the halves (x1, x2) -> (x1 cos - x2 sin,
    x2 cos + x1 sin) with frequencies theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta",
                                   "in_mult", "key_mult", "out_mult"))
def _attention(u, lp, n_valid, *, n_heads, n_kv, head_dim, theta, in_mult,
               key_mult, out_mult):
    """The attention branch on ``u [T, H]``, one KV head's query heads at a
    time."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        rep = n_heads // n_kv
        pos = jnp.arange(t)
        x = u * in_mult
        q = (x @ _f32(lp["wq"])).reshape(t, n_heads, head_dim)
        k = ((x @ _f32(lp["wk"])) * key_mult).reshape(t, n_kv, head_dim)
        v = (x @ _f32(lp["wv"])).reshape(t, n_kv, head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)

        def group(qkv):
            qg, kg, vg = qkv                      # [T, rep, D], [T, D], [T, D]
            s = jnp.einsum("qrd,kd->rqk", qg, kg) / jnp.sqrt(
                jnp.float32(head_dim))
            w = jax.nn.softmax(
                jnp.where(mask[None], s, jnp.finfo(jnp.float32).min), axis=-1)
            return jnp.einsum("rqk,kd->qrd", w, vg)

        out = jax.lax.map(group, (
            q.reshape(t, n_kv, rep, head_dim).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        out = out.transpose(1, 0, 2, 3).reshape(t, n_heads * head_dim)
        return (out @ _f32(lp["wo"])) * out_mult


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "kernel", "eps", "in_mult", "mup",
                                   "out_mult", "norm_before_gate"))
def _mamba(u, lp, *, heads, head_dim, groups, state, kernel, eps, in_mult,
           mup, out_mult, norm_before_gate):
    """The Mamba-2 branch on ``u [T, H]``, the recurrence token by token
    from a zero state, a group's heads at a time. ``norm_before_gate`` (the
    family's other order, which the published 34B does not use and the
    program refuses): ``RMS_groups(y) * w * silu(z)``."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        d = heads * head_dim
        gn = groups * state
        zxd = (u * in_mult) @ _f32(lp["ssm_in"])
        on_z, on_x, on_b, on_c, on_dt = mup
        z = zxd[:, :d] * on_z
        xbc = jnp.concatenate([zxd[:, d:2 * d] * on_x,
                               zxd[:, 2 * d:2 * d + gn] * on_b,
                               zxd[:, 2 * d + gn:2 * d + 2 * gn] * on_c], -1)
        dt = zxd[:, 2 * d + 2 * gn:] * on_dt
        # depthwise causal convolution, zeros before the first token
        seq = jnp.concatenate([jnp.zeros((kernel - 1, xbc.shape[1])), xbc])
        w = _f32(lp["ssm_conv_w"])                                  # [K, c]
        conv = _f32(lp["ssm_conv_b"])[None, :] + sum(
            w[k][None, :] * seq[k:k + t] for k in range(kernel))
        xbc = jax.nn.silu(conv)
        hg = heads // groups
        x = xbc[:, :d].reshape(t, groups, hg, head_dim)
        bm = xbc[:, d:d + gn].reshape(t, groups, state)
        cm = xbc[:, d + gn:].reshape(t, groups, state)
        dt = jax.nn.softplus(dt + _f32(lp["ssm_dt_bias"])[None, :])  # [T, H]
        a = jnp.exp(dt * -jnp.exp(_f32(lp["ssm_A_log"]))[None, :])
        dt, a = dt.reshape(t, groups, hg), a.reshape(t, groups, hg)

        def of_group(xs):
            a_g, dt_g, x_g, b_g, c_g = xs      # [T, hg] x2, [T, hg, P], [T, N] x2

            def step(s, at):
                a_t, dt_t, x_t, b_t, c_t = at
                s = a_t[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] \
                    * b_t[None, None, :]
                return s, jnp.einsum("hpn,n->hp", s, c_t)

            return jax.lax.scan(step, jnp.zeros((hg, head_dim, state)),
                                (a_g, dt_g, x_g, b_g, c_g))[1]   # [T, hg, P]

        y = jax.lax.map(of_group, tuple(
            jnp.moveaxis(v, 1, 0) for v in (a, dt, x, bm, cm)))   # [G,T,hg,P]
        y = jnp.moveaxis(y, 0, 1).reshape(t, heads, head_dim)
        x = x.reshape(t, heads, head_dim)
        y = y + _f32(lp["ssm_D"])[None, :, None] * x
        y = y.reshape(t, d)
        if not norm_before_gate:
            y = y * jax.nn.silu(z)
        yg = y.reshape(t, groups, d // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
        y = yg.reshape(t, d) * _f32(lp["ssm_gate_norm"])
        if norm_before_gate:
            y = y * jax.nn.silu(z)
        return (y @ _f32(lp["ssm_out"])) * out_mult


def _blocks(width: int, most: int) -> int:
    """The least number of equal blocks of at most ``most`` columns."""
    return next(n for n in range(1, width + 1)
                if width % n == 0 and width // n <= most)


@partial(jax.jit, static_argnames=("on_gate", "on_out"))
def _mlp(v, w_gate, w_up, w_down, layer, *, on_gate, on_out):
    """The gated MLP of layer ``layer``, a block of its inner width at a
    time, each cut from the stacks ``[L, ...]`` where they lie: a layer's
    three matrices cut out whole (0.66 GB), or one in float32 (0.44 GB),
    would not fit beside the engine's pools."""
    with jax.default_matmul_precision("highest"):
        hidden, width = w_gate.shape[1:]
        n = _blocks(width, 4096)
        cols = width // n

        def block(acc, j):
            up = lambda w: _f32(jax.lax.dynamic_slice(
                w, (layer, 0, j * cols), (1, hidden, cols))[0])
            down = _f32(jax.lax.dynamic_slice(
                w_down, (layer, j * cols, 0), (1, cols, hidden))[0])
            gate = (v @ up(w_gate)) * on_gate
            return acc + (jax.nn.silu(gate) * (v @ up(w_up))) @ down, None

        out, _ = jax.lax.scan(block, jnp.zeros_like(v), jnp.arange(n))
        return out * on_out


@jax.jit
def _head(hid, final_norm, w_out, eps, mult):
    """The final norm and the head, a block of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hid, final_norm, eps)
        vocab = w_out.shape[1]
        n = _blocks(vocab, 16384)
        cols = vocab // n
        out = jax.lax.map(lambda j: x @ _f32(jax.lax.dynamic_slice_in_dim(
            w_out, j * cols, cols, 1)), jnp.arange(n))          # [n, T, cols]
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab) * mult


ATTN = ("wq", "wk", "wv", "wo")
MAMBA = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_A_log",
         "ssm_D", "ssm_gate_norm", "ssm_out")


def logits_at(params, model: dict, tokens: list[int], positions: list[int],
              pad_to: int = 0) -> np.ndarray:
    """Float32 logits [len(positions), vocab] after the tokens at
    ``positions`` of the sequence ``tokens``; ``pad_to`` pads the sequence
    (masked) so that several lengths share one compiled program."""
    if model.get("model_type") != "falcon_h1":
        raise ValueError("this reference is Falcon-H1's block")
    if model.get("attn_layer_indices") or model.get("rope_scaling") \
            or not model.get("mamba_rms_norm", True):
        raise ValueError("this reference has attention in every block, a "
                         "norm on the gated output and scales no frequency")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    eps = float(model["rms_norm_eps"])
    heads, head_dim = model["mamba_n_heads"], model["mamba_d_head"]
    if model["mamba_d_ssm"] != heads * head_dim:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    layers = params["layers"]
    h = _f32(params["embed"][jnp.asarray(ids)]) \
        * float(model["embedding_multiplier"])
    for i in range(model["num_hidden_layers"]):
        lp = {k: v[i] for k, v in layers.items() if not k.startswith("w_")}
        u = _rms_norm(h, lp["attn_norm"], eps)
        a = _attention(
            u, {k: lp[k] for k in ATTN}, jnp.int32(n),
            n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
            theta=float(model["rope_theta"]),
            in_mult=float(model["attention_in_multiplier"]),
            key_mult=float(model["key_multiplier"]),
            out_mult=float(model["attention_out_multiplier"]))
        s = _mamba(
            u, {k: lp[k] for k in MAMBA}, heads=heads, head_dim=head_dim,
            groups=model["mamba_n_groups"], state=model["mamba_d_state"],
            kernel=model["mamba_d_conv"], eps=eps,
            in_mult=float(model["ssm_in_multiplier"]),
            mup=tuple(float(m) for m in model["ssm_multipliers"]),
            out_mult=float(model["ssm_out_multiplier"]),
            norm_before_gate=bool(model.get("mamba_norm_before_gate", False)))
        h = h + a + s
        on_gate, on_out = (float(m) for m in model["mlp_multipliers"])
        h = h + _mlp(_rms_norm(h, lp["mlp_norm"], eps), layers["w_gate"],
                     layers["w_up"], layers["w_down"], jnp.int32(i),
                     on_gate=on_gate, on_out=on_out)
    return np.asarray(_head(h[jnp.asarray(positions)], params["final_norm"],
                            params["lm_head"], jnp.float32(eps),
                            jnp.float32(model["lm_head_multiplier"])))
