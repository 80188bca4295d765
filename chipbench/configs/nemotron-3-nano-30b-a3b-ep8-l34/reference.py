"""The plain reference of ``nemotron-3-nano-30b-a3b-ep8-l34``: NemotronH's
forward pass (``transformers``' ``modeling_nemotron_h.py``) in straightforward
``jax.numpy``, float32 activations, highest matmul precision, no cache, no
kernel, no batching, one layer at a time and a layer in blocks (a group of
heads, an expert at a time) so that it fits beside the engine's pool.

Block ``l`` of kind ``hybrid_override_pattern[l]`` (``RMS_w(z) = z /
sqrt(mean(z^2) + eps) * w``, eps ``layer_norm_epsilon``; no projection has a
bias): ``h <- h + mixer_l(RMS_{w_l}(h))``; behind the last block a final
RMSNorm and the untied head.

``M``, Mamba-2 (d = ``mamba_num_heads`` x ``mamba_head_dim``, G = ``n_groups``,
N = ``ssm_state_size``, c = d + 2 G N, K = ``conv_kernel``):
``[z | xBC | dt] = u W_in`` (widths d, c, heads);
``xBC_t <- silu(b + sum_k w[k] xBC_{t-K+1+k})``, depthwise and causal, zeros
before the first token; ``x [H, P], B [G, N], C [G, N] = split(xBC)``;
``dt_t = softplus(dt_t + dt_bias)`` (no clamp: the config has no
``time_step_limit``); ``a_t = exp(dt_t A)``, ``A = -exp(A_log)``, a scalar a
head; ``S_t = a_t S_{t-1} + dt_t x_t (x) B_t[g(h)]`` from ``S = 0``;
``y_t = S_t C_t[g(h)] + D x_t``; the gate first and then its norm:
``y <- y silu(z)``, RMSNorm over each group of d / G channels, times a weight
``[d]``; ``out = y W_out``. **The recurrence is computed token by token** (a
``lax.scan`` over positions): independent of the program's blocked form.

``*``, attention: ``wq``, ``wk``, ``wv``, ``wo``; ``num_attention_heads`` query
heads over ``num_key_value_heads`` KV heads of ``head_dim``; causal softmax at
``1 / sqrt(head_dim)``; **no positional encoding** (``rope_scope: "none"``: the
published attention applies none, ``rope_theta`` is unread).

``E``, routed: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of
``s + bias`` are chosen (``n_group`` 1); weights ``s_chosen / (sum s_chosen +
1e-20) x routed_scaling_factor``; an expert is ``down(relu(up(x))^2)``, two
matrices and no gate; the shared expert the same form at its own width, added
once. The router keeps its published width; the experts held here are the
first ``n_routed_experts`` and what the others would add is left out.

Departures: weights are the engine's bf16 leaves widened to float32, not a
float32 master copy (``A_log``, ``dt_bias``, ``D`` and the selection bias are
float32 leaves already); every held expert is computed for every token and
the unchosen get weight 0, which is the same sum; a padded position
(``pad_to``) is masked in attention and, being behind the live ones, reaches
no live position through the causal convolution or the recurrence.

``routing_margin_at`` names the tied positions (``chipbench/README.md``): in
the biased sigmoid scores the choice is made in, over the held experts; it
also takes, for a probed position, the margins of the context positions it
attends to by ``ATTENDED`` or more in an attention layer above. What a flip
at an earlier position sends on through a Mamba layer's state has no weight
to read off: it decays with the distance, and is not counted.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's layout:
under ``layers`` a stack a kind of layer, ``wq``, ``wk``, ``wv``, ``wo``,
``attn_norm`` ``[A, ...]``; ``mlp_norm``, ``router [R, H, E_pub]``,
``router_bias``, ``w_up [R, E, H, M]``, ``w_down [R, E, M, H]``, ``shared_up``,
``shared_down``; ``ssm_norm``, ``ssm_in [M, H, d + c + heads]``, ``ssm_conv_w
[M, K, c]``, ``ssm_conv_b``, ``ssm_dt_bias``, ``ssm_A_log``, ``ssm_D``,
``ssm_gate_norm``, ``ssm_out [M, d, H]``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ATTENDED = 0.1     # as rehearsal/tiny-moe/reference.py: a tenth of a weight
EXPERT_LEAVES = ("w_up", "w_down")    # cut where they are used


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim"))
def _attention(x, lp, n_valid, *, n_heads, n_kv, head_dim):
    """(attention output [T, heads x D] before Wo, the largest weight any
    head gives each (query, key) pair [T, T]). One KV head's group of query
    heads, and four of its heads, at a time. No position enters."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        rep = n_heads // n_kv
        part = 4 if rep % 4 == 0 else 1
        pos = jnp.arange(t)
        q = (x @ _f32(lp["wq"])).reshape(t, n_kv * rep // part, part, head_dim)
        k = (x @ _f32(lp["wk"])).reshape(t, n_kv, head_dim)
        v = (x @ _f32(lp["wv"])).reshape(t, n_kv, head_dim)
        k = jnp.repeat(k, rep // part, axis=1)
        v = jnp.repeat(v, rep // part, axis=1)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)

        def group(qkv):
            qg, kg, vg = qkv                      # [T, part, D], [T, D], [T, D]
            s = jnp.einsum("qrd,kd->rqk", qg, kg) / jnp.sqrt(
                jnp.float32(head_dim))
            w = jax.nn.softmax(
                jnp.where(mask[None], s, jnp.finfo(jnp.float32).min), axis=-1)
            return jnp.einsum("rqk,kd->qrd", w, vg), jnp.max(w, axis=0)

        out, seen = jax.lax.map(group, (q.transpose(1, 0, 2, 3),
                                        k.transpose(1, 0, 2),
                                        v.transpose(1, 0, 2)))
        return (out.transpose(1, 0, 2, 3).reshape(t, n_heads * head_dim),
                jnp.max(seen, axis=0))


@jax.jit
def _project(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ _f32(w)


@jax.jit
def _relu2_ffn(x, w_up, w_down):
    """An expert without a gate: down(relu(up(x))^2)."""
    with jax.default_matmul_precision("highest"):
        return jnp.square(jax.nn.relu(x @ _f32(w_up))) @ _f32(w_down)


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "kernel", "eps"))
def _mamba(u, lp, *, heads, head_dim, groups, state, kernel, eps):
    """The Mamba-2 mixer on the normed state ``u [T, H]``, the recurrence
    token by token from a zero state."""
    with jax.default_matmul_precision("highest"):
        t = u.shape[0]
        d = heads * head_dim
        gn = groups * state
        zxd = u @ _f32(lp["ssm_in"])
        z, xbc, dt = zxd[:, :d], zxd[:, d:d + d + 2 * gn], zxd[:, 2 * d + 2 * gn:]
        # depthwise causal convolution, zeros before the first token
        seq = jnp.concatenate([jnp.zeros((kernel - 1, xbc.shape[1])), xbc])
        w = _f32(lp["ssm_conv_w"])                                  # [K, c]
        conv = _f32(lp["ssm_conv_b"])[None, :] + sum(
            w[k][None, :] * seq[k:k + t] for k in range(kernel))
        xbc = jax.nn.silu(conv)
        x = xbc[:, :d].reshape(t, heads, head_dim)
        bm = jnp.repeat(xbc[:, d:d + gn].reshape(t, groups, state),
                        heads // groups, axis=1)                    # [T, H, N]
        cm = jnp.repeat(xbc[:, d + gn:].reshape(t, groups, state),
                        heads // groups, axis=1)
        dt = jax.nn.softplus(dt + _f32(lp["ssm_dt_bias"])[None, :])  # [T, H]
        a = jnp.exp(dt * -jnp.exp(_f32(lp["ssm_A_log"]))[None, :])

        def step(s, xs):
            a_t, dt_t, x_t, b_t, c_t = xs
            s = a_t[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] \
                * b_t[:, None, :]
            return s, jnp.einsum("hpn,hn->hp", s, c_t)

        _, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state)),
                            (a, dt, x, bm, cm))
        y = y + _f32(lp["ssm_D"])[None, :, None] * x
        y = y.reshape(t, d) * jax.nn.silu(z)
        yg = y.reshape(t, groups, d // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
        return (yg.reshape(t, d) * _f32(lp["ssm_gate_norm"])) @ _f32(lp["ssm_out"])


def _margin(select, top_k, held):
    """[T]: how far the nearest held expert (the first ``held`` of the
    router's) is from changing sides, in the scores the choice is made in.
    For a held, chosen expert: its score less the best unchosen score; for a
    held, unchosen one: the weakest chosen score less its own."""
    top = jax.lax.top_k(select, top_k + 1)[0]
    weakest_chosen, best_unchosen = top[:, top_k - 1, None], top[:, top_k, None]
    distance = jnp.where(select >= weakest_chosen, select - best_unchosen,
                         weakest_chosen - select)
    is_held = jnp.arange(select.shape[-1]) < held
    return jnp.min(jnp.where(is_held, distance, jnp.inf), axis=-1)


@partial(jax.jit, static_argnames=("top_k", "held", "scale", "normalise"))
def _route(x, router, bias, *, top_k, held, scale, normalise):
    """([T, held] weight of each held expert, 0 where it is not chosen;
    [T] routing margin)."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ _f32(router))           # [T, E]
        select = scores + _f32(bias)[None, :]
        chosen = jax.lax.top_k(select, top_k)[1]             # [T, k]
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        if normalise:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        picked = picked * scale
        gates = jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], chosen].add(picked)
        return gates[:, :held], _margin(select, top_k, held)


def _routed(x, lp, layers, j, model):
    """Routed layer ``j`` of the routed stack; an expert's matrices are cut
    out of the stack one expert at a time."""
    held = layers["w_up"].shape[1]
    gates, margin = _route(
        x, lp["router"], lp["router_bias"],
        top_k=model["num_experts_per_tok"], held=held,
        scale=float(model.get("routed_scaling_factor") or 1.0),
        normalise=bool(model.get("norm_topk_prob", True)))
    out = jnp.zeros_like(x)
    for e in range(held):
        out = out + gates[:, e, None] * _relu2_ffn(
            x, layers["w_up"][j, e], layers["w_down"][j, e])
    if model.get("n_shared_experts"):
        out = out + _relu2_ffn(x, lp["shared_up"], lp["shared_down"])
    return out, margin


ATTN = ("wq", "wk", "wv", "wo", "attn_norm")
ROUTED = ("mlp_norm", "router", "router_bias", "shared_up", "shared_down")


def _forward(params, model: dict, tokens: list[int], positions: list[int],
             pad_to: int):
    """(logits [len(positions), vocab], routing margin [len(positions)]: the
    least over the routed layers)."""
    if model.get("model_type") != "nemotron_h" or (model.get("n_group") or 1) > 1:
        raise ValueError("this reference is NemotronH's block, routed by "
                         "sigmoid scores over one group")
    if model.get("mlp_hidden_act", "relu2") != "relu2" \
            or model.get("rope_scope", "none") != "none":
        raise ValueError("this reference computes relu2 experts without a "
                         "gate and attention without positions")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    at = jnp.asarray(positions)
    eps = float(model.get("layer_norm_epsilon", model.get("norm_eps", 1e-5)))
    layers = params["layers"]
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    h = _f32(params["embed"][jnp.asarray(ids)])
    margin = jnp.full((len(positions),), jnp.inf)
    below = jnp.full((len(ids),), jnp.inf)    # each position's, layers so far
    seen = {"M": 0, "*": 0, "E": 0}
    for kind in pattern:
        i = seen[kind]
        seen[kind] += 1
        if kind == "M":
            lp = {k: v[i] for k, v in layers.items() if k.startswith("ssm_")}
            out = _mamba(_rms_norm(h, lp["ssm_norm"], eps), lp,
                         heads=model["mamba_num_heads"],
                         head_dim=model["mamba_head_dim"],
                         groups=model["n_groups"],
                         state=model["ssm_state_size"],
                         kernel=model["conv_kernel"], eps=eps)
        elif kind == "*":
            lp = {k: layers[k][i] for k in ATTN}
            a, attended = _attention(
                _rms_norm(h, lp["attn_norm"], eps), lp, jnp.int32(n),
                n_heads=model["num_attention_heads"],
                n_kv=model["num_key_value_heads"], head_dim=model["head_dim"])
            out = _project(a, lp["wo"])
            # An expert flipped at an earlier position in a layer below
            # reaches this one through attention, by the weight it is
            # attended with.
            reach = jnp.where(attended[at] >= ATTENDED, below[None, :], jnp.inf)
            margin = jnp.minimum(margin, reach.min(axis=-1))
        elif kind == "E":
            lp = {k: layers[k][i] for k in ROUTED if k in layers}
            out, m = _routed(_rms_norm(h, lp["mlp_norm"], eps), lp, layers, i,
                             model)
            margin = jnp.minimum(margin, m[at])
            below = jnp.minimum(below, m)
        else:
            raise ValueError(f"layer kind {kind!r}: this reference has "
                             "'M', '*' and 'E'")
        h = h + out
    logits = _project(_rms_norm(h[at], params["final_norm"], eps),
                      params["lm_head"])
    return np.asarray(logits), np.asarray(margin)


def logits_at(params, model: dict, tokens: list[int], positions: list[int],
              pad_to: int = 0) -> np.ndarray:
    """Float32 logits [len(positions), vocab] after the tokens at
    ``positions`` of the sequence ``tokens``; ``pad_to`` pads the sequence
    (masked) so that several lengths share one compiled program."""
    return _forward(params, model, tokens, positions, pad_to)[0]


def routing_margin_at(params, model: dict, tokens: list[int],
                      positions: list[int], pad_to: int = 0) -> np.ndarray:
    """Float32 [len(positions)]: each position's routing margin, the least
    of ``_margin`` over the routed layers, from the parameters and the
    tokens alone."""
    return _forward(params, model, tokens, positions, pad_to)[1]
