"""The plain reference of ``smallthinker-21b-a3b-l12``: SmallThinker-21BA3B's
forward pass in straightforward ``jax.numpy``, float32 activations, highest
matmul precision, no cache, no kernel, no batching, one layer at a time and a
layer in blocks (an attention group, an expert at a time) so that it fits
beside the engine's pool.

One layer ``l``, from the model's ``config.json`` (H = 2560; input ``h [T, H]``;
``RMS_w(z) = z / sqrt(mean(z^2) + eps) * w``; no projection has a bias):

1. ``a = RMS_attn_norm(h)``.
2. Routing, from ``a``, before attention (the family's "router placed before
   attention"; ``router_input: "attn_norm"``): ``r = a W_r``, ``W_r [H, 64]``;
   ``S`` = the ``moe_num_active_primary_experts`` (6) largest of ``r``;
   ``w = softmax(r[S])`` (``moe_primary_router_apply_softmax`` with
   ``norm_topk_prob``: the softmax over all 64 renormalised over the chosen,
   the same numbers).
3. ``q = a W_q`` (28 heads x 128), ``k = a W_k``, ``v = a W_v`` (4 KV heads x
   128). Where ``rope_layout[l]`` is 1: rotary embedding (``rope_theta`` 1.5e6,
   rotate-half) on ``q`` and ``k``; where 0 no position enters. Where
   ``sliding_window_layout[l]`` is 1 query ``i`` sees the keys ``j`` with
   ``0 <= i - j < sliding_window_size`` (4096), else every ``j <= i``. Scores
   ``q.k / sqrt(128)``, softmax in float32, ``h' = h + concat(heads) W_o``.
4. ``m = RMS_mlp_norm(h')``; ``y = sum over e in S of w_e W_down^e
   (relu(m W_gate^e) * (m W_up^e))`` (``expert_act: "relu"``, the family's
   sparse ReGLU; width ``moe_ffn_hidden_size`` 768); ``h'' = h' + y``.
5. Behind the last layer: final RMSNorm and an untied head over the vocabulary.

**Assumed** (no key of the source's ``config.json`` settles them; each is a
key of this configuration's ``config.json``, so another choice is a change of
data here and in the program alike): ``expert_act`` (the source has no
``hidden_act``), ``router_input`` (that the router reads the normalised state
and not the raw residual), ``attention_bias`` false. Assumed without a key
(``about.json`` says so): the rotary embedding rotates the halves, as HF's
``rotate_half``; the family's "secondary experts" have no key in the 21B
config and none is modelled.

Departures: weights are the engine's bf16 leaves widened to float32, not a
float32 master copy; every held expert is computed for every token and the
unchosen get weight 0, which is the same sum.

``routing_margin_at`` names the tied positions (``chipbench/README.md``): in
the units the choice is made in, router logits (softmax keeps their order),
over the held experts, which here are all 64; it also takes, for a probed
position, the margins of the context positions it attends to by ``ATTENDED``
or more in a layer above. A layer's choice is made before its attention and
acts behind it, so what a flip changes is seen from the next layer on, as in
a model routed from the FFN's own input.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's layout:
under ``layers`` the stacked ``[L, ...]`` leaves ``wq``, ``wk``, ``wv``, ``wo``,
``attn_norm``, ``mlp_norm``, ``router [L, H, 64]`` and ``w_gate`` / ``w_up``
``[L, E, H, M]``, ``w_down [L, E, M, H]``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ATTENDED = 0.1     # as rehearsal/tiny-moe/reference.py: a tenth of a weight
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")    # cut where they are used


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, pos, theta):
    """x [T, ..., D]; rotate the halves (x1, x2) -> (x1 cos - x2 sin,
    x2 cos + x1 sin) with frequencies theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta",
                                   "window", "rope"))
def _attention(a, lp, n_valid, *, n_heads, n_kv, head_dim, theta, window,
               rope):
    """(attention output [T, heads x D] before Wo, the largest weight any
    head gives each (query, key) pair [T, T]). One KV head's group of query
    heads at a time."""
    with jax.default_matmul_precision("highest"):
        t = a.shape[0]
        rep = n_heads // n_kv
        pos = jnp.arange(t)
        q = (a @ _f32(lp["wq"])).reshape(t, n_kv, rep, head_dim)
        k = (a @ _f32(lp["wk"])).reshape(t, n_kv, head_dim)
        v = (a @ _f32(lp["wv"])).reshape(t, n_kv, head_dim)
        if rope:
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
        if window:
            mask = mask & (pos[:, None] - pos[None, :] < window)

        def group(qkv):
            qg, kg, vg = qkv                      # [T, rep, D], [T, D], [T, D]
            s = jnp.einsum("qrd,kd->rqk", qg, kg) / jnp.sqrt(
                jnp.float32(head_dim))
            # (the float's least, not -inf: a padded position past the
            # window sees no key at all, and a row of -inf is NaN)
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


@partial(jax.jit, static_argnames=("act",))
def _gated(x, w_gate, w_up, w_down, *, act):
    with jax.default_matmul_precision("highest"):
        return (ACTIVATIONS[act](x @ _f32(w_gate)) * (x @ _f32(w_up))) \
            @ _f32(w_down)


def _margin(logits, top_k, held):
    """[T]: how far the nearest held expert (the first ``held`` of the
    router's) is from changing sides, in router logits. For a held, chosen
    expert: its logit less the best unchosen one; for a held, unchosen one:
    the weakest chosen logit less its own."""
    top = jax.lax.top_k(logits, top_k + 1)[0]
    weakest_chosen, best_unchosen = top[:, top_k - 1, None], top[:, top_k, None]
    distance = jnp.where(logits >= weakest_chosen, logits - best_unchosen,
                         weakest_chosen - logits)
    is_held = jnp.arange(logits.shape[-1]) < held
    return jnp.min(jnp.where(is_held, distance, jnp.inf), axis=-1)


@partial(jax.jit, static_argnames=("top_k", "held"))
def _route(a, router, *, top_k, held):
    """([T, held] weight of each held expert, 0 where it is not chosen;
    [T] routing margin), from the state ``a`` that enters attention."""
    with jax.default_matmul_precision("highest"):
        logits = a @ _f32(router)                              # [T, E]
        chosen_logits, chosen = jax.lax.top_k(logits, top_k)   # [T, k]
        picked = jax.nn.softmax(chosen_logits, axis=-1)
        gates = jnp.zeros_like(logits).at[
            jnp.arange(a.shape[0])[:, None], chosen].add(picked)
        return gates[:, :held], _margin(logits, top_k, held)


def _experts(m, gates, layers, j, act):
    """Layer ``j``'s held experts on ``m``, weighted by ``gates``; an
    expert's matrices are cut out of the stack one expert at a time."""
    out = jnp.zeros_like(m)
    for e in range(gates.shape[1]):
        out = out + gates[:, e, None] * _gated(
            m, layers["w_gate"][j, e], layers["w_up"][j, e],
            layers["w_down"][j, e], act=act)
    return out


def _forward(params, model: dict, tokens: list[int], positions: list[int],
             pad_to: int):
    """(logits [len(positions), vocab], routing margin [len(positions)]: the
    least over the layers)."""
    if not model.get("moe_primary_router_apply_softmax", True) \
            or not model.get("norm_topk_prob", True):
        raise ValueError("this reference routes by the softmax over the "
                         "chosen logits")
    if model.get("router_input") != "attn_norm":
        raise ValueError("this reference routes from the attention norm's output")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    at = jnp.asarray(positions)
    eps = float(model["rms_norm_eps"])
    layers = params["layers"]
    held = layers["w_gate"].shape[1]
    h = _f32(params["embed"][jnp.asarray(ids)])
    margin = jnp.full((len(positions),), jnp.inf)
    below = jnp.full((len(ids),), jnp.inf)    # each position's, layers so far
    for i in range(model["num_hidden_layers"]):
        lp = {k: v[i] for k, v in layers.items() if k not in EXPERT_LEAVES}
        a = _rms_norm(h, lp["attn_norm"], eps)
        gates, m = _route(a, lp["router"],
                          top_k=model["moe_num_active_primary_experts"],
                          held=held)
        o, attended = _attention(
            a, lp, jnp.int32(n), n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
            theta=float(model["rope_theta"]),
            window=int(model["sliding_window_size"])
            if model["sliding_window_layout"][i] else 0,
            rope=bool(model["rope_layout"][i]))
        h = h + _project(o, lp["wo"])
        # An expert flipped at an earlier position in a layer below reaches
        # this one through attention, by the weight it is attended with.
        reach = jnp.where(attended[at] >= ATTENDED, below[None, :], jnp.inf)
        margin = jnp.minimum(margin, jnp.minimum(m[at], reach.min(axis=-1)))
        below = jnp.minimum(below, m)
        h = h + _experts(_rms_norm(h, lp["mlp_norm"], eps), gates, layers, i,
                         model["expert_act"])
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
    of ``_margin`` over the layers, from the parameters and the tokens
    alone."""
    return _forward(params, model, tokens, positions, pad_to)[1]
