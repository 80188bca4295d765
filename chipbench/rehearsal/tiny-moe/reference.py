"""The plain reference of the rehearsal's ``tiny-moe`` configuration: a
decoder whose feed-forward is a routed mixture of experts, in
straightforward ``jax.numpy``, float32 activations, highest matmul precision,
no cache, no kernel, no batching, one layer at a time over the same
parameters the engine serves. The first user of the hook in
``harness/probe.py``: the harness finds this file by the configuration's
directory and calls ``logits_at``.

Attention is the default reference's (pre-norm residual blocks, RMSNorm,
rotary embedding in the half-rotation convention, grouped-query causal
attention scaled by 1/sqrt(head_dim)); it is written out again here because
a configuration's reference is one file that stands alone. The feed-forward
follows the sparsely-gated layer of Mixtral (arXiv:2401.04088, section 2.1)
with DeepSeekMoE's shared expert (arXiv:2401.06066, section 3.2): router
logits ``x W_r`` in float32 over all ``n_routed_experts``; the
``num_experts_per_tok`` largest are chosen per token; their weights are the
softmax over the chosen logits alone; the layer's output is the weighted sum
of the chosen experts' SwiGLU outputs plus, unweighted, one SwiGLU of width
``n_shared_experts x moe_intermediate_size`` that every token passes.
Departures: weights are the engine's bf16 leaves widened to float32; every
expert is computed for every token and the unchosen ones get weight 0, which
is the same sum.

``routing_margin_at`` says which probed positions are tied: where a held
expert's router score lies so close to the boundary of being chosen that
the engine's bf16 rounding can choose otherwise than this float32 pass, and
the position's logits then differ by tenths with no fault in the program
(``harness/probe.py`` leaves such a position out; ``about.json``'s ``probe``
block says how close is tied). Here every expert is held; a configuration
that is one chip's share of a deployment holds the first
``n_routed_experts`` of a router ``n_routed_experts_published`` wide
(``chipbench/README.md``), and ``_margin`` looks at those alone.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's
layout: stacked ``[L, ...]`` leaves under ``layers``, the experts' matrices
``[L, E, ...]``, ``router`` ``[L, H, E]``, ``shared_*`` for the shared expert.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# A context position counts toward a probed position's margin where some
# head attends to it by this much: a flip there moves that position's hidden
# state by about a chosen expert's weight (0.3 of a unit-scale output), and a
# tenth of that is what the tolerances allow.
ATTENDED = 0.1


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _margin(scores, top_k, held):
    """[T]: how far the nearest held expert (the first ``held`` of the
    router's ``E``) is from changing sides. For a held, chosen expert: its
    score less the best unchosen score; for a held, unchosen one: the
    weakest chosen score less its own; the least of those. In the units the
    choice is made in (here router logits: softmax keeps their order)."""
    top = jax.lax.top_k(scores, top_k + 1)[0]
    weakest_chosen, best_unchosen = top[:, top_k - 1, None], top[:, top_k, None]
    distance = jnp.where(scores >= weakest_chosen, scores - best_unchosen,
                         weakest_chosen - scores)
    is_held = jnp.arange(scores.shape[-1]) < held
    return jnp.min(jnp.where(is_held, distance, jnp.inf), axis=-1)


def _experts(x, lp, top_k, shared, held):
    f32 = lambda a: a.astype(jnp.float32)
    scores = x @ f32(lp["router"])                       # [T, E]
    n_experts = scores.shape[-1]
    chosen_scores, chosen = jax.lax.top_k(scores, top_k)   # [T, k]
    chosen_w = jax.nn.softmax(chosen_scores, axis=-1)
    out = jnp.zeros_like(x)
    for e in range(n_experts):
        w_e = jnp.sum(jnp.where(chosen == e, chosen_w, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * _swiglu(
            x, f32(lp["w_gate"][e]), f32(lp["w_up"][e]), f32(lp["w_down"][e]))
    if shared:
        out = out + _swiglu(x, f32(lp["shared_gate"]), f32(lp["shared_up"]),
                            f32(lp["shared_down"]))
    return out, _margin(scores, top_k, held)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta",
                                   "eps", "top_k", "shared", "held"))
def _layer(h, lp, n_valid, *, n_heads, n_kv, head_dim, theta, eps, top_k,
           shared, held):
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        f32 = lambda a: a.astype(jnp.float32)
        pos = jnp.arange(t)
        x = _rms_norm(h, lp["attn_norm"], eps)
        q = (x @ f32(lp["wq"])).reshape(t, n_heads, head_dim)
        k = (x @ f32(lp["wk"])).reshape(t, n_kv, head_dim)
        v = (x @ f32(lp["wv"])).reshape(t, n_kv, head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, n_heads // n_kv, axis=1)
        v = jnp.repeat(v, n_heads // n_kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(mask[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", w, v)
        h = h + a.reshape(t, n_heads * head_dim) @ f32(lp["wo"])
        out, margin = _experts(_rms_norm(h, lp["mlp_norm"], eps), lp, top_k,
                               shared, held)
        return h + out, margin, jnp.max(w, axis=0)


@jax.jit
def _head(hid, final_norm, w_out, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(hid, final_norm, eps) @ w_out.astype(jnp.float32)


def _forward(params, model: dict, tokens: list[int], positions: list[int],
             pad_to: int):
    """(logits [len(positions), vocab], routing margin [len(positions)]: the
    least over the layers)."""
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    at = jnp.asarray(positions)
    h = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    margin = jnp.full((len(positions),), jnp.inf)
    below = jnp.full((len(ids),), jnp.inf)    # each position's, layers so far
    for i in range(model["num_hidden_layers"]):
        lp = {k: v[i] for k, v in params["layers"].items()}
        h, m, attended = _layer(h, lp, jnp.int32(n),
                      n_heads=model["num_attention_heads"],
                      n_kv=model["num_key_value_heads"],
                      head_dim=model["head_dim"],
                      theta=float(model["rope_theta"]),
                      eps=float(model["rms_norm_eps"]),
                      top_k=model["num_experts_per_tok"],
                      shared=bool(model.get("n_shared_experts")),
                      held=model["n_routed_experts"])
        # An expert flipped at an earlier position in a layer below reaches
        # this one through attention, by the weight it is attended with.
        reach = jnp.where(attended[at] >= ATTENDED, below[None, :], jnp.inf)
        margin = jnp.minimum(margin, jnp.minimum(m[at], reach.min(axis=-1)))
        below = jnp.minimum(below, m)
    logits = _head(h[at], params["final_norm"], params["lm_head"],
                   jnp.float32(model["rms_norm_eps"]))
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
