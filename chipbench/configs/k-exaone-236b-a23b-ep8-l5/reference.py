"""The plain reference of ``k-exaone-236b-a23b-ep8-l5``: one chip's share of
K-EXAONE-236B-A23B's forward pass in straightforward ``jax.numpy``, float32
activations, highest matmul precision, no cache, no kernel, no batching, one
layer at a time and a layer in blocks (an attention group, an expert, a slice
of the dense FFN at a time) so that it fits beside the engine's pool.

The layer equations, from the model's ``config.json`` (H = 6144;
``RMS_w(z) = z / sqrt(mean(z^2) + eps) * w``):

- Attention, layer ``l`` of kind ``layer_types[l]``: ``q = x Wq`` (64 heads x
  128), ``k = x Wk``, ``v = x Wv`` (8 KV heads x 128), no biases. Scores
  ``q.k / sqrt(128)``; position ``i`` sees ``j <= i``, and in a
  ``sliding_attention`` layer only ``i - j < sliding_window`` (128); softmax
  in float32; ``o = concat(heads) Wo``.
- FFN of the first ``first_k_dense_replace`` layers: ``Wd(silu(Wg x) * (Wu
  x))``, width ``intermediate_size`` (18,432).
- FFN of every other layer (``scoring_func: sigmoid``, ``norm_topk_prob``,
  ``routed_scaling_factor`` 2.5, ``n_group = topk_group = 1``: group limiting
  is the identity): ``s = sigmoid(x Wr)``, ``Wr [H, 128]``; the
  ``num_experts_per_tok`` (8) experts with the largest ``s + b`` are chosen;
  weights ``g_i = 2.5 * s_i / sum of the chosen s_j``; ``y = sum over the
  chosen i held here of g_i E_i(x) + S(x)``, ``E_i`` and the one shared expert
  ``S`` SwiGLU of width ``moe_intermediate_size`` (2,048).
- Final RMSNorm and an untied head over the vocabulary slice.

**Assumed** (no key of ``config.json`` settles them; taken from the family's
published block, EXAONE 4.0, and stated in this configuration's
``config.json`` so that another choice is a change of data here and in the
program alike): ``qk_norm``: ``q`` and ``k`` pass an RMSNorm over the 128 of
each head; ``rope_scope: "sliding"``: rotary embedding (theta 1e6, default
type, half-rotation convention) on sliding layers only, full-attention layers
carry no position; ``norm_placement: "post"``: ``h' = h +
RMS_post_attn(Attn(h))``, ``h'' = h' + RMS_post_ffn(FFN(h'))``, the
sub-layer's input not normalised (``"pre"`` is Llama's ``h + f(RMS(h))``);
``router_bias``: the selection bias ``b [128]`` is present.

**The share.** ``num_experts`` (16) is what this chip holds of
``num_experts_published`` (128), the first 16 by convention; the router keeps
its published width. What the absent experts would add is left out, here as
in the program, and that partial result goes on to the next layer.
``vocab_size`` is the slice. ``num_nextn_predict_layers`` (the drafting block)
is not part of the main path and is absent.

Departures: weights are the engine's bf16 leaves widened to float32, not a
float32 master copy; every held expert is computed for every token and the
unchosen get weight 0, which is the same sum.

``routing_margin_at`` names the tied positions (``chipbench/README.md``): in
the units the choice is made in, the biased scores ``s + b``, over the held
experts alone; it also takes, for a probed position, the margins of the
context positions it attends to by ``ATTENDED`` or more in a layer above.

Shares no code with ``dynamo_tpu``; it reads only the parameter tree's layout:
under ``layers`` the routed layers' stacked ``[L - first_k, ...]`` leaves
under their plain names (``wq``, ``router [.., H, 128]``, ``router_bias``,
``w_gate [.., 16, H, M]``, ``shared_*``, ``q_norm``, ...) and the leading
dense layers' ``[first_k, ...]`` under ``lead_<name>``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ATTENDED = 0.1     # as rehearsal/tiny-moe/reference.py: a tenth of a weight
FFN_SLICE = 4608   # columns of the dense FFN computed at a time


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
                                   "eps", "window", "rope", "qk_norm"))
def _attention(x, lp, n_valid, *, n_heads, n_kv, head_dim, theta, eps,
               window, rope, qk_norm):
    """(attention output [T, heads x D] before Wo, the largest weight any
    head gives each (query, key) pair [T, T]). One KV head's group of query
    heads at a time."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        rep = n_heads // n_kv
        pos = jnp.arange(t)
        q = (x @ _f32(lp["wq"])).reshape(t, n_kv, rep, head_dim)
        k = (x @ _f32(lp["wk"])).reshape(t, n_kv, head_dim)
        v = (x @ _f32(lp["wv"])).reshape(t, n_kv, head_dim)
        if qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
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
            # window sees no key at all, and a row of -inf is NaN, which a
            # weight of 0 would then carry into the positions that count)
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
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _dense_ffn(x, layers, i):
    """Leading layer ``i``'s dense FFN a slice of its width at a time, cut
    out of the stack where it is used (a whole matrix taken out first would
    be a copy of it): the sum over slices of the columns' SwiGLU is the
    whole (the activation is columnwise)."""
    width = layers["lead_w_gate"].shape[-1]
    out = jnp.zeros_like(x)
    for lo in range(0, width, FFN_SLICE):
        hi = min(lo + FFN_SLICE, width)
        out = out + _swiglu(x, layers["lead_w_gate"][i, :, lo:hi],
                            layers["lead_w_up"][i, :, lo:hi],
                            layers["lead_w_down"][i, lo:hi])
    return out


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
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        picked = picked * scale
        gates = jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], chosen].add(picked)
        return gates[:, :held], _margin(select, top_k, held)


def _routed_ffn(x, lp, layers, j, model):
    """Routed layer ``j`` of the routed stack; an expert's matrices are cut
    out of the stack one expert at a time."""
    held = layers["w_gate"].shape[1]
    bias = lp.get("router_bias")
    if bias is None:
        bias = jnp.zeros((lp["router"].shape[-1],), jnp.float32)
    gates, margin = _route(
        x, lp["router"], bias, top_k=model["num_experts_per_tok"], held=held,
        scale=float(model.get("routed_scaling_factor") or 1.0),
        normalise=bool(model.get("norm_topk_prob", True)))
    out = jnp.zeros_like(x)
    for e in range(held):
        out = out + gates[:, e, None] * _swiglu(
            x, layers["w_gate"][j, e], layers["w_up"][j, e],
            layers["w_down"][j, e])
    if model.get("num_shared_experts"):
        out = out + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    return out, margin


FFN_LEAVES = ("w_gate", "w_up", "w_down")    # cut where they are used


def _layer_params(layers: dict, i: int, first_dense: int) -> dict:
    """Layer ``i``'s leaves but its FFN's large ones: of the leading dense
    stack (``lead_<name>``) or of the routed stack behind it."""
    if i < first_dense:
        return {k[len("lead_"):]: v[i] for k, v in layers.items()
                if k.startswith("lead_") and k[len("lead_"):] not in FFN_LEAVES}
    return {k: v[i - first_dense] for k, v in layers.items()
            if not k.startswith("lead_") and k not in FFN_LEAVES}


def _forward(params, model: dict, tokens: list[int], positions: list[int],
             pad_to: int):
    """(logits [len(positions), vocab], routing margin [len(positions)]: the
    least over the routed layers)."""
    if model.get("scoring_func") != "sigmoid" or (model.get("n_group") or 1) > 1:
        raise ValueError("this reference routes by sigmoid scores over one group")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    at = jnp.asarray(positions)
    eps = float(model["rms_norm_eps"])
    theta = float((model.get("rope_parameters") or {}).get("rope_theta")
                  or model.get("rope_theta"))
    pre = model.get("norm_placement", "pre") == "pre"
    first_dense = int(model.get("first_k_dense_replace") or 0)
    h = _f32(params["embed"][jnp.asarray(ids)])
    margin = jnp.full((len(positions),), jnp.inf)
    below = jnp.full((len(ids),), jnp.inf)    # each position's, layers so far
    for i in range(model["num_hidden_layers"]):
        lp = _layer_params(params["layers"], i, first_dense)
        sliding = model["layer_types"][i] == "sliding_attention"
        x = _rms_norm(h, lp["attn_norm"], eps) if pre else h
        a, attended = _attention(
            x, lp, jnp.int32(n), n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
            theta=theta, eps=eps,
            window=int(model["sliding_window"]) if sliding else 0,
            rope=sliding or model.get("rope_scope", "all") == "all",
            qk_norm=bool(model.get("qk_norm")))
        a = _project(a, lp["wo"])
        h = h + (a if pre else _rms_norm(a, lp["attn_norm"], eps))
        x = _rms_norm(h, lp["mlp_norm"], eps) if pre else h
        if i < first_dense:
            f = _dense_ffn(x, params["layers"], i)
        else:
            f, m = _routed_ffn(x, lp, params["layers"], i - first_dense, model)
            # An expert flipped at an earlier position in a layer below
            # reaches this one through attention, by the weight it is
            # attended with.
            reach = jnp.where(attended[at] >= ATTENDED, below[None, :], jnp.inf)
            margin = jnp.minimum(margin, jnp.minimum(m[at], reach.min(axis=-1)))
            below = jnp.minimum(below, m)
        h = h + (f if pre else _rms_norm(f, lp["mlp_norm"], eps))
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
