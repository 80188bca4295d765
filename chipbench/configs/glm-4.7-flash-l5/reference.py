"""The plain reference of ``glm-4.7-flash-l5``: the first five layers of
GLM-4.7-Flash's forward pass in straightforward ``jax.numpy``, float32
activations, highest matmul precision, no cache, no kernel, no batching, one
layer at a time and a layer in blocks (a head, an expert, a slice of the
dense FFN, a slice of the vocabulary at a time) so that it fits beside the
engine's pool.

Multi-head latent attention is computed here in its **expanded** form: a key
and a value of every head are built for every position. The program computes
the absorbed form (``dynamo_tpu/models/llama.py _latent_attention``: the
up-projections folded into the query and the output, attention over the
cached latent row alone). They are the same numbers in another order; that
the two agree is the test of the absorption.

The layer equations, from the model's ``config.json`` (H = 2048;
``RMS_w(z) = z / sqrt(mean(z^2) + eps) * w``; DeepSeek-V2's attention,
arXiv:2405.04434; DeepSeek-V3's router, arXiv:2412.19437):

- Attention, every layer, on ``x = RMS(h)``: ``c_q = RMS(x W_dq)`` (H ->
  ``q_lora_rank`` 768); ``[q_nope | q_rope] = c_q W_uq`` (20 heads x (192 +
  64)); ``[c_kv | k_r] = x W_dkv`` (H -> ``kv_lora_rank`` 512 + 64); ``c_kv
  <- RMS(c_kv)``; ``q_rope, k_r <- rope(., position)``, ``k_r`` one row
  shared by the 20 heads; per head ``k_nope = c_kv W_uk`` (512 -> 192), ``v
  = c_kv W_uv`` (512 -> 256); scores ``(q_nope . k_nope + q_rope . k_r) /
  sqrt(192 + 64)``; position ``i`` sees ``j <= i``; softmax in float32; ``o =
  concat(heads' softmax v) W_o`` (5120 -> H). No biases.
- FFN of the first ``first_k_dense_replace`` (1) layers: ``Wd(silu(Wg x) *
  (Wu x))``, width ``intermediate_size`` (10,240).
- FFN of every other layer (``topk_method: noaux_tc``: sigmoid scores and a
  stored correction bias; ``n_group = topk_group = 1``: group limiting is
  the identity): ``s = sigmoid(x Wr)`` over 64; the ``num_experts_per_tok``
  (4) experts with the largest ``s + b`` are chosen; weights ``g_i =
  routed_scaling_factor (1.8) * s_i / sum of the chosen s_j``; ``y = sum of
  g_i E_i(x) + S(x)``, ``E_i`` and the one shared expert ``S`` SwiGLU of
  width ``moe_intermediate_size`` (1,536).
- Final RMSNorm and an untied head over the vocabulary.

**Assumed** (stated in ``about.json``): the half-rotation rotary convention
on the 64 rope values (with seeded weights the published interleaving is a
permutation of ``W_uq``'s and ``W_dkv``'s columns); the softmax scale with no
extra factor (``rope_scaling`` is null); the shared expert's width 1,536 x
``n_shared_experts``. ``num_nextn_predict_layers`` (the drafting block) is
not part of the main path and is absent.

Departures: weights are the engine's bf16 leaves widened to float32, not a
float32 master copy; every expert is computed for every token and the
unchosen get weight 0, which is the same sum.

``routing_margin_at`` names the tied positions (``chipbench/README.md``): in
the units the choice is made in, the biased scores ``s + b``; it also takes,
for a probed position, the margins of the context positions it attends to by
``ATTENDED`` or more in a layer above.

Shares no code with the program under test; it reads only the parameter
tree's layout: under ``layers`` the routed layers' stacked ``[L - first_k,
...]`` leaves under their plain names (``wq_a [.., H, 768]``, ``q_a_norm``,
``wq_b [.., 768, 20 x 256]``, ``wkv_a [.., H, 576]``, ``kv_a_norm``, ``w_uk
[.., 20, 192, 512]`` (a head's ``W_uk`` transposed), ``w_uv [.., 20, 512,
256]``, ``wo [.., 5120, H]``, ``router [.., H, 64]``, ``router_bias``,
``w_gate [.., 64, H, M]``, ``shared_*``, ...) and the leading dense layer's
``[first_k, ...]`` under ``lead_<name>``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ATTENDED = 0.1     # as rehearsal/tiny-moe/reference.py: a tenth of a weight
FFN_SLICE = 2560   # columns of the dense FFN computed at a time
VOCAB_SLICE = 32768    # columns of the head computed at a time


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


@partial(jax.jit, static_argnames=("n_heads", "nope", "rot", "theta", "eps"))
def _attention(x, lp, n_valid, *, n_heads, nope, rot, theta, eps):
    """(attention output [T, heads x v] before W_o, the largest weight any
    head gives each (query, key) pair [T, T]). Expanded: every head's keys
    and values over every position, one head at a time."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        pos = jnp.arange(t)
        if "wq_a" in lp:
            q = _rms_norm(x @ _f32(lp["wq_a"]), lp["q_a_norm"], eps) \
                @ _f32(lp["wq_b"])
        else:
            q = x @ _f32(lp["wq"])
        q = q.reshape(t, n_heads, nope + rot)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
        ckv = x @ _f32(lp["wkv_a"])
        rank = ckv.shape[-1] - rot
        c_kv = _rms_norm(ckv[:, :rank], lp["kv_a_norm"], eps)
        k_r = _rope(ckv[:, rank:], pos, theta)                    # [T, rot]
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
        scale = 1.0 / jnp.sqrt(jnp.float32(nope + rot))

        def head(args):
            qn, qr, w_uk, w_uv = args     # [T,nope] [T,rot] [nope,rank] [rank,v]
            k_nope = c_kv @ w_uk.T                                 # [T, nope]
            v = c_kv @ w_uv                                        # [T, v]
            s = (qn @ k_nope.T + qr @ k_r.T) * scale
            w = jax.nn.softmax(
                jnp.where(mask, s, jnp.finfo(jnp.float32).min), axis=-1)
            return w @ v, w

        out, seen = jax.lax.map(head, (
            q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
            _f32(lp["w_uk"]), _f32(lp["w_uv"])))
        return (out.transpose(1, 0, 2).reshape(t, -1), jnp.max(seen, axis=0))


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
    out of the stack where it is used: the sum over slices of the columns'
    SwiGLU is the whole (the activation is columnwise)."""
    width = layers["lead_w_gate"].shape[-1]
    out = jnp.zeros_like(x)
    for lo in range(0, width, FFN_SLICE):
        hi = min(lo + FFN_SLICE, width)
        out = out + _swiglu(x, layers["lead_w_gate"][i, :, lo:hi],
                            layers["lead_w_up"][i, :, lo:hi],
                            layers["lead_w_down"][i, lo:hi])
    return out


def _margin(select, top_k):
    """[T]: how far the nearest expert is from changing sides, in the scores
    the choice is made in: the weakest chosen score less the best unchosen
    one."""
    top = jax.lax.top_k(select, top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k]


@partial(jax.jit, static_argnames=("top_k", "scale", "normalise"))
def _route(x, router, bias, *, top_k, scale, normalise):
    """([T, E] weight of each expert, 0 where it is not chosen; [T] routing
    margin)."""
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
        return gates, _margin(select, top_k)


def _routed_ffn(x, lp, layers, j, model):
    """Routed layer ``j`` of the routed stack; an expert's matrices are cut
    out of the stack one expert at a time."""
    bias = lp.get("router_bias")
    if bias is None:
        bias = jnp.zeros((lp["router"].shape[-1],), jnp.float32)
    gates, margin = _route(
        x, lp["router"], bias, top_k=model["num_experts_per_tok"],
        scale=float(model.get("routed_scaling_factor") or 1.0),
        normalise=bool(model.get("norm_topk_prob", True)))
    out = jnp.zeros_like(x)
    for e in range(layers["w_gate"].shape[1]):
        out = out + gates[:, e, None] * _swiglu(
            x, layers["w_gate"][j, e], layers["w_up"][j, e],
            layers["w_down"][j, e])
    if model.get("n_shared_experts"):
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


def _head(x, w):
    """The head a slice of the vocabulary at a time."""
    return jnp.concatenate(
        [_project(x, w[:, lo:lo + VOCAB_SLICE])
         for lo in range(0, w.shape[-1], VOCAB_SLICE)], axis=-1)


def _forward(params, model: dict, tokens: list[int], positions: list[int],
             pad_to: int):
    """(logits [len(positions), vocab], routing margin [len(positions)]: the
    least over the routed layers)."""
    if model.get("topk_method", "noaux_tc") != "noaux_tc" or (
            model.get("n_group") or 1) > 1:
        raise ValueError("this reference routes by biased sigmoid scores "
                         "over one group")
    n = len(tokens)
    ids = np.zeros((max(pad_to, n),), np.int32)
    ids[:n] = tokens
    at = jnp.asarray(positions)
    eps = float(model["rms_norm_eps"])
    first_dense = int(model.get("first_k_dense_replace") or 0)
    h = _f32(params["embed"][jnp.asarray(ids)])
    margin = jnp.full((len(positions),), jnp.inf)
    below = jnp.full((len(ids),), jnp.inf)    # each position's, layers so far
    for i in range(model["num_hidden_layers"]):
        lp = _layer_params(params["layers"], i, first_dense)
        a, attended = _attention(
            _rms_norm(h, lp["attn_norm"], eps), lp, jnp.int32(n),
            n_heads=model["num_attention_heads"],
            nope=model["qk_nope_head_dim"], rot=model["qk_rope_head_dim"],
            theta=float(model["rope_theta"]), eps=eps)
        h = h + _project(a, lp["wo"])
        x = _rms_norm(h, lp["mlp_norm"], eps)
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
        h = h + f
    logits = _head(_rms_norm(h[at], params["final_norm"], eps),
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
