"""The plain reference: a Mistral/Llama-shaped decoder's forward pass in
straightforward ``jax.numpy``, float32 activations, highest matmul precision,
no cache, no kernel, no batching, one layer at a time over the same
parameters the engine serves.

Follows the published architecture (Mistral 7B, arXiv:2310.06825, as
implemented by HF ``MistralForCausalLM``): pre-norm residual blocks,
RMSNorm, rotary embedding in the half-rotation convention on Q and K,
grouped-query causal attention scaled by 1/sqrt(head_dim), SwiGLU MLP, final
RMSNorm, untied (or tied) output head. No sliding window: both benchmark
configurations publish ``sliding_window: null``. Departure: weights are the
engine's bf16 leaves widened to float32, not a float32 master copy.

Shares no code with ``dynamo_tpu/models/llama.py``; it reads only the
parameter tree's layout (stacked ``[L, ...]`` leaves under ``layers``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, H, D]; rotate the halves (x1, x2) -> (x1 cos - x2 sin,
    x2 cos + x1 sin) with frequencies theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta", "eps"))
def _layer(h, lp, n_valid, *, n_heads, n_kv, head_dim, theta, eps):
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        f32 = lambda a: a.astype(jnp.float32)
        pos = jnp.arange(t)
        x = _rms_norm(h, lp["attn_norm"], eps)
        q = (x @ f32(lp["wq"])).reshape(t, n_heads, head_dim)
        k = (x @ f32(lp["wk"])).reshape(t, n_kv, head_dim)
        v = (x @ f32(lp["wv"])).reshape(t, n_kv, head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(mask[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        h = h + a.reshape(t, n_heads * head_dim) @ f32(lp["wo"])
        x = _rms_norm(h, lp["mlp_norm"], eps)
        gate = x @ f32(lp["w_gate"])
        up = x @ f32(lp["w_up"])
        return h + (jax.nn.silu(gate) * up) @ f32(lp["w_down"])


@jax.jit
def _head(hid, final_norm, w_out, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(hid, final_norm, eps) @ w_out.astype(jnp.float32)


def logits_at(params, model: dict, tokens: list[int], positions: list[int],
              pad_to: int = 0) -> np.ndarray:
    """Float32 logits [len(positions), vocab] after the tokens at
    ``positions`` of the sequence ``tokens``. ``pad_to`` pads the sequence
    (masked) so that several lengths share one compiled program."""
    n = len(tokens)
    t = max(pad_to, n)
    ids = np.zeros((t,), np.int32)
    ids[:n] = tokens
    hd = model.get("head_dim",
                   model["hidden_size"] // model["num_attention_heads"])
    h = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    layers = params["layers"]
    for i in range(model["num_hidden_layers"]):
        lp = {k: v[i] for k, v in layers.items()}
        h = _layer(h, lp, jnp.int32(n),
                   n_heads=model["num_attention_heads"],
                   n_kv=model["num_key_value_heads"], head_dim=hd,
                   theta=float(model["rope_theta"]),
                   eps=float(model["rms_norm_eps"]))
    w_out = (params["embed"].T if model.get("tie_word_embeddings")
             else params["lm_head"])
    return np.asarray(_head(h[jnp.asarray(positions)], params["final_norm"],
                            w_out, jnp.float32(model["rms_norm_eps"])))
