"""Llama-family transformer in pure JAX over a paged KV cache.

This is the engine's model math — the part the reference delegates to
vLLM/SGLang/TRT-LLM (SURVEY.md §7: first-party JAX engine). Design points:

- **One forward for prefill and decode.** A step processes up to ``T``
  query tokens per sequence (T=chunk for prefill, T=1 for decode) against a
  paged KV cache addressed by per-request block tables. Static shapes per
  (batch-bucket, T-bucket) so XLA compiles once per bucket.
- **Token-major outside attention.** Embedding, norms, projections, rope,
  the KV scatter and the MLP run over ``[N, H]``: the step's live tokens,
  rows packed end to end, in a bucket N that the caller picks (``B*T`` is
  the plain rectangle, and what a decode step is). Only attention sees
  rows: ``q`` is laid out ``[B, T, heads, D]`` for the kernel and its
  output packed back (``TokenLayout``). A ragged mixed step so pays its
  matmuls for the tokens it has, not for ``B x T`` positions.
- **Layers are scanned** (``lax.scan`` over stacked layer params) so 80-layer
  models trace/compile in constant time. The whole KV cache is the scan's
  carry, written and read in place at (layer, block): no layer of it is
  ever cut out (``_run_layers``).
- **Paged attention via gather** in the portable path: context KV is gathered
  from cache blocks by block table then attended densely with position
  masking (XLA fuses this well); a Pallas kernel (ops/) replaces it on TPU.
- **Block 0 is the trash block**: padding tokens scatter their KV there, so
  no dynamic control flow is needed for ragged batches.

Sharding: logical axes annotated per param (parallel/mesh.py rules) — heads
and MLP intermediate on the "model" mesh axis, experts on "expert".
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.config import LayerPlan, ModelConfig, body_of
from dynamo_tpu.obs.profiler import phase as _perf_phase
from dynamo_tpu.utils.logging import get_logger

log = get_logger("models.llama")

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Parameter init (shapes + logical sharding axes)
# ---------------------------------------------------------------------------

LEAD = "lead_"   # prefix of the leading group's leaves under params["layers"]


def layer_stacks(layers: Params) -> dict[str, Params]:
    """``params["layers"]`` by the names a plan's mixers give their stacks
    (``Mixer.stack``). The leaves lie in two layouts, and this is the one
    reader of both (ROADMAP.md, Design: the debt). A model of attention
    then FFN layers has the repeated group "rep", the stacked ``[L_rep,
    ...]`` leaves under their plain names, and, where its first
    ``cfg.first_k_dense`` layers are of another shape (a dense FFN before
    routed ones), those as a second stack "lead" ``[L_lead, ...]`` under
    ``lead_<name>`` in the same flat dict: every leaf of ``layers`` stays an
    array, which is what the loaders, the sharding rules and the
    benchmark's weight rounding walk. A hybrid pattern has a stack a kind,
    "M", "*" and "E", told apart by the leaves' names; SambaY's layout
    (``cfg.decoder_layout``) has "M", "*", the cross layers' "X" (attention's
    names behind ``x_``), the memory units' "G" and the FFNs' "-"."""
    from dynamo_tpu.models import mamba

    rep = {k: v for k, v in layers.items() if not k.startswith(LEAD)}
    attn = ("wq", "wk", "wv", "wo", "attn_norm", "q_norm", "k_norm",
            *_SAMBAY_ATTN)
    if "gmu_in" in rep:
        return {
            "M": {k: v for k, v in rep.items() if k in mamba.LEAVES1},
            "*": {k: v for k, v in rep.items() if k in attn},
            "X": {k[2:]: v for k, v in rep.items() if k.startswith("x_")},
            "G": {k: v for k, v in rep.items() if k.startswith("gmu_")},
            "-": {k: v for k, v in rep.items() if k in _SAMBAY_FFN},
        }
    return {
        "lead": {k[len(LEAD):]: v for k, v in layers.items()
                 if k.startswith(LEAD)},
        "rep": rep,
        "M": {k: v for k, v in rep.items() if k in mamba.LEAVES},
        "*": {k: v for k, v in rep.items() if k in attn},
        "E": {k: v for k, v in rep.items()
              if k not in mamba.LEAVES and k not in attn},
    }


#: what SambaY's layout adds to an attention layer's leaves (a norm's bias,
#: the projections' biases, differential attention's four vectors and its
#: norm over a pair's width), and its FFN stack's leaves
_SAMBAY_ATTN = ("attn_norm_b", "bq", "bk", "bv", "bo", "diff_lq1", "diff_lk1",
                "diff_lq2", "diff_lk2", "diff_norm")
_SAMBAY_FFN = ("mlp_norm", "mlp_norm_b", "w_gate", "w_up", "w_down")


def _layer_axes(cfg: ModelConfig, routed: bool) -> Params:
    layer = {
        "wq": ("layers", None, "heads"),
        "wk": ("layers", None, "kv_heads"),
        "wv": ("layers", None, "kv_heads"),
        "wo": ("layers", "heads", None),
        "attn_norm": ("layers", None),
        "mlp_norm": ("layers", None),
    }
    if cfg.qk_norm:
        layer.update(q_norm=("layers", None), k_norm=("layers", None))
    if cfg.latent:
        # No leaf of it is divided: the engine refuses a mesh for this cache.
        for name in ("wq", "wk", "wv"):
            del layer[name]
        layer.update(
            wo=("layers", None, None), wkv_a=("layers", None, None),
            kv_a_norm=("layers", None), w_uk=("layers", None, None, None),
            w_uv=("layers", None, None, None),
            **({"wq_a": ("layers", None, None), "q_a_norm": ("layers", None),
                "wq_b": ("layers", None, None)} if cfg.q_lora_rank
               else {"wq": ("layers", None, None)}))
    if routed:
        layer.update(
            router=("layers", None, "expert"),
            w_gate=("layers", "expert", None, "moe_mlp"),
            w_up=("layers", "expert", None, "moe_mlp"),
            w_down=("layers", "expert", "moe_mlp", None),
        )
        if cfg.holds_share:
            # The router is as wide as the published model; the experts
            # held are this chip's and are not divided again.
            layer["router"] = ("layers", None, None)
        if cfg.router_bias:
            layer["router_bias"] = ("layers", None)
        if cfg.num_shared_experts:
            layer.update(
                shared_gate=("layers", None, "mlp"),
                shared_up=("layers", None, "mlp"),
                shared_down=("layers", "mlp", None),
            )
    else:
        layer.update(
            w_gate=("layers", None, "mlp"),
            w_up=("layers", None, "mlp"),
            w_down=("layers", "mlp", None),
        )
    return layer


def _hybrid_axes(cfg: ModelConfig) -> Params:
    """A hybrid pattern's leaves, a stack a kind of layer: attention's four
    matrices and norm, the routed layer's (two matrices an expert where it
    has no gate), the Mamba mixer's (models/mamba.py)."""
    from dynamo_tpu.models import mamba

    routed = _layer_axes(cfg, True)
    layer = {k: routed.pop(k) for k in ("wq", "wk", "wv", "wo", "attn_norm")}
    if not cfg.layers_of("*"):
        layer = {}
    if cfg.layers_of("E"):
        if not cfg.expert_gated:
            routed.pop("w_gate")
            routed.pop("shared_gate", None)
        layer.update(routed)
    if cfg.has_ssm:
        layer.update(mamba.logical_axes())
    return layer


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Logical axis names per parameter leaf (for mesh sharding rules)."""
    if cfg.decoder_layout:
        # No leaf is divided: the engine refuses a mesh for recurrent state.
        shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        return jax.tree.map(
            lambda a: (("layers",) if a.ndim else ()) + (None,) * (a.ndim - 1),
            shapes) | {"embed": ("vocab", None), "final_norm": (None,),
                       "final_norm_b": (None,)}
    layer = (_hybrid_axes(cfg) if cfg.hybrid_pattern
             else _layer_axes(cfg, cfg.is_moe))
    if cfg.ssm_beside_attention:
        from dynamo_tpu.models import mamba

        layer.update(mamba.logical_axes(own_norm=False))
    if cfg.first_k_dense:
        layer.update({LEAD + k: v
                      for k, v in _layer_axes(cfg, False).items()})
    axes: Params = {"embed": ("vocab", None), "final_norm": (None,), "layers": layer}
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = (None, "vocab")
    return axes


#: A leaf of more elements than this is drawn a layer at a time: its
#: float32 form (4 GiB and up) would not fit beside the finished weights.
INIT_WHOLE_MAX = 2**30


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init params (tests/tiny models; real weights come from loaders).

    Each leaf is drawn whole, as float32 normals, scaled and cast. One
    static rule on a leaf's size: a stack of more than ``INIT_WHOLE_MAX``
    elements is drawn a layer at a time instead, from its key split by
    layer, so that the float32 temporary is one layer's (64 experts of
    2560 x 768 over 12 layers are 6 GB of float32 whole, on a 16 GB chip
    beside two finished stacks). Such a stack's values differ from what a
    whole draw would give; every leaf under the limit keeps, bit for bit,
    what its seed always gave."""
    dt = _dtype(cfg)
    k = iter(jax.random.split(key, 24))
    h = cfg.hidden_size
    L = cfg.num_layers - cfg.first_k_dense     # the repeated group

    gain = cfg.init_gain        # {} for a model without multipliers

    def whole(key, shape, fan_in, pad=None, leaf=None):
        w = jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)
        if leaf in gain:        # a scalar, or one a column
            w = w * gain[leaf]
        w = w.astype(dt)
        if pad:     # (axis from the end, stored size): zeros behind the draw
            axis, size = pad
            widths = [(0, 0)] * len(shape)
            widths[axis] = (0, size - shape[axis])
            w = jnp.pad(w, widths)
        return w

    def dense(key, shape, fan_in, pad=None, leaf=None):
        if math.prod(shape) <= INIT_WHOLE_MAX:
            return whole(key, shape, fan_in, pad, leaf)
        return lax.map(lambda k1: whole(k1, shape[1:], fan_in, pad, leaf),
                       jax.random.split(key, shape[0]))

    def latent_attention(L):
        # What latent attention (``cfg.latent``) has in place of ``wq``
        # (where the query has a down-projection), ``wk`` and ``wv``: the
        # two down-projections with their norms, the query's up-projection,
        # and the latent's two up-projections by head, ``w_uk [heads, nope,
        # rank]`` (keys; stored transposed, as the absorb reads it) and
        # ``w_uv [heads, rank, v]``. (The norms' weights around 1, not at
        # it: a norm left out, or its weight, is then something a
        # comparison can see.)
        r, qr, heads = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.num_heads
        ks = iter(jax.random.split(next(k), 12))

        def around_one(shape):
            return (1.0 + 0.1 * jax.random.normal(
                next(ks), shape, jnp.float32)).astype(dt)

        out = {
            "wkv_a": dense(next(ks), (L, h, cfg.latent_row), h),
            "kv_a_norm": around_one((L, r)),
            "w_uk": dense(next(ks), (L, heads, cfg.qk_nope_head_dim, r), r),
            "w_uv": dense(next(ks), (L, heads, r, cfg.v_head_dim), r),
            "wo": dense(next(ks), (L, cfg.o_size, h), cfg.o_size),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        }
        if qr:
            out.update(wq_a=dense(next(ks), (L, h, qr), h),
                       q_a_norm=around_one((L, qr)),
                       wq_b=dense(next(ks), (L, qr, cfg.q_size), qr))
        else:
            out["wq"] = dense(next(ks), (L, h, cfg.q_size), h)
        return out

    def attention(L):
        if cfg.latent:
            return latent_attention(L)
        out = {
            "wq": dense(next(k), (L, h, cfg.q_size), h, leaf="wq"),
            "wk": dense(next(k), (L, h, cfg.kv_size), h, leaf="wk"),
            "wv": dense(next(k), (L, h, cfg.kv_size), h, leaf="wv"),
            "wo": dense(next(k), (L, cfg.q_size, h), cfg.q_size, leaf="wo"),
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
        }
        if cfg.qk_norm:
            out.update(q_norm=jnp.ones((L, cfg.head_dim), dt),
                       k_norm=jnp.ones((L, cfg.head_dim), dt))
        return out

    def dense_ffn(L):
        i = cfg.intermediate_size
        return {"w_gate": dense(next(k), (L, h, i), h, leaf="w_gate"),
                "w_up": dense(next(k), (L, h, i), h),
                "w_down": dense(next(k), (L, i, h), i, leaf="w_down")}

    if cfg.hybrid_pattern:
        return _init_hybrid(cfg, k, dense, attention)
    if cfg.decoder_layout:
        return _init_sambay(cfg, k, dense, attention, dense_ffn)
    layer: Params = attention(L)
    if cfg.is_moe:
        E, m = cfg.num_experts, cfg.moe_intermediate_size
        layer.update(
            router=dense(next(k), (L, h, cfg.router_width), h),
            w_gate=dense(next(k), (L, E, h, m), h),
            w_up=dense(next(k), (L, E, h, m), h),
            w_down=dense(next(k), (L, E, m, h), m),
        )
        if cfg.num_shared_experts:
            sm = cfg.moe_intermediate_size * cfg.num_shared_experts
            layer.update(
                shared_gate=dense(next(k), (L, h, sm), h),
                shared_up=dense(next(k), (L, h, sm), h),
                shared_down=dense(next(k), (L, sm, h), sm),
            )
    else:
        layer.update(dense_ffn(L))
    params: Params = {
        "embed": dense(next(k), (cfg.vocab_size, h), h, leaf="embed"),
        "final_norm": jnp.ones((h,), dt),
        "layers": layer,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(k), (h, cfg.vocab_size), h,
                                  leaf="lm_head")
    # Drawn behind everything above, so that a model without them keeps
    # the weights its seed always gave.
    if cfg.ssm_beside_attention:
        # The mixer beside attention reads attention's norm: none of its own.
        from dynamo_tpu.models import mamba

        layer.update(mamba.init_layers(cfg, dense, next(k), L, own_norm=False))
    if cfg.is_moe and cfg.router_bias:
        # A selection bias is learned to even the experts' load: small
        # beside the scores it is added to, and not zero, so that a choice
        # by score alone reads otherwise.
        layer["router_bias"] = 0.02 * jax.random.normal(
            next(k), (L, cfg.router_width), jnp.float32)
    if cfg.first_k_dense:
        lead = {**attention(cfg.first_k_dense), **dense_ffn(cfg.first_k_dense)}
        layer.update({LEAD + name: v for name, v in lead.items()})
    return params


def _init_hybrid(cfg: ModelConfig, k, dense, attention) -> Params:
    """:func:`init_params` for a hybrid pattern: a stack a kind of layer,
    each as long as the pattern has layers of that kind (``k``: the keys'
    iterator, ``dense`` and ``attention`` the draws of :func:`init_params`).
    A layer is one mixer under one norm, so attention's stack has no
    ``mlp_norm`` and the routed one no attention."""
    from dynamo_tpu.models import mamba

    dt = _dtype(cfg)
    h = cfg.hidden_size
    layer: Params = {}
    if A := cfg.layers_of("*"):
        layer.update(attention(A))
        del layer["mlp_norm"]
    if R := cfg.layers_of("E"):
        E, m, sm = (cfg.num_experts, cfg.moe_intermediate_size,
                    cfg.shared_expert_width)
        # Stored at cfg.expert_store_width: zeros behind the model's width.
        stored = cfg.expert_store_width
        cols = (-1, stored) if stored != m else None
        rows = (-2, stored) if stored != m else None
        layer["mlp_norm"] = jnp.ones((R, h), dt)
        layer["router"] = dense(next(k), (R, h, cfg.router_width), h)
        if cfg.expert_gated:
            layer["w_gate"] = dense(next(k), (R, E, h, m), h, cols)
        layer["w_up"] = dense(next(k), (R, E, h, m), h, cols)
        layer["w_down"] = dense(next(k), (R, E, m, h), m, rows)
        if cfg.num_shared_experts:
            if cfg.expert_gated:
                layer["shared_gate"] = dense(next(k), (R, h, sm), h)
            layer["shared_up"] = dense(next(k), (R, h, sm), h)
            layer["shared_down"] = dense(next(k), (R, sm, h), sm)
        if cfg.router_bias:
            layer["router_bias"] = 0.02 * jax.random.normal(
                next(k), (R, cfg.router_width), jnp.float32)
    if M := cfg.layers_of("M"):
        layer.update(mamba.init_layers(cfg, dense, next(k), M))
    params: Params = {
        "embed": dense(next(k), (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dt),
        "layers": layer,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(k), (h, cfg.vocab_size), h)
    return params


def _init_sambay(cfg: ModelConfig, k, dense, attention, dense_ffn) -> Params:
    """:func:`init_params` for SambaY's layout (``cfg.decoder_layout``): a
    stack a kind of mixer and one of all the layers' FFNs (``k``, ``dense``,
    ``attention``, ``dense_ffn``: the keys' iterator and the draws of
    :func:`init_params`). Norm weights are drawn around 1 and every bias,
    lambda vector and ``D`` around its published start with a spread, not at
    it: a leaf at exactly 0 or 1 is one a comparison with the reference
    cannot see (tests/test_phi4_flash.py leaves each out in turn)."""
    from dynamo_tpu.models import mamba

    dt = _dtype(cfg)
    h, d, hd = cfg.hidden_size, cfg.ssm_inner, cfg.head_dim
    keys = iter(jax.random.split(next(k), 64))

    def around(mean, shape, spread, dtype=dt):
        return (mean + spread * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    def norm(name, n):
        return {name: around(1.0, (n, h), 0.1),
                name + "_b": around(0.0, (n, h), 0.1)}

    def diff(n):
        # (the published start of the four vectors is N(0, 0.1))
        return {**{f"diff_{v}": around(0.0, (n, hd), 0.1, jnp.float32)
                   for v in ("lq1", "lk1", "lq2", "lk2")},
                "diff_norm": around(1.0, (n, 2 * hd), 0.1)}

    A, X, G = cfg.attn_layers, cfg.layers_of("X"), cfg.layers_of("G")
    layer: Params = attention(A)
    del layer["mlp_norm"]
    layer.update(norm("attn_norm", A), **diff(A), **{
        b: around(0.0, (A, w), 0.1) for b, w in (
            ("bq", cfg.q_size), ("bk", cfg.kv_size), ("bv", cfg.kv_size),
            ("bo", h))})
    layer.update(
        x_wq=dense(next(k), (X, h, cfg.q_size), h),
        x_wo=dense(next(k), (X, cfg.q_size, h), cfg.q_size),
        x_bq=around(0.0, (X, cfg.q_size), 0.1),
        x_bo=around(0.0, (X, h), 0.1),
        **{"x_" + name: v for name, v in {**norm("attn_norm", X),
                                          **diff(X)}.items()})
    layer.update(norm("gmu_norm", G),
                 gmu_in=dense(next(k), (G, h, d), h),
                 gmu_out=dense(next(k), (G, d, h), d))
    layer.update(mamba.init_layers1(cfg, dense, next(k), cfg.layers_of("S")),
                 **norm("ssm_norm", cfg.layers_of("S")))
    layer.update(dense_ffn(cfg.num_layers), **norm("mlp_norm", cfg.num_layers))
    final = norm("final_norm", 1)
    return {
        "embed": dense(next(k), (cfg.vocab_size, h), h),
        "final_norm": final["final_norm"][0],
        "final_norm_b": final["final_norm_b"][0],
        "layers": layer,
    }


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w + b


def norm_of(cfg: ModelConfig, x: jax.Array, lp: Params, name: str) -> jax.Array:
    """The model's norm of ``x`` under the leaf ``name`` of ``lp``: RMSNorm,
    or LayerNorm with the bias ``<name>_b`` (``cfg.norm_kind``)."""
    if cfg.norm_kind == "layer":
        return layer_norm(x, lp[name], lp[name + "_b"], cfg.rms_norm_eps)
    return rms_norm(x, lp[name], cfg.rms_norm_eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, half-rotate (HF llama) convention.

    x: [..., H, D]; positions: [...] (token-major [N] in the step, [B, T]
    for a rectangle).
    """
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [...,D/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


#: floor for quantization scales — avoids div-by-zero on all-zero updates
#: (e.g. trash-block padding writes) while keeping real scales untouched.
_KV_SCALE_EPS = 1e-8


def _as_layers(cache):
    """A single layer's cache [NB,...] seen as a one-layer stack [1,NB,...]
    (a free reshape), so the helpers below have one form: the whole cache
    and a layer index."""
    return jax.tree.map(lambda a: a[None], cache)


def _scatter_kv(cache, new: jax.Array, slot_idx: jax.Array, layer=None):
    """Write new KV [N,KH,D] (or a rectangle [B,T,KH,D]) into layer
    ``layer`` of the paged cache [L,NB,BS,KH,D] at flat slots, and return
    the whole cache: one scatter on the buffer itself, which XLA does in
    place where the buffer is donated and loop-carried. No layer of it is
    cut out and put back. ``layer=None`` takes a single layer
    [NB,BS,KH,D] and returns one.

    slot_idx: [N] (or [B,T]) flat slot index (block*block_size + offset);
    padding tokens point at the trash block (block 0).

    Quantized caches ({"q": int8 [L,NB,BS,KH,D], "s": f32 [L,NB,KH]})
    quantize at scatter time, symmetric per-block-per-head (engine/cache.py).
    """
    if layer is None:
        out = _scatter_kv(_as_layers(cache), new, slot_idx, 0)
        return jax.tree.map(lambda a: a[0], out)
    if isinstance(cache, dict):
        return _scatter_kv_quant(cache, new, slot_idx, layer)
    _, _, bs, kh, d = cache.shape
    idx = slot_idx.reshape(-1)
    vals = new.reshape(-1, kh, d)
    return cache.at[layer, idx // bs, idx % bs].set(vals, mode="drop")


def _scatter_kv_quant(cache: dict, new: jax.Array, slot_idx: jax.Array,
                      layer) -> dict:
    """Int8/int4 scatter: abs-max over the block update sets/merges the
    block's per-head scale, existing rows of touched blocks are rescaled to
    the new scale, then the new rows are quantized and written.

    A write at block offset 0 marks the block as freshly (re)tenanted and
    resets its scale — otherwise a recycled block would inherit the previous
    tenant's (possibly much larger) scale forever. Mid-block writes merge via
    max so already-committed rows never lose range. Rows past the write
    frontier hold stale garbage but every reader masks by kv_len.

    A uint8 payload means packed int4 (engine/cache.py): values quantize to
    ±7 and pack two nibbles per byte along head_dim; the scale lifecycle
    (reset / max-merge / requant of committed rows) is identical — requant
    unpacks, rescales, and repacks the touched blocks.

    Like the plain scatter it works on the whole cache: the touched blocks
    are read and written at ``(layer, blk)``, and only the layer's scales
    ([NB,KH], small) are taken out and put back.
    """
    from dynamo_tpu.ops.paged_attention import pack_int4, unpack_int4

    q, s_all = cache["q"], cache["s"]
    s = s_all[layer]                                             # [NB,KH]
    int4 = q.dtype == jnp.uint8
    qmax = 7.0 if int4 else 127.0
    _, nb, bs, kh, _dp = q.shape
    d = new.shape[-1]
    idx = slot_idx.reshape(-1)                                   # [N]
    vals = new.reshape(-1, kh, d).astype(jnp.float32)            # [N,KH,D]
    blk = jnp.clip(idx // bs, 0, nb - 1)
    off = idx % bs

    row_amax = jnp.max(jnp.abs(vals), axis=-1)                   # [N,KH]
    upd_amax = jnp.zeros((nb, kh), jnp.float32).at[blk].max(row_amax)
    resets = jnp.zeros((nb,), jnp.int32).at[blk].max(
        (off == 0).astype(jnp.int32)) > 0                        # fresh tenant
    s_cand = upd_amax / qmax
    s_new = jnp.where(resets[:, None], s_cand, jnp.maximum(s, s_cand))
    s_new = jnp.maximum(s_new, jnp.where(upd_amax > 0, _KV_SCALE_EPS, s_new))

    # Rescale the already-written rows of every touched block. Gathering per
    # token row (duplicates write identical values) keeps shapes static; cost
    # is bounded by (tokens-in-update × block_size), not by NB.
    ratio = jnp.where(s_new > 0, s / jnp.maximum(s_new, _KV_SCALE_EPS), 0.0)
    old = q[layer, blk]                                          # [N,BS,KH,Dp]
    old = (unpack_int4(old) if int4 else old).astype(jnp.float32)  # [N,BS,KH,D]
    requant = jnp.clip(jnp.round(old * ratio[blk][:, None, :, None]),
                       -qmax, qmax).astype(jnp.int32)
    requant = pack_int4(requant) if int4 else requant.astype(jnp.int8)
    q = q.at[layer, blk].set(requant, mode="drop")

    # Quantize and write the new rows (overwrites the rescaled slots).
    s_rows = jnp.maximum(s_new[blk], _KV_SCALE_EPS)              # [N,KH]
    q_rows = jnp.clip(jnp.round(vals / s_rows[:, :, None]), -qmax, qmax)
    q_rows = (pack_int4(q_rows.astype(jnp.int32)) if int4
              else q_rows.astype(jnp.int8))
    q = q.at[layer, idx // bs, off].set(q_rows, mode="drop")
    return {"q": q, "s": s_all.at[layer].set(s_new)}


def _gather_kv(cache, block_tables: jax.Array, layer=None) -> jax.Array:
    """Gather context KV of layer ``layer``: cache [L,NB,BS,KH,D],
    block_tables [B,NBLK] → [B, NBLK*BS, KH, D] laid out in position order,
    one gather at ``(layer, block)`` of the whole cache. ``layer=None``
    takes a single layer [NB,BS,KH,D]. Quantized caches are dequantized on
    gather (dense fallback path); packed-int4 payloads (uint8) unpack their
    nibbles first."""
    if layer is None:
        return _gather_kv(_as_layers(cache), block_tables, 0)
    if isinstance(cache, dict):
        from dynamo_tpu.ops.paged_attention import unpack_int4

        g = cache["q"][layer, block_tables]               # [B,NBLK,BS,KH,Dp]
        if g.dtype == jnp.uint8:
            g = unpack_int4(g)
        g = g.astype(jnp.float32)
        g = g * cache["s"][layer, block_tables][:, :, None, :, None]
    else:
        g = cache[layer, block_tables]                    # [B,NBLK,BS,KH,D]
    b, nblk, bs, kh, d = g.shape
    return g.reshape(b, nblk * bs, kh, d)


def _cache_block_size(cache) -> int:
    """block_size from a per-layer-stacked cache (plain array or {"q","s"})."""
    return (cache["q"] if isinstance(cache, dict) else cache).shape[2]


def paged_attention(
    q: jax.Array,           # [B, T, H, D]
    ctx_k: jax.Array,       # [B, S, KH, D]
    ctx_v: jax.Array,       # [B, S, KH, D]
    q_positions: jax.Array,  # [B, T]
    kv_lens: jax.Array,      # [B] total valid context length
    window: int = 0,         # static; > 0: query i sees keys j, i - j < window
    scale: float | None = None,  # on q; None: head size ** -0.5
) -> jax.Array:
    """Dense attention over gathered paged context with causal position mask.

    Portable path (CPU + TPU); the Pallas paged-attention kernel
    (ops/paged_attention.py) is numerically equivalent.
    """
    b, t, h, d = q.shape
    s = ctx_k.shape[1]
    kh = ctx_k.shape[2]
    rep = h // kh
    qf = q.astype(jnp.float32) * (d**-0.5 if scale is None else scale)
    qf = qf.reshape(b, t, kh, rep, d)
    scores = jnp.einsum("btkrd,bskd->btkrs", qf, ctx_k.astype(jnp.float32))
    ctx_idx = jnp.arange(s)[None, None, :]                      # [1,1,S]
    visible = (ctx_idx <= q_positions[:, :, None]) & (ctx_idx < kv_lens[:, None, None])
    if window:
        visible = visible & (ctx_idx > q_positions[:, :, None] - window)
    scores = jnp.where(visible[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkrs,bskd->btkrd", probs, ctx_v.astype(jnp.float32))
    return out.reshape(b, t, h, ctx_v.shape[-1]).astype(q.dtype)


def mm(x: jax.Array, w) -> jax.Array:
    """Dense matmul that understands weight-only int8 leaves
    ({"q": int8, "so": per-out-channel scale} — models/quant.py). The
    scale factors out of the contraction exactly; XLA fuses the int8→bf16
    widening into the dot so weights stream from HBM at 1 byte/elem."""
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["so"].astype(x.dtype)
    return x @ w


def embed_lookup(embed, token_ids: jax.Array, dt) -> jax.Array:
    """Embedding gather over a plain or row-quantized ({"q","sr"}) table."""
    if isinstance(embed, dict):
        return (embed["q"][token_ids].astype(dt)
                * embed["sr"][token_ids][..., None].astype(dt))
    return embed[token_ids].astype(dt)


def swiglu(x: jax.Array, w_gate, w_up, w_down,
           multipliers: tuple[float, ...] = ()) -> jax.Array:
    """``multipliers``: a scalar on the gate before its activation and one
    on the output (``ModelConfig.mlp_multipliers``); () is none."""
    gate = mm(x, w_gate)
    if multipliers:
        gate = gate * multipliers[0]
    out = mm(jax.nn.silu(gate) * mm(x, w_up), w_down)
    return out * multipliers[1] if multipliers else out


def moe_mlp(x: jax.Array, lp: Params, cfg: ModelConfig,
            routing=None) -> jax.Array:
    """MoE FFN, all-experts formulation (every held expert computed for
    every token, combined by the router's weights). Exact for any E and
    E/k times the work: the plain form the grouped ones are held to
    (tests/test_moe.py, tests/test_smallthinker.py). No engine serves it:
    on one chip every routed model runs the grouped ``held_rows``, share
    or whole, and across an "expert" axis the dropless one (models/moe.py;
    ``ModelRunner.moe_impl``). ``routing``: as ``moe_mlp_held``'s.

    x: [..., H] (token-major [N, H] in the step)
    """
    from dynamo_tpu.models.moe import gate_act, route, ungated_ffn

    h = x.shape[-1]
    xt = x.reshape(-1, h)                                     # [N, H]
    topi, weights = routing or route(xt, lp, cfg)             # [N, k]
    e = lp["w_up"].shape[0]                                   # experts held
    gate_mask = jnp.zeros((xt.shape[0], cfg.router_width), jnp.float32)
    gate_mask = gate_mask.at[jnp.arange(xt.shape[0])[:, None], topi].add(
        weights)[:, :e]                                       # [N, E]
    # all-experts compute: [N,E,m]
    up = jnp.einsum("nh,ehm->nem", xt, lp["w_up"])
    if cfg.expert_gated:
        gate = jnp.einsum("nh,ehm->nem", xt, lp["w_gate"])
        act = gate_act(cfg)(gate) * up
    else:
        act = gate_act(cfg)(up)
    per_expert = jnp.einsum("nem,emh->neh", act, lp["w_down"])
    out = jnp.einsum("neh,ne->nh", per_expert.astype(jnp.float32), gate_mask).astype(x.dtype)
    if cfg.num_shared_experts and cfg.expert_gated:
        out = out + swiglu(xt, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    elif cfg.num_shared_experts:
        out = out + ungated_ffn(xt, lp["shared_up"], lp["shared_down"],
                                gate_act(cfg))
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

class TokenLayout(NamedTuple):
    """How a step's rows ``[B, T]`` map onto its tokens ``[N]``.

    Tokens are the rows' live query tokens packed end to end (row 0's
    ``q_len[0]`` tokens, then row 1's, ...), padded to the bucket N. The
    dense layers run over tokens; attention runs over rows. ``tok_row`` /
    ``tok_off`` name each token's rectangle position and ``row_tok`` each
    rectangle position's token; padding on either side points at some live
    neighbour, whose value is computed twice and read by nobody. The paged
    kernel needs neither move: it finds a row's queries among the tokens by
    the row's first token, ``starts`` (``_attention``).

    With ``N == B*T`` the packing is the rectangle itself, row-major: the
    four index arrays are None and both moves are reshapes. That is a
    decode step (T=1) and every caller that keeps a rectangle."""

    b: int
    t: int
    tok_row: jax.Array | None = None   # [N] row of each token
    tok_off: jax.Array | None = None   # [N] offset of each token in its row
    row_tok: jax.Array | None = None   # [B, T] token at each row position
    starts: jax.Array | None = None    # [B] first token of each row

    def to_rows(self, x: jax.Array) -> jax.Array:
        """[N, ...] -> [B, T, ...]."""
        if self.row_tok is None:
            return x.reshape(self.b, self.t, *x.shape[1:])
        return x[self.row_tok]

    def to_tokens(self, x: jax.Array) -> jax.Array:
        """[B, T, ...] -> [N, ...]."""
        if self.tok_row is None:
            return x.reshape(self.b * self.t, *x.shape[2:])
        return x[self.tok_row, self.tok_off]


def token_layout(q_len: jax.Array, b: int, t: int, n: int) -> tuple[
        TokenLayout, jax.Array]:
    """The layout of a step with ``q_len [B]`` live tokens a row in a
    bucket of ``n`` tokens, and which of the n are live ``[N]``. The caller
    sees to it that ``sum(q_len) <= n``: tokens past n would be dropped."""
    if n == b * t:
        valid = jnp.arange(t)[None, :] < q_len[:, None]
        return TokenLayout(b, t), valid.reshape(-1)
    ends = jnp.cumsum(q_len)
    starts = ends - q_len
    i = jnp.arange(n, dtype=q_len.dtype)
    tok_row = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), b - 1)
    tok_off = jnp.clip(i - starts[tok_row], 0, t - 1)
    row_tok = jnp.minimum(
        starts[:, None] + jnp.arange(t, dtype=q_len.dtype)[None, :], n - 1)
    return TokenLayout(b, t, tok_row, tok_off, row_tok, starts), i < ends[-1]


def _attention(cfg: ModelConfig, lp: Params, layer, x, cache_k, cache_v, *,
               lay: TokenLayout, positions, slot, block_tables, q_start,
               kv_lens, attn_impl: str = "dense", mesh=None,
               use_ring: bool = False, window: int = 0, cross: bool = False,
               lambda_init=None):
    """The attention mixer on the normed state ``x [N, H]``: Q/K/V, this
    step's K/V written at ``(layer, slot)`` of the WHOLE cache
    ([L,NB,BS,KH,D], or the stage-local part of it under pp), attention
    over layer ``layer`` of it, ``wo``. ``layer`` is the layer's place in
    the cache, which has the attention layers alone. Nothing here
    materialises a layer of the cache: the scatter, the kernel's DMAs and
    the dense gather all address the carried buffer by layer index.
    Returns (out [N, H], cache_k, cache_v).

    Token-major: ``positions`` and ``slot [N]``. The Q/K/V/O projections,
    rope and the scatter run over the N tokens; ``q`` alone is laid out as
    rows ``[B, T, heads, D]`` (``lay``) for attention, and the attention
    output packed back to ``[N, q_size]`` (under the paged kernel a packed
    step's ``q`` stays ``[N, heads, D]`` too). Ring attention takes K and V
    as rows too, and only a rectangle (``N == B*T``), where those moves are
    reshapes. ``window`` is static (> 0: a sliding layer, query i sees the
    keys j with i - j < window), and with it, by the configuration, whether
    the layer carries positions at all (``rope_scope``).

    ``cross``: the mixer has a query projection alone and writes nothing:
    it attends over layer ``layer`` as another mixer of the same step left
    it (SambaY's cross-decoder). Under ``cfg.diff_attention`` the kernel and
    the cache see the KV heads in whole pairs side by side, ``[k1 | k2 |
    k3 | k4 ...]`` one cache head (``cfg.cache_kv_heads`` of them: the same
    bytes), V likewise, and a Q head as its own 64 in its key head's place
    among zeros, so that a head's scores are its own key head's and it
    reads its cache head's value heads (:func:`_pair_queries`); its pair's
    two value heads cut from that, the two softmaxes' difference, the norm
    over a pair's width and ``1 - lambda_init`` (a traced scalar, the
    layer's) are :func:`_diff_combine` on what comes back."""
    n = x.shape[0]
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    # (the cache's view of the heads: the model's, or pairs of them)
    kvh, hd = cfg.cache_kv_heads, cfg.cache_head_dim
    scale = {"scale": cfg.head_dim ** -0.5} if cfg.diff_attention else {}
    # The three products stay [N, out] up to the barrier and get their head
    # axis after it. A reshape XLA can fold into the dot makes the weight
    # operand [heads, D, H], the stored matrix transposed, and the compiler
    # then cuts the layer's matrix out of its stack and copies it in that
    # layout in every layer of every step (PERF.md section 6, PR 40).
    # (a multiplier of 1 is no operation of the program, here and below)
    if cfg.attention_in_multiplier != 1.0:
        x = x * cfg.attention_in_multiplier
    with _perf_phase("proj"):
        if cross:
            q = jax.lax.optimization_barrier(mm(x, lp["wq"]))
            k = v = None
        else:
            q, k, v = jax.lax.optimization_barrier(
                (mm(x, lp["wq"]), mm(x, lp["wk"]), mm(x, lp["wv"])))
        if cfg.attention_bias:
            q = q + lp["bq"]
            if not cross:
                k, v = k + lp["bk"], v + lp["bv"]
    if cfg.key_multiplier != 1.0:
        k = k * cfg.key_multiplier
    q = q.reshape(n, cfg.num_heads, cfg.head_dim)
    if cfg.diff_attention:
        q = _pair_queries(q, cfg.num_kv_heads, kvh)
    if not cross:
        k = k.reshape(n, kvh, hd)
        v = v.reshape(n, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.rope_scope == "all" or (window and cfg.rope_scope == "sliding"):
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    kernel = attn_impl in ("pallas", "pallas_interpret")
    # A packed step (N < B*T) under the kernel: its tokens lie row after row
    # from ``lay.starts`` on. (Rows split over "data" keep the rectangle:
    # the tokens have no batch axis.)
    packed = kernel and lay.starts is not None and (
        mesh is None or mesh.shape.get("data", 1) == 1)
    # Phase hooks (obs/profiler.py): jax.named_scope annotations for
    # XLA profiles, plus wall capture in eager profiling runs. Under
    # jit they execute at trace time only — zero ops in the program.
    if not cross:
        with _perf_phase("scatter"):
            if packed and not isinstance(cache_k, dict):
                # ... so a row's K and V go into the pools by runs of
                # consecutive slots, a copy a block and not an update a
                # token (ops/kv_write.py); padded tokens write nothing.
                from dynamo_tpu.ops.kv_write import kv_write, kv_write_sharded

                cache_k, cache_v = (
                    kv_write if tp == 1 else partial(kv_write_sharded, mesh))(
                    k, v, cache_k, cache_v, block_tables, q_start, kv_lens,
                    lay.starts, layer=layer,
                    interpret=attn_impl == "pallas_interpret")
            else:
                cache_k = _scatter_kv(cache_k, k, slot, layer)
                cache_v = _scatter_kv(cache_v, v, slot, layer)

    def out_proj(attn):
        # attn [N, heads, D] as attention leaves it
        if cfg.diff_attention:
            with _perf_phase("attn_diff"):
                attn = _diff_combine(cfg, lp, attn, lambda_init)
        else:
            attn = attn.reshape(n, cfg.q_size)
        with _perf_phase("proj"):
            out = mm(attn, lp["wo"])
            if cfg.attention_bias:
                out = out + lp["bo"]
        if cfg.attention_out_multiplier != 1.0:
            out = out * cfg.attention_out_multiplier
        return out

    if kernel:
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_kernel,
            paged_attention_sharded,
        )

        # TP: shard_map the kernel over the head axis; GSPMD's psum in the
        # wo projection completes the TP contraction.
        attend = partial(
            paged_attention_kernel if tp == 1
            else partial(paged_attention_sharded, mesh),
            layer=layer, interpret=attn_impl == "pallas_interpret",
            window=window, **scale)
    if packed:
        # q goes in token-major as it is and the output comes back so. No
        # array of B x T positions exists here, in or around the kernel.
        with _perf_phase("attention"):
            attn = attend(q, cache_k, cache_v, block_tables, q_start,
                          kv_lens, starts=lay.starts, t=lay.t)
        return out_proj(attn), cache_k, cache_v
    # The rows are gathered from q's grouped view [N, KH, REP, D], the split
    # the kernel's wrapper makes of them anyway: from [N, heads, D] the
    # compiler moves a chunk step's [B, T] rectangle twice on its way to
    # the kernel's [B, KH, T*REP, D] (PERF.md section 6, PR 40).
    q = lay.to_rows(q.reshape(n, kvh, -1, hd))
    q = q.reshape(lay.b, lay.t, cfg.num_heads, hd)            # [B,T,heads,D]
    if use_ring:
        from dynamo_tpu.ops.ring_attention import ring_attention_prefill

        if window:
            raise ValueError("ring attention has no window: a model with "
                             "sliding layers cannot prefill over 'seq'")
        with _perf_phase("attention"):
            attn = ring_attention_prefill(
                mesh, q, lay.to_rows(k), lay.to_rows(v), kv_lens)
    elif kernel:
        with _perf_phase("attention"):
            attn = attend(q, cache_k, cache_v, block_tables, q_start, kv_lens)
    else:
        with _perf_phase("gather"):
            ctx_k = _gather_kv(cache_k, block_tables, layer)
            ctx_v = _gather_kv(cache_v, block_tables, layer)
        with _perf_phase("attention"):
            attn = paged_attention(
                q, ctx_k, ctx_v,
                q_start[:, None] + jnp.arange(lay.t)[None, :], kv_lens,
                window=window, **scale)
    return out_proj(lay.to_tokens(attn)), cache_k, cache_v


def _latent_attention(cfg: ModelConfig, lp: Params, layer, x, cache, *,
                      lay: TokenLayout, positions, slot, block_tables,
                      q_start, kv_lens, attn_impl: str = "dense", mesh=None,
                      use_ring: bool = False, window: int = 0):
    """Multi-head latent attention (``cfg.latent``; arXiv:2405.04434) on the
    normed state ``x [N, H]``, in its absorbed form for every row, a decode
    row's one token and a chunk's alike. Returns (out [N, H], cache).

    What is cached is one row a token and layer, ``[c_kv | k_r | zeros]``
    (``cfg.latent_row`` values stored ``cfg.cache_head_dim`` wide): the
    normed latent ``c_kv = RMSNorm(x W_dkv[:, :rank])`` and the one rotary
    key ``k_r = rope(x W_dkv[:, rank:])`` every head shares. ``cache`` is
    the one pool ``[L, NB, BS, 1, W]`` (engine/cache.py): there is no V
    pool, a token's value is its row's first ``rank`` values. No key or
    value by head is ever built over a context: a head's keys are ``c_kv
    W_uk`` and its values ``c_kv W_uv``, so

        scores = q_nope . (c_kv W_uk) + q_rope . k_r
               = (q_nope W_uk^T) . c_kv + q_rope . k_r
        out    = (softmax(scores) c_kv) W_uv

    which is attention with ``heads`` query heads ``[q_nope W_uk^T | q_rope
    | 0]`` over ONE KV head whose key is the cached row and whose value is
    that row's first ``rank`` lanes: the paged walk with ``rep = heads``
    (ops/paged_attention.py, ``v_cache`` None), or the dense gather on the
    same two operands. The scale is the full head's, ``(nope + rope) **
    -0.5``. ``w_uk`` is stored ``[heads, nope, rank]`` and ``w_uv [heads,
    rank, v]``: each product is one ``dot_general`` batched over heads that
    reads the leaf as it lies."""
    if use_ring or window or mesh is not None and mesh.shape.get("model", 1) > 1:
        raise ValueError("latent attention is served on one device, full "
                         "layers: no ring prefill, no window, no 'model' axis")
    n, heads = x.shape[0], cfg.num_heads
    rank, nope, rot = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    width = cfg.cache_head_dim
    with _perf_phase("mla_down"):
        # The down-projections, their norms, the query's up-projection and
        # the rotary positions of the rope parts.
        if cfg.q_lora_rank:
            c_q, ckv = jax.lax.optimization_barrier(
                (mm(x, lp["wq_a"]), mm(x, lp["wkv_a"])))
            q = mm(rms_norm(c_q, lp["q_a_norm"], cfg.rms_norm_eps),
                   lp["wq_b"])
        else:
            q, ckv = jax.lax.optimization_barrier(
                (mm(x, lp["wq"]), mm(x, lp["wkv_a"])))
        q = q.reshape(n, heads, nope + rot)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions,
                                             cfg.rope_theta)
        c_kv = rms_norm(ckv[:, :rank], lp["kv_a_norm"], cfg.rms_norm_eps)
        k_r = rope(ckv[:, None, rank:], positions, cfg.rope_theta)[:, 0]
        row = jnp.concatenate(
            [c_kv, k_r, jnp.zeros((n, width - rank - rot), c_kv.dtype)], -1)
    with _perf_phase("mla_absorb"):
        # q' = q_nope W_uk^T, [N, heads, rank], and the query as the walk
        # takes it: zeros where the stored row is padding.
        q_lat = jnp.einsum("nhd,hdc->nhc", q_nope, lp["w_uk"])
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((n, heads, width - rank - rot),
                                      q_lat.dtype)], -1)
    with _perf_phase("mla_write"):
        cache = _scatter_kv(cache, row.astype(cache.dtype), slot, layer)
    scale = (nope + rot) ** -0.5
    kernel = attn_impl in ("pallas", "pallas_interpret")
    if kernel:
        from dynamo_tpu.ops.paged_attention import paged_attention_kernel

        attend = partial(
            paged_attention_kernel, layer=layer, scale=scale, v_width=rank,
            interpret=attn_impl == "pallas_interpret")
    with _perf_phase("mla_walk"):
        if kernel and lay.starts is not None:
            o_lat = attend(q, cache, None, block_tables, q_start, kv_lens,
                           starts=lay.starts, t=lay.t)
        elif kernel:
            o_lat = lay.to_tokens(attend(
                lay.to_rows(q), cache, None, block_tables, q_start, kv_lens))
        else:
            ctx = _gather_kv(cache, block_tables, layer)     # [B, S, 1, W]
            o_lat = lay.to_tokens(paged_attention(
                lay.to_rows(q), ctx, ctx[..., :rank],
                q_start[:, None] + jnp.arange(lay.t)[None, :], kv_lens,
                scale=scale))
    with _perf_phase("mla_unabsorb"):
        # o = o_lat W_uv, [N, heads, v]
        o = jnp.einsum("nhc,hcv->nhv", o_lat, lp["w_uv"])
    with _perf_phase("proj"):
        out = mm(o.reshape(n, cfg.o_size), lp["wo"])
    return out, cache


def _pair_queries(q: jax.Array, kv_heads: int, cache_heads: int) -> jax.Array:
    """Differential attention's queries ``[N, heads, D]`` as the cache's
    view takes them, ``[N, heads, W]``, ``W`` the ``kv_heads / cache_heads``
    key heads of ``D`` that lie side by side in one cache head: a query in
    the place of its key head among them and zeros elsewhere. Pairs are
    adjacent heads (Q heads ``2p`` and ``2p + 1``, KV heads ``2j`` and ``2j
    + 1``, Q pair ``p`` reading KV pair ``p // (Q heads a KV head)``), so Q
    head ``i`` scores against KV head ``2 (i // 2 // per) + i % 2``. A
    head's scores are then its own key head's, to the bit (the zeros add
    nothing), and what it reads is its cache head's value heads."""
    n, h, d = q.shape
    per = h // kv_heads                       # Q heads a KV head
    side = kv_heads // cache_heads            # key heads in a cache head
    i = jnp.arange(h)
    key = 2 * (i // 2 // per) + i % 2         # each Q head's key head
    at = jax.nn.one_hot(key % side, side, dtype=q.dtype)           # [h, side]
    return (at[None, :, :, None] * q[:, :, None, :]).reshape(n, h, side * d)


def _diff_combine(cfg: ModelConfig, lp: Params, attn: jax.Array,
                  lambda_init) -> jax.Array:
    """``attn [N, heads, W]``, each head's softmax read against its cache
    head's value heads side by side, to differential attention's output
    ``[N, heads x D]``: of each a pair's ``[v1 | v2]`` (``2 D`` of the
    ``W``), then ``RMSNorm(A1 - lambda A2; w) (1 - lambda_init)`` a pair,
    ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, float32."""
    n, h, w = attn.shape
    d2 = 2 * cfg.head_dim
    lam = (jnp.exp(jnp.sum(lp["diff_lq1"] * lp["diff_lk1"]))
           - jnp.exp(jnp.sum(lp["diff_lq2"] * lp["diff_lk2"])) + lambda_init)
    a = attn.astype(jnp.float32).reshape(n, h // 2, 2, w)
    if w != d2:
        # Q pair p's value pair, p // (Q heads a KV head), among the w / d2
        # that its cache head holds
        pair = (jnp.arange(h // 2) // (h // cfg.num_kv_heads)) % (w // d2)
        a = jnp.take_along_axis(
            a.reshape(n, h // 2, 2, w // d2, d2),
            pair[None, :, None, None, None], axis=3)[:, :, :, 0]
    x = a[:, :, 0] - lam * a[:, :, 1]
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    x = x * lp["diff_norm"].astype(jnp.float32) * (1.0 - lambda_init)
    return x.astype(attn.dtype).reshape(n, h // 2 * d2)


def _ffn(cfg: ModelConfig, lp: Params, x, routing, moe_impl: str, mesh,
         live):
    """The FFN on the normed state ``x [N, H]``: where ``lp`` has a
    ``router`` the routed experts (activation ``cfg.expert_act``) in the
    form ``moe_impl`` names, "held", "ep" or the plain :func:`moe_mlp`, on
    ``routing`` where the choice was made before attention
    (``cfg.router_input``); else the dense SwiGLU. Returns (out [N, H],
    counts): the routed layer's int32 [3] under "held" (models/moe.py
    ``held_rows``; ``live`` [N] names the bucket's live tokens for it),
    else None."""
    counts = None
    if "router" in lp:
        if moe_impl == "held":
            # One chip told which experts it holds: grouped, with counts.
            from dynamo_tpu.models.moe import moe_mlp_held

            mlp_out, counts = moe_mlp_held(x, lp, cfg, live, routing, mesh)
        elif moe_impl == "ep":
            # Dropless ragged dispatch (serving default for ep>1): exact
            # under any routing skew — see models/moe.py.
            from dynamo_tpu.models.moe import moe_mlp_dropless

            mlp_out = moe_mlp_dropless(x, lp, cfg, mesh=mesh,
                                       routing=routing)
        else:
            mlp_out = moe_mlp(x, lp, cfg, routing)
    else:
        with _perf_phase("mlp"):
            mlp_out = swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                             cfg.mlp_multipliers)
    return mlp_out, counts


#: the norm a mixer reads, by its kind (a joined mixer has none of its own:
#: it reads what the mixer before it read)
_NORM = {"*": "attn_norm", "-": "mlp_norm", "E": "mlp_norm", "M": "ssm_norm",
         "S": "ssm_norm", "X": "attn_norm", "G": "gmu_norm"}


def _run_layers(cfg: ModelConfig, plan: LayerPlan, layers: Params, h,
                cache_k, cache_v, ssm=None, *, lay: TokenLayout, q_start,
                q_len=None, live=None, ssm_slots=None,
                attn_impl: str = "dense", moe_impl: str = "dense", mesh=None,
                use_ring: bool = False, narrow: bool = False, **attn):
    """Run the layers of ``plan`` (``cfg.layer_plan``, or a pipeline
    stage's part of it) over ``layers``, their stacked params: the one
    runner of ``forward`` and of both pp schedules. The plan's pieces in
    order (``plan.spans``): layers traced one by one, and a run's period
    as the body of a ``lax.scan`` over its trips. Returns (hidden, cache_k,
    cache_v, ssm, counts).

    SambaY's layout adds three kinds and two things to carry. A Mamba-1
    mixer ("S", models/mamba.py ``mixer1``) that ``keeps`` leaves its scan
    output, before the gate, as the step's memory ``mem [N, d]``, carried
    beside the hidden state and never stored; a memory unit ("G") gates it,
    ``(mem * silu(x W_1)) W_2``; a cross mixer ("X") attends with a query
    projection alone over the layer of the cache that an attention mixer
    earlier in the same step wrote (:func:`_attention`, ``cross``). With
    ``narrow`` the layers from ``plan.last_from`` on, which only the tokens
    whose logits are taken need, run over each row's last live token: the
    hidden state and the memory are gathered there ``[B, ...]``, the rows'
    arrays become those of a step of one query a row at that token's
    position, and the hidden state comes back ``[B, H]``.

    A layer is its mixers in order, each ``h + mixer(norm(h))`` (or, under
    ``cfg.norm_placement == "post"``, ``h + norm(mixer(h))``) under the
    norm its kind names: attention (:func:`_attention`), an FFN dense or
    routed (:func:`_ffn`), a Mamba-2 mixer (models/mamba.py). A mixer that
    is ``joined`` stands beside the one before it: the two read one norm's
    output and their outputs are summed into one add to the residual,
    ``h + (a(u) + s(u))`` with ``u = norm(h)``. Token-major:
    ``hid [N, H]`` throughout. What differs from layer to layer (a mixer's
    kind, its window) is static structure of the body, never a traced
    branch; what is the same is traced once a period. One thing couples a
    layer's mixers: under ``cfg.router_input == "attn_norm"`` the routing
    is made from the state that enters attention and rides past it to the
    same layer's routed mixer.

    The carry is the hidden state and the buffers the plan's kinds need,
    each entering the loop once, whole, and addressed by absolute layer
    index (``Mixer.layer``): K and V where a layer attends, the state pool
    ``ssm`` where one is recurrent, and under ``moe_impl="held"`` the
    routed layers' int32 [3] counts, summed over them (else None). (The
    cache must not ride xs→ys: XLA then cuts each layer out, stacks it back
    and keeps a second K and V as a temporary, which cost over half of a
    decode step on the v5e — PERF.md section 6. A buffer no layer uses is
    not carried: it would be an operand of every loop of a program that
    never reads it.)

    The scan has two forms. A period of one layer whose stack the scan
    walks whole carries that layer's params and index on xs: the scan over
    stacked params that a model of identical layers always was. Any other
    period carries the trip's index alone: the body takes each layer's
    params from its stack at the layer's own place, so every matrix has the
    one dot that reads it for a consumer and is read where it lies. (A
    period's ``[p, ...]`` slice on xs has ``p`` consumers: XLA then copies
    the period's ``wq``, ``wo``, ``wk`` and ``wv`` out of the stack in
    every trip and the dots read the copy, 8 % of a decode step at four
    layers a period — PERF.md section 6, PR 42.) Under ``moe_impl="held"``
    the experts' stacks do not ride xs either and are never cut by layer:
    the grouped matmul takes the stack whole and the layer's place in it
    (``expert_layer``; models/moe.py ``held_rows``).

    A body the program holds more than once is traced once (PR 54). The
    bodies are the plan's (``plan.bodies``: lead, one period, rest), each
    by its description (``body_of``: every mixer's kind, stack, window,
    joined; not its place). Layers of a description that stands there more
    than once run through one ``jax.jit``-wrapped ``one``, made once a call
    of this function, which jax traces once a signature, the kernels'
    bodies inside it with it: 13 bodies -> 3 in Nemotron's cut, 5 -> 3 in
    K-EXAONE's, 4 -> 2 in SmallThinker's, in every program a start warms.
    Two layers of one description differ in where they stand alone, so each
    stack's place is an int32 operand (a constant outside the scan, which
    XLA folds into the slice; the trip's inside it), and the stacks, the
    experts' stacks, the carry and the rows' arrays are operands, whole: a
    layer's params are read ``a[i]`` inside the body, every matrix by the
    one dot that reads it where it lies. A description that stands once is
    traced as it always was, with no wrapper: the choice reads the plan, no
    model's name and no option. The wrapper is ``inline=True``: jax writes
    the one traced body's equations into the program at each place, the
    lowering's caches hit on the kernels they share, and the compiled
    program is the parent's instruction for instruction (checked for the
    described v5e, PERF.md section 6, PR 54). As a call (no ``inline``) XLA
    inlines every one before it fuses, but then fuses a few elementwise
    producers otherwise (Nemotron's decode program 451 -> 443 fusions): a
    module a third the size, not the parent's program, for seconds the
    chip's host did not tell apart."""
    from dynamo_tpu.models import mamba
    from dynamo_tpu.models.moe import route

    stacks = layer_stacks(layers)
    of_kind = {m.kind: m.stack for mixers in plan.layers for m in mixers}
    # (a part of the carry that is None is no operand of the loop; the last
    # is the step's memory, which the mixer that keeps it sets)
    carry = (h, *((cache_k, cache_v) if "*" in of_kind else (None, None)),
             ssm, None, None)
    experts = {}
    if moe_impl == "held" and "E" in of_kind:
        carry = (*carry[:4], jnp.zeros((3,), jnp.int32), None)
        routed = stacks[of_kind["E"]]
        experts = {k: routed.pop(k) for k in ("w_gate", "w_up", "w_down")
                   if k in routed}
    post = cfg.norm_placement == "post"
    # Every traced value a layer reads beside the carry, which a shared
    # body takes as operands (the layout's two integers are static).
    rows, env = lay[:2], (stacks, experts, lay[2:], q_start, q_len, live,
                          ssm_slots, attn)
    lambdas = {kind: jnp.asarray(cfg.lambda_init(kind)) for kind in "*X"
               if cfg.diff_attention and kind in of_kind}

    def one(rows, carry, mixers, place, env, lp=None):
        """One layer on ``carry``. ``place``: each stack's place here (its
        mixers' own outside the scan, the trip's inside it; an int32 operand
        of a shared body); ``rows`` and ``env`` as above; ``lp`` the layer's
        params where the scan hands them in. Of ``mixers`` it reads the
        description alone (``body_of``)."""
        hid, k, v, state, counts, mem = carry
        stacks, experts, lay, q_start, q_len, live, ssm_slots, attn = env
        lay = TokenLayout(*rows, *lay)
        if lp is None:
            lp = {}
            for stack, i in place.items():
                lp.update(jax.tree.map(lambda a: a[i], stacks[stack]))
        routing = None
        # What no inner phase names (norms, rope, residual adds, the moves
        # of q and of the attention output around the kernel) is the
        # layer's rest (obs/profiler.py DEVICE_PHASES).
        with _perf_phase("layer"):
            beside = None       # the output a joined mixer is added to
            for m, after in zip(mixers, (*mixers[1:], None)):
                i = place[m.stack]
                if not m.joined:
                    norm = lp[_NORM[m.kind]]
                    x = hid if post else norm_of(cfg, hid, lp, _NORM[m.kind])
                diff = ({"lambda_init": lambdas[m.kind][i]}
                        if m.kind in lambdas else {})
                if m.kind == "X":
                    out, _, _ = _attention(
                        cfg, lp, m.layer, x, k, v, lay=lay, q_start=q_start,
                        attn_impl=attn_impl, mesh=mesh, cross=True, **diff,
                        **attn)
                elif m.kind == "S":
                    out, state, y = mamba.mixer1(
                        cfg, lp, i, x, state, lay=lay, slots=ssm_slots,
                        q_start=q_start, q_len=q_len, live=live,
                        impl={"dense": "jnp"}.get(attn_impl, attn_impl))
                    if m.keeps:
                        mem = y
                elif m.kind == "G":
                    with _perf_phase("gmu"):
                        out = mm(mem * jax.nn.silu(mm(x, lp["gmu_in"])),
                                 lp["gmu_out"])
                elif m.kind == "*":
                    if cfg.router_input == "attn_norm" and any(
                            o.kind == "E" for o in mixers):
                        with _perf_phase("moe_route"):
                            routing = route(x, lp, cfg)
                    # (no "+ 0": it would be an equation of every program)
                    at = (m.layer - m.place) + i if m.layer != m.place else i
                    if cfg.latent:      # one pool: ``v`` stays None
                        out, k = _latent_attention(
                            cfg, lp, at, x, k, lay=lay, q_start=q_start,
                            attn_impl=attn_impl, mesh=mesh,
                            use_ring=use_ring, window=m.window, **attn)
                    else:
                        out, k, v = _attention(
                            cfg, lp, at, x, k, v, lay=lay,
                            q_start=q_start, attn_impl=attn_impl, mesh=mesh,
                            use_ring=use_ring, window=m.window, **diff,
                            **attn)
                elif m.kind == "M":
                    out, state = mamba.mixer(
                        cfg, lp, i, x, state, lay=lay, slots=ssm_slots,
                        q_start=q_start, q_len=q_len, live=live,
                        impl={"dense": "jnp"}.get(attn_impl, attn_impl))
                else:
                    if experts and m.kind == "E":
                        lp = {**lp, **experts, "expert_layer": i}
                    out, c = _ffn(cfg, lp, x, routing, moe_impl, mesh, live)
                    if c is not None:
                        counts = counts + c
                if m.joined:
                    out = beside + out
                if after is not None and after.joined:
                    beside = out
                    continue
                if post:
                    out = rms_norm(out, norm, cfg.rms_norm_eps)
                hid = hid + out
        return hid, k, v, state, counts, mem

    bodies = plan.bodies
    shared = {}         # a repeated description -> its one jitted body

    def run(carry, mixers, at=lambda m: m.place):
        """One layer, mixer ``m``'s stack at place ``at(m)``: through its
        description's shared body where the program holds that description
        more than once, else as it is."""
        place = {}
        for m in mixers:
            if m.stack not in place:
                place[m.stack] = at(m)
        body = body_of(mixers)
        if bodies.count(body) < 2:
            return one(rows, carry, mixers, place, env)
        if (body, rows) not in shared:
            def layer(carry, place, env, rows=rows):
                return one(rows, carry, body, place, env)

            shared[body, rows] = jax.jit(layer, inline=True)
        return shared[body, rows](
            carry, {s: jnp.asarray(i, jnp.int32) for s, i in place.items()},
            env)

    def scanned(carry, period, trips):
        index = jnp.arange(trips, dtype=jnp.int32)
        on_xs = stacks[period[0][0].stack]
        if len(period) == 1 and trips == len(jax.tree.leaves(on_xs)[0]):
            def layer_fn(carry, xs):
                lp, i = xs
                return one(rows, carry, period[0],
                           {m.stack: i for m in period[0]}, env, lp), None

            return lax.scan(layer_fn, carry, (on_xs, index))[0]

        def period_fn(carry, trip):
            for mixers in period:
                # (a stack's places step from trip to trip by the
                # period's layers that read it)
                carry = run(carry, mixers, lambda m: trip * sum(
                    any(o.stack == m.stack for o in layer)
                    for layer in period) + m.place)
            return carry, None

        return lax.scan(period_fn, carry, index)[0]

    def to_last_tokens(carry):
        """The rows' arrays and the carry for the layers that run over each
        row's last live token alone: one query a row at that token's
        position (a decode program's rows are that already)."""
        nonlocal lay, q_start, q_len, live, attn, rows, env
        if lay.t == 1:
            return carry
        hid, *held, mem = carry
        with _perf_phase("layout"):
            hid, mem = [_last_hidden(a, lay, q_len) for a in (hid, mem)]
            q_start = jnp.maximum(q_start + q_len - 1, 0)
            live, q_len = q_len > 0, jnp.minimum(q_len, 1)
            lay = TokenLayout(lay.b, 1)
            attn = {**attn, "positions": q_start,
                    "slot": jnp.zeros_like(q_start)}
        rows, env = lay[:2], (stacks, experts, lay[2:], q_start, q_len, live,
                              ssm_slots, attn)
        return hid, *held, mem

    for first, count, trips in plan.spans:
        if narrow and first == plan.last_from:
            carry = to_last_tokens(carry)
        pieces = plan.layers[first:first + count]
        if trips:
            carry = scanned(carry, pieces, trips)
        else:
            for mixers in pieces:
                carry = run(carry, mixers)
    h, k, v, ssm, counts, _mem = carry
    return (h, *((k, v) if "*" in of_kind else (cache_k, cache_v)), ssm,
            counts)


def _positions_and_slots(lay: TokenLayout, valid, q_start, block_tables,
                         bs: int):
    """Position and flat cache slot of each token [N]: read off the rows'
    [B, T] rectangles of both (integers, small). Padding → trash block 0."""
    pos_rows = q_start[:, None] + jnp.arange(lay.t)[None, :]       # [B, T]
    blk = jnp.take_along_axis(
        block_tables,
        jnp.clip(pos_rows // bs, 0, block_tables.shape[1] - 1), axis=1)
    slot = jnp.where(valid, lay.to_tokens(blk * bs + pos_rows % bs), 0)
    return lay.to_tokens(pos_rows), slot


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,    # [B, T] int32
    q_start: jax.Array,      # [B] position of first query token
    q_len: jax.Array,        # [B] number of valid query tokens (≤ T)
    block_tables: jax.Array,  # [B, NBLK] int32 block ids into the cache
    cache_k: jax.Array,      # [L, NB, BS, KH, D]
    cache_v: jax.Array,
    attn_impl: str = "dense",
    moe_impl: str = "dense",
    mesh=None,
    sp_prefill: bool = False,
    return_all_hidden: bool = False,
    embed_override: jax.Array | None = None,  # [B, T, H] multimodal embeds
    embed_mask: jax.Array | None = None,      # [B, T] True → use override
    pp_microbatches: int = 0,                 # pp>1: schedule depth (0 = auto)
    num_tokens: int | None = None,            # token bucket N (None = B*T)
    moe_counts: bool = False,                 # also return the routed layers' counts
    ssm=None,                                 # the state pool (models/mamba.py)
    ssm_slots: jax.Array | None = None,       # [B] each row's row of it
) -> tuple[jax.Array, ...]:
    """One engine step. Returns (last_hidden [B,H], cache_k, cache_v) —
    or (hidden [B,T,H], ...) with ``return_all_hidden`` (the speculative
    verify step needs logits at every chunk position). A model with
    recurrent layers (``cfg.has_ssm``) takes the state pool ``ssm`` and
    each row's place in it (a padded row: the trash row) and returns the
    pool behind ``cache_v``. With ``moe_counts``
    (``moe_impl="held"`` only) a fourth: int32 [3], over the step's routed
    layers the (token, choice) rows computed here, the experts that had
    rows, and the rows of each layer's largest group, summed.

    Query token j of sequence b sits at position q_start[b]+j; its KV is
    written into the cache slot named by the block table; attention sees all
    cache positions ≤ its own. Works unchanged for prefill chunks (T>1) and
    decode (T=1).

    The inputs are rows; the work is over tokens. The rows' live tokens are
    packed into ``[N, H]`` (``num_tokens``, static; the caller picks a bucket
    that holds ``sum(q_len)``) and stay so through every layer, as rows only
    inside attention (``TokenLayout``). None, or B*T, is the rectangle:
    every position a token, what T=1 is anyway. Ring prefill and pp keep the
    rectangle (attention over "seq" shards it; the pp schedules cut
    microbatches from it).
    """
    b, t = token_ids.shape
    bs = _cache_block_size(cache_k)
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    dp = mesh.shape.get("data", 1) if mesh is not None else 1
    sp = mesh.shape.get("seq", 1) if mesh is not None else 1
    if mesh is not None and mesh.shape.get("pipe", 1) > 1:
        # Pipeline-parallel path: layer blocks sharded over "pipe".
        out = forward_pp(params, cfg, token_ids, q_start, q_len, block_tables,
                         cache_k, cache_v, mesh, attn_impl=attn_impl,
                         moe_impl=moe_impl, microbatches=pp_microbatches)
        # (the stages' counts are not gathered: a program without them)
        return (*out, None) if moe_counts else out
    if attn_impl in ("pallas", "pallas_interpret") and tp > 1 and (
        cfg.num_kv_heads % tp != 0 or b % dp != 0
    ):
        # Heads/batch don't divide the mesh: fall back to the dense gather
        # path, partitioned by GSPMD. Trace-time decision — tracing happens
        # once per (batch, chunk) bucket, so this logs once per bucket that
        # actually serves the slow path rather than silently degrading.
        # CPU meshes only: on a TPU the engine refuses such a mesh at
        # construction (ModelRunner), and this raise is its backstop.
        reason = (f"num_kv_heads={cfg.num_kv_heads} mod tp={tp}"
                  if cfg.num_kv_heads % tp != 0 else f"batch={b} mod dp={dp}")
        if jax.default_backend() == "tpu":
            raise ValueError(
                f"paged-attention kernel cannot serve bucket (b={b}, t={t}): "
                f"{reason} does not divide, and the dense gather path is "
                "not an acceptable substitute on a TPU")
        log.warning(
            "paged-attention kernel disabled for bucket (b=%d, t=%d): %s does "
            "not divide; serving the dense gather path", b, t, reason)
        attn_impl = "dense"
    # Sequence-parallel prefill (ring attention over "seq"): exact for a
    # fresh full-prompt chunk — its attention context is the chunk itself.
    # Trace-time divisibility guards; fall back to the dense path otherwise.
    use_ring = (
        sp_prefill and sp > 1 and t > 1 and t % sp == 0
        and cfg.num_kv_heads % tp == 0 and b % dp == 0
    )
    n = b * t if num_tokens is None or use_ring else num_tokens
    with _perf_phase("layout"):
        lay, valid = token_layout(q_len, b, t, n)
        positions, slot = _positions_and_slots(
            lay, valid, q_start, block_tables, bs)                 # [N]
        kv_lens = q_start + q_len                                  # [B]

    with _perf_phase("embed"):
        h = embed_lookup(params["embed"], lay.to_tokens(token_ids),
                         _dtype(cfg))                              # [N, H]
        if embed_override is not None:
            # Multimodal positions carry encoder outputs instead of token
            # embeddings (their placeholder ids exist only for position/hash
            # bookkeeping — see preprocessor digest-salted placeholders).
            h = jnp.where(lay.to_tokens(embed_mask)[:, None],
                          lay.to_tokens(embed_override).astype(h.dtype), h)
        if cfg.embedding_multiplier != 1.0:
            h = h * cfg.embedding_multiplier

    # A plan whose last layers only the tokens whose logits are taken need
    # (SambaY's cross-decoder) runs them over each row's last token, and the
    # hidden state comes back [B, H]; a caller that wants every position
    # gets every layer over every position.
    plan = cfg.layer_plan
    narrow = plan.last_from is not None and not return_all_hidden
    h, cache_k, cache_v, ssm, counts = _run_layers(
        cfg, plan, params["layers"], h, cache_k, cache_v, ssm,
        lay=lay, positions=positions, slot=slot, block_tables=block_tables,
        q_start=q_start, q_len=q_len, kv_lens=kv_lens, live=valid,
        ssm_slots=ssm_slots, attn_impl=attn_impl, moe_impl=moe_impl,
        mesh=mesh, use_ring=use_ring, narrow=narrow)
    # The head's own preparation: the final norm and each row's last token.
    with _perf_phase("logits"):
        h = norm_of(cfg, h, params, "final_norm")
        if return_all_hidden:
            last = lay.to_rows(h)                                  # [B, T, H]
        else:       # (narrowed rows of several tokens are [B, H] already)
            last = h if narrow and t > 1 else _last_hidden(h, lay, q_len)
    out = (last, cache_k, cache_v) + ((ssm,) if cfg.has_ssm else ())
    return (*out, counts) if moe_counts else out


def _last_hidden(h: jax.Array, lay: TokenLayout, q_len: jax.Array) -> jax.Array:
    """Hidden state [B, H] at each row's last live token, out of [N, H]."""
    last_off = jnp.clip(q_len - 1, 0, lay.t - 1)                   # [B]
    if lay.row_tok is None:
        return jnp.take_along_axis(
            lay.to_rows(h), last_off[:, None, None], axis=1)[:, 0]
    return h[jnp.take_along_axis(lay.row_tok, last_off[:, None], axis=1)[:, 0]]


def forward_pp(
    params: Params,
    cfg: ModelConfig,
    token_ids: jax.Array,
    q_start: jax.Array,
    q_len: jax.Array,
    block_tables: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    mesh,
    attn_impl: str = "dense",
    microbatches: int = 0,
    moe_impl: str = "dense",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pipeline-parallel forward: layer blocks sharded over the "pipe" axis.

    The reference's planner sizes ``pp`` for its engines
    (components/src/dynamo/planner/utils/planner_core.py:110-118); here PP
    is first-party. Each stage holds ``L/pp`` stacked layers and the
    matching slice of the paged KV cache (kv_cache_spec shards the layer
    dim). Inside one ``shard_map`` over "pipe", a GPipe-style microbatch
    schedule runs M + pp - 1 ticks: every tick each stage computes its
    layer block on ONE microbatch and ``ppermute``s the activations to the
    next stage, so in steady state all pp stages work on different
    microbatches simultaneously — efficiency M/(M+pp-1) vs 1/pp for the
    naive select-and-broadcast pipeline (kept as the fallback for shapes
    too small to split).

    Microbatch axis: prefill chunks (T > 1) split along T — sub-chunk c's
    attention context is the cache, which sub-chunks < c of the same stage
    populated at earlier ticks (the tick order IS the causal order).
    Decode (T = 1) splits along B. Bubble ticks write their (garbage) KV
    to trash block 0 — the same masking the engine's padding rows use —
    and contribute nothing to the output.

    The Pallas paged-attention kernel runs INSIDE the stage block
    (pallas_call nests fine under shard_map; this is the same composition
    paged_attention_sharded uses over "model"). tp/ep stay 1 when pp > 1
    (runner-guarded).
    """
    pp = mesh.shape["pipe"]
    if cfg.num_layers % pp != 0:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by pp={pp}")
    stage = cfg.layer_plan.stage(cfg.num_layers // pp)
    b, t = token_ids.shape
    bs = _cache_block_size(cache_k)
    nblk = block_tables.shape[1]
    from jax.sharding import PartitionSpec as P

    # The schedules cut microbatches from the [B, T] rectangle, so pp keeps
    # it: each microbatch is a rectangle [B', T'] whose B'*T' positions are
    # its tokens (TokenLayout's reshape case).
    lay, valid = token_layout(q_len, b, t, b * t)
    positions, slot = _positions_and_slots(lay, valid, q_start, block_tables, bs)
    h0 = embed_lookup(params["embed"], token_ids.reshape(-1), _dtype(cfg))

    # Microbatch count: the largest divisor of the split axis ≤ the target
    # (default 2*pp — enough for ~2/3+ steady-state efficiency without
    # blowing up compile time on the tick loop).
    target = microbatches if microbatches > 0 else 2 * pp
    split_t = t > 1
    axis = t if split_t else b
    m = min(target, axis)
    while m > 1 and axis % m:
        m -= 1
    use_kernel = attn_impl in ("pallas", "pallas_interpret")

    if m < 2:
        if use_kernel:
            log.warning(
                "pp>1 bucket (b=%d, t=%d) too small to microbatch: serving "
                "the sequential dense-attention pipeline", b, t)
        return _forward_pp_sequential(
            params, cfg, lay, positions, q_start, q_start + q_len, slot,
            block_tables, cache_k, cache_v, mesh, h0, q_len, pp, moe_impl)

    # Per-microbatch statics, uniformly [M, B'*T', ...] (token-major).
    if split_t:
        tm = t // m
        bm = b
        h0_mb = h0.reshape(b, m, tm, -1).swapaxes(0, 1).reshape(m, b * tm, -1)
        pos_mb = positions.reshape(b, m, tm).swapaxes(0, 1).reshape(m, -1)
        slot_mb = slot.reshape(b, m, tm).swapaxes(0, 1).reshape(m, -1)
        bt_mb = jnp.broadcast_to(block_tables[None], (m, b, nblk))
        qs_mb = q_start[None, :] + (jnp.arange(m) * tm)[:, None]
        # visible context after sub-chunk c = everything ≤ its last valid
        # token; clip keeps rows whose q_len ends mid-earlier-chunk exact.
        kl_mb = q_start[None, :] + jnp.minimum(
            q_len[None, :], (jnp.arange(m)[:, None] + 1) * tm)
    else:
        tm = t
        bm = b // m
        h0_mb = h0.reshape(m, bm * t, -1)
        pos_mb = positions.reshape(m, bm * t)
        slot_mb = slot.reshape(m, bm * t)
        bt_mb = block_tables.reshape(m, bm, nblk)
        qs_mb = q_start.reshape(m, bm)
        kl_mb = (q_start + q_len).reshape(m, bm)

    lay_mb = TokenLayout(bm, tm)

    def pp_fn(lp_stack, ck_loc, cv_loc, h0_mb, pos_mb, slot_mb, bt_mb, qs_mb, kl_mb):
        s = lax.axis_index("pipe")

        def tick(i, carry):
            h_cur, ck, cv, out = carry
            mb = i - s                     # microbatch at this stage now
            mbc = jnp.clip(mb, 0, m - 1)
            live = (mb >= 0) & (mb < m)
            # Bubble ticks compute on stale activations (finite — zeros at
            # worst) and must leave no trace: KV writes go to trash block 0
            # and the output contribution is masked.
            slot_t = jnp.where(live, slot_mb[mbc], 0)
            h_in = jnp.where(s == 0, h0_mb[mbc], h_cur)
            h_out, ck, cv, *_ = _run_layers(
                cfg, stage, lp_stack, h_in, ck, cv, lay=lay_mb,
                positions=pos_mb[mbc],
                slot=slot_t, block_tables=bt_mb[mbc], q_start=qs_mb[mbc],
                kv_lens=kl_mb[mbc], attn_impl=attn_impl, moe_impl=moe_impl)
            out = out.at[mbc].add(jnp.where((s == pp - 1) & live, h_out, 0))
            h_nxt = lax.ppermute(
                h_out, "pipe", [(j, (j + 1) % pp) for j in range(pp)])
            return (h_nxt, ck, cv, out)

        init = (jnp.zeros_like(h0_mb[0]), ck_loc, cv_loc, jnp.zeros_like(h0_mb))
        _, ck_loc, cv_loc, out = lax.fori_loop(0, m + pp - 1, tick, init)
        # Only the last stage accumulated into `out`; the psum replicates it.
        return lax.psum(out, "pipe"), ck_loc, cv_loc

    fn = jax.shard_map(
        pp_fn, mesh=mesh,
        in_specs=(P("pipe"), P("pipe"), P("pipe"), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P("pipe"), P("pipe")),
        check_vma=False,
    )
    out, cache_k, cache_v = fn(params["layers"], cache_k, cache_v,
                               h0_mb, pos_mb, slot_mb, bt_mb, qs_mb, kl_mb)
    if split_t:
        out = out.reshape(m, b, tm, -1).swapaxes(0, 1)
    h = rms_norm(out.reshape(b * t, -1), params["final_norm"], cfg.rms_norm_eps)
    return _last_hidden(h, lay, q_len), cache_k, cache_v


def _forward_pp_sequential(params, cfg, lay, positions, q_start, kv_lens, slot,
                           block_tables, cache_k, cache_v, mesh, h0, q_len, pp,
                           moe_impl="dense"):
    """Fallback pipeline for shapes too small to microbatch (e.g. a lone
    decode row): pp select-and-broadcast rounds — every stage computes the
    full batch each round, round i keeps stage i's result. Efficiency 1/pp;
    correctness identical. Dense attention only (the warning at the call
    site covers the kernel case)."""
    from jax.sharding import PartitionSpec as P

    stage = cfg.layer_plan.stage(cfg.num_layers // pp)

    def pp_fn(lp_stack, ck_local, cv_local, h):
        s = lax.axis_index("pipe")
        for i in range(pp):
            h_out, ck_new, cv_new, *_ = _run_layers(
                cfg, stage, lp_stack, h, ck_local, cv_local, lay=lay,
                positions=positions, slot=slot, block_tables=block_tables,
                q_start=q_start, kv_lens=kv_lens, moe_impl=moe_impl)
            keep = s == i
            # tree_map: quantized caches are {"q","s"} pytrees.
            ck_local = jax.tree.map(lambda a, b: jnp.where(keep, a, b),
                                    ck_new, ck_local)
            cv_local = jax.tree.map(lambda a, b: jnp.where(keep, a, b),
                                    cv_new, cv_local)
            h = lax.psum(jnp.where(keep, h_out, jnp.zeros_like(h_out)), "pipe")
        return h, ck_local, cv_local

    fn = jax.shard_map(
        pp_fn, mesh=mesh,
        in_specs=(P("pipe"), P("pipe"), P("pipe"), P()),
        out_specs=(P(), P("pipe"), P("pipe")),
        check_vma=False,
    )
    h, cache_k, cache_v = fn(params["layers"], cache_k, cache_v, h0)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return _last_hidden(h, lay, q_len), cache_k, cache_v


def logits_from_hidden(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Project hidden [B,H] → logits [B,V] (tied or separate lm head).
    Row-quantized embeddings put the scale on the vocab axis, so it
    applies per logit column after the contraction."""
    with _perf_phase("logits"):
        if cfg.tie_word_embeddings:
            e = params["embed"]
            if isinstance(e, dict):
                out = (hidden @ e["q"].astype(hidden.dtype).T) * e["sr"].astype(hidden.dtype)
            else:
                out = hidden @ e.T
        else:
            out = mm(hidden, params["lm_head"])
        if cfg.lm_head_multiplier != 1.0:
            out = out * cfg.lm_head_multiplier
        return out
