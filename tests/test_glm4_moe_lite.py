"""GLM-4.7-Flash's block (``model_type: "glm4_moe_lite"``) at a small size
on the CPU, against the plain reference the benchmark's configuration brings
(``chipbench/configs/glm-4.7-flash-l5/reference.py``): multi-head latent
attention over a paged cache of one row a token, absorbed for decode rows
and chunk rows alike (``models/llama.py _latent_attention``), a leading
dense layer, then experts chosen by biased sigmoid scores and a shared
expert. The reference expands (a key and a value of every head at every
position); the program absorbs: that they agree is the test of the
absorption.

Everything here is float32 with seeded random weights; the widths keep the
published ratios (a rope part beside the nope part, a value head wider than
the nope part, a latent narrower than heads x head size). The tolerance says
why it is what it is; it is tight enough that a bf16 latent cache fails it,
and leave-one-out controls that change one piece of the mathematics each
fail it too.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import ModelConfig

ROOT = Path(__file__).resolve().parents[1]
CONFIG_DIR = ROOT / "chipbench" / "configs" / "glm-4.7-flash-l5"
BS = 16

# The published config cut to CPU size: the same keys, tiny widths.
TINY = {
    "model_type": "glm4_moe_lite", "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 128, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 48, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "attention_bias": False,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "n_shared_experts": 1, "vocab_size": 256,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
    "num_nextn_predict_layers": 0, "hidden_act": "silu",
}

# float32 against float32 over three layers: the two sides sum in other
# orders (absorbed against expanded products, a grouped matmul, an online
# softmax) and differ by rounding, ~1e-6 of unit-scale logits a layer; 2e-4
# leaves an order of magnitude. bf16 anywhere on the path reads 1e-2 or more.
LOGIT_TOL = 2e-4
N_TOKENS = 72       # two chunks of 32 and eight decoded tokens
N_DECODE = 8


def _reference():
    spec = importlib.util.spec_from_file_location(
        "glm_flash_reference", CONFIG_DIR / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(tmp_path, **over) -> tuple[ModelConfig, dict]:
    model = {**TINY, **over}
    (tmp_path / "config.json").write_text(json.dumps(model))
    cfg = ModelConfig.from_hf_config(str(tmp_path))
    return dataclasses.replace(cfg, dtype="float32"), model


def _serve(cfg, params, tokens, *, attn_impl="dense", chunk=32,
           n_decode=N_DECODE, cache_dtype=None):
    """Logits [len(tokens), vocab] as the engine's step computes them:
    prefill in chunks of ``chunk`` and then one token at a time, through the
    one latent pool, one row of a batch of two (the other is padding)."""
    n = len(tokens)
    nblk = -(-n // BS)
    pool = jnp.zeros((cfg.attn_layers, nblk + 2, BS, 1, cfg.cache_head_dim),
                     jnp.dtype(cache_dtype or cfg.dtype))
    bt = jnp.zeros((2, nblk), jnp.int32).at[0].set(jnp.arange(1, nblk + 1))
    out = []

    @jax.jit     # one program a chunk width, as a step is
    def step(ids, start, length, pool):
        hid, pool, none, counts = llama.forward(
            params, cfg, ids, start, length, bt, pool, None,
            attn_impl=attn_impl, moe_impl="held", return_all_hidden=True,
            moe_counts=True)
        assert none is None
        return llama.logits_from_hidden(params, cfg, hid[0]), pool, counts

    n_prefill = n - n_decode
    cuts = [(s, min(chunk, n_prefill - s)) for s in range(0, n_prefill, chunk)]
    cuts += [(s, 1) for s in range(n_prefill, n)]
    for start, length in cuts:
        t = 1 if length == 1 else chunk
        ids = np.zeros((2, t), np.int32)
        ids[0, :length] = tokens[start:start + length]
        logits, pool, counts = step(
            jnp.asarray(ids), jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32), pool)
        assert counts.shape == (3,) and int(counts[0]) > 0
        out.append(np.asarray(logits[:length], np.float32))
    # the padded lanes of every written row stay zero
    assert not np.asarray(pool[..., cfg.latent_row:], np.float32).any()
    return np.concatenate(out)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cfg, model = _config(tmp_path_factory.mktemp("glm"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


@pytest.fixture(scope="module")
def served(tiny):
    cfg, model, params = tiny
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, N_TOKENS).tolist()
    ref = _reference().logits_at(params, model, tokens, list(range(N_TOKENS)))
    return tokens, ref


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def test_the_reader_takes_the_latent_sizes(tiny):
    cfg = tiny[0]
    assert cfg.latent and cfg.kv_lora_rank == 128 and cfg.q_lora_rank == 32
    assert (cfg.head_dim, cfg.v_head_dim) == (32, 48)
    assert (cfg.q_size, cfg.kv_size, cfg.o_size) == (128, 136, 192)
    assert (cfg.cache_kv_heads, cfg.cache_head_dim, cfg.latent_row) == (
        1, 256, 136)
    assert cfg.router_scoring == "sigmoid" and cfg.router_bias
    assert cfg.routed_scaling_factor == 1.8 and cfg.first_k_dense == 1
    # a leading dense layer, then one routed body scanned twice
    assert cfg.layer_plan.split == (1, 1, 2, 0)


def test_the_published_config_reads_at_its_widths():
    cfg = ModelConfig.from_hf_config(str(CONFIG_DIR))
    assert (cfg.num_layers, cfg.num_heads, cfg.hidden_size) == (5, 20, 2048)
    assert (cfg.q_size, cfg.kv_size, cfg.o_size) == (5120, 576, 5120)
    assert (cfg.cache_kv_heads, cfg.cache_head_dim) == (1, 640)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 4)
    assert cfg.shared_expert_width == 1536 and cfg.vocab_size == 154880


@pytest.mark.parametrize("model_type", ["deepseek_v2", "deepseek_v3", None])
def test_a_latent_rank_under_another_model_type_is_refused(tmp_path,
                                                           model_type):
    """Read by the common reader it would be a 4-head GQA model of head
    size 16: served wrongly, so refused by its key."""
    model = {**TINY, "model_type": model_type}
    if model_type is None:
        del model["model_type"]
    (tmp_path / "config.json").write_text(json.dumps(model))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        ModelConfig.from_hf_config(str(tmp_path))


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("num_nextn_predict_layers", 1), ("topk_method", "greedy"),
    ("attention_bias", True), ("num_key_value_heads", 2)])
def test_what_is_not_served_is_refused_by_its_key(tmp_path, key, value):
    (tmp_path / "config.json").write_text(json.dumps({**TINY, key: value}))
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(str(tmp_path))


# ---------------------------------------------------------------------------
# prefill in two chunks, then eight decoded tokens, through the latent pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "pallas_interpret"])
def test_prefill_then_decode_matches_the_reference(tiny, served, attn_impl):
    cfg, _model, params = tiny
    tokens, ref = served
    got = _serve(cfg, params, tokens, attn_impl=attn_impl)
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) < LOGIT_TOL


def test_a_bf16_latent_fails_the_tolerance(tiny, served):
    """The same path with the cached row rounded to bf16 is off by many
    times the tolerance: the comparison would catch a lower precision of
    the latent alone."""
    cfg, _model, params = tiny
    tokens, ref = served
    got = _serve(cfg, params, tokens, cache_dtype="bfloat16")
    assert np.max(np.abs(got - ref)) > 10 * LOGIT_TOL


def test_absorbed_equals_expanded_on_one_layer(tiny):
    """One layer's attention over 40 tokens: the program's absorbed form
    over the cached rows against keys and values built by head."""
    cfg, model, params = tiny
    ref = _reference()
    lp = jax.tree.map(lambda a: a[0], llama.layer_stacks(
        params["layers"])["rep"])
    n = 40
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (n, cfg.hidden_size)), jnp.float32)
    want, _ = ref._attention(
        x, lp, jnp.int32(n), n_heads=cfg.num_heads,
        nope=cfg.qk_nope_head_dim, rot=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps)
    want = np.asarray(want @ lp["wo"])
    nblk = -(-n // BS)
    pool = jnp.zeros((1, nblk + 1, BS, 1, cfg.cache_head_dim), jnp.float32)
    bt = jnp.arange(1, nblk + 1, dtype=jnp.int32)[None]
    q_start, q_len = jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32)
    lay, valid = llama.token_layout(q_len, 1, 48, 48)
    pos, slot = llama._positions_and_slots(lay, valid, q_start, bt, BS)
    xs = jnp.zeros((48, cfg.hidden_size), jnp.float32).at[:n].set(x)
    got, pool = llama._latent_attention(
        cfg, lp, 0, xs, pool, lay=lay, positions=pos, slot=slot,
        block_tables=bt, q_start=q_start, kv_lens=q_len)
    np.testing.assert_allclose(np.asarray(got[:n]), want, atol=2e-5)


def _no_rope_key(cfg, lp, layer, x, cache, **kw):
    """The control's attention: the scores lose ``q_rope . k_r``."""
    real = llama.rope
    try:
        llama.rope = lambda x, pos, theta: jnp.zeros_like(x)
        return _REAL_ATTENTION(cfg, lp, layer, x, cache, **kw)
    finally:
        llama.rope = real


_REAL_ATTENTION = llama._latent_attention


def _plain_route(scale=None, normalise=None):
    real = moe.route

    def route(xt, lp, cfg):
        over = {k: v for k, v in (("routed_scaling_factor", scale),
                                  ("norm_topk_prob", normalise))
                if v is not None}
        return real(xt, lp, dataclasses.replace(cfg, **over))
    return route


# One piece of the layer's mathematics changed in the program and the
# reference left as it is: each must read far outside the tolerance.
CONTROLS = (
    "no_k_r_term", "no_norm_on_c_kv", "no_norm_on_c_q",
    "scale_of_a_192_wide_head", "unscaled_routing_weights",
    "unnormalised_routing_weights", "no_shared_expert")


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_fails_the_comparison(tiny, served, control, monkeypatch):
    cfg, _model, params = tiny
    tokens, ref = served
    if control == "no_k_r_term":
        monkeypatch.setattr(llama, "_latent_attention", _no_rope_key)
    elif control in ("no_norm_on_c_kv", "no_norm_on_c_q"):
        # the norm taken away: RMSNorm is the identity on what it is given
        leaf = "kv_a_norm" if control.endswith("c_kv") else "q_a_norm"
        real = llama.rms_norm

        def rms_norm(x, w, eps):
            layers = params["layers"]
            mine = any(w.shape == layers[k].shape[1:] and k.endswith(leaf)
                       for k in layers)
            return x * w if mine and w.shape[-1] != cfg.hidden_size \
                else real(x, w, eps)
        monkeypatch.setattr(llama, "rms_norm", rms_norm)
    elif control == "scale_of_a_192_wide_head":
        # (the tiny nope part is 24 wide: its scale in place of 32's)
        real = llama.paged_attention
        monkeypatch.setattr(
            llama, "paged_attention",
            lambda *a, scale=None, **k: real(
                *a, scale=cfg.qk_nope_head_dim ** -0.5, **k))
    elif control == "unscaled_routing_weights":
        monkeypatch.setattr(moe, "route", _plain_route(scale=1.0))
    elif control == "unnormalised_routing_weights":
        monkeypatch.setattr(moe, "route", _plain_route(normalise=False))
    elif control == "no_shared_expert":
        layers = {k: (jnp.zeros_like(v) if k == "shared_down" else v)
                  for k, v in params["layers"].items()}
        params = {**params, "layers": layers}
    wrong = _serve(cfg, params, tokens)
    # a changed score, norm or weight moves unit-scale logits by hundredths
    # and more; 0.01 is 50 tolerances
    assert np.max(np.abs(wrong - ref)) > 0.01


# ---------------------------------------------------------------------------
# the paged kernel's latent mode, interpreted, against jax.numpy
# ---------------------------------------------------------------------------

def _latent_case(rng, *, rows, width, useful, nblk, dtype):
    """A pool of ``rows`` sequences' blocks with the lanes past ``useful``
    zero, as the program writes them, and queries with zeros there."""
    nb = rows * nblk + 1
    pool = rng.standard_normal((2, nb, BS, 1, width)).astype(np.float32)
    pool[..., useful:] = 0.0
    bt = np.arange(1, nb, dtype=np.int32).reshape(rows, nblk)
    return jnp.asarray(pool, dtype), jnp.asarray(bt)


def _want(q, pool, bt, q_start, kv_lens, *, layer, rank, scale):
    """[B, T, H, rank] by plain jax.numpy over the gathered rows."""
    ctx = llama._gather_kv(pool, bt, layer)               # [B, S, 1, W]
    t = q.shape[1]
    return llama.paged_attention(
        q, ctx, ctx[..., :rank], q_start[:, None] + jnp.arange(t)[None, :],
        kv_lens, scale=scale)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", ["decode_rows", "chunk_rows", "packed_step"])
def test_latent_walk_matches_jnp(case, dtype, tol):
    """Rows of one token, chunk rows and a packed step's tokens, contexts
    that end mid-block (and one past a group of the walk), 20 query heads
    over the one row a token; layer 1 of a two-layer pool."""
    from dynamo_tpu.ops.paged_attention import paged_attention_kernel

    rng = np.random.default_rng(7)
    heads, width, useful, rank, nblk = 20, 256, 136, 128, 40
    scale = 32 ** -0.5
    pool, bt = _latent_case(rng, rows=3, width=width, useful=useful,
                            nblk=nblk, dtype=dtype)
    t = 1 if case == "decode_rows" else 32
    # contexts end mid-block; row 1's passes the 512 keys of a decode group
    kv_lens = jnp.asarray([37, 600, 0] if t == 1 else [45, 293, 0], jnp.int32)
    q_len = jnp.asarray([1, 1, 0] if t == 1 else [32, 21, 0], jnp.int32)
    q_start = kv_lens - q_len
    q = rng.standard_normal((3, t, heads, width)).astype(np.float32)
    q[..., useful:] = 0.0
    q = jnp.asarray(q, dtype)
    want = np.asarray(_want(q, pool, bt, q_start, kv_lens, layer=1,
                            rank=rank, scale=scale), np.float32)
    kw = dict(layer=1, interpret=True, scale=scale, v_width=rank)
    if case == "packed_step":
        lay, valid = llama.token_layout(q_len, 3, t, 64)
        got = paged_attention_kernel(
            lay.to_tokens(q), pool, None, bt, q_start, kv_lens,
            starts=lay.starts, t=t, **kw)
        assert got.shape == (64, heads, rank)
        got, want = (np.asarray(got, np.float32)[:53],
                     np.asarray(lay.to_tokens(jnp.asarray(want)))[:53])
    else:
        got = np.asarray(paged_attention_kernel(
            q, pool, None, bt, q_start, kv_lens, **kw), np.float32)
        assert got.shape == (3, t, heads, rank)
        live = np.arange(t)[None, :] < np.asarray(q_len)[:, None]
        got, want = got[live], want[live]
    assert np.max(np.abs(got - want)) < tol


def test_latent_walk_refuses_what_it_does_not_serve():
    from dynamo_tpu.ops.paged_attention import paged_attention_kernel

    pool = jnp.zeros((1, 4, BS, 1, 256), jnp.float32)
    q = jnp.zeros((1, 1, 4, 256), jnp.float32)
    args = (jnp.ones((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32))
    for bad in ({"v_width": 0}, {"v_width": 100}, {"v_width": 384},
                {"v_width": 128, "window": 8}):
        with pytest.raises(ValueError, match="latent pool"):
            paged_attention_kernel(q, pool, None, *args, layer=0,
                                   interpret=True, **bad)
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention_kernel(q, jnp.zeros((1, 4, BS, 2, 256)), None, *args,
                               layer=0, interpret=True, v_width=128)


# ---------------------------------------------------------------------------
# the cache: one pool, sized and counted by its own bytes
# ---------------------------------------------------------------------------

def test_the_cache_is_one_pool(tiny):
    from dynamo_tpu.engine.cache import (
        KVCacheSpec,
        abstract_caches,
        allocate_cache,
    )

    cfg = dataclasses.replace(tiny[0], dtype="bfloat16")
    spec = KVCacheSpec.for_model(cfg, 10, BS)
    assert spec.latent and spec.kind == "latent" and spec.pools == 1
    assert spec.shape == (3, 10, BS, 1, 256) and spec.row_width == 136
    # 3 layers x 16 tokens x 256 stored values x 2 B, once
    assert spec.bytes_per_block() == 3 * BS * 256 * 2
    assert spec.bytes_per_token() == 3 * 256 * 2
    pool, none = allocate_cache(spec)
    assert none is None and pool.shape == spec.shape
    one, none = abstract_caches(spec)
    assert none is None and one.shape == spec.shape
    with pytest.raises(ValueError, match="quantized latent pool"):
        KVCacheSpec.for_model(cfg, 10, BS, kv_dtype="int8")
    # a model of keys and values by head keeps its two pools
    plain = KVCacheSpec.for_model(ModelConfig(), 10, BS)
    assert plain.pools == 2 and plain.kind == "kv" and not plain.latent
    assert plain.bytes_per_block() == 2 * 2 * BS * 2 * 16 * 2


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    d = tmp_path_factory.mktemp("glm_engine")
    (d / "config.json").write_text(json.dumps(TINY))
    return EngineCore(EngineConfig(
        model=str(d), allow_random_weights=True, max_batch_size=4,
        max_model_len=256, prefill_chunk=32, num_blocks=40,
        attn_impl="pallas_interpret")), d


def test_the_engine_serves_it_and_counts_the_pool_once(engine):
    """Through ``EngineCore``: a prompt of three chunks and a few decoded
    tokens beside a second request; the pool is one array, a block's bytes
    are one pool's, and ``stats()["attn"]`` says so."""
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from test_engine import make_req, run_to_completion   # the suite's

    core, _ = engine
    r = core.runner
    assert r.cache_v is None and r.cache_k.shape == (3, 40, BS, 1, 256)
    assert r._block_bytes_per_device() == 3 * BS * 256 * 2
    assert core.metrics.kv_cache_bytes == 40 * 3 * BS * 256 * 2
    rng = np.random.default_rng(0)
    reqs = [make_req(rng.integers(0, 256, n).tolist(), max_tokens=5, rid=rid)
            for rid, n in (("a", 70), ("b", 9))]
    collected, finished = run_to_completion(core, reqs, max_steps=60)
    assert finished == {"a", "b"}
    assert [len(v) for v in collected.values()] == [5, 5]
    attn = AsyncJaxEngine(core).stats()["attn"]
    assert attn["cache_kind"] == "latent" and attn["pools"] == 1
    assert (attn["row_stored"], attn["row_useful"]) == (256, 136)
    assert attn["bytes_per_token"] == 3 * 256 * 2
    # request a's chunks of 32, 32 and 6 tokens end at 32, 64 and 70
    assert attn["chunk_rows"] >= 3 and attn["chunk_ctx_tokens"] >= 166
    shapes = core.metrics.step_shapes
    assert shapes["cache_kind"] == "latent"
    assert shapes["kv_block_bytes_per_layer"] == BS * 136 * 2
    assert shapes["head_dim"] == (136 + 128) // 2


@pytest.mark.parametrize("option,value", [
    ("tp", 2), ("pp", 2), ("sp", 2), ("kv_dtype", "int8"),
    ("host_kv_blocks", 8), ("stream_ckpt_blocks", 4)])
def test_what_the_latent_cache_does_not_serve_is_refused(engine, option,
                                                         value):
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    _, d = engine
    with pytest.raises(ValueError, match="latent"):
        EngineCore(EngineConfig(
            model=str(d), allow_random_weights=True, max_batch_size=4,
            max_model_len=256, num_blocks=40, **{option: value}))
