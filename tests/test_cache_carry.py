"""The KV cache rides the layer loop whole, as carried state written and read
in place at (layer, block) — models/llama.py ``_run_layers``.

Two things are held here. The semantics: ``forward`` returns the hidden state
and the cache that a plain Python loop over layers returns when it cuts
``cache[l]`` out, scatters into it, attends over it and stacks the layers
back (the form the carry replaced, written out as the reference). And the
memory: the bytes a compiled step holds beyond its arguments do not grow
with the pool, which is what lets the engine reserve the device's memory for
blocks instead of for copies of them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.cache import KVCacheSpec, _zeros
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import MODEL_PRESETS

BS, NB, NBLK = 4, 24, 4


def _reference_forward(params, cfg, token_ids, q_start, q_len, block_tables,
                       cache_k, cache_v, attn_impl):
    """forward(), one layer of the cache at a time: a Python loop that takes
    ``cache[l]`` out, writes the step's K/V into that slice, attends over
    the slice and stacks the slices back."""
    b, t = token_ids.shape
    bs, nblk = llama._cache_block_size(cache_k), block_tables.shape[1]
    positions = q_start[:, None] + jnp.arange(t)[None, :]
    valid = jnp.arange(t)[None, :] < q_len[:, None]
    kv_lens = q_start + q_len
    blk = jnp.take_along_axis(
        block_tables, jnp.clip(positions // bs, 0, nblk - 1), axis=1)
    slot = jnp.where(valid, blk * bs + positions % bs, 0)

    def scatter(layer_cache, new):
        if isinstance(layer_cache, dict):
            # The quantized write of ONE layer (its own tests: test_kv_quant).
            return llama._scatter_kv(layer_cache, new, slot)
        nb, _, kh, d = layer_cache.shape
        flat = layer_cache.reshape(nb * bs, kh, d)
        flat = flat.at[slot.reshape(-1)].set(new.reshape(-1, kh, d))
        return flat.reshape(nb, bs, kh, d)

    h = llama.embed_lookup(params["embed"], token_ids, jnp.dtype(cfg.dtype))
    layers_k, layers_v = [], []
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        ck, cv = jax.tree.map(lambda a: a[l], (cache_k, cache_v))
        x = llama.rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q = llama.mm(x, lp["wq"]).reshape(b, t, cfg.num_heads, cfg.head_dim)
        k = llama.mm(x, lp["wk"]).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        v = llama.mm(x, lp["wv"]).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        q = llama.rope(q, positions, cfg.rope_theta)
        k = llama.rope(k, positions, cfg.rope_theta)
        ck, cv = scatter(ck, k), scatter(cv, v)
        if attn_impl == "pallas_interpret":
            from dynamo_tpu.ops.paged_attention import paged_attention_kernel

            attn = paged_attention_kernel(q, ck, cv, block_tables, q_start,
                                          kv_lens, interpret=True)
        else:
            attn = llama.paged_attention(
                q, llama._gather_kv(ck, block_tables),
                llama._gather_kv(cv, block_tables), positions, kv_lens)
        h = h + llama.mm(attn.reshape(b, t, cfg.q_size), lp["wo"])
        x = llama.rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        h = h + (llama.moe_mlp(x, lp, cfg) if cfg.is_moe else
                 llama.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]))
        layers_k.append(ck)
        layers_v.append(cv)
    h = llama.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.clip(q_len - 1, 0, t - 1)
    last_h = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    stack = lambda *xs: jnp.stack(xs)  # noqa: E731
    return (last_h, jax.tree.map(stack, *layers_k),
            jax.tree.map(stack, *layers_v))


def _step_inputs(rng, cfg, kind):
    """A decode step (T=1, rows deep in their context) or a mixed chunk
    (T=8: a fresh prompt, a continuing chunk, a decode row, a padding row)."""
    if kind == "decode":
        q_start = np.array([5, 0, 14, 9], np.int32)
        q_len = np.array([1, 1, 1, 0], np.int32)       # last row is padding
        t = 1
    else:
        q_start = np.array([0, 8, 11, 0], np.int32)
        q_len = np.array([8, 5, 1, 0], np.int32)
        t = 8
    b = len(q_start)
    ids = rng.permutation(NB - 1)[: b * NBLK].reshape(b, NBLK) + 1
    tokens = rng.integers(1, cfg.vocab_size, (b, t))
    return (jnp.asarray(tokens, jnp.int32), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(ids, jnp.int32))


def _warm_cache(rng, spec):
    """A cache that already holds something in every layer, so that a write
    to the wrong layer, or a read of it, changes the answer."""
    def fill(z):
        if z.dtype == jnp.float32 and z.ndim == 3:      # scales
            return jnp.asarray(rng.uniform(0.01, 0.03, z.shape), jnp.float32)
        if jnp.issubdtype(z.dtype, jnp.integer):
            return jnp.asarray(rng.integers(-100, 100, z.shape), z.dtype)
        return jnp.asarray(rng.standard_normal(z.shape), z.dtype)
    return jax.tree.map(fill, _zeros(spec))


@pytest.mark.parametrize("kind", ["decode", "mixed"])
@pytest.mark.parametrize("model,attn_impl,kv_dtype", [
    pytest.param("tiny-llama", "dense", "float32", id="dense"),
    pytest.param("tiny-llama", "pallas_interpret", "float32",
                 id="pallas_interpret"),
    pytest.param("tiny-llama", "dense", "int8", id="int8"),
    pytest.param("tiny-moe", "dense", "float32", id="moe"),
])
def test_forward_with_carried_cache_equals_layer_by_layer(model, attn_impl,
                                                          kv_dtype, kind):
    cfg = dataclasses.replace(MODEL_PRESETS[model], num_layers=3,
                              dtype="float32")
    rng = np.random.default_rng(7)
    params = llama.init_params(cfg, jax.random.key(3))
    spec = KVCacheSpec.for_model(cfg, NB, BS, kv_dtype=kv_dtype)
    cache_k, cache_v = _warm_cache(rng, spec), _warm_cache(rng, spec)
    inputs = _step_inputs(rng, cfg, kind)

    got = jax.jit(lambda p, ck, cv: llama.forward(
        p, cfg, *inputs, ck, cv, attn_impl=attn_impl))(params, cache_k, cache_v)
    want = jax.jit(lambda p, ck, cv: _reference_forward(
        p, cfg, *inputs, ck, cv, attn_impl))(params, cache_k, cache_v)

    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got[1:]), jax.tree.leaves(want[1:])):
        assert g.shape == w.shape and g.dtype == w.dtype
        if jnp.issubdtype(g.dtype, jnp.integer):
            # Quantized payloads: a rounding tie may fall either way.
            assert np.abs(np.asarray(g, np.int32) - np.asarray(w, np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5, rtol=1e-5)
    # Every layer took this step's rows, and nothing else moved: the rows
    # the step did not write are the rows it was given, bit for bit.
    written = np.zeros((NB, BS), bool)
    q_start, q_len, bt = (np.asarray(x) for x in inputs[1:])
    for r in range(len(q_start)):
        for p in range(q_start[r], q_start[r] + q_len[r]):
            written[bt[r, p // BS], p % BS] = True
    written[0] = True                                   # the trash block
    quant = isinstance(cache_k, dict)
    before = np.asarray(cache_k["q"] if quant else cache_k)
    after = np.asarray(got[1]["q"] if quant else got[1])
    if not quant:                       # int8 requantizes whole touched blocks
        np.testing.assert_array_equal(after[:, ~written], before[:, ~written])
    changed = (after[:, written] != before[:, written]).any(axis=(1, 2, 3))
    assert changed.all(), changed       # one flag a layer


# -- The memory claim -----------------------------------------------------------

@pytest.fixture
def f32_core(monkeypatch):
    """Engines over a float32 tiny model. XLA:CPU has no bf16 scatter: it
    widens the whole operand to f32 and back, which is that backend's copy
    and not the program's. (The bf16 pool is held to the same claim by the
    compile for a described v5e, tests/test_ops.py.)"""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    monkeypatch.setitem(
        MODEL_PRESETS, "tiny-llama-f32",
        dataclasses.replace(MODEL_PRESETS["tiny-llama"], name="tiny-llama-f32",
                            dtype="float32"))
    return lambda **kw: EngineCore(EngineConfig(
        model="tiny-llama-f32", max_batch_size=4, max_model_len=256,
        num_blocks=64, **kw))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"],
                         ids=["model-precision", "int8"])
def test_step_holds_no_copy_of_the_pool(f32_core, kv_dtype):
    """Lowered against an abstract cache at two pool sizes, as _fit_pool
    does, the step program's bytes beyond its arguments do not grow with
    the pool: under 5 % of a block for each block added."""
    from dynamo_tpu.obs.compile_ledger import BucketSig

    runner = f32_core(kv_dtype=kv_dtype).runner
    sig = BucketSig("mixed", 4, 16, runner.max_nblk, True, kv_dtype)
    n0, n1 = 256, 1024
    (_, extra0), (_, extra1) = (
        runner._probe_step_memory(sig, n) for n in (n0, n1))
    copies = (extra1 - extra0) / (n1 - n0)
    assert copies < 0.05 * runner._block_bytes_per_device(), (
        extra0, extra1, runner._block_bytes_per_device())


def test_stats_say_how_the_pool_was_sized(f32_core):
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    core = f32_core()
    stats = AsyncJaxEngine(core).stats()
    assert stats["kv_pool_blocks"] == 64 == stats["kv_cache_shape"][1]
    assert stats["kv_block_bytes"] == core.runner.spec.bytes_per_block()
    # A given pool is not probed: nothing was measured, and it says so.
    assert "kv_step_copy_bytes_per_block" in stats
    assert stats["kv_step_copy_bytes_per_block"] is None
    # Probed (what a TPU start-up does), the step's copies are ~0 B a block.
    runner = core.runner
    n = runner._fit_pool(64 * 1024 * 1024)
    assert n > runner.max_nblk
    assert abs(runner.step_copy_bytes_per_block) < 0.05 * stats["kv_block_bytes"]


# -- The Q/K/V projections ------------------------------------------------------

def _one_layer(kind, dtype):
    """(cfg, one layer's params, window): a dense layer, a sliding layer
    with QK norm, a layer whose matrices are int8 ``{"q", "so"}`` leaves."""
    cfg = dataclasses.replace(MODEL_PRESETS["tiny-llama"], dtype=dtype)
    window = 0
    if kind == "qk_norm-window":
        window = 6
        cfg = dataclasses.replace(
            cfg, qk_norm=True, sliding_window=window,
            layer_types=("sliding_attention",) * cfg.num_layers)
    params = llama.init_params(cfg, jax.random.key(11))
    if kind == "int8":
        from dynamo_tpu.models.quant import quantize_params_int8

        params = quantize_params_int8(params, cfg, quantize_embed=False)
        assert set(params["layers"]["wq"]) == {"q", "so"}
    return cfg, jax.tree.map(lambda a: a[1], params["layers"]), window


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["dense", "qk_norm-window", "int8"])
def test_layer_equals_the_projections_reshaped_in_place(monkeypatch, kind,
                                                        dtype):
    """``_attention`` holds its Q, K and V products two-dimensional up to an
    optimization barrier and splits the heads after it, so that the chip's
    compiler reads each matrix where it lies (tests/test_ops.py holds the
    compiled text to that). It is the arithmetic it was: with the barrier
    taken away the text is ``mm(x, w).reshape(n, heads, D)`` again, and a
    mixed step over a warm cache gives the same hidden state and the same
    cache, bit for bit in float32 and to a bf16 rounding in bfloat16."""
    cfg, lp, window = _one_layer(kind, dtype)
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(5)
    spec = KVCacheSpec.for_model(cfg, NB, BS, kv_dtype=dtype)
    cache_k, cache_v = _warm_cache(rng, spec), _warm_cache(rng, spec)
    _, q_start, q_len, bt = _step_inputs(rng, cfg, "mixed")
    b, t = len(q_start), 8
    lay, valid = llama.token_layout(q_len, b, t, b * t)
    positions, slot = llama._positions_and_slots(lay, valid, q_start, bt, BS)
    hid = jnp.asarray(rng.standard_normal((b * t, cfg.hidden_size)), dt)

    def layer(lp, hid, ck, cv):
        x = llama.rms_norm(hid, lp["attn_norm"], cfg.rms_norm_eps)
        attn, ck, cv = llama._attention(
            cfg, lp, 1, x, ck, cv, lay=lay, positions=positions, slot=slot,
            block_tables=bt, q_start=q_start, kv_lens=q_start + q_len,
            window=window)
        hid = hid + attn
        x = llama.rms_norm(hid, lp["mlp_norm"], cfg.rms_norm_eps)
        return hid + llama._ffn(cfg, lp, x, None, "dense", None, None)[0], ck, cv

    barriers = []
    real = jax.lax.optimization_barrier
    monkeypatch.setattr(jax.lax, "optimization_barrier",
                        lambda x: (barriers.append(x), real(x))[1])
    got = jax.jit(layer)(lp, hid, cache_k, cache_v)
    n = b * t
    (held,) = barriers
    assert [a.shape for a in held] == [
        (n, cfg.q_size), (n, cfg.kv_size), (n, cfg.kv_size)]
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    want = jax.jit(layer)(lp, hid, cache_k, cache_v)
    assert len(barriers) == 1
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == dt
        if dtype == "float32":
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            # One unit in the last place of bf16 (2**-8 relative), where
            # a backend rounds the product before rope on one side only.
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=2 ** -7, atol=2 ** -7)
