"""A step's dense layers run over its live tokens ``[N, H]``; attention alone
sees rows (models/llama.py ``TokenLayout``).

Held here: ``forward(num_tokens=N)`` returns the logits and writes the KV
that a plain ``[B, T]`` rectangle does, for ragged mixed batches, dense and
with the routed MLP, over a bf16 and an int8 cache, off a mesh and with the
heads split two ways. The rectangle is ``test_cache_carry``'s reference: a
plain Python loop over layers in ``jax.numpy``, in float32 and without
``forward``'s layout code; no token stream is compared (random weights
decide those by a hair).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.cache import KVCacheSpec, _zeros, cache_sharding
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import MODEL_PRESETS
from dynamo_tpu.obs.compile_ledger import token_bucket
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh, shard_params
from tests.test_cache_carry import _reference_forward, _warm_cache

BS, NBLK, B, T = 4, 8, 8, 16
NB = 1 + B * NBLK                 # block 0 is the trash block

# (q_start, q_len) a row; rows beyond the list are padding. Every case holds
# no more live tokens than token_bucket("mixed", B, T) = 24.
CASES = {
    "chunk_and_decode": (T, [(5, 1), (9, 1), (20, 1), (0, 16)]),
    "two_chunks": (T, [(7, 1), (0, 13), (16, 6)]),
    "three_chunks": (T, [(3, 1), (0, 9), (8, 5), (12, 3), (30, 1)]),
    "empty_rows": (T, [(3, 1), (0, 0), (8, 12), (0, 0), (17, 1)]),
    # The step's token budget cut the chunk short of its block boundary.
    "budget_cut": (T, [(11, 1), (5, 7), (26, 1)]),
    "decode_only": (1, [(5, 1), (0, 1), (14, 1), (31, 1), (9, 1)]),
}


def _inputs(case: str, cfg):
    t, rows = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    q_start = np.zeros(B, np.int32)
    q_len = np.zeros(B, np.int32)
    for i, (start, length) in enumerate(rows):
        q_start[i], q_len[i] = start, length
    tables = 1 + rng.permutation(B * NBLK).reshape(B, NBLK).astype(np.int32)
    tokens = rng.integers(1, cfg.vocab_size, (B, t)).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (tokens, q_start, q_len, tables))


def _dequant(cache):
    if isinstance(cache, dict):
        return (cache["q"].astype(jnp.float32)
                * cache["s"][:, :, None, :, None])
    return cache.astype(jnp.float32)


@pytest.fixture(scope="module")
def models():
    """Float32 parameters of the dense and the routed toy, made once."""
    out = {}
    for name in ("tiny-llama", "tiny-moe"):
        cfg = dataclasses.replace(MODEL_PRESETS[name], dtype="float32")
        out[name] = (cfg, llama.init_params(cfg, jax.random.key(5)))
    return out


def _run(models, case, model, kv, tp, dtype="float32"):
    """(token-major, rectangle): logits [B, V] of the live rows and the
    dequantized K and V, trash block left out."""
    cfg32, params32 = models[model]
    cfg = dataclasses.replace(cfg32, dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype), params32)
    inputs = _inputs(case, cfg)
    t = inputs[0].shape[1]
    n = token_bucket("mixed", B, t)
    assert int(inputs[2].sum()) <= n
    rng = np.random.default_rng(11)
    spec = KVCacheSpec.for_model(cfg, NB, BS, kv_dtype=kv)
    cache_k, cache_v = _warm_cache(rng, spec), _warm_cache(rng, spec)
    # The rectangle reads the same cache, in float32 where it is not int8.
    spec32 = KVCacheSpec.for_model(cfg32, NB, BS, kv_dtype=kv)
    ref_k, ref_v = (jax.tree.map(
        lambda a, z: a.astype(z.dtype), c, _zeros(spec32))
        for c in (cache_k, cache_v))
    mesh = None
    if tp > 1:
        mesh = make_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
        params = shard_params(params, llama.param_logical_axes(cfg), mesh)
        sh = cache_sharding(spec, mesh)
        cache_k, cache_v = (jax.device_put(c, sh) for c in (cache_k, cache_v))

    def step(p, ck, cv):
        hid, ck, cv = llama.forward(p, cfg, *inputs, ck, cv, mesh=mesh,
                                    num_tokens=n)
        return llama.logits_from_hidden(p, cfg, hid), ck, cv

    got = jax.jit(step)(params, cache_k, cache_v)
    def rectangle(p, ck, cv):
        hid, ck, cv = _reference_forward(p, cfg32, *inputs, ck, cv, "dense")
        return llama.logits_from_hidden(p, cfg32, hid), ck, cv

    want = jax.jit(rectangle)(params32, ref_k, ref_v)
    live = np.asarray(inputs[2]) > 0

    def view(out):
        logits, ck, cv = out
        return (np.asarray(logits, np.float32)[live],
                np.asarray(_dequant(ck))[:, 1:], np.asarray(_dequant(cv))[:, 1:])

    return view(got), view(want)


@pytest.mark.parametrize("tp", [1, 2], ids=["one_device", "model2"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8"], ids=["plain_kv", "int8_kv"])
@pytest.mark.parametrize("model", ["tiny-llama", "tiny-moe"],
                         ids=["dense", "routed"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_token_major_step_equals_the_rectangle(models, case, model, kv, tp):
    got, want = _run(models, case, model, kv, tp)
    # int8: a value that lands on the other side of a rounding step moves
    # one element of the cache by one step (1/127 of its block's range).
    tol = 1e-4 if kv == "bfloat16" else 3e-2
    np.testing.assert_allclose(got[0], want[0], atol=tol, rtol=tol)
    for g, w in zip(got[1:], want[1:]):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=tol, rtol=tol)


@pytest.mark.parametrize("case", sorted(CASES))
def test_bf16_step_stays_near_the_float32_rectangle(models, case):
    """The dense model as it is served: bf16 weights, activations and cache.
    What is held is the log-probabilities, within the distance bf16 leaves
    at these widths, and the KV the step wrote, block for block. (A routed
    layer's choice can flip under bf16 rounding: its parity is held in
    float32, above.)"""
    got, want = _run(models, case, "tiny-llama", "bfloat16", 1,
                     dtype="bfloat16")
    lp = lambda x: np.asarray(jax.nn.log_softmax(jnp.asarray(x), axis=-1))  # noqa: E731
    assert np.abs(lp(got[0]) - lp(want[0])).max() < 0.1
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, atol=0.06, rtol=0.02)


def test_layout_maps_rows_and_tokens_both_ways():
    """Rows to tokens and back is the identity on live positions, padding
    tokens are marked, and N == B*T is the rectangle by reshape alone."""
    q_len = jnp.asarray([1, 0, 5, 3, 0, 1, 0, 0], jnp.int32)
    lay, valid = llama.token_layout(q_len, 8, 8, 16)
    assert np.asarray(valid).tolist() == [True] * 10 + [False] * 6
    rect = jnp.arange(64).reshape(8, 8)
    toks = np.asarray(lay.to_tokens(rect))
    assert toks[:10].tolist() == [0, 16, 17, 18, 19, 20, 24, 25, 26, 40]
    back = np.asarray(lay.to_rows(jnp.asarray(toks)))
    for r, n in enumerate(np.asarray(q_len)):
        assert back[r, :n].tolist() == rect[r, :n].tolist()
    whole, valid = llama.token_layout(q_len, 8, 8, 64)
    assert whole.row_tok is None and whole.tok_row is None
    assert np.asarray(whole.to_tokens(rect)).tolist() == list(range(64))
    assert int(valid.sum()) == 10
