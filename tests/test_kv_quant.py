"""Int8 KV-cache tests: quant parity, capacity math, tiering round-trips.

The quantization contract (engine/cache.py, models/llama.py,
ops/paged_attention.py): kv_dtype="int8" stores the paged cache as int8
payload + per-(layer, block, kv-head) float32 scales, quantizes at scatter
time, and dequantizes either on gather (dense fallback) or inside the
Pallas kernel's per-block matmuls. Accuracy is a tolerance story — blocks
round-trip at ~1/127 relative error — so parity is asserted with max-abs
bounds, never bit-equality against the float cache.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.cache import KVCacheSpec, allocate_cache
from dynamo_tpu.engine.engine import EngineCore
from dynamo_tpu.models.config import resolve_model_config
from dynamo_tpu.tokens import compute_block_hashes_for_tokens

from tests.test_engine import make_req, run_to_completion, tiny_config

PROMPT = list(range(30, 54))  # 24 tokens = 6 full blocks of 4


# -- capacity math -----------------------------------------------------------

def test_bytes_per_block_near_halves_for_8b():
    cfg = resolve_model_config("llama-3-8b-lite")
    bf16 = KVCacheSpec.for_model(cfg, 1, 16)
    int8 = KVCacheSpec.for_model(cfg, 1, 16, kv_dtype="int8")
    assert bf16.dtype == int8.dtype  # model dtype untouched by kv quant
    ratio = int8.bytes_per_block() / bf16.bytes_per_block()
    assert ratio <= 0.55, f"int8 block is {ratio:.3f}x bf16, want <= 0.55"
    assert int8.quantized and not bf16.quantized
    assert int8.scale_shape == (cfg.num_layers, 1, cfg.num_kv_heads)


# -- scatter/gather round-trip (model write/read path) -----------------------

def _quant_cache(nb=8, bs=4, kh=2, d=8):
    return {"q": jnp.zeros((nb, bs, kh, d), jnp.int8),
            "s": jnp.zeros((nb, kh), jnp.float32)}


def test_scatter_gather_roundtrip():
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    rng = np.random.default_rng(0)
    new = jnp.asarray(rng.normal(size=(2, 8, 2, 8)).astype(np.float32))
    # row i writes blocks 0/1, row ii blocks 2/3 (block_size 4)
    slots = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7],
                         [8, 9, 10, 11, 12, 13, 14, 15]], jnp.int32)
    cache = _scatter_kv(_quant_cache(), new, slots)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    got = _gather_kv(cache, bt)  # [2, 8, 2, 8]
    err = np.abs(np.asarray(got) - np.asarray(new)).max()
    scale = np.abs(np.asarray(new)).max()
    assert err / scale < 0.02, err / scale


def test_scatter_offset0_resets_recycled_block_scale():
    """A freed block re-tenanted by a new sequence starts its write at
    offset 0 — the old tenant's (possibly huge) scale must not bleed into
    the new tenant's precision."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    big = jnp.full((1, 4, 2, 8), 100.0, jnp.float32)
    cache = _scatter_kv(_quant_cache(), big,
                        jnp.arange(4, dtype=jnp.int32)[None])
    # recycle block 0: new tenant writes small values from offset 0
    small = jnp.full((1, 4, 2, 8), 0.01, jnp.float32)
    cache = _scatter_kv(cache, small, jnp.arange(4, dtype=jnp.int32)[None])
    got = np.asarray(_gather_kv(cache, jnp.asarray([[0]], jnp.int32)))
    # with the stale scale (100/127) the quant step would be ~0.8
    assert np.abs(got - 0.01).max() < 1e-3


def test_scatter_append_merges_scales():
    """Appending rows to a partially-filled block (offset > 0) must keep the
    earlier rows decodable — the block scale only grows (max-merge) and the
    committed rows are rescaled, not clobbered."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    first = jnp.full((1, 2, 2, 8), 0.5, jnp.float32)
    cache = _scatter_kv(_quant_cache(), first, jnp.asarray([[0, 1]], jnp.int32))
    second = jnp.full((1, 2, 2, 8), 4.0, jnp.float32)
    cache = _scatter_kv(cache, second, jnp.asarray([[2, 3]], jnp.int32))
    got = np.asarray(_gather_kv(cache, jnp.asarray([[0]], jnp.int32)))[0]
    assert np.abs(got[:2] - 0.5).max() < 0.05
    assert np.abs(got[2:4] - 4.0).max() < 0.05


# -- kernel parity (in-kernel dequant vs dense on dequantized gather) --------

def test_pallas_interpret_matches_dense_on_quant_cache():
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv
    from dynamo_tpu.ops.paged_attention import paged_attention_kernel

    rng = np.random.default_rng(1)
    nb, bs, kh, d, b, h = 8, 16, 2, 64, 2, 4
    kc = _quant_cache(nb, bs, kh, d)
    vc = _quant_cache(nb, bs, kh, d)
    ctx = 2 * bs  # two full blocks of context per row
    slots = jnp.stack([jnp.arange(ctx), 2 * bs + jnp.arange(ctx)]).astype(jnp.int32)
    kc = _scatter_kv(kc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    vc = _scatter_kv(vc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    q_start = jnp.full((b,), ctx - 1, jnp.int32)
    kv_lens = jnp.full((b,), ctx, jnp.int32)

    out_kernel = paged_attention_kernel(q, kc, vc, bt, q_start, kv_lens,
                                        interpret=True)

    # Dense reference over the SAME quantized content (dequantized gather):
    # any difference is kernel math, not quantization noise.
    kg, vg = _gather_kv(kc, bt), _gather_kv(vc, bt)
    rep = h // kh
    qr = (q * (d ** -0.5)).reshape(b, 1, kh, rep, d).astype(jnp.float32)
    scores = jnp.einsum("btkrd,bskd->btkrs", qr, kg.astype(jnp.float32))
    mask = jnp.arange(ctx)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    ref = jnp.einsum("btkrs,bskd->btkrd",
                     jax.nn.softmax(scores, axis=-1), vg.astype(jnp.float32))
    err = np.abs(np.asarray(out_kernel) - np.asarray(ref.reshape(b, 1, h, d))).max()
    assert err < 2e-4, err


# -- engine-level parity & e2e smoke ----------------------------------------

def _greedy(kv_dtype, **kw):
    core = EngineCore(tiny_config(kv_dtype=kv_dtype, **kw))
    out, fin = run_to_completion(
        core, [make_req(prompt=PROMPT, max_tokens=6, rid="r")])
    assert fin == {"r"}
    return out["r"]


@pytest.mark.parametrize("variant", [
    {},                                    # plain decode
    {"spec_ngram": 2, "spec_k": 4},        # verify path
    {"attn_impl": "pallas_interpret"},     # kernel path (interpreted)
], ids=["dense", "verify", "pallas_interpret"])
def test_int8_engine_parity(variant):
    """int8 vs model-precision engines on the same greedy request: tokens
    may legitimately diverge once logits get close, but each variant must be
    internally deterministic and agree with model precision on an initial
    prefix (quantization noise is small vs the tiny model's logit gaps)."""
    toks_f = _greedy("bfloat16", **variant)
    toks_q = _greedy("int8", **variant)
    assert toks_f == _greedy("bfloat16", **variant)  # determinism
    assert toks_q == _greedy("int8", **variant)
    assert len(toks_f) == len(toks_q) == 6
    common = 0
    for a, b in zip(toks_f, toks_q):
        if a != b:
            break
        common += 1
    assert common >= 1, (toks_f, toks_q)


def test_int8_engine_logprob_tolerance():
    """First-token logprob (prefill-dominated, pre-divergence) must agree
    within a small absolute tolerance between int8 and model precision."""

    def first_lp(kv_dtype):
        core = EngineCore(tiny_config(kv_dtype=kv_dtype))
        core.add_request(make_req(prompt=PROMPT, max_tokens=2, rid="r"))
        while core.has_work():
            for rid, out in core.step().items():
                if out.log_probs:
                    return out.log_probs[0]
        raise AssertionError("no logprob emitted")

    assert abs(first_lp("int8") - first_lp("bfloat16")) < 0.05


def test_kv_dtype_validation():
    with pytest.raises(ValueError, match="kv_dtype"):
        EngineCore(tiny_config(kv_dtype="fp8"))


def test_metrics_report_kv_quant():
    core = EngineCore(tiny_config(kv_dtype="int8"))
    stats = core.metrics.snapshot(core.sched, core.pool)
    assert stats["kv_quant_enabled"] is True
    assert stats["kv_cache_bytes"] == (
        core.runner.spec.bytes_per_block() * core.runner.spec.num_blocks)
    plain = EngineCore(tiny_config())
    assert plain.metrics.snapshot(plain.sched, plain.pool)["kv_quant_enabled"] is False


def test_allocate_cache_quantized_shapes():
    spec = KVCacheSpec(num_blocks=8, block_size=4, num_layers=2,
                       num_kv_heads=2, head_dim=8, dtype="float32",
                       kv_dtype="int8")
    ck, cv = allocate_cache(spec, None)
    assert ck["q"].shape == spec.shape and ck["q"].dtype == jnp.int8
    assert ck["s"].shape == spec.scale_shape and ck["s"].dtype == jnp.float32
    assert cv["q"].shape == spec.shape


# -- tiering: offload round-trip + disagg export/import ----------------------

def test_int8_offload_onboard_determinism():
    # 12 usable blocks: prompt A (6 blocks) must be evicted by the fillers.
    core = EngineCore(tiny_config(kv_dtype="int8", num_blocks=13,
                                  host_kv_blocks=64))
    assert core.kvbm is not None
    prompt_a = list(range(100, 124))
    first, _ = run_to_completion(
        core, [make_req(prompt=prompt_a, max_tokens=6, rid="a1")])
    fillers = [make_req(prompt=[200 + 30 * i + j for j in range(24)],
                        max_tokens=4, rid=f"f{i}") for i in range(4)]
    run_to_completion(core, fillers)
    assert core.kvbm.stats.offloaded_blocks > 0
    # Host tier stores PACKED quantized blocks — flat uint8, one row per
    # block of exactly bytes_per_block() (half the bf16 footprint).
    host = core.kvbm.tiers[0]
    assert host._arena.dtype == np.uint8
    assert host._arena.shape[1:] == (core.runner.spec.bytes_per_block(),)
    second, _ = run_to_completion(
        core, [make_req(prompt=prompt_a, max_tokens=6, rid="a2")])
    assert core.kvbm.stats.onboarded_blocks > 0
    # The int8 payload round-trips bit-for-bit through the host tier, so
    # the greedy continuation stays identical.
    assert second["a2"] == first["a1"]


@pytest.mark.parametrize("src_dtype,dst_dtype", [
    ("int8", "int8"),       # packed blocks all the way
    ("int8", "bfloat16"),   # mixed: dequantize at import
    ("bfloat16", "int8"),   # mixed: requantize at import
    ("int4", "int4"),       # packed nibbles all the way
    ("int4", "bfloat16"),   # unpack + dequantize at import
    ("bfloat16", "int4"),   # quantize + pack at import
    ("int8", "int4"),       # cross-kind: requantize through float
])
def test_export_import_across_kv_dtypes(src_dtype, dst_dtype):
    src = EngineCore(tiny_config(kv_dtype=src_dtype))
    run_to_completion(src, [make_req(prompt=PROMPT, max_tokens=1, rid="s")])
    hashes = compute_block_hashes_for_tokens(PROMPT, 4)
    plan = src.export_blocks(hashes)
    assert len(plan) == 6  # all full prompt blocks resident + committed
    if src_dtype in ("int8", "int4"):
        assert plan[0][2].dtype == np.uint8 and plan[0][2].ndim == 1
    dst = EngineCore(tiny_config(kv_dtype=dst_dtype))
    assert dst.import_blocks(plan) == 6
    # The imported prefix is matchable: a re-sent prompt hits it.
    out, _ = run_to_completion(
        dst, [make_req(prompt=PROMPT, max_tokens=6, rid="d")])
    stats = dst.metrics.snapshot(dst.sched, dst.pool)
    assert stats["prefix_hit_rate"] > 0
    assert len(out["d"]) == 6


# -- int4: packed-nibble KV (quarter bf16 footprint) --------------------------

def test_bytes_per_block_int4_near_quarters():
    cfg = resolve_model_config("llama-3-8b-lite")
    bf16 = KVCacheSpec.for_model(cfg, 1, 16)
    int4 = KVCacheSpec.for_model(cfg, 1, 16, kv_dtype="int4")
    ratio = int4.bytes_per_block() / bf16.bytes_per_block()
    assert ratio <= 0.30, f"int4 block is {ratio:.3f}x bf16, want <= 0.30"
    assert int4.quantized and int4.packed_int4
    assert int4.payload_dtype == jnp.uint8
    assert int4.payload_head_dim == cfg.head_dim // 2
    assert int4.scale_shape == (cfg.num_layers, 1, cfg.num_kv_heads)


def test_int4_odd_head_dim_rejected():
    spec = KVCacheSpec(num_blocks=8, block_size=4, num_layers=2,
                       num_kv_heads=2, head_dim=7, dtype="float32",
                       kv_dtype="int4")
    with pytest.raises(ValueError, match="even head_dim"):
        spec.payload_head_dim


def test_allocate_cache_int4_shapes():
    spec = KVCacheSpec(num_blocks=8, block_size=4, num_layers=2,
                       num_kv_heads=2, head_dim=8, dtype="float32",
                       kv_dtype="int4")
    ck, cv = allocate_cache(spec, None)
    assert ck["q"].shape == spec.payload_shape  # trailing dim = head_dim/2
    assert ck["q"].shape[-1] == 4
    assert ck["q"].dtype == jnp.uint8
    assert ck["s"].shape == spec.scale_shape and ck["s"].dtype == jnp.float32
    assert cv["q"].shape == spec.payload_shape


def _int4_cache(nb=8, bs=4, kh=2, d=8):
    return {"q": jnp.zeros((nb, bs, kh, d // 2), jnp.uint8),
            "s": jnp.zeros((nb, kh), jnp.float32)}


def test_scatter_gather_roundtrip_int4():
    """±7 quantization: round-trip error bounded by half a quant step
    (amax/14) per element."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    rng = np.random.default_rng(0)
    new = jnp.asarray(rng.normal(size=(2, 8, 2, 8)).astype(np.float32))
    slots = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7],
                         [8, 9, 10, 11, 12, 13, 14, 15]], jnp.int32)
    cache = _scatter_kv(_int4_cache(), new, slots)
    assert cache["q"].dtype == jnp.uint8 and cache["q"].shape[-1] == 4
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    got = _gather_kv(cache, bt)
    err = np.abs(np.asarray(got) - np.asarray(new)).max()
    scale = np.abs(np.asarray(new)).max()
    assert err / scale < 0.08, err / scale


def test_int4_scatter_append_merges_scales():
    """The int8 scale lifecycle (offset-0 reset, max-merge, committed-row
    requant) must survive the pack/unpack round through uint8 nibbles."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    first = jnp.full((1, 2, 2, 8), 0.5, jnp.float32)
    cache = _scatter_kv(_int4_cache(), first, jnp.asarray([[0, 1]], jnp.int32))
    second = jnp.full((1, 2, 2, 8), 4.0, jnp.float32)
    cache = _scatter_kv(cache, second, jnp.asarray([[2, 3]], jnp.int32))
    got = np.asarray(_gather_kv(cache, jnp.asarray([[0]], jnp.int32)))[0]
    assert np.abs(got[:2] - 0.5).max() < 0.3    # 4.0/7 quant step
    assert np.abs(got[2:4] - 4.0).max() < 0.3


def _logits_over_cache(kv_dtype, attn_impl, verify):
    """Float32 logits of one forward that reads a ``kv_dtype`` cache: the
    prompt is prefilled into it, then a fixed continuation (one decode
    token, or a five-token verify chunk with logits at every position)
    attends to it. No token is sampled in between, so the int4 and the
    model-precision runs see the same inputs."""
    from dynamo_tpu.models import llama

    cfg = resolve_model_config("tiny-llama")
    params = llama.init_params(cfg, jax.random.key(0))
    spec = KVCacheSpec.for_model(cfg, 16, 4, kv_dtype=kv_dtype)
    ck, cv = allocate_cache(spec, None)
    bt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    kw = dict(attn_impl=attn_impl)
    n = len(PROMPT)
    _, ck, cv = llama.forward(
        params, cfg, jnp.asarray([PROMPT], jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.asarray([n], jnp.int32), bt, ck, cv,
        **kw)
    nxt = [7, 8, 9, 10, 11] if verify else [7]
    hidden, _, _ = llama.forward(
        params, cfg, jnp.asarray([nxt], jnp.int32),
        jnp.asarray([n], jnp.int32), jnp.asarray([len(nxt)], jnp.int32),
        bt, ck, cv, return_all_hidden=verify, **kw)
    return np.asarray(llama.logits_from_hidden(params, cfg, hidden),
                      np.float32)


#: rms(logits over an int4 cache - logits over a model-precision cache) /
#: std(logits). Read on this model: 0.17-0.21 in every variant, 8.5-20x the
#: int8 cache's 0.010-0.021, which is what 7 levels against 127 give. The
#: top two logits lie 0.08 apart, under that noise: that is why the greedy
#: streams of the two engines part at the first token, and why no stream is
#: compared here.
INT4_LOGIT_RMS_TOL = 0.3


@pytest.mark.parametrize("variant, fwd", [
    ({}, ("dense", False)),                                   # plain decode
    ({"spec_ngram": 2, "spec_k": 4}, ("dense", True)),        # verify path
    ({"attn_impl": "pallas_interpret"},                       # kernel path
     ("pallas_interpret", False)),
    ({"attn_impl": "pallas_interpret", "spec_ngram": 2, "spec_k": 4},
     ("pallas_interpret", True)),              # a verify chunk in the kernel
], ids=["dense", "verify", "pallas_interpret", "pallas_verify"])
def test_int4_engine_parity(variant, fwd):
    """The int4 engine is deterministic, and one forward over an int4 cache
    gives the model-precision logits within ``INT4_LOGIT_RMS_TOL`` — and
    farther from them than an int8 cache does, as a 4-bit cache must."""
    toks_q = _greedy("int4", **variant)
    assert toks_q == _greedy("int4", **variant)  # determinism
    assert len(toks_q) == 6
    ref = _logits_over_cache("bfloat16", *fwd)
    err = {kv: float(np.sqrt(np.mean((_logits_over_cache(kv, *fwd) - ref) ** 2))
                     / ref.std()) for kv in ("int8", "int4")}
    assert err["int8"] < err["int4"] < INT4_LOGIT_RMS_TOL, err


def test_int4_offload_onboard_determinism():
    """Mirror of the int8 offload round-trip: the packed nibble payload
    must move through the host tier bit-for-bit."""
    core = EngineCore(tiny_config(kv_dtype="int4", num_blocks=13,
                                  host_kv_blocks=64))
    assert core.kvbm is not None
    prompt_a = list(range(100, 124))
    first, _ = run_to_completion(
        core, [make_req(prompt=prompt_a, max_tokens=6, rid="a1")])
    fillers = [make_req(prompt=[200 + 30 * i + j for j in range(24)],
                        max_tokens=4, rid=f"f{i}") for i in range(4)]
    run_to_completion(core, fillers)
    assert core.kvbm.stats.offloaded_blocks > 0
    host = core.kvbm.tiers[0]
    assert host._arena.dtype == np.uint8
    assert host._arena.shape[1:] == (core.runner.spec.bytes_per_block(),)
    second, _ = run_to_completion(
        core, [make_req(prompt=prompt_a, max_tokens=6, rid="a2")])
    assert core.kvbm.stats.onboarded_blocks > 0
    assert second["a2"] == first["a1"]
