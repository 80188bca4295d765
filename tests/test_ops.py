"""Numerical-equivalence tests for the Pallas hot-op kernels (interpret mode).

Mirrors the reference's kernel-adjacent unit testing (its CUDA block-copy is
tested via block_manager tests); here the kernels are compared bit-for-tol
against the portable XLA paths they replace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import paged_attention
from dynamo_tpu.ops.paged_attention import paged_attention_kernel


def _make_case(rng, b, t, h, kh, d, nb, bs, nblk, dtype=jnp.float32):
    """Random paged-cache attention case with per-seq positions/lengths."""
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
    k_cache = jnp.asarray(rng.standard_normal((nb, bs, kh, d)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((nb, bs, kh, d)), dtype)
    # Distinct block ids per row (block 0 = trash block, never assigned).
    ids = rng.permutation(nb - 1)[: b * nblk].reshape(b, nblk) + 1
    block_tables = jnp.asarray(ids, jnp.int32)
    q_start = jnp.asarray(rng.integers(0, nblk * bs - t, size=(b,)), jnp.int32)
    q_len = jnp.full((b,), t, jnp.int32)
    return q, k_cache, v_cache, block_tables, q_start, q_len


def _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len):
    b, t = q.shape[:2]
    bs = k_cache.shape[1]
    positions = q_start[:, None] + jnp.arange(t)[None, :]
    kv_lens = q_start + q_len
    g = k_cache[block_tables]
    ctx_k = g.reshape(b, -1, *g.shape[3:])
    g = v_cache[block_tables]
    ctx_v = g.reshape(b, -1, *g.shape[3:])
    return paged_attention(q, ctx_k, ctx_v, positions, kv_lens)


@pytest.mark.parametrize("t", [1, 8])
@pytest.mark.parametrize("kh,h", [(2, 2), (2, 8)])
def test_paged_attention_kernel_matches_dense(t, kh, h):
    rng = np.random.default_rng(0)
    case = _make_case(rng, b=3, t=t, h=h, kh=kh, d=64, nb=32, bs=16, nblk=4)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    out = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_paged_attention_kernel_ragged_lengths():
    """Rows with different kv_lens (mid-block boundaries) still match."""
    rng = np.random.default_rng(1)
    b, t, h, kh, d, nb, bs, nblk = 4, 4, 4, 2, 64, 32, 16, 4
    q, k_cache, v_cache, block_tables, _, _ = _make_case(rng, b, t, h, kh, d, nb, bs, nblk)
    q_start = jnp.asarray([0, 5, 17, 40], jnp.int32)
    q_len = jnp.asarray([4, 4, 4, 4], jnp.int32)
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    out = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_paged_attention_kernel_zero_len_row():
    """A padding row (kv_len=0) must produce finite output, not NaN."""
    rng = np.random.default_rng(2)
    q, k_cache, v_cache, block_tables, q_start, q_len = _make_case(
        rng, b=2, t=1, h=2, kh=2, d=64, nb=16, bs=16, nblk=2
    )
    q_start = jnp.asarray([0, 0], jnp.int32)
    kv_lens = jnp.asarray([1, 0], jnp.int32)  # row 1 is padding
    out = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, kv_lens, interpret=True
    )
    assert np.isfinite(np.asarray(out)).all()


def test_paged_attention_kernel_qchunked_matches_dense(monkeypatch):
    """Force multiple query-row chunks (the long-prefill VMEM-bounded path)
    and check equivalence across chunk boundaries."""
    import dynamo_tpu.ops.paged_attention as pa

    rng = np.random.default_rng(4)
    # kh * r * (d+256) * 4 with small cap ⇒ several chunks
    case = _make_case(rng, b=2, t=16, h=8, kh=2, d=128, nb=32, bs=16, nblk=4)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)

    real_call = pa.pl.pallas_call
    seen_grid = {}

    def spy(kernel, *a, grid_spec=None, **kw):
        seen_grid["grid"] = grid_spec.grid
        return real_call(kernel, *a, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    monkeypatch.setattr(
        pa, "_SCRATCH_CAP_BYTES", 64 * 1024, raising=False
    )
    out = pa.paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len, interpret=True
    )
    assert seen_grid["grid"][1] > 1, "expected multiple q-row chunks"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("num_splits", [1, 2], ids=["sequential", "split_k"])
def test_paged_attention_kernel_leaves_padded_query_chunks(monkeypatch,
                                                           num_splits):
    """Rows of a mixed step in one [B, T] rectangle: a full chunk, a chunk
    cut short, a one-token row deep in its context, a padding row. Every
    live position equals the dense path; a query chunk that holds padding
    only is not walked at all, and so reads 0 where it used to hold the
    attention of a position nobody asked for."""
    import dynamo_tpu.ops.paged_attention as pa

    rng = np.random.default_rng(6)
    b, t, h, kh, d, bs, nblk = 4, 32, 4, 2, 128, 16, 4
    q, k_cache, v_cache, block_tables, _, _ = _make_case(
        rng, b, t, h, kh, d, nb=32, bs=bs, nblk=nblk)
    q_start = jnp.asarray([0, 16, 41, 0], jnp.int32)
    q_len = jnp.asarray([32, 11, 1, 0], jnp.int32)
    ref = np.asarray(_dense_ref(q, k_cache, v_cache, block_tables, q_start,
                                q_len))
    monkeypatch.setattr(pa, "_SCRATCH_CAP_BYTES", 48 * 1024, raising=False)
    grids = []
    real_call = pa.pl.pallas_call
    monkeypatch.setattr(
        pa.pl, "pallas_call",
        lambda kernel, *a, grid_spec=None, **kw: (
            grids.append(grid_spec.grid),
            real_call(kernel, *a, grid_spec=grid_spec, **kw))[1])
    out = np.asarray(pa.paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=num_splits, interpret=True))
    nq = grids[0][1]
    assert nq == 4 and grids[0][2] == num_splits, grids
    tq = t // nq
    for row, n in enumerate(np.asarray(q_len)):
        np.testing.assert_allclose(out[row, :n], ref[row, :n],
                                   atol=2e-5, rtol=2e-5)
        dead_from = -(-int(n) // tq) * tq      # first wholly padded chunk
        assert not out[row, dead_from:].any(), row
        assert n == 0 or np.abs(ref[row, dead_from:]).max(initial=1.0) > 0


def test_paged_attention_sharded_tp_matches_dense():
    """shard_map'd kernel over a tp=2 mesh (heads split) matches the dense
    path — the TP serving configuration of the kernel."""
    from dynamo_tpu.ops.paged_attention import paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=2))
    rng = np.random.default_rng(3)
    q, k_cache, v_cache, block_tables, q_start, q_len = _make_case(
        rng, b=2, t=4, h=8, kh=2, d=64, nb=32, bs=16, nblk=4
    )
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    out = paged_attention_sharded(
        mesh, q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_engine_pallas_interpret_matches_dense():
    """End-to-end: greedy generation identical between attn impls."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.utils.config import EngineConfig

    def run(attn_impl):
        cfg = EngineConfig(
            model="tiny-llama", attn_impl=attn_impl, max_batch_size=4,
            max_model_len=256, num_blocks=64, dtype="float32",
        )
        core = EngineCore(cfg)
        req = PreprocessedRequest(
            request_id="r1",
            token_ids=list(range(1, 20)),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
        )
        core.add_request(req)
        toks = []
        while core.has_work():
            for out in core.step().values():
                toks.extend(out.token_ids)
        return toks

    assert run("dense") == run("pallas_interpret")


# -- The whole cache and a layer index ------------------------------------------

def _whole_cache(rng, kv, nl, nb, bs, kh, d):
    """A random [L, NB, BS, KH, D] cache in storage format ``kv``."""
    if kv == "bfloat16":
        return jnp.asarray(rng.standard_normal((nl, nb, bs, kh, d)),
                           jnp.bfloat16)
    scales = jnp.asarray(rng.uniform(0.005, 0.02, (nl, nb, kh)), jnp.float32)
    if kv == "int8":
        payload = jnp.asarray(rng.integers(-127, 128, (nl, nb, bs, kh, d)),
                              jnp.int8)
    else:  # packed int4: any byte is two valid nibbles
        payload = jnp.asarray(rng.integers(0, 256, (nl, nb, bs, kh, d // 2)),
                              jnp.uint8)
    return {"q": payload, "s": scales}


def _spoil_other_layers(cache, layer):
    """The same cache with every OTHER layer overwritten by garbage (NaN,
    extreme payloads, infinite scales)."""
    def spoil(a):
        if a.dtype == jnp.float32:                      # scales
            bad = jnp.inf
        elif jnp.issubdtype(a.dtype, jnp.floating):
            bad = jnp.nan
        else:
            bad = jnp.iinfo(a.dtype).max
        keep = (jnp.arange(a.shape[0]) == layer).reshape(
            (-1,) + (1,) * (a.ndim - 1))
        return jnp.where(keep, a, jnp.full_like(a, bad))
    return jax.tree.map(spoil, cache)


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("ns", [1, 2], ids=["nosplit", "split2"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8", "int4"])
@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_kernel_addresses_one_layer_of_the_whole_cache(layer, kv, ns, t):
    """The kernel on the whole [L, NB, ...] cache with layer index ``l`` is
    the kernel on that layer's slice, bit for bit, and the dense reference
    within tolerance; what the other layers hold does not matter."""
    from dynamo_tpu.models.llama import _gather_kv

    nl, b, h, kh, d, nb, bs, nblk = 3, 3, 4, 2, 64, 16, 16, 4
    rng = np.random.default_rng(100 * layer + 10 * ns + t)
    kc = _whole_cache(rng, kv, nl, nb, bs, kh, d)
    vc = _whole_cache(rng, kv, nl, nb, bs, kh, d)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    ids = rng.permutation(nb - 1)[: b * nblk].reshape(b, nblk) + 1
    bt = jnp.asarray(ids, jnp.int32)
    q_start = jnp.asarray([0, 21, nblk * bs - t], jnp.int32)    # ragged
    kv_lens = q_start + t

    def kernel(k, v, **kw):
        return np.asarray(paged_attention_kernel(
            q, k, v, bt, q_start, kv_lens, num_splits=ns, interpret=True,
            **kw).astype(jnp.float32))

    # The layer index traced, as inside the model's scan.
    whole = np.asarray(jax.jit(
        lambda k, v, l: paged_attention_kernel(
            q, k, v, bt, q_start, kv_lens, layer=l, num_splits=ns,
            interpret=True))(kc, vc, jnp.int32(layer)).astype(jnp.float32))
    one = jax.tree.map(lambda a: a[layer], (kc, vc))
    np.testing.assert_array_equal(whole, kernel(*one))
    np.testing.assert_array_equal(
        whole, kernel(_spoil_other_layers(kc, layer),
                      _spoil_other_layers(vc, layer), layer=layer))
    ref = paged_attention(
        q.astype(jnp.float32), _gather_kv(kc, bt, layer).astype(jnp.float32),
        _gather_kv(vc, bt, layer).astype(jnp.float32),
        q_start[:, None] + jnp.arange(t)[None, :], kv_lens)
    np.testing.assert_allclose(whole, np.asarray(ref), atol=2e-2, rtol=2e-2)


# -- Ahead-of-time compile for the v5e (no chip: the installed libtpu) ---------

@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


@pytest.mark.parametrize("b,t,nblk,nb,kv,ns,compiles", [
    pytest.param(32, 1, 512, 18000, "bfloat16", 1, True, id="bf16-decode"),
    # The mixed step's shape: T = prefill_chunk, decode rows one token of it.
    pytest.param(8, 512, 512, 18000, "bfloat16", 1, True, id="bf16-chunk512"),
    pytest.param(1, 1, 512, 18000, "bfloat16", 8, True, id="bf16-split-k"),
    pytest.param(32, 1, 16, 449, "int8", 1, True, id="int8-small-pool"),
    # The scale sidecars ride scalar prefetch into SMEM (1 MiB), 512 B a
    # block for K and for V: the compiler refuses the pool, and so must
    # the engine's own arithmetic, at construction.
    pytest.param(32, 1, 16, 36000, "int8", 1, False, id="int8-pool-refused"),
])
def test_kernel_compiles_for_v5e(v5e_device, b, t, nblk, nb, kv, ns, compiles):
    """Mosaic itself, at the llama-3-8b geometry (32 Q / 8 KV heads x 128,
    block 16), judges the kernel's block shapes and memory — and the
    engine's SMEM arithmetic (ModelRunner._check_kernel_fits) has to agree
    with it on which pools fit."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops.paged_attention import (
        SMEM_USABLE_BYTES,
        scalar_prefetch_bytes,
    )

    kh, h, d, bs = 8, 32, 128, 16
    sh = SingleDeviceSharding(v5e_device)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # The whole cache and a traced layer index, as the model's layer loop
    # hands them over. What SMEM holds must not grow with the layers: the
    # quantized pool's scales go in one layer at a time.
    nl = 4
    cache = abstract((nl, nb, bs, kh, d), jnp.bfloat16)
    if kv == "int8":
        cache = {"q": abstract((nl, nb, bs, kh, d), jnp.int8),
                 "s": abstract((nl, nb, kh), jnp.float32)}
    lowered = jax.jit(
        lambda q, k, v, bt, qs, kl, layer: paged_attention_kernel(
            q, k, v, bt, qs, kl, layer=layer, num_splits=ns)
    ).lower(abstract((b, t, h, d), jnp.bfloat16), cache, cache,
            abstract((b, nblk), jnp.int32), abstract((b,), jnp.int32),
            abstract((b,), jnp.int32), abstract((), jnp.int32))
    fits = scalar_prefetch_bytes(
        batch=b, nblk=nblk, num_blocks=nb if kv == "int8" else 0,
        kv_heads=kh) <= SMEM_USABLE_BYTES
    assert fits == compiles
    if compiles:
        lowered.compile()
    else:
        with pytest.raises(Exception, match="smem"):
            lowered.compile()


def test_step_holds_no_copy_of_the_pool_on_v5e(v5e_device):
    """The benchmark's 16-layer Mistral-7B cut, a decode step compiled for
    the described v5e at two pool sizes (what ModelRunner._fit_pool does on
    the chip): the bytes beyond the arguments do not grow with the bf16
    pool. Memory only: no time is read here."""
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "chipbench"))
    import aot_check

    config_dir = root / "chipbench" / "configs" / "mistral-7b-v0.3-l16"
    parts = aot_check.build_abstract_runner(config_dir, {})
    n0, n1 = 1024, 2048
    r0, r1 = (aot_check.compile_bucket(*parts, 8, 1, 64, True, n)
              for n in (n0, n1))
    assert r0["kernel"] and r1["kernel"]
    block = 2 * 16 * 16 * 8 * 128 * 2               # K and V, 16 layers, bf16
    copies = (r1["beyond_arguments_bytes"] - r0["beyond_arguments_bytes"]) \
        / (n1 - n0)
    assert copies < 0.05 * block, (r0, r1)


def test_paged_attention_kernel_parity_at_bench_shapes():
    """Interpret-mode parity at the llama-3-8b-lite geometry the bench
    actually dispatches (kh=8, d=128, bs=16) — the configuration whose
    lowering regressed in round 1. bf16 q/cache like the real run."""
    rng = np.random.default_rng(7)
    case = _make_case(rng, b=2, t=1, h=8, kh=8, d=128, nb=24, bs=16, nblk=4,
                      dtype=jnp.bfloat16)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    out = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_paged_attention_kernel_parity_bench_shapes_int8_cache():
    """Same bench geometry with the int8 quantized cache (in-kernel
    dequant): kernel vs dense on identical quantized content."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    rng = np.random.default_rng(8)
    nb, bs, kh, d, b, h = 24, 16, 8, 128, 2, 8
    kc = {"q": jnp.zeros((nb, bs, kh, d), jnp.int8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    vc = {"q": jnp.zeros((nb, bs, kh, d), jnp.int8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    ctx = 2 * bs
    slots = jnp.stack([jnp.arange(ctx), 2 * bs + jnp.arange(ctx)]).astype(jnp.int32)
    kc = _scatter_kv(kc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    vc = _scatter_kv(vc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    q_start = jnp.full((b,), ctx - 1, jnp.int32)
    kv_lens = jnp.full((b,), ctx, jnp.int32)

    out_kernel = paged_attention_kernel(q, kc, vc, bt, q_start, kv_lens,
                                        interpret=True)
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


# -- Split-K flash decode -----------------------------------------------------

@pytest.mark.parametrize("ns", [2, 4])
def test_split_k_bitwise_equal_sequential_bf16(ns):
    """The split-K combine must not perturb bf16 decode output at all:
    partial flash state is f32 and the logsumexp-weighted merge reproduces
    the sequential accumulator bit-for-bit after the bf16 round."""
    rng = np.random.default_rng(11)
    case = _make_case(rng, b=2, t=1, h=8, kh=8, d=128, nb=24, bs=16, nblk=4,
                      dtype=jnp.bfloat16)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    seq = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=1, interpret=True)
    split = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=ns, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(split, np.float32), np.asarray(seq, np.float32))


def test_split_k_matches_sequential_f32_tight():
    """f32 split-K differs from sequential only by combine-order float
    association — tight allclose, not bitwise."""
    rng = np.random.default_rng(12)
    case = _make_case(rng, b=3, t=1, h=4, kh=2, d=64, nb=32, bs=16, nblk=8)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    seq = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=1, interpret=True)
    split = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=4, interpret=True)
    np.testing.assert_allclose(np.asarray(split), np.asarray(seq),
                               atol=2e-6, rtol=2e-6)


def test_split_k_wildly_ragged_batch_matches_dense():
    """Ragged rows spanning [1 block, max blocks] under forced split-K:
    rows whose context ends before a split's range contribute empty
    partials (m=-inf, l=0) that the combine must ignore."""
    rng = np.random.default_rng(13)
    b, t, h, kh, d, nb, bs, nblk = 4, 1, 4, 2, 64, 48, 16, 8
    q, k_cache, v_cache, block_tables, _, _ = _make_case(
        rng, b, t, h, kh, d, nb, bs, nblk)
    # kv_lens 1 (one block, one token) .. 128 (all 8 blocks full)
    kv_lens = jnp.asarray([1, 16, 63, nblk * bs], jnp.int32)
    q_start = kv_lens - 1
    q_len = jnp.ones((b,), jnp.int32)
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    for ns in (2, 4, 8):
        out = paged_attention_kernel(
            q, k_cache, v_cache, block_tables, q_start, kv_lens,
            num_splits=ns, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_split_k_forced_beyond_nblk_clamps():
    """An absurd forced num_splits clamps to nblk and still matches."""
    from dynamo_tpu.ops.paged_attention import resolve_num_splits

    assert resolve_num_splits(999, nblk=4, batch=1, q_chunks=1, q_tokens=1) == 4
    assert resolve_num_splits(0, nblk=512, batch=1, q_chunks=1, q_tokens=8) == 1
    rng = np.random.default_rng(14)
    case = _make_case(rng, b=2, t=1, h=4, kh=2, d=64, nb=16, bs=16, nblk=2)
    q, k_cache, v_cache, block_tables, q_start, q_len = case
    seq = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=1, interpret=True)
    out = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=999, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                               atol=2e-6, rtol=2e-6)


# -- q-chunked split-K prefill ------------------------------------------------

def test_split_k_prefill_chunk_parity_bench_geometry():
    """Forced split-K with T>1 query rows (chunked prefill at the bench
    attention geometry kh=8, d=128) matches the sequential block walk —
    the satellite that lets long chunked prefills fill idle TensorCores."""
    rng = np.random.default_rng(21)
    b, t, h, kh, d, nb, bs, nblk = 1, 8, 8, 8, 128, 20, 16, 16
    q, k_cache, v_cache, block_tables, _, _ = _make_case(
        rng, b, t, h, kh, d, nb, bs, nblk)
    q_start = jnp.asarray([nblk * bs - t], jnp.int32)  # full-context chunk
    q_len = jnp.full((b,), t, jnp.int32)
    seq = paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        num_splits=1, interpret=True)
    for ns in (2, 4):
        out = paged_attention_kernel(
            q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
            num_splits=ns, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                                   atol=2e-5, rtol=2e-5)
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_resolve_num_splits_prefill_cost_model():
    """The auto gate prices splits with the cost model: q-chunked prefill
    engages split-K exactly when batch × q-chunks underfills the cores,
    stays sequential for callers without state geometry (legacy decode
    call sites), and is clamped by the f32 partial-state VMEM budget."""
    from dynamo_tpu.obs.costmodel import auto_num_splits
    from dynamo_tpu.ops.paged_attention import (
        _SPLIT_STATE_CAP_BYTES,
        resolve_num_splits,
    )

    # Decode (t=1) auto behavior is unchanged by the prefill gate.
    assert resolve_num_splits(
        0, nblk=32, batch=1, q_chunks=1, q_tokens=1
    ) == auto_num_splits(32, batch=1)
    # One row-program on an 8-core chip underfills → the cost model's
    # split count engages for the prefill chunk.
    want = auto_num_splits(32, batch=1, q_chunks=1)
    assert want > 1
    assert resolve_num_splits(
        0, nblk=32, batch=1, q_chunks=1, q_tokens=8,
        state_rows=8, kv_heads=8, head_dim=128) == want
    # batch × q-chunks already fills the cores → sequential.
    assert resolve_num_splits(
        0, nblk=32, batch=8, q_chunks=4, q_tokens=8,
        state_rows=8, kv_heads=8, head_dim=128) == 1
    # The f32 partial-state budget caps huge chunks back to sequential.
    rows = 4096
    assert rows * 8 * (128 + 256) * 4 > _SPLIT_STATE_CAP_BYTES
    assert resolve_num_splits(
        0, nblk=64, batch=1, q_chunks=1, q_tokens=rows,
        state_rows=rows, kv_heads=8, head_dim=128) == 1
    # Callers that pass no state geometry (pre-existing call sites) keep
    # the sequential walk for t>1.
    assert resolve_num_splits(0, nblk=512, batch=1, q_chunks=1,
                              q_tokens=8) == 1


# -- Packed int4 KV -----------------------------------------------------------

def test_pack_unpack_int4_roundtrip_and_odd_dim():
    from dynamo_tpu.ops.paged_attention import pack_int4, unpack_int4

    rng = np.random.default_rng(15)
    vals = jnp.asarray(rng.integers(-8, 8, size=(5, 3, 16)), jnp.int32)
    packed = pack_int4(vals)
    assert packed.dtype == jnp.uint8 and packed.shape == (5, 3, 8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(vals))
    with pytest.raises(ValueError, match="even trailing dim"):
        pack_int4(jnp.zeros((2, 7), jnp.int32))


def test_paged_attention_kernel_parity_bench_shapes_int4_cache():
    """Bench geometry with the packed-int4 cache (uint8 nibbles, in-kernel
    unpack + dequant): kernel vs dense gather on identical quantized
    content, so the only divergence is float association."""
    from dynamo_tpu.models.llama import _gather_kv, _scatter_kv

    rng = np.random.default_rng(16)
    nb, bs, kh, d, b, h = 24, 16, 8, 128, 2, 8
    kc = {"q": jnp.zeros((nb, bs, kh, d // 2), jnp.uint8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    vc = {"q": jnp.zeros((nb, bs, kh, d // 2), jnp.uint8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    ctx = 2 * bs
    slots = jnp.stack([jnp.arange(ctx), 2 * bs + jnp.arange(ctx)]).astype(jnp.int32)
    kc = _scatter_kv(kc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    vc = _scatter_kv(vc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    assert kc["q"].dtype == jnp.uint8 and kc["q"].shape[-1] == d // 2
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    q_start = jnp.full((b,), ctx - 1, jnp.int32)
    kv_lens = jnp.full((b,), ctx, jnp.int32)

    out_kernel = paged_attention_kernel(q, kc, vc, bt, q_start, kv_lens,
                                        interpret=True)
    kg, vg = _gather_kv(kc, bt), _gather_kv(vc, bt)
    rep = h // kh
    qr = (q * (d ** -0.5)).reshape(b, 1, kh, rep, d).astype(jnp.float32)
    scores = jnp.einsum("btkrd,bskd->btkrs", qr, kg.astype(jnp.float32))
    mask = jnp.arange(ctx)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    ref = jnp.einsum("btkrs,bskd->btkrd",
                     jax.nn.softmax(scores, axis=-1), vg.astype(jnp.float32))
    err = np.abs(np.asarray(out_kernel) - np.asarray(ref.reshape(b, 1, h, d))).max()
    assert err < 5e-4, err


def test_int4_cache_split_k_matches_sequential():
    """Split-K over a packed-int4 cache matches the sequential kernel on
    the same quantized content (float-association tolerance)."""
    from dynamo_tpu.models.llama import _scatter_kv

    rng = np.random.default_rng(17)
    nb, bs, kh, d, b, h, nblk = 16, 16, 2, 64, 2, 4, 4
    kc = {"q": jnp.zeros((nb, bs, kh, d // 2), jnp.uint8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    vc = {"q": jnp.zeros((nb, bs, kh, d // 2), jnp.uint8),
          "s": jnp.zeros((nb, kh), jnp.float32)}
    ctx = nblk * bs
    slots = jnp.stack([jnp.arange(ctx), ctx + jnp.arange(ctx)]).astype(jnp.int32)
    kc = _scatter_kv(kc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    vc = _scatter_kv(vc, jnp.asarray(rng.normal(size=(b, ctx, kh, d)), jnp.float32), slots)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    q_start = jnp.full((b,), ctx - 1, jnp.int32)
    kv_lens = jnp.full((b,), ctx, jnp.int32)
    seq = paged_attention_kernel(q, kc, vc, bt, q_start, kv_lens,
                                 num_splits=1, interpret=True)
    split = paged_attention_kernel(q, kc, vc, bt, q_start, kv_lens,
                                   num_splits=2, interpret=True)
    np.testing.assert_allclose(np.asarray(split), np.asarray(seq),
                               atol=2e-6, rtol=2e-6)
