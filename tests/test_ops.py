"""Numerical-equivalence tests for the Pallas hot-op kernels (interpret mode).

Mirrors the reference's kernel-adjacent unit testing (its CUDA block-copy is
tested via block_manager tests); here the kernels are compared bit-for-tol
against the portable XLA paths they replace.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import paged_attention
from dynamo_tpu.ops.paged_attention import paged_attention_kernel


@pytest.fixture
def small_groups(monkeypatch):
    """Groups of 2 blocks of 16, so that the small tables here hold several
    groups and odd block counts end in a partial one. The kernel picks
    16-32 blocks for itself."""
    import dynamo_tpu.ops.paged_attention as pa

    monkeypatch.setattr(pa, "_GROUP_KEYS", 32)
    monkeypatch.setattr(pa, "_GROUP_KEYS_WIDE", 32)


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


def _assert_live_rows_match(out, ref, q_len, tol):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    for row, n in enumerate(np.asarray(q_len)):
        np.testing.assert_allclose(out[row, :n], ref[row, :n], atol=tol, rtol=tol)
    assert np.isfinite(out).all()


# The walk at the geometry the cells run (block 16, head 128, the groups of
# 16 and 32 blocks the kernel picks for itself): rows are (q_start, q_len).
_WALK_CASES = {
    # Decode: ragged rows, a one-token context, a padding row; 9 and 38
    # used blocks are no multiple of the group's 32.
    "t1-ragged-zero-row": (1, 40, [(0, 1), (129, 1), (0, 0), (599, 1)]),
    # A mixed step's rectangle: the chunk at its depth, a decode row deep
    # in its context, a short chunk (its later query chunks are padding),
    # a padding row. 8 / 40 / 104 blocks under the deepest query chunk.
    "t512-depth0": (512, 40, [(0, 512), (599, 1), (37, 100), (0, 0)]),
    "t512-depth512": (512, 64, [(512, 512), (599, 1), (37, 100), (0, 0)]),
    "t512-depth1536": (512, 128, [(1536, 512), (599, 1), (37, 100), (0, 0)]),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kh", [8, 2], ids=["kh8", "kh2"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_grouped_walk_matches_dense(case, kh, dtype, tol):
    """One grid step a (row, query chunk), a loop over the groups of blocks
    the chunk can see: every live position equals the dense path, float32
    tight and in the cells' bf16 (the float32 cache takes the plain strided
    load, bf16 the two-heads-a-word one)."""
    t, nblk, rows = _WALK_CASES[case]
    rng = np.random.default_rng(31)
    b, rep, d, bs = len(rows), 4, 128, 16
    q, k_cache, v_cache, block_tables, _, _ = _make_case(
        rng, b, t, kh * rep, kh, d, nb=b * nblk + 1, bs=bs, nblk=nblk, dtype=dtype)
    q_start = jnp.asarray([r[0] for r in rows], jnp.int32)
    q_len = jnp.asarray([r[1] for r in rows], jnp.int32)
    ref = _dense_ref(q, k_cache, v_cache, block_tables, q_start, q_len)
    out = paged_attention_kernel(q, k_cache, v_cache, block_tables, q_start,
                                 q_start + q_len, interpret=True)
    _assert_live_rows_match(out, ref, q_len, tol)


@pytest.mark.parametrize("kv", ["int8", "int4"])
@pytest.mark.parametrize("case,kh", [
    *((case, 2) for case in sorted(_WALK_CASES)),
    ("t1-ragged-zero-row", 8), ("t512-depth1536", 8),
], ids=lambda v: f"kh{v}" if isinstance(v, int) else v)
def test_grouped_walk_quantized_pool_matches_dense(case, kh, kv):
    """The same walks over an int8 and a packed-int4 pool (the payload goes
    to the MXU as it is; a block's scale multiplies its 16 columns of the
    group's scores and of its probabilities), against the dense gather of
    the same quantized content."""
    from dynamo_tpu.models.llama import _gather_kv

    t, nblk, rows = _WALK_CASES[case]
    rng = np.random.default_rng(37)
    b, rep, d, bs = len(rows), 4, 128, 16
    nb = b * nblk + 1
    kc, vc = (_whole_cache(rng, kv, 1, nb, bs, kh, d) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, t, kh * rep, d)), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(nb - 1)[: b * nblk].reshape(b, nblk) + 1,
                     jnp.int32)
    q_start = jnp.asarray([r[0] for r in rows], jnp.int32)
    q_len = jnp.asarray([r[1] for r in rows], jnp.int32)
    out = paged_attention_kernel(q, kc, vc, bt, q_start, q_start + q_len,
                                 layer=0, interpret=True)
    ref = paged_attention(
        q.astype(jnp.float32), _gather_kv(kc, bt, 0).astype(jnp.float32),
        _gather_kv(vc, bt, 0).astype(jnp.float32),
        q_start[:, None] + jnp.arange(t)[None, :], q_start + q_len)
    _assert_live_rows_match(out, ref, q_len, 2e-2)


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


@pytest.mark.parametrize("group_keys", [32, None],
                         ids=["groups-of-2", "one-group"])
def test_paged_attention_kernel_leaves_padded_query_chunks(
        monkeypatch, group_keys):
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
    if group_keys:      # two groups under the deepest chunk, not one
        monkeypatch.setattr(pa, "_GROUP_KEYS", group_keys)
        monkeypatch.setattr(pa, "_GROUP_KEYS_WIDE", group_keys)
    grids = []
    real_call = pa.pl.pallas_call
    monkeypatch.setattr(
        pa.pl, "pallas_call",
        lambda kernel, *a, grid_spec=None, **kw: (
            grids.append(grid_spec.grid),
            real_call(kernel, *a, grid_spec=grid_spec, **kw))[1])
    out = np.asarray(pa.paged_attention_kernel(
        q, k_cache, v_cache, block_tables, q_start, q_start + q_len,
        interpret=True))
    (_, nq), = grids                      # (row, query chunk): no third axis
    assert nq == 4, grids
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


# (layer, kv, table, t, window): every layer, pool format and table width on
# a full layer; then, at the middle layer, a table whose live entries name a
# block past the pool and one before it, and a sliding layer whose walk
# begins at block 3 of 5 (one group of two, from an odd block on).
_WHOLE_CACHE_CASES = [
    pytest.param(layer, kv, table, t, 0,
                 id=f"{name}-{kv}-{table}-{'decode' if t == 1 else 'chunk'}")
    for layer, name in enumerate(["first", "middle", "last"])
    for kv in ["bfloat16", "int8", "int4"]
    for table in ["tight", "max_nblk"] for t in [1, 8]
] + [
    pytest.param(1, kv, table, t, window,
                 id=f"middle-{kv}-{table}-{'decode' if t == 1 else 'chunk'}")
    for kv in ["bfloat16", "int8", "int4"]
    for table, window in [("ids_out_of_range", 0), ("sliding", 24)]
    for t in [1, 8]
]


@pytest.mark.parametrize("layer,kv,table,t,window", _WHOLE_CACHE_CASES)
def test_kernel_addresses_one_layer_of_the_whole_cache(small_groups, layer,
                                                       kv, table, t, window):
    """The kernel on the whole [L, NB, ...] cache with layer index ``l`` is
    the kernel on that layer's slice, bit for bit, and the dense reference
    within tolerance; what the other layers hold does not matter, and
    neither does the table's width: handed ``max_nblk`` entries a row (what
    every step program is compiled for) of which 1, 2 and 5 are live, it
    gives what the tight table gives. An id that names no block of the pool
    is read as the nearest one that does (the copies' addresses are not
    checked by the hardware: ``disable_bounds_checks``), so such a table
    gives what the clamped table gives, scales of a quantized pool
    included. Every walk here lands its groups of two blocks by one wait a
    buffer, the half group at a row's end too."""
    from dynamo_tpu.models.llama import _gather_kv

    # Five blocks a row in groups of two: 1, 2 and 5 used blocks (a group
    # and a half group, one group, two and a half).
    nl, b, h, kh, d, nb, bs, nblk = 3, 3, 4, 2, 64, 16, 16, 5
    rng = np.random.default_rng(100 * layer + 10 + t)
    kc = _whole_cache(rng, kv, nl, nb, bs, kh, d)
    vc = _whole_cache(rng, kv, nl, nb, bs, kh, d)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    ids = rng.permutation(nb - 1)[: b * nblk].reshape(b, nblk) + 1
    bt = jnp.asarray(ids, jnp.int32)
    q_start = jnp.asarray([0, 21, nblk * bs - t], jnp.int32)    # ragged
    kv_lens = q_start + t
    # As dispatch() fills a wide table: the row's blocks, then zeros.
    width = nblk if table == "tight" else 512
    wide = jnp.zeros((b, width), jnp.int32).at[:, :nblk].set(bt)
    if table == "ids_out_of_range":
        wide = wide.at[0, 0].set(nb + 7).at[1, 1].set(-3).at[2, 4].set(nb)
        bt = jnp.clip(wide[:, :nblk], 0, nb - 1)

    def kernel(k, v, **kw):
        return np.asarray(paged_attention_kernel(
            q, k, v, bt, q_start, kv_lens, interpret=True, window=window,
            **kw).astype(jnp.float32))

    # The layer index traced, as inside the model's scan.
    whole = np.asarray(jax.jit(
        lambda k, v, l: paged_attention_kernel(
            q, k, v, wide, q_start, kv_lens, layer=l, window=window,
            interpret=True))(kc, vc, jnp.int32(layer)).astype(jnp.float32))
    one = jax.tree.map(lambda a: a[layer], (kc, vc))
    np.testing.assert_array_equal(whole, kernel(*one))
    np.testing.assert_array_equal(
        whole, kernel(_spoil_other_layers(kc, layer),
                      _spoil_other_layers(vc, layer), layer=layer))
    ref = paged_attention(
        q.astype(jnp.float32), _gather_kv(kc, bt, layer).astype(jnp.float32),
        _gather_kv(vc, bt, layer).astype(jnp.float32),
        q_start[:, None] + jnp.arange(t)[None, :], kv_lens, window=window)
    np.testing.assert_allclose(whole, np.asarray(ref), atol=2e-2, rtol=2e-2)


# -- Ahead-of-time compile for the v5e (no chip: the installed libtpu) ---------

@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


#: What a Pallas kernel may hold in VMEM on a v5e unless it asks for more
#: (``vmem_limit_bytes``, which this kernel does not set): 16 MiB of 128.
V5E_SCOPED_VMEM_BYTES = 16 << 20


def _vmem_bytes(grid_spec, blocks):
    """VMEM the kernel holds: its scratch buffers, and two of each block
    the pipeline moves for it (``blocks``: (shape, dtype)). The last two
    dims pad to the dtype's (sublane, 128) tile."""
    def padded(shape, dtype):
        item = jnp.dtype(dtype).itemsize
        sub = {4: 8, 2: 16, 1: 32}[item]
        *lead, s2, s1 = shape
        return (int(np.prod(lead)) * -(-s2 // sub) * sub
                * -(-s1 // 128) * 128 * item)

    scratch = sum(padded(m.shape, m.dtype) for m in grid_spec.scratch_shapes
                  if "vmem" in str(m.memory_space).lower())
    return scratch + 2 * sum(padded(shape, dtype) for shape, dtype in blocks)


@pytest.mark.parametrize("b,t,nblk,nb,kv,h,kh,window,compiles", [
    pytest.param(32, 1, 512, 18000, "bfloat16", 32, 8, 0, True, id="bf16-decode"),
    # The mixed step's shape: T = prefill_chunk, decode rows one token of it.
    pytest.param(8, 512, 512, 18000, "bfloat16", 32, 8, 0, True, id="bf16-chunk512"),
    # The cells' own step programs, every one at the table's one width
    # (max_model_len 8192 / 16): the first and the widest of the decode
    # ladder and the full chunk, over the 7B cut's pool and the Nemo cut's
    # (both 32 Q / 8 KV x 128), and at the two KV heads a chip holds under
    # tp=4.
    pytest.param(8, 1, 512, 6817, "bfloat16", 32, 8, 0, True, id="7b-b8-decode"),
    pytest.param(8, 512, 512, 6817, "bfloat16", 32, 8, 0, True, id="7b-b8-t512"),
    pytest.param(64, 1, 512, 6817, "bfloat16", 32, 8, 0, True, id="7b-b64-decode"),
    pytest.param(8, 1, 512, 9915, "bfloat16", 32, 8, 0, True, id="nemo-b8-decode"),
    pytest.param(8, 512, 512, 9915, "bfloat16", 32, 8, 0, True, id="nemo-b8-t512"),
    pytest.param(64, 1, 512, 9915, "bfloat16", 32, 8, 0, True, id="nemo-b64-decode"),
    pytest.param(8, 1, 512, 12279, "bfloat16", 8, 2, 0, True, id="tp4-b8-decode"),
    pytest.param(8, 512, 512, 12279, "bfloat16", 8, 2, 0, True, id="tp4-b8-t512"),
    # The other cells' decode programs at their own heads and windows:
    # K-EXAONE's full and sliding layers, SmallThinker's, Nemotron's two KV
    # heads, Falcon-H1's five query heads a KV head.
    pytest.param(16, 1, 512, 20434, "bfloat16", 64, 8, 0, True,
                 id="kexaone-b16-decode"),
    pytest.param(16, 1, 512, 20434, "bfloat16", 64, 8, 128, True,
                 id="kexaone-b16-decode-sliding"),
    pytest.param(8, 1, 512, 7804, "bfloat16", 28, 4, 0, True,
                 id="smallthinker-b8-decode"),
    pytest.param(8, 1, 512, 7804, "bfloat16", 28, 4, 4096, True,
                 id="smallthinker-b8-decode-sliding"),
    pytest.param(32, 1, 512, 32769, "bfloat16", 32, 2, 0, True,
                 id="nemotron-b32-decode"),
    pytest.param(16, 1, 512, 14820, "bfloat16", 20, 4, 0, True,
                 id="falcon-h1-b16-decode"),
    pytest.param(32, 1, 16, 449, "int8", 32, 8, 0, True, id="int8-small-pool"),
    # The scale sidecars ride scalar prefetch into SMEM (1 MiB), 512 B a
    # block for K and for V: the compiler refuses the pool, and so must
    # the engine's own arithmetic, at construction.
    pytest.param(32, 1, 16, 36000, "int8", 32, 8, 0, False, id="int8-pool-refused"),
])
def test_kernel_compiles_for_v5e(v5e_device, monkeypatch, b, t, nblk, nb, kv,
                                 h, kh, window, compiles):
    """Mosaic itself, at the llama-3-8b geometry (32 Q / 8 KV heads x 128,
    block 16: the benchmark's cuts have it too) and at the other cells'
    heads and windows, judges the kernel's copies, loads and memory — and the
    engine's SMEM arithmetic (ModelRunner._check_kernel_fits) has to agree
    with it on which pools fit. The kernel's VMEM (two groups of K and of
    V, the softmax state, the query and output blocks) stays under the
    scoped limit it runs with, and it is compiled with no hardware check
    behind a copy's addresses: the kernel holds them in range itself."""
    from jax.sharding import SingleDeviceSharding

    import dynamo_tpu.ops.paged_attention as pa
    from dynamo_tpu.ops.paged_attention import (
        SMEM_USABLE_BYTES,
        scalar_prefetch_bytes,
    )

    d, bs = 128, 16
    sh = SingleDeviceSharding(v5e_device)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    calls = []
    real_call = pa.pl.pallas_call
    monkeypatch.setattr(
        pa.pl, "pallas_call",
        lambda kernel, *a, grid_spec=None, compiler_params=None, **kw: (
            calls.append((grid_spec, compiler_params)),
            real_call(kernel, *a, grid_spec=grid_spec,
                      compiler_params=compiler_params, **kw))[1])

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
            q, k, v, bt, qs, kl, layer=layer, window=window)
    ).lower(abstract((b, t, h, d), jnp.bfloat16), cache, cache,
            abstract((b, nblk), jnp.int32), abstract((b,), jnp.int32),
            abstract((b,), jnp.int32), abstract((), jnp.int32))
    fits = scalar_prefetch_bytes(
        batch=b, nblk=nblk, num_blocks=nb if kv == "int8" else 0,
        kv_heads=kh) <= SMEM_USABLE_BYTES
    assert fits == compiles
    (grid_spec, params), = calls
    assert len(grid_spec.grid) == 2          # (row, query chunk): no walk axis
    assert params.vmem_limit_bytes is None
    assert params.disable_bounds_checks
    rchunk = grid_spec.in_specs[0].block_shape[2]
    blocks = 2 * [((kh, rchunk, d), jnp.bfloat16)]   # the query slab, the output
    assert _vmem_bytes(grid_spec, blocks) < V5E_SCOPED_VMEM_BYTES
    if compiles:
        lowered.compile()
    else:
        with pytest.raises(Exception, match="smem"):
            lowered.compile()


def _aot_parts(config: str):
    """chipbench/aot_check.py and its shell of a runner over one of the
    benchmark's configurations, shapes on the described v5e."""
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "chipbench"))
    import aot_check

    config_dir = root / "chipbench" / "configs" / config
    return aot_check, aot_check.build_abstract_runner(config_dir, {})


def _compiled_texts(monkeypatch) -> list[str]:
    """The text of every program compiled from here on, as
    ``aot_check.compile_bucket`` asks for it (``Compiled.as_text``): the
    list fills as they compile."""
    texts = []
    as_text = jax.stages.Compiled.as_text
    monkeypatch.setattr(
        jax.stages.Compiled, "as_text",
        lambda self, *a, **kw: (texts.append(as_text(self, *a, **kw)),
                                texts[-1])[1])
    return texts


def test_step_holds_no_copy_of_the_pool_on_v5e(v5e_device):
    """The benchmark's 16-layer Mistral-7B cut, a decode step compiled for
    the described v5e at two pool sizes (what ModelRunner._fit_pool does on
    the chip): the bytes beyond the arguments do not grow with the bf16
    pool. Memory only: no time is read here."""
    aot_check, parts = _aot_parts("mistral-7b-v0.3-l16")
    n0, n1 = 1024, 2048
    r0, r1 = (aot_check.compile_bucket(*parts, 8, 1, 64, True, n)
              for n in (n0, n1))
    assert r0["kernel"] and r1["kernel"]
    block = 2 * 16 * 16 * 8 * 128 * 2               # K and V, 16 layers, bf16
    copies = (r1["beyond_arguments_bytes"] - r0["beyond_arguments_bytes"]) \
        / (n1 - n0)
    assert copies < 0.05 * block, (r0, r1)


@pytest.mark.parametrize("config,b,t", [
    ("mistral-7b-v0.3-l16", 8, 1),
    ("mistral-7b-v0.3-l16", 8, 512),
    ("mistral-nemo-12b-l10", 8, 1),
    ("k-exaone-236b-a23b-ep8-l5", 32, 1),
    ("smallthinker-21b-a3b-l12", 16, 1),
    ("smallthinker-21b-a3b-l12", 16, 512),
])
def test_step_reads_the_qkv_matrices_in_place_on_v5e(v5e_device, monkeypatch,
                                                     config, b, t):
    """A step of each of the benchmark's configurations, compiled for the
    described v5e: nothing in it cuts a layer's ``wq``, ``wk``, ``wv`` or
    ``wo`` out of its stack, alone or a period of them at a time, or copies
    one into another layout. The three products reach the compiler as
    ``[N, H] x [H, out]`` and read the stack where it lies, as ``wo`` and
    the MLP's three do (models/llama.py ``_attention``); with the head split
    folded into the dot the weight operand was the matrix transposed, and a
    slice and a copy of it, 15 % of a decode step's device time, ran in
    every layer (PERF.md section 6, PR 40). A scan over periods of several
    layers takes each layer's matrices from the stack by the layer's own
    index (``_run_layers``); with a period's slice on the scan's xs a
    ``[4, 3584, 2560]``, a ``[4, 2560, 3584]`` and two ``[4, 2560, 512]``
    were copied out in every trip, 8 % (PR 42).
    The program's text only: no time is read here."""
    import re

    aot_check, parts = _aot_parts(config)
    runner, cfg = parts[:2]
    texts = _compiled_texts(monkeypatch)
    assert aot_check.compile_bucket(
        *parts, b, t, runner.max_nblk, True, 2048)["kernel"]
    (text,) = texts
    h, q, kv = cfg.hidden_size, cfg.q_size, cfg.kv_size
    mats = {(h, q), (h, kv), (q, h)}              # wq; wk and wv; wo
    mats |= {shape[::-1] for shape in mats}
    moved = []
    for name, dims in re.findall(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]",
                                 text, re.M):
        shape = tuple(int(d) for d in dims.split(","))
        # A matrix behind any leading dimensions: one layer's ``1``, a
        # period's ``p``, a stack's ``L``. ``copy-start`` / ``copy-done``
        # are a prefetch of a whole argument, beside the work, and not these.
        if shape[-2:] in mats and (name.split(".")[0] == "copy"
                                   or "slice" in name and "fusion" in name):
            moved.append(f"{name} [{dims}]")
    assert not moved, moved


def _rehearsal_step_text(tmp_path, monkeypatch, model: str, b: int, t: int) -> str:
    """A step program of the rehearsal's ``tiny`` or ``tiny-moe`` at the
    kernel's head size (128: the v5e's kernel takes no other), compiled for
    the described v5e; its text."""
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "chipbench"))
    import aot_check

    hf = json.loads(
        (root / "chipbench/rehearsal" / model / "config.json").read_text())
    hf.update(head_dim=128, hidden_size=256)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    parts = aot_check.build_abstract_runner(tmp_path, {"max_model_len": 1024})
    texts = _compiled_texts(monkeypatch)
    assert aot_check.compile_bucket(
        *parts, b, t, parts[0].max_nblk, True, 256)["kernel"]
    (text,) = texts
    return text


def _scan_body_instructions(text: str) -> list[tuple[str, str]]:
    """(name, opcode) of the instructions of the scan's body: the ``while``
    body and what it calls, fused computations and reducers apart."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w\-.]+) \(.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and " = " in line:
            m = re.match(r"^\s*(?:ROOT )?%?([\w\-.]+) = .*?\s([\w\-]+)\(", line)
            if m:
                cur.append((m.group(1), m.group(2), line))
    body = re.search(r"while\(.*body=%?([\w\-.]+)", text).group(1)
    todo, seen = [body], []
    while todo:
        c = todo.pop()
        for name, opcode, line in comps[c]:
            seen.append((name, opcode))
            if opcode == "call":
                todo.append(re.search(r"to_apply=%?([\w\-.]+)", line).group(1))
    return seen


@pytest.mark.parametrize("model, t", [("tiny", 1), ("tiny", 16),
                                      ("tiny-moe", 1), ("tiny-moe", 16)])
def test_phase_table_of_a_step_compiled_for_a_v5e(v5e_device, tmp_path,
                                                  monkeypatch, model, t):
    """The phase table (obs/profiler.py ``phase_table``) of a decode and a
    mixed step of a dense and a routed model, read off the text compiled for
    the described v5e: the kernel's calls are ``attention``, the grouped
    matmuls the compiler made of ``lax.ragged_dot`` and gave no scope are
    ``moe_experts`` through what reads them, and every instruction of the
    scan's body that does work has a phase. The program's text only: no
    time is read here."""
    from dynamo_tpu.obs.profiler import DEVICE_PHASES, phase_table

    text = _rehearsal_step_text(tmp_path, monkeypatch, model, 8, t)
    table = phase_table(text)
    assert set(table.values()) <= set(DEVICE_PHASES)
    kernels = [n for n in table if n.startswith("paged_attention")]
    assert kernels and all(table[n] == "attention" for n in kernels)
    idle = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "call", "while", "copy-start", "copy-done")
    unnamed = [(n, o) for n, o in _scan_body_instructions(text)
               if o not in idle and n not in table]
    assert not unnamed, unnamed
    phases = set(table.values())
    assert {"layer", "proj", "scatter", "attention", "logits"} <= phases
    if model == "tiny-moe":
        grouped = [n for n in table if n.startswith("ragged-dot")]
        assert len(grouped) >= 3
        assert all(table[n] == "moe_experts" for n in grouped)
        assert {"moe_route", "moe_experts", "moe_shared"} <= phases
    else:
        assert "mlp" in phases and "moe_experts" not in phases


def test_step_keeps_no_copy_of_a_period_on_v5e(v5e_device):
    """The benchmark's 12-layer SmallThinker cut (three periods of four
    layers), a 16-row decode step compiled for the described v5e: beyond
    its arguments the program keeps a few megabytes, not a period's
    attention matrices (184 MB with a period's slice on the scan's xs,
    5.5 MB with each layer indexed in the stack: PERF.md section 6, PR 42).
    Memory only: no time is read here."""
    aot_check, parts = _aot_parts("smallthinker-21b-a3b-l12")
    r = aot_check.compile_bucket(*parts, 16, 1, parts[0].max_nblk, True, 2048)
    assert r["kernel"]
    assert r["beyond_arguments_bytes"] < 32 * 2**20, r


def _hybrid_step(config: str, b: int, t: int):
    """A step program of a configuration with recurrent layers, compiled
    for the described v5e: ``aot_check.compile_bucket`` with the state pool
    handed in by its keyword, as ``ModelRunner._run_step`` does. Returns
    (the compiled program, the configuration, the pool's abstract leaves)."""
    from dynamo_tpu.engine.cache import KVCacheSpec, abstract_cache
    from dynamo_tpu.models import mamba

    _aot, (runner, cfg, ec, params, state, on_chip) = _aot_parts(config)
    cache = on_chip(abstract_cache(
        KVCacheSpec.for_model(cfg, 2048, ec.block_size), None))
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    nblk = runner.max_nblk
    inputs = on_chip((
        sds((b, t), i32), sds((b,), i32), sds((b,), i32), sds((b, nblk), i32),
        sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
        sds((b,), f32), sds((b,), f32), sds((b,), f32), sds((b,), bool),
        sds((b,), bool)))
    pool = on_chip(mamba.state_shapes(cfg, ec.max_batch_size))
    fn = runner._build_step_fn(b, t, nblk, fast_greedy=True)
    return fn.lower(params, cache, cache, *state, *inputs,
                    ssm=pool).compile(), cfg, pool


@pytest.mark.parametrize("b, t", [(32, 1), (8, 64)], ids=["decode", "chunk"])
def test_step_updates_the_state_pool_in_place_on_v5e(v5e_device, b, t):
    """The benchmark's 34-layer Nemotron-3-Nano cut, a step compiled for
    the described v5e: the recurrent state's pool (2.08 GB of float32 for
    64 slots and 15 layers, beside a 36 MB pool of convolution tails) is
    donated and aliased to the program's output, and nothing in the program
    has its shape but the in-place updates (a scatter a Mamba layer body)
    and the loops that carry it; the experts' stacks are read where they lie
    (with an expert's 1,856 columns stored unpadded the compiler kept
    ``w_up`` in another order and copied all 2.2 GB of it in every step:
    PERF.md section 6, PR 45). Beyond its arguments the program keeps a few
    rows' states, not a pool. Memory and text only: no time is read here."""
    import re

    compiled, cfg, pool = _hybrid_step("nemotron-3-nano-30b-a3b-ep8-l34", b, t)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in pool.values())
    assert pool_bytes > 2e9
    # donated: both leaves come back in the buffers they came in
    assert mem.alias_size_in_bytes > pool_bytes
    assert mem.temp_size_in_bytes < 0.05 * pool_bytes, mem
    shapes = {",".join(map(str, x.shape)) for x in pool.values()}
    e, h, m = cfg.num_experts, cfg.hidden_size, cfg.expert_store_width
    stacks = {f"{cfg.layers_of('E')},{e},{h},{m}",
              f"{cfg.layers_of('E')},{e},{m},{h}"}
    # (custom-call: the one-token update's kernel, whose output is the pool
    # it was given, ops/ssm_update.py; dynamic-update-slice: a chunk row's
    # state put back; conditional: the loop over rows that skips the others)
    allowed = ("parameter", "get-tuple-element", "tuple", "while", "scatter",
               "fusion", "bitcast", "custom-call", "dynamic-update-slice",
               "conditional")
    odd = []
    for name, dims, op in re.findall(
            r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
            text, re.M):
        if dims in shapes and op not in allowed:
            odd.append(f"{name} [{dims}] {op}")
        if dims in stacks and op not in ("parameter", "get-tuple-element",
                                         "bitcast"):
            odd.append(f"{name} [{dims}] {op}")
    assert not odd, odd
    # a fusion of the pool's shape is an update of it in place, nothing else
    roots = re.findall(
        r"ROOT %(\S+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", text)
    assert all(op in ("scatter", "dynamic-update-slice", "tuple", "bitcast",
                      "get-tuple-element", "custom-call", "conditional",
                      "parameter")
               or dims not in shapes for _n, dims, op in roots), \
        [r for r in roots if r[1] in shapes]
    assert "ssm_update" in text


# -- the one-token update of the state pool (ops/ssm_update.py), interpreted --

def _ssm_update_case(b: int, ones: int, chunks: bool, seed: int):
    """A pool of 2 layers x (b + 3 slots and the trash row) and a step's B
    rows: ``ones`` rows of one token at places spread over the bucket when
    ``chunks`` (rows of several tokens lie between and behind them, each on
    a slot of its own: a prompt's last chunk of one token does not lead),
    leading it otherwise; the rest is padding and names the trash row."""
    rng = np.random.default_rng(seed)
    h, p, n, g = 4, 8, 128, 2
    slots_n = b + 3
    pool = jnp.asarray(rng.standard_normal((2, slots_n + 1, h, p, n)),
                       jnp.float32)
    perm = rng.permutation(slots_n)[:b].astype(np.int32)
    one = np.zeros(b, bool)
    if chunks and 0 < ones < b:
        one[np.sort(rng.choice(np.arange(1, b), ones, replace=False))] = True
    else:
        one[:ones] = True
    if chunks:
        slots = perm                            # every row a sequence
    else:
        slots = np.where(one, perm, slots_n).astype(np.int32)
    a = jnp.asarray(rng.uniform(0.2, 1.0, (b, h)), jnp.float32)
    a = a.at[0].set(0.0)                        # a row that starts from zeros
    dx = jnp.asarray(rng.standard_normal((b, h, p)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, g, n)), jnp.float32)
    return pool, jnp.asarray(slots), jnp.asarray(one), a, dx, bm, cm


@pytest.mark.parametrize("chunks", [False, True], ids=["padding", "chunks"])
@pytest.mark.parametrize("ones", ["0", "1", "b-1", "b"])
@pytest.mark.parametrize("b", [8, 16])
def test_ssm_update_moves_the_one_token_rows_alone(b, ones, chunks):
    """The kernel against ``_scan_one`` on the rows of one token: ``y`` and
    their slots equal to the tolerance of the blocked scan's test; every
    other slot of the pool, the other layer and **the trash row** bit for
    bit what they were (rows of several tokens and padded rows cost the
    kernel nothing and it writes nothing for them); with no row of one
    token the whole pool is bit for bit what it was."""
    from dynamo_tpu.models import mamba
    from dynamo_tpu.ops.ssm_update import update_rows

    k = {"0": 0, "1": 1, "b-1": b - 1, "b": b}[ones]
    pool, slots, one, a, dx, bm, cm = _ssm_update_case(
        b, k, chunks, seed=b * 10 + k + chunks)
    layer = 1
    before = np.asarray(pool)
    got, y = update_rows(pool, layer, slots, one, a, dx, bm, cm,
                         interpret=True)
    got, y = np.asarray(got), np.asarray(y)
    live = np.flatnonzero(np.asarray(one))
    assert len(live) == k
    want_y, want_s = mamba._scan_one(
        jnp.asarray(before[layer, np.asarray(slots)]), a, dx, bm, cm)
    moved = np.asarray(slots)[live]
    np.testing.assert_allclose(got[layer, moved], np.asarray(want_s)[live],
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live], atol=2e-4,
                               rtol=2e-4)
    assert (y[~np.asarray(one)] == 0).all()
    rest = np.ones(before.shape[:2], bool)
    rest[layer, moved] = False
    assert not rest[layer, moved].any() and rest[layer, -1]    # the trash row
    assert np.array_equal(got[rest], before[rest])
    if k:
        assert not np.array_equal(got[layer, moved], before[layer, moved])
    else:
        assert np.array_equal(got, before)


def test_live_steps_name_the_marked_rows_in_order_then_the_last_again():
    from dynamo_tpu.ops.ssm_update import live_steps

    one = jnp.asarray([0, 1, 0, 0, 1, 1, 0, 1], bool)
    slots = jnp.arange(8, dtype=jnp.int32) * 3 + 2
    rows, row_slots, n = live_steps(one, slots)
    assert int(n) == 4
    assert np.asarray(rows).tolist() == [1, 4, 5, 7, 7, 7, 7, 7]
    assert np.asarray(row_slots).tolist() == [5, 14, 17, 23, 23, 23, 23, 23]
    rows, row_slots, n = live_steps(jnp.zeros(8, bool), slots)
    assert int(n) == 0 and np.asarray(rows).tolist() == [0] * 8
    assert np.asarray(row_slots).tolist() == [2] * 8
    rows, _, n = live_steps(jnp.ones(8, bool), slots)
    assert int(n) == 8 and np.asarray(rows).tolist() == list(range(8))


# -- the streaming expert kernel (ops/moe_stream.py) on the described v5e ------

def _on_a_tpu(monkeypatch):
    """The routed layer asks the backend whether its kernel can run
    (models/moe.py ``streams_experts``); a program compiled here for the
    described chip is traced as the chip's engine traces it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _step_text(monkeypatch, config: str, b: int, t: int) -> str:
    """The text of one of the benchmark's configurations' step programs,
    compiled for the described v5e as an engine on the chip builds it."""
    aot_check, parts = _aot_parts(config)
    with monkeypatch.context() as m:
        _on_a_tpu(m)
        texts = _compiled_texts(m)
        assert aot_check.compile_bucket(
            *parts, b, t, parts[0].max_nblk, True, 2048)["kernel"]
    (text,) = texts
    return text


def _without_metadata(text: str) -> str:
    """A program's text less what names its source: the header's tables of
    files, functions, locations and frames, each instruction's ``metadata``,
    and a kernel's payload, whose debug locations hold the call stack up to
    the caller's own line."""
    import re

    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    text = re.sub(r'"body":"[A-Za-z0-9+/=]+"', '"body":"<kernel>"', text)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("n", [8, 16, 64], ids=["b8", "b16", "b64"])
def test_expert_stream_kernel_compiles_for_v5e(v5e_device, monkeypatch, n):
    """Mosaic itself, at SmallThinker's decode shapes (hidden 2560, 64
    experts x 768, 6 a token) over the twelve layers' stack and a traced
    layer index: the kernel lowers, and within the VMEM it asks for. Held
    to its own arithmetic (``vmem_bytes``, what the predicate reads): it
    compiles with no more than that."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops import moe_stream

    nl, e, h, m, k = 12, 64, 2560, 768, 6
    need = moe_stream.vmem_bytes(n, h, m, 2)
    assert 2 * 3 * h * m * 2 < need <= moe_stream.VMEM_LIMIT_BYTES
    monkeypatch.setattr(moe_stream, "VMEM_LIMIT_BYTES", need)
    sh = SingleDeviceSharding(v5e_device)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    compiled = jax.jit(
        lambda x, topi, w, wg, wu, wd, live, layer: moe_stream.stream_rows(
            x, topi, w, wg, wu, wd, live, layer, jax.nn.relu)
    ).lower(abstract((n, h), jnp.bfloat16), abstract((n, k), jnp.int32),
            abstract((n, k), jnp.float32),
            abstract((nl, e, h, m), jnp.bfloat16),
            abstract((nl, e, h, m), jnp.bfloat16),
            abstract((nl, e, m, h), jnp.bfloat16),
            abstract((n,), jnp.bool_), abstract((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged" not in text
    # nothing of an expert stack's size beside the arguments
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 * h * m * 2


@pytest.mark.parametrize("config, n, streams", [
    ("smallthinker-21b-a3b-l12", 8, True),        # a decode program
    ("smallthinker-21b-a3b-l12", 16, True),
    ("smallthinker-21b-a3b-l12", 64, True),       # the decode ladder's top
    ("smallthinker-21b-a3b-l12", 264, False),     # a chunk program: b8 t256
    ("smallthinker-21b-a3b-l12", 520, False),     # b8 t512
    ("k-exaone-236b-a23b-ep8-l5", 16, False),     # 75.5 MB an expert
    ("k-exaone-236b-a23b-ep8-l5", 8, False),
])
def test_which_programs_stream_their_experts(monkeypatch, config, n, streams):
    """The routed layer's one predicate (models/moe.py ``streams_experts``)
    at the benchmark's two routed configurations: SmallThinker's decode
    programs yes, its chunk programs no (hundreds of rows: the grouped form
    is the right one), K-EXAONE's no (an expert does not fit VMEM twice);
    and nowhere but on a TPU, in a program that is one chip's (a mesh of
    several partitions the grouped form; a kernel it could only replicate)."""
    from pathlib import Path

    from dynamo_tpu.models import moe
    from dynamo_tpu.models.config import resolve_model_config

    cfg = resolve_model_config(str(
        Path(__file__).resolve().parents[1] / "chipbench/configs" / config))
    shape = (n, cfg.hidden_size, cfg.moe_intermediate_size,
             jnp.dtype(cfg.dtype).itemsize)
    assert not moe.streams_experts(*shape)             # the CPU's answer
    _on_a_tpu(monkeypatch)
    assert moe.streams_experts(*shape) == streams
    devices = np.array(jax.devices())
    assert moe.streams_experts(
        *shape, jax.sharding.Mesh(devices[:1], ("model",))) == streams
    assert not moe.streams_experts(
        *shape, jax.sharding.Mesh(devices[:2], ("model",)))


def test_decode_step_streams_its_experts_and_a_chunk_step_groups_them_on_v5e(
        v5e_device, monkeypatch):
    """SmallThinker's ``b8 t1`` step compiled for the described v5e holds the
    streaming kernel, under the ``moe_experts`` phase by the scope the
    program gives, and no grouped matmul, and nothing in it copies, slices
    or transposes an expert stack or a layer's slab of one; its ``b8 t512``
    step keeps ``lax.ragged_dot``. The program's text only: no time is read
    here."""
    import re

    from dynamo_tpu.obs.profiler import phase_table

    text = _step_text(monkeypatch, "smallthinker-21b-a3b-l12", 8, 1)
    assert "ragged" not in text
    kernels = {n: p for n, p in phase_table(text).items()
               if n.startswith("moe_stream")}
    assert len(kernels) == 4 and set(kernels.values()) == {"moe_experts"}, \
        kernels                                   # a period's four layers
    h, m = 2560, 768
    moved = []
    for name, dims in re.findall(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]",
                                 text, re.M):
        shape = tuple(int(d) for d in dims.split(","))
        # an expert's matrix behind any leading dimensions: a layer's 64,
        # the stack's [12, 64]
        if shape[-2:] in {(h, m), (m, h)} and re.match(
                r"copy|slice|dynamic-slice|transpose|.*fusion", name) \
                and not name.startswith(("copy-start", "copy-done")):
            moved.append(f"{name} [{dims}]")
    assert not moved, moved

    chunk = _step_text(monkeypatch, "smallthinker-21b-a3b-l12", 8, 512)
    assert "ragged-dot" in chunk and "moe_stream" not in chunk


def test_kexaone_decode_step_keeps_the_grouped_form_on_v5e(v5e_device,
                                                           monkeypatch):
    """K-EXAONE's ``b16 t1`` step compiled for the described v5e is the
    program it was before the streaming kernel: the predicate says no (an
    expert of 75.5 MB), so its text, metadata stripped, equals the text
    built with the kernel out of reach."""
    from dynamo_tpu.models import moe

    text = _step_text(monkeypatch, "k-exaone-236b-a23b-ep8-l5", 16, 1)
    assert "ragged-dot" in text and "moe_stream" not in text
    monkeypatch.setattr(moe, "streams_experts", lambda *a: False)
    assert _without_metadata(text) == _without_metadata(
        _step_text(monkeypatch, "k-exaone-236b-a23b-ep8-l5", 16, 1))


@pytest.mark.parametrize("b,t,n,h,kh,window", [
    # The cells' mixed programs: the Mistral cuts' (32 Q / 8 KV x 128),
    # K-EXAONE's sliding and full layers (64 / 8), SmallThinker's (28 / 4:
    # the heads padded to whole sublane tiles) and Nemotron's (32 / 2).
    pytest.param(8, 512, 520, 32, 8, 0, id="7b-b8-t512"),
    pytest.param(8, 16, 24, 32, 8, 0, id="7b-b8-t16"),
    pytest.param(16, 512, 528, 64, 8, 128, id="kexaone-b16-t512-sliding"),
    pytest.param(16, 512, 528, 64, 8, 0, id="kexaone-b16-t512-full"),
    pytest.param(8, 512, 520, 28, 4, 4096, id="smallthinker-b8-t512"),
    pytest.param(32, 512, 544, 32, 2, 0, id="nemotron-b32-t512"),
])
def test_token_major_kernel_compiles_for_v5e(v5e_device, monkeypatch, b, t, n,
                                             h, kh, window):
    """Mosaic itself on the kernel's token-major entry at the cells' mixed
    shapes: the tile's copies from a row's first token (no tile boundary),
    the transposes that spread a tile over the slabs and bring the output
    back, the output written over ``q``'s buffer. The
    grid's steps are in order and the VMEM stays under the scoped limit."""
    from jax.sharding import SingleDeviceSharding

    import dynamo_tpu.ops.paged_attention as pa

    d, bs, nblk, nb, nl = 128, 16, 512, 6817, 4
    sh = SingleDeviceSharding(v5e_device)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    calls = []
    real_call = pa.pl.pallas_call
    monkeypatch.setattr(
        pa.pl, "pallas_call",
        lambda kernel, *a, **kw: (calls.append(kw), real_call(kernel, *a, **kw))[1])
    cache = abstract((nl, nb, bs, kh, d), jnp.bfloat16)
    rows = abstract((b,), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, bt, qs, kl, layer, starts: paged_attention_kernel(
            q, k, v, bt, qs, kl, layer=layer, window=window, starts=starts,
            t=t)
    ).lower(abstract((n, h, d), jnp.bfloat16), cache, cache,
            abstract((b, nblk), jnp.int32), rows, rows,
            abstract((), jnp.int32), rows).compile()
    (kw,) = calls
    assert kw["name"] == "paged_attention"
    assert kw["compiler_params"].dimension_semantics == ("arbitrary",) * 2
    assert kw["compiler_params"].disable_bounds_checks
    assert kw["input_output_aliases"] == {
        kw["grid_spec"].num_scalar_prefetch: 0}
    assert _vmem_bytes(kw["grid_spec"], []) < V5E_SCOPED_VMEM_BYTES
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # nothing of the rectangle's size: the padded tokens at most
    tile = kw["grid_spec"].scratch_shapes[6].shape[0]
    assert tile * (h // kh) <= 512
    shapes = _result_shapes(text)
    assert max(int(np.prod(s)) for s in shapes) < b * t * h * d // 2, shapes


def _result_shapes(text: str) -> set[tuple[int, ...]]:
    """The shapes of the instructions' results in a compiled program's
    text, the parameters' own left out."""
    import re

    return {tuple(int(x) for x in dims.split(","))
            for dims, op in re.findall(
                r"^\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]+)\]\S* (\S+?)\(",
                text, re.M)
            if op != "parameter"}


def test_mixed_step_holds_no_rectangle_on_v5e(v5e_device, monkeypatch):
    """The 7B cut's ``b8 t512`` mixed program compiled for the described
    v5e: no instruction's result has the ``8 x 512 x 4096`` elements of
    ``q``'s rectangle but what has a weight's shape (the parent's program
    had ``bf16[8,512,32,128]``, ``[8,8,2048,128]`` and five more), and the
    kernel's call keeps the name the trace's readers find it by. The
    program's text only: no time is read here."""
    import re

    text = _step_text(monkeypatch, "mistral-7b-v0.3-l16", 8, 512)
    assert re.search(r"%paged_attention\S* = bf16\[648,32,128\]", text)
    hidden, mlp, vocab, kv = 4096, 14336, 32768, 1024
    weights = {(hidden, hidden), (hidden, kv), (hidden, mlp), (mlp, hidden),
               (hidden, vocab), (vocab, hidden)}
    pool = (16, 2048, 16, 8, 128)                 # updated in place
    big = {s for s in _result_shapes(text)
           if int(np.prod(s)) >= 8 * 512 * hidden and s != pool
           and tuple(x for x in s if x != 1)[-2:] not in weights}
    assert not big, big


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
