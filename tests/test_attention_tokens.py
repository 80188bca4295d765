"""The paged kernel's token-major entry (ops/paged_attention.py, ``starts``):
``q`` goes in as a packed step's dense layers leave it, ``[N, H, D]``, and
the output comes back in the same places.

Held here, interpreted: on the live tokens it equals the rectangle entry bit
for bit (the rows gathered into ``[B, T]``, the kernel, the output gathered
back: the path a packed step took until PR 50), over ``test_token_major``'s
ragged batches and two made for the tiles' edges, for 4, 7, 8 and 16 query
heads a kv head, a plain, an int8 and a packed-int4 pool, a full and a
sliding layer, one group of blocks a walk and several with a partial last
one (each landed by one wait a buffer); a token of no row stays finite. And a
packed ``forward`` under the kernel holds no array of ``B x T`` positions
outside it, and the kernel's body no more equations than it had before the
walk's copies were rewritten (PR 53: a body's length is set-up time).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.ops.paged_attention as pa
from dynamo_tpu.engine.cache import KVCacheSpec, _zeros
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import MODEL_PRESETS
from dynamo_tpu.obs.compile_ledger import token_bucket
from tests.test_ops import _whole_cache
from tests.test_token_major import B, BS, CASES, NB, NBLK, T

D, LAYERS = 128, 2
EDGES = {
    # The last row's tile runs past N = 24: tokens 14..29 of 24.
    "tile_past_n": (T, [(5, 1), (0, 12), (30, 1), (8, 10)]),
    # Row 0 ends five tokens into its tile and row 1's chunk lies in the
    # rest of it: row 1's output must be its own.
    "row_ends_mid_tile": (T, [(0, 5), (7, 16), (3, 1)]),
}
# The packed steps (a step of one-token rows alone is its own rectangle, and
# never takes this entry).
ROWS = {**{k: v for k, v in CASES.items() if k != "decode_only"}, **EDGES}


def _step(case: str, kh: int, rep: int, kv: str, dtype):
    t, rows = ROWS[case]
    rng = np.random.default_rng(sorted(ROWS).index(case))
    q_start, q_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for i, (start, length) in enumerate(rows):
        q_start[i], q_len[i] = start, length
    n = token_bucket("mixed", B, t)
    assert q_len.sum() <= n
    tables = 1 + rng.permutation(B * NBLK).reshape(B, NBLK).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((n, kh * rep, D)), dtype)
    k, v = (_whole_cache(rng, kv, LAYERS, NB, BS, kh, D) for _ in range(2))
    if kv == "bfloat16":
        k, v = k.astype(dtype), v.astype(dtype)
    lay, valid = llama.token_layout(jnp.asarray(q_len), B, t, n)
    return (q, k, v, jnp.asarray(tables), jnp.asarray(q_start),
            jnp.asarray(q_start + q_len)), lay, np.asarray(valid)


@functools.lru_cache(maxsize=None)
def _entries(window: int, chunk_rows: int, group_keys: int):
    """The two entries under ``jit``, one pair a (window, chunk size, group
    size): the row cases are data, so each shape is compiled once and not
    once a case."""
    del chunk_rows, group_keys     # read by the kernel when it is traced
    kw = dict(interpret=True, window=window, t=T)

    def tokens(q, k, v, tables, q_start, kv_lens, starts):
        return pa.paged_attention_kernel(q, k, v, tables, q_start, kv_lens,
                                         layer=jnp.int32(1), starts=starts,
                                         **kw)

    def rectangle(q, k, v, tables, q_start, kv_lens):
        return pa.paged_attention_kernel(q, k, v, tables, q_start, kv_lens,
                                         layer=jnp.int32(1), **kw)

    return jax.jit(tokens), jax.jit(rectangle)


def _both(case, kh, rep, kv, dtype, window):
    """(token-major, rectangle) outputs [N, H, D] and the live tokens [N]."""
    (q, *rest), lay, valid = _step(case, kh, rep, kv, dtype)
    tokens, rectangle = _entries(window, pa._CHUNK_ROWS, pa._GROUP_KEYS)
    got = tokens(q, *rest, lay.starts)
    rect = rectangle(lay.to_rows(q).reshape(B, T, q.shape[1], D), *rest)
    return np.asarray(got), np.asarray(lay.to_tokens(rect)), valid


@pytest.fixture
def small_chunks(monkeypatch):
    """Query chunks of 32 rows, so that a 16-token row is two to eight
    chunks and the tiles are 2-8 tokens (the served 512 rows would make
    every row here one chunk)."""
    monkeypatch.setattr(pa, "_CHUNK_ROWS", 32)


@pytest.mark.parametrize("window", [0, 6], ids=["full", "sliding"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8", "int4"],
                         ids=["plain_kv", "int8_kv", "int4_kv"])
@pytest.mark.parametrize("kh,rep", [(2, 4), (1, 7), (2, 8), (1, 16)],
                         ids=["rep4", "rep7", "rep8", "rep16"])
@pytest.mark.parametrize("case", sorted(ROWS))
def test_token_major_entry_equals_the_rectangle_entry(
        small_chunks, case, kh, rep, kv, window):
    got, want, valid = _both(case, kh, rep, kv, jnp.float32, window)
    assert valid.any()
    np.testing.assert_array_equal(got[valid], want[valid])
    assert np.isfinite(got).all()


@pytest.fixture
def small_groups(monkeypatch):
    """Groups of two blocks of 4 keys, so that a context of up to 32 tokens
    is one to four groups, the last one half a group where its blocks are
    odd (the served 16-32 blocks would make every walk here one group)."""
    monkeypatch.setattr(pa, "_GROUP_KEYS", 2 * BS)
    monkeypatch.setattr(pa, "_GROUP_KEYS_WIDE", 2 * BS)


@pytest.mark.parametrize("chunks", ["one_chunk", "small_chunks",
                                    "small_groups"])
@pytest.mark.parametrize("kh,rep", [(2, 4), (4, 7), (1, 7)],
                         ids=["rep4", "rep7", "odd_heads"])
@pytest.mark.parametrize("case", sorted(ROWS))
def test_token_major_entry_in_bf16(request, case, kh, rep, chunks):
    """As served: bf16 queries and pool; 28 heads and 7 are padded to whole
    sublane tiles of 16."""
    if chunks != "one_chunk":
        request.getfixturevalue("small_chunks")
    if chunks == "small_groups":
        request.getfixturevalue("small_groups")
    got, want, valid = _both(case, kh, rep, "bfloat16", jnp.bfloat16, 0)
    np.testing.assert_array_equal(got[valid].view(np.uint16),
                                  want[valid].view(np.uint16))
    assert np.isfinite(got.astype(np.float32)).all()


# -- No rectangle in a packed forward ----------------------------------------

def _avals(jaxpr, inside=False):
    """Every value of ``jaxpr`` and of the jaxprs nested in its equations,
    as (shape, whether it lies inside a ``pallas_call``)."""
    for eqn in jaxpr.eqns:
        inner = inside or eqn.primitive.name == "pallas_call"
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                yield v.aval.shape, inner
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub, inner)


@pytest.mark.parametrize("case", sorted(set(ROWS) - set(EDGES)))
def test_packed_forward_holds_no_rectangle(case):
    """Outside the kernel's call no value of a packed step's ``forward`` has
    ``B x T x q_size`` elements or more (the KV pool and the parameters are
    arguments, not values made here; the dense toy, whose widest value is
    the MLP's ``[N, intermediate]``): the ``[B, T]`` rectangle of ``q`` and
    of the attention output cannot come back unseen."""
    cfg = dataclasses.replace(MODEL_PRESETS["tiny-llama"], dtype="float32")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    cache = _zeros(KVCacheSpec.for_model(cfg, NB, BS, kv_dtype="float32"))
    t, rows = CASES[case]
    q_start, q_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for i, (start, length) in enumerate(rows):
        q_start[i], q_len[i] = start, length
    n = token_bucket("mixed", B, t)
    assert n < B * t

    def step(p, ck, cv, tokens, tables):
        return llama.forward(p, cfg, tokens, jnp.asarray(q_start),
                             jnp.asarray(q_len), tables, ck, cv,
                             attn_impl="pallas_interpret", num_tokens=n)

    closed = jax.make_jaxpr(step)(
        params, cache, cache, jnp.zeros((B, t), jnp.int32),
        jnp.ones((B, NBLK), jnp.int32))
    shapes = list(_avals(closed.jaxpr))
    assert any(inner for _, inner in shapes), "no kernel in the step"
    pool = int(np.prod(jax.tree.leaves(cache)[0].shape))
    limit = B * t * cfg.q_size
    assert limit < pool
    big = {shape for shape, inner in shapes
           if not inner and limit <= int(np.prod(shape)) < pool}
    assert not big, big


# -- The kernel body's length -------------------------------------------------

def _equations(jaxpr) -> int:
    """The equations of ``jaxpr`` and of every jaxpr nested in them."""
    return sum(1 + sum(_equations(sub)
                       for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def _kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


# What the body counted before PR 53 (the parent's file traced on the same
# shapes): the walk's copies may not lengthen it.
@pytest.mark.parametrize("entry,window,kv,limit", [
    ("decode", 0, "bfloat16", 165), ("decode", 6, "bfloat16", 177),
    ("chunk", 0, "bfloat16", 165), ("tokens", 0, "bfloat16", 181),
    ("tokens", 6, "bfloat16", 193), ("decode", 0, "int8", 225),
], ids=["decode", "decode_sliding", "chunk", "tokens", "tokens_sliding",
        "decode_int8"])
def test_kernel_body_is_no_longer_than_it_was(entry, window, kv, limit):
    """Every warmed program traces and lowers the kernel's body again, and
    an equation under ``pl.when`` costs ~3 ms there (PERF.md, PR 49): the
    body's equations, nested ones counted, stay at or under the count they
    had with one wait a block and a table read in two dimensions."""
    kh, rep = 2, 4
    shape = jax.ShapeDtypeStruct
    cache = shape((LAYERS, NB, BS, kh, D), jnp.bfloat16)
    if kv == "int8":
        cache = {"q": shape(cache.shape, jnp.int8),
                 "s": shape((LAYERS, NB, kh), jnp.float32)}
    rows = shape((B,), jnp.int32)
    t = 1 if entry == "decode" else T
    kw = dict(layer=jnp.int32(1), window=window, interpret=True)
    if entry == "tokens":
        q = shape((token_bucket("mixed", B, T), kh * rep, D), jnp.bfloat16)
        kw.update(t=T)
    else:
        q = shape((B, t, kh * rep, D), jnp.bfloat16)

    def call(q, k, v, tables, q_start, kv_lens, *starts):
        return pa.paged_attention_kernel(
            q, k, v, tables, q_start, kv_lens,
            **({"starts": starts[0]} if starts else {}), **kw)

    closed = jax.make_jaxpr(call)(
        q, cache, cache, shape((B, NBLK), jnp.int32), rows, rows,
        *([rows] if entry == "tokens" else []))
    (kernel,) = _kernel_calls(closed.jaxpr)
    body = sum(_equations(sub)
               for sub in jax.core.jaxprs_in_params(kernel.params))
    assert body <= limit, body


# -- The heads split two ways -------------------------------------------------

@pytest.mark.parametrize("kv", ["bfloat16", "int8"], ids=["plain_kv", "int8_kv"])
@pytest.mark.parametrize("case", sorted(set(ROWS) - set(EDGES)))
def test_packed_step_under_the_kernel_split_over_model(case, kv):
    """``test_token_major``'s two-way mesh with attention the interpreted
    kernel: a packed ``forward`` (the token-major entry inside the
    ``shard_map`` over "model") gives the hidden states and the KV the
    rectangle ``forward`` gives, under the same mesh."""
    from dynamo_tpu.engine.cache import cache_sharding
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh, shard_params
    from tests.test_cache_carry import _warm_cache
    from tests.test_token_major import _inputs

    cfg = dataclasses.replace(MODEL_PRESETS["tiny-llama"], dtype="float32")
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    params = shard_params(llama.init_params(cfg, jax.random.key(5)),
                          llama.param_logical_axes(cfg), mesh)
    inputs = _inputs(case, cfg)
    n = token_bucket("mixed", B, inputs[0].shape[1])
    spec = KVCacheSpec.for_model(cfg, NB, BS, kv_dtype=kv)
    rng = np.random.default_rng(11)
    caches = [jax.device_put(_warm_cache(rng, spec), cache_sharding(spec, mesh))
              for _ in range(2)]

    def run(num_tokens):
        return jax.jit(lambda p, ck, cv: llama.forward(
            p, cfg, *inputs, ck, cv, mesh=mesh, attn_impl="pallas_interpret",
            num_tokens=num_tokens))(params, *caches)

    live = np.asarray(inputs[2]) > 0
    for got, want in zip(jax.tree.leaves(run(n)), jax.tree.leaves(run(None))):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if got.shape[0] == B:
            got, want = got[live], want[live]
        else:
            got, want = got[:, 1:], want[:, 1:]      # block 0 is trash
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
