"""A packed step's K and V into the paged cache by blocks
(ops/kv_write.py), interpreted, against the scatter it stands in for
(models/llama.py ``_scatter_kv`` at ``_positions_and_slots``' slots): every
block but the trash block holds bit for bit what ``cache.at[...].set``
leaves there, in both pools, and the trash block is left as it was.

One parametrised test holds the cases: a chunk that starts at offset 0, 1, 7
and 15 of a block with 1, 15, 16, 17, 511 and 512 tokens, beside decode rows
and a row with no token, in a bucket with padded tokens; the benchmark's four
cache views; the first and the last layer; a table whose ids name no block
of the pool; rows whose scalars are out of range. And the call under "model"
on a two-way mesh, the kernel's body by its equations, Mosaic at the
benchmark's views, the 7B cut's mixed and decode programs' texts, and two
chunk steps and a decode step against a one-shot prefill.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models import llama
from dynamo_tpu.obs.compile_ledger import token_bucket
from dynamo_tpu.ops import kv_write as kw
from tests.test_attention_tokens import _equations, _kernel_calls

BS, B, T = 16, 4, 512
NBLK = 34                  # 15 + 512 positions reach into the 33rd block
NB = 1 + B * NBLK
N = token_bucket("mixed", B, T)


def _rows(off: int, length: int):
    """A decode row, a row with no token, the chunk (it starts ``off`` into
    its third block), a decode row: (q_start, q_len) a row."""
    return [(5, 1), (0, 0), (2 * BS + off, length), (31, 1)]


@functools.lru_cache(maxsize=None)
def _write():
    """The interpreted call under one ``jit``: the cases are data, so a
    shape is compiled once and not once a case."""
    return jax.jit(lambda k, v, ck, cv, tables, q_start, kv_lens, starts,
                   layer: kw.kv_write(k, v, ck, cv, tables, q_start, kv_lens,
                                      starts, layer=layer, interpret=True))


def _step(rows, *, layers=2, kh=2, d=128, nb=NB, nblk=NBLK, n=N, t=T,
          bs=BS, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    b = len(rows)
    q_start = np.array([r[0] for r in rows], np.int32)
    q_len = np.array([r[1] for r in rows], np.int32)
    tables = 1 + rng.permutation(nb - 1)[: b * nblk].reshape(b, nblk).astype(
        np.int32)
    k, v = (jnp.asarray(rng.standard_normal((n, kh, d)), dtype)
            for _ in range(2))
    ck, cv = (jnp.asarray(rng.standard_normal((layers, nb, bs, kh, d)), dtype)
              for _ in range(2))
    lay, valid = llama.token_layout(jnp.asarray(q_len), b, t, n)
    _, slot = llama._positions_and_slots(
        lay, valid, jnp.asarray(q_start), jnp.asarray(tables), bs)
    return (k, v, ck, cv, jnp.asarray(tables), jnp.asarray(q_start),
            jnp.asarray(q_start + q_len), lay.starts), slot


def _check(args, slot, layer, write=None):
    k, v, ck, cv = args[:4]
    out_k, out_v = (write or _write())(*args, jnp.int32(layer))
    for new, pool, out in ((k, ck, out_k), (v, cv, out_v)):
        want = llama._scatter_kv(pool, new, slot, layer)
        np.testing.assert_array_equal(
            np.asarray(out[:, 1:], np.float32),
            np.asarray(want[:, 1:], np.float32))
        # (the scatter put the padded tokens there; the kernel nothing)
        np.testing.assert_array_equal(
            np.asarray(out[:, 0], np.float32), np.asarray(pool[:, 0], np.float32))


_GRID = [(off, length) for off in (0, 1, 7, 15)
         for length in (1, 15, 16, 17, 511, 512)]
_VIEWS = {"kh8_d128": (8, 128), "kh4_d128": (4, 128), "kh2_d128": (2, 128),
          "kh2_d640": (2, 640)}
CASES = {
    **{f"off{off}_len{length}": dict(rows=_rows(off, length))
       for off, length in _GRID},
    # The benchmark's cache views, a pool of a few blocks: a chunk from
    # mid-block over two block ends beside decode rows.
    **{f"view_{name}": dict(rows=[(3, 1), (9, 40), (0, 0), (17, 1)], kh=kh,
                            d=d, nb=25, nblk=6, n=token_bucket("mixed", 4, 64),
                            t=64)
       for name, (kh, d) in _VIEWS.items()},
    "layer_first": dict(rows=_rows(7, 40), layers=3, layer=0),
    "layer_last": dict(rows=_rows(7, 40), layers=3, layer=2),
    # several chunks in one program (short prompts share a step)
    "three_chunks": dict(rows=[(0, 100), (13, 150), (16, 200), (40, 1)]),
    "float32_pool": dict(rows=_rows(7, 40), dtype=jnp.float32),
    "block_of_8": dict(rows=[(3, 1), (9, 40), (0, 0), (17, 1)], nb=49, nblk=12,
                       n=68, t=64, bs=8),
}


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=pytest.mark.slow) if c == "off15_len512" else c
    for c in CASES])
def test_kernel_writes_what_the_scatter_writes(case):
    spec = dict(CASES[case])
    layer = spec.pop("layer", 1)
    args, slot = _step(spec.pop("rows"), **spec)
    _check(args, slot, layer)


@pytest.mark.parametrize("fault", ["ids_past_the_pool", "ids_negative",
                                   "starts_past_the_tokens",
                                   "negative_start_position",
                                   "lengths_past_the_tokens"])
def test_kernel_holds_every_address_in_range(fault):
    """What the docstring's list says of each dynamic address: a table, a
    row's first token, a position or a length that is out of range reads and
    writes what its clamped value does (the kernel runs with no hardware
    bounds check), and never outside the arrays (the interpreter would
    raise)."""
    args, _ = _step(_rows(7, 40))
    k, v, ck, cv, tables, q_start, kv_lens, starts = args
    n = k.shape[0]
    bad, held = {
        "ids_past_the_pool": (
            (tables + NB, q_start, kv_lens, starts),
            (jnp.full_like(tables, NB - 1), q_start, kv_lens, starts)),
        "ids_negative": (
            (tables - 2 * NB, q_start, kv_lens, starts),
            (jnp.zeros_like(tables), q_start, kv_lens, starts)),
        "starts_past_the_tokens": (
            (tables, q_start, kv_lens, starts + n),
            (tables, q_start, kv_lens, n - (kv_lens - q_start))),
        "negative_start_position": (
            (tables, q_start.at[2].set(-5), kv_lens.at[2].set(35), starts),
            (tables, q_start.at[2].set(0), kv_lens.at[2].set(40), starts)),
        "lengths_past_the_tokens": (
            (tables, q_start, kv_lens.at[2].add(4 * n), starts),
            (tables, q_start, kv_lens.at[2].set(q_start[2] + n),
             starts.at[2].set(0))),
    }[fault]
    got = _write()(k, v, ck, cv, *bad, jnp.int32(1))
    want = _write()(k, v, ck, cv, *held, jnp.int32(1))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_kernel_under_model_writes_each_shard_its_heads():
    """``kv_write_sharded`` on a two-way mesh: the KV heads split over
    "model", each shard's call writing its own, equals the one call."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    args, slot = _step([(3, 1), (9, 40), (0, 0), (17, 1)], kh=4, nb=25,
                       nblk=6, n=token_bucket("mixed", 4, 64), t=64)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    heads, pool = P(None, "model", None), P(None, None, None, "model", None)
    k, v, ck, cv, *rest = args
    placed = (put(k, heads), put(v, heads), put(ck, pool), put(cv, pool),
              *(put(a, P()) for a in rest))
    write = jax.jit(lambda *a: kw.kv_write_sharded(
        mesh, *a[:-1], layer=a[-1], interpret=True))
    _check(placed, slot, 1, write)
    out_k, _ = write(*placed, jnp.int32(1))
    assert tuple(out_k.sharding.spec)[:4] == tuple(pool)[:4]


def test_kernel_body_stays_short():
    """Every cold program build traces and lowers the body (PERF.md, PR 49):
    its equations, nested ones counted, at the 7B cut's chunk shape and the
    count the module's docstring states."""
    shape = jax.ShapeDtypeStruct
    cache = shape((16, 64, BS, 8, 128), jnp.bfloat16)
    new, rows = shape((520, 8, 128), jnp.bfloat16), shape((8,), jnp.int32)
    closed = jax.make_jaxpr(functools.partial(kw.kv_write, layer=jnp.int32(1)))(
        new, new, cache, cache, shape((8, 512), jnp.int32), rows, rows, rows)
    (kernel,) = _kernel_calls(closed.jaxpr)
    body = sum(_equations(sub)
               for sub in jax.core.jaxprs_in_params(kernel.params))
    assert body <= 130, body
    assert f"body is {body} equations" in kw.__doc__


def test_chunks_from_mid_block_then_a_decode_step_equal_the_one_shot_prefill():
    """A mixed step end to end, under the interpreted kernels: a prompt of
    37 tokens as a chunk of 13 and a chunk of 24 (it starts five slots into
    a block of 8) beside another sequence's decode row, then a decode step
    (a rectangle: the scatter), against the prompt prefilled in one
    rectangle under the dense gather: the logits after the last chunk and
    after the decode step, and what the cache holds for the sequence."""
    import dataclasses

    from dynamo_tpu.engine.cache import KVCacheSpec, _zeros
    from dynamo_tpu.models.config import MODEL_PRESETS

    cfg = dataclasses.replace(MODEL_PRESETS["tiny-llama"], dtype="float32")
    params = llama.init_params(cfg, jax.random.key(7))
    bs, nblk, b = 8, 8, 4
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 38).astype(np.int32)
    other = rng.integers(1, cfg.vocab_size, 3).astype(np.int32)
    tables = 1 + rng.permutation(b * nblk).reshape(b, nblk).astype(np.int32)
    spec = KVCacheSpec.for_model(cfg, 1 + b * nblk, bs)

    def step(impl, rows, t, n, caches):
        """rows: (row, q_start, tokens); returns logits [B, V] and caches."""
        toks = np.zeros((b, t), np.int32)
        q_start, q_len = np.zeros(b, np.int32), np.zeros(b, np.int32)
        for r, start, new in rows:
            toks[r, :len(new)], q_start[r], q_len[r] = new, start, len(new)
        hid, ck, cv = jax.jit(lambda p, ck, cv: llama.forward(
            p, cfg, jnp.asarray(toks), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(tables), ck, cv, attn_impl=impl,
            num_tokens=n))(params, *caches)
        return np.asarray(llama.logits_from_hidden(params, cfg, hid)), (ck, cv)

    kernel = "pallas_interpret"
    caches = (_zeros(spec), _zeros(spec))
    n = token_bucket("mixed", b, 32)
    _, caches = step(kernel, [(0, 0, other[:2]), (2, 0, prompt[:13])], 32, n,
                     caches)
    last, caches = step(kernel, [(0, 2, other[2:]), (2, 13, prompt[13:37])],
                        32, n, caches)
    # (the chunk steps left the trash block as it was; the decode step's
    # padded rows scatter into it)
    assert not any(np.asarray(c)[:, 0].any() for c in caches)
    dec, caches = step(kernel, [(2, 37, prompt[37:])], 1, None, caches)

    whole = (_zeros(spec), _zeros(spec))
    want_last, whole = step("dense", [(2, 0, prompt[:37])], 64, None, whole)
    want_dec, whole = step("dense", [(2, 37, prompt[37:])], 1, None, whole)
    np.testing.assert_allclose(last[2], want_last[2], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(dec[2], want_dec[2], atol=1e-4, rtol=1e-4)
    mine = tables[2, :5]                      # 38 positions: five blocks
    for got, want in zip(caches, whole):
        g, w = (np.asarray(c)[:, mine].reshape(cfg.num_layers, 5 * bs, -1)[:, :38]
                for c in (got, want))
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


# -- Ahead-of-time compile for the v5e (no chip: the installed libtpu) ---------

@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


@pytest.mark.parametrize("rows,n", [(8, 520), (32, 48)], ids=["b8_t512", "b32_t16"])
@pytest.mark.parametrize("view", sorted(_VIEWS))
def test_kernel_compiles_for_v5e(v5e_device, view, rows, n):
    """Mosaic itself, at each of the benchmark's cache views (K-EXAONE's and
    the Mistral cuts' 8 heads of 128, Falcon-H1's and SmallThinker's 4,
    Nemotron's 2, Phi-4's 2 of 640) with ``k`` and ``v`` straight from HBM:
    the kernel lowers, the donated pools are the results' buffers and the
    call holds no temporary (a copy of a pool, or of ``k`` into another
    layout, would be one)."""
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(v5e_device)
    kh, d = _VIEWS[view]

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    pool, new = a((9, 1024, BS, kh, d), jnp.bfloat16), a((n, kh, d), jnp.bfloat16)
    vec = a((rows,), jnp.int32)
    compiled = jax.jit(
        lambda k, v, ck, cv, tables, q_start, kv_lens, starts, layer:
        kw.kv_write(k, v, ck, cv, tables, q_start, kv_lens, starts,
                    layer=layer), donate_argnums=(2, 3)).lower(
        new, new, pool, pool, a((rows, 512), jnp.int32), vec, vec, vec,
        a((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.alias_size_in_bytes == 2 * 9 * 1024 * BS * kh * d * 2
    assert "%kv_write" in compiled.as_text()


def test_a_mixed_program_writes_by_blocks_and_a_decode_program_scatters(
        monkeypatch):
    """The 7B cut's ``b8 t512`` and ``b8 t1`` programs compiled for the
    described v5e: the mixed one holds one ``kv_write`` call (the scanned
    layer's) whose two results are the pools, and nothing else makes a value
    of a pool's shape (no scatter, no copy); the decode program holds no
    such call and keeps its in-place scatter."""
    import re

    from tests.test_ops import _step_text

    made = r"= bf16\[16,2048,16,8,128\]\S* ([a-z-]+)\("
    mixed = _step_text(monkeypatch, "mistral-7b-v0.3-l16", 8, 512)
    assert len(re.findall(
        r"%kv_write\S* = \(bf16\[16,2048,16,8,128\]\S*, bf16\[16,2048,16,8,128\]",
        mixed)) == 1
    assert set(re.findall(made, mixed)) <= {"parameter", "get-tuple-element"}
    decode = _step_text(monkeypatch, "mistral-7b-v0.3-l16", 8, 1)
    # (the text's table of files names this one)
    assert "%kv_write" not in decode
    assert "fusion" in set(re.findall(made, decode))
