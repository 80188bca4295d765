"""K-EXAONE-236B-A23B's block at a small size on the CPU, against the plain
reference the benchmark's configuration brings
(``chipbench/configs/k-exaone-236b-a23b-ep8-l5/reference.py``): a routed
layer that holds a share of the published experts (sigmoid scores, a
selection bias, normalised and scaled weights, a shared expert), a leading
dense layer, sliding and full attention in one period, QK norm, rotary
embedding on the sliding layers only, post-norm residuals.

Everything here is float32 with seeded random weights. The tolerances say
why they are what they are; each is tight enough that computing in bf16
where float32 is stated fails it (``test_bf16_fails_the_tolerance``).
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
from dynamo_tpu.ops.paged_attention import paged_attention_kernel

ROOT = Path(__file__).resolve().parents[1]
CONFIG_DIR = ROOT / "chipbench" / "configs" / "k-exaone-236b-a23b-ep8-l5"
BS = 16

# The published config cut to CPU size: every mechanism, tiny widths. 1 dense
# + 7 routed layers: one whole period L L G L and three of the next.
TINY = {
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "sliding_window": 40, "sliding_window_pattern": "LLLG",
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "num_experts": 4, "num_experts_published": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False, "vocab_size": 256,
    "max_position_embeddings": 4096,
    "qk_norm": True, "rope_scope": "sliding", "norm_placement": "post",
    "router_bias": True,
}

# float32 against float32 over eight layers: the two sides sum in other
# orders (a grouped matmul, an online softmax) and differ by rounding,
# 1e-6 of unit-scale logits a layer; 2e-4 leaves an order of magnitude.
# bf16 anywhere on the path reads 1e-2 or more.
LOGIT_TOL = 2e-4


def _period_windows(cfg) -> tuple[int, ...]:
    """The attention windows of the layers of one period of the plan."""
    p = cfg.layer_plan
    return tuple(layer[0].window
                 for layer in p.layers[p.lead:p.lead + p.period])


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kexaone_reference", CONFIG_DIR / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(tmp_path, **over) -> tuple[ModelConfig, dict]:
    model = {**TINY, **over}
    (tmp_path / "config.json").write_text(json.dumps(model))
    cfg = ModelConfig.from_hf_config(str(tmp_path))
    return dataclasses.replace(cfg, dtype="float32"), model


def _serve(cfg, params, tokens, *, attn_impl, chunk=512, n_decode=6,
           dtype=None):
    """Logits [len(tokens), vocab] as the engine's step computes them:
    prefill in chunks of ``chunk`` and then one token at a time, through a
    paged cache of the whole model, one row of a batch of two (the other is
    padding)."""
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
        params = jax.tree.map(
            lambda a: a.astype(dtype) if a.dtype == jnp.float32 and a.ndim > 1
            else a, params)
    n = len(tokens)
    nblk = -(-n // BS)
    shape = (cfg.num_layers, nblk + 2, BS, cfg.num_kv_heads, cfg.head_dim)
    ck = jnp.zeros(shape, jnp.dtype(cfg.dtype))
    cv = jnp.zeros(shape, jnp.dtype(cfg.dtype))
    bt = jnp.zeros((2, nblk), jnp.int32).at[0].set(jnp.arange(1, nblk + 1))
    out = []

    @jax.jit     # one program a chunk width, as a step is
    def step(ids, start, length, ck, cv):
        hid, ck, cv, counts = llama.forward(
            params, cfg, ids, start, length, bt, ck, cv,
            attn_impl=attn_impl, moe_impl="held", return_all_hidden=True,
            moe_counts=True)
        return llama.logits_from_hidden(params, cfg, hid[0]), ck, cv, counts

    n_prefill = n - n_decode
    cuts = [(s, min(chunk, n_prefill - s)) for s in range(0, n_prefill, chunk)]
    cuts += [(s, 1) for s in range(n_prefill, n)]
    for start, length in cuts:
        t = 1 if length == 1 else chunk
        ids = np.zeros((2, t), np.int32)
        ids[0, :length] = tokens[start:start + length]
        logits, ck, cv, counts = step(
            jnp.asarray(ids), jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32), ck, cv)
        assert counts.shape == (3,) and int(counts[0]) > 0
        out.append(np.asarray(logits[:length], np.float32))
    return np.concatenate(out)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cfg, model = _config(tmp_path_factory.mktemp("kexaone"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


@pytest.fixture(scope="module")
def served(tiny):
    """The reference's logits of a 600-token sequence: it crosses the
    window (40), a block (16), a group of the kernel's walk (256 keys under
    a chunk, 512 under a decode row) and a 512 chunk."""
    cfg, model, params = tiny
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 600).tolist()
    ref = _reference().logits_at(params, model, tokens, list(range(600)))
    return tokens, ref


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through the paged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "pallas_interpret"])
def test_prefill_then_decode_matches_the_reference(tiny, served, attn_impl):
    cfg, _model, params = tiny
    tokens, ref = served
    got = _serve(cfg, params, tokens, attn_impl=attn_impl)
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) < LOGIT_TOL


def test_bf16_fails_the_tolerance(tiny, served):
    """The same path with bf16 weights and activations is off by a hundred
    times the tolerance: the comparison would catch a lower precision."""
    cfg, _model, params = tiny
    tokens, ref = served
    got = _serve(cfg, params, tokens[:80], attn_impl="dense", chunk=64,
                 dtype="bfloat16")
    assert np.max(np.abs(got - ref[:80])) > 20 * LOGIT_TOL


def test_the_other_norm_placement_is_a_change_of_data(tmp_path):
    """``norm_placement: "pre"`` in config.json moves the program and the
    reference alike."""
    cfg, model = _config(tmp_path, norm_placement="pre")
    params = llama.init_params(cfg, jax.random.key(4))
    tokens = np.random.default_rng(6).integers(0, cfg.vocab_size, 70).tolist()
    ref = _reference().logits_at(params, model, tokens, list(range(70)))
    got = _serve(cfg, params, tokens, attn_impl="dense", chunk=64)
    assert np.max(np.abs(got - ref)) < LOGIT_TOL
    post = _serve(dataclasses.replace(cfg, norm_placement="post"), params,
                  tokens, attn_impl="dense", chunk=64)
    assert np.max(np.abs(post - ref)) > 0.1


# ---------------------------------------------------------------------------
# the router, by hand
# ---------------------------------------------------------------------------

def test_router_against_a_hand_computation(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, cfg.hidden_size)).astype(np.float32)
    w = rng.standard_normal((cfg.hidden_size, 16)).astype(np.float32) / 8
    # A bias large enough to change the choice: expert 15 is always chosen
    # and weighs by its score alone.
    b = (0.02 * rng.standard_normal(16)).astype(np.float32)
    b[15] = 1.0
    topi, weights = moe.route(jnp.asarray(x), {"router": jnp.asarray(w),
                                               "router_bias": jnp.asarray(b)},
                              cfg)
    s = 1.0 / (1.0 + np.exp(-(x @ w)))
    for n in range(5):
        chosen = np.argsort(-(s[n] + b))[:4]
        assert set(chosen) == set(np.asarray(topi[n]).tolist())
        assert 15 in chosen
        want = 2.5 * s[n, chosen] / s[n, chosen].sum()
        got = dict(zip(np.asarray(topi[n]).tolist(),
                       np.asarray(weights[n]).tolist()))
        # float32 sigmoid and a four-term sum: rounding alone
        np.testing.assert_allclose([got[e] for e in chosen], want, rtol=2e-6)
    # without the bias the choice is by score, and differs
    plain, _ = moe.route(jnp.asarray(x), {"router": jnp.asarray(w)},
                         dataclasses.replace(cfg, router_bias=False))
    assert any(15 not in row for row in np.asarray(plain).tolist())


def test_softmax_router_is_what_it_was(tiny):
    """One function for both scorings: a softmax model's routing is the
    softmax over its chosen logits."""
    cfg = dataclasses.replace(tiny[0], router_scoring="softmax",
                              router_bias=False, routed_scaling_factor=1.0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((cfg.hidden_size, 16)), jnp.float32)
    topi, weights = moe.route(x, {"router": w}, cfg)
    topv, want_i = jax.lax.top_k(x @ w, 4)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(jax.nn.softmax(topv, axis=-1)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# the share: the parts of all the chips add up to the uncut layer
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """Four chips hold 4 of 16 experts each. Each computes its own experts'
    rows; the routed parts of the four, with the shared expert (which every
    chip computes alike) counted once, are the whole layer's result."""
    cfg = tiny[0]
    whole = dataclasses.replace(cfg, num_experts=16, num_experts_published=0)
    lp = jax.tree.map(lambda a: a[0], llama.layer_stacks(
        llama.init_params(whole, jax.random.key(9))["layers"])["rep"])
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (37, cfg.hidden_size)), jnp.float32)
    uncut = llama.moe_mlp(x, lp, whole)           # every expert, all-experts form
    shared = llama.swiglu(x, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
    total = jnp.zeros_like(x)
    rows = 0
    for chip in range(4):
        # Chip ``chip`` holds experts 4*chip .. 4*chip+3. The layer holds
        # "the first ``held``" by convention, so hand it the router with
        # its own experts' columns first: the same routing, renumbered.
        order = np.roll(np.arange(16), -4 * chip)
        mine = {**lp, "router": lp["router"][:, order],
                "router_bias": lp["router_bias"][order],
                **{k: lp[k][4 * chip:4 * chip + 4]
                   for k in ("w_gate", "w_up", "w_down")}}
        part, counts = moe.moe_mlp_held(x, mine, cfg)
        total = total + (part - shared)
        rows += int(counts[0])
    # every (token, choice) pair was computed on exactly one chip
    assert rows == 37 * cfg.num_experts_per_tok
    # float32 sums of four terms in another order
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               atol=2e-5)


@pytest.mark.parametrize("ep", [2, 4])
def test_the_whole_model_sharded_over_ep_routes_by_the_bias(tiny, ep):
    """The deployment this configuration is a share of: the whole layer
    with its experts over an "expert" mesh axis. Each shard routes over all
    16 with the selection bias, as the held share and the reference do: a
    bias large enough to change the choice, so that routing by score alone
    would read otherwise."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    cfg = tiny[0]
    whole = dataclasses.replace(cfg, num_experts=16, num_experts_published=0)
    lp = jax.tree.map(lambda a: a[0], llama.layer_stacks(
        llama.init_params(whole, jax.random.key(9))["layers"])["rep"])
    lp = {**lp, "router_bias": 25.0 * lp["router_bias"]}
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, cfg.hidden_size)), jnp.float32)
    by_score, _ = moe.route(x, lp, dataclasses.replace(whole, router_bias=False))
    by_bias, _ = moe.route(x, lp, whole)
    assert (np.sort(np.asarray(by_score)) != np.sort(np.asarray(by_bias))).any()
    mesh = make_mesh(MeshConfig(ep=ep))
    out = jax.jit(lambda x, w: moe.moe_mlp_dropless(x, w, whole, mesh=mesh))(
        x, lp)
    # float32 partial sums of ``ep`` shards in another order
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(llama.moe_mlp(x, lp, whole)),
                               atol=2e-5)


def test_a_biased_router_requires_its_bias(tiny):
    cfg = tiny[0]
    lp = jax.tree.map(lambda a: a[0], llama.layer_stacks(tiny[2]["layers"])["rep"])
    x = jnp.ones((3, cfg.hidden_size), jnp.float32)
    with pytest.raises(KeyError, match="router_bias"):
        moe.route(x, {"router": lp["router"]}, cfg)


def test_held_rows_counts_and_padding(tiny):
    cfg = tiny[0]
    lp = jax.tree.map(lambda a: a[0], llama.layer_stacks(tiny[2]["layers"])["rep"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (10, cfg.hidden_size)), jnp.float32)
    live = jnp.arange(10) < 6
    y, counts = moe.moe_mlp_held(x, lp, cfg, live)
    topi, _ = moe.route(x, lp, cfg)
    here = np.asarray(topi)[:6] < cfg.num_experts
    sizes = np.bincount(np.asarray(topi)[:6][here], minlength=cfg.num_experts)
    assert counts.tolist() == [int(here.sum()), int((sizes > 0).sum()),
                               int(sizes.max())]
    # a live token's result does not depend on the padding beside it
    y6, _ = moe.moe_mlp_held(x[:6], lp, cfg)
    np.testing.assert_allclose(np.asarray(y[:6]), np.asarray(y6), atol=1e-6)
    # the all-experts form over the held share is the same sum
    np.testing.assert_allclose(np.asarray(y6),
                               np.asarray(llama.moe_mlp(x[:6], lp, cfg)),
                               atol=2e-5)


def test_a_share_has_to_divide_the_published_count(tmp_path):
    with pytest.raises(ValueError, match="divide"):
        _config(tmp_path, num_experts=5)
    with pytest.raises(ValueError, match="group-limited"):
        _config(tmp_path, n_group=4, topk_group=2)


# ---------------------------------------------------------------------------
# the kernel's window walk against the dense mask
# ---------------------------------------------------------------------------

def _pool(rng, nb, kh, d, quant):
    k = jnp.asarray(rng.standard_normal((nb, BS, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb, BS, kh, d)), jnp.float32)
    if not quant:
        return k, v, k, v
    out = []
    for a in (k, v):
        s = jnp.max(jnp.abs(a), axis=(1, 3)) / 127.0          # [NB, KH]
        q = jnp.clip(jnp.round(a / s[:, None, :, None]), -127, 127)
        out.append(({"q": q.astype(jnp.int8), "s": s},
                    q * s[:, None, :, None]))
    return out[0][0], out[1][0], out[0][1], out[1][1]


# (t, q_start a row, q_len a row): decode rows near and far past the
# window; a T=512 chunk deep in a context beside one-token rows and a
# padding row; a chunk that starts inside the first window.
WALKS = {
    "decode_rows": (1, [5, 130, 700, 0], [1, 1, 1, 0]),
    "chunk_512": (512, [300, 811, 0, 40], [512, 1, 0, 1]),
    "chunk_at_start": (64, [0, 100], [64, 30]),
}


@pytest.mark.parametrize("window", [24, 128, 300])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("case", sorted(WALKS))
def test_window_walk_matches_the_dense_mask(case, quant, window):
    t, q_start, q_len = WALKS[case]
    b, kh, rep, d = len(q_start), 2, 2, 16
    rng = np.random.default_rng(len(case) + window)
    nblk = 52                                   # 832 positions a row
    kq, vq, k, v = _pool(rng, 1 + b * nblk, kh, d, quant)
    bt = jnp.asarray(1 + np.arange(b * nblk).reshape(b, nblk), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, kh * rep, d)), jnp.float32)
    qs, ql = jnp.asarray(q_start, jnp.int32), jnp.asarray(q_len, jnp.int32)
    got = paged_attention_kernel(q, kq, vq, bt, qs, qs + ql, interpret=True,
                                 window=window)
    want = llama.paged_attention(
        q, llama._gather_kv(k, bt), llama._gather_kv(v, bt),
        qs[:, None] + jnp.arange(t)[None, :], qs + ql, window=window)
    full = llama.paged_attention(
        q, llama._gather_kv(k, bt), llama._gather_kv(v, bt),
        qs[:, None] + jnp.arange(t)[None, :], qs + ql)
    live = np.arange(t)[None, :] < np.asarray(q_len)[:, None]
    # float32 on both sides, another order of summation (an online softmax
    # over groups of 256 or 512 keys)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    deep = live & ((np.asarray(q_start)[:, None] + np.arange(t)) >= window)
    if deep.any():                              # the window hides something
        assert not np.allclose(np.asarray(want)[deep], np.asarray(full)[deep],
                               atol=1e-3)


# ---------------------------------------------------------------------------
# the normal path: EngineCore, default flags
# ---------------------------------------------------------------------------

def _engine_config(tmp_path, **kw):
    from dynamo_tpu.utils.config import EngineConfig

    (tmp_path / "config.json").write_text(json.dumps(TINY))
    return EngineConfig(model=str(tmp_path), allow_random_weights=True,
                        num_blocks=160, max_batch_size=8, max_model_len=1024,
                        prefill_chunk=64, decode_bucket=(4, 8), **kw)


@pytest.mark.parametrize("attn_impl", ["dense", "pallas_interpret"])
def test_engine_serves_it_and_counts(tmp_path, attn_impl):
    """Through ``EngineCore`` as any model: the scheduler, the pool, the
    lattice. The engine computes in bf16, so the logprobs it reports are
    held to the reference loosely here (the chip's probe has the limits);
    the counters are exact."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.obs.sched_ledger import get_sched_ledger
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    core = EngineCore(_engine_config(tmp_path, attn_impl=attn_impl))
    assert core.runner.moe_impl == "held"
    before = get_sched_ledger().snapshot()
    rng = np.random.default_rng(11)
    reqs = [PreprocessedRequest(
        token_ids=rng.integers(0, 256, n).tolist(),
        stop_conditions=StopConditions(max_tokens=5, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
        for n in (150, 20, 70)]
    for r in reqs:
        core.add_request(r)
    toks = {r.request_id: [] for r in reqs}
    lps = {r.request_id: [] for r in reqs}
    for _ in range(200):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            toks[rid] += out.token_ids
            lps[rid] += out.log_probs
    assert all(len(v) == 5 for v in toks.values())
    ref = _reference()
    diffs = []
    for r in reqs:
        seq = r.token_ids + toks[r.request_id]
        at = list(range(len(r.token_ids) - 1, len(seq) - 1))
        logits = ref.logits_at(core.runner.params, TINY, seq[:-1], at)
        lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        diffs += [abs(float(lp[j, t]) - lps[r.request_id][j])
                  for j, t in enumerate(toks[r.request_id])]
    # bf16 through eight layers against float32: a few hundredths; a wrong
    # mask, position, window or expert is off by tenths to whole units at
    # every position.
    assert float(np.median(diffs)) < 0.05
    after = get_sched_ledger().snapshot()
    d = {k: after[k] - before[k] for k in after
         if k.startswith(("moe_", "kv_blocks_"))}
    routed = 7
    assert d["moe_layer_steps_total"] > 0
    assert d["moe_layer_steps_total"] % routed == 0
    # 4 of 16 experts held, 4 chosen a token: about one row a live token a
    # routed layer, and no more experts touched than are held
    assert 0 < d["moe_rows_total"] <= 4 * 255 * routed
    assert 0 < d["moe_experts_touched_total"] <= 4 * d["moe_layer_steps_total"]
    assert d["moe_largest_group_total"] <= d["moe_rows_total"]
    # six of the eight layers slide (window 40): they walk less than they hold
    assert d["kv_blocks_live_total"] * 2 < d["kv_blocks_walked_total"] \
        < d["kv_blocks_live_total"] * 8
    assert core.metrics.snapshot(core.sched, core.pool)["moe"][
        "experts_held"] == 4


def test_engine_refuses_what_it_cannot_run(tmp_path):
    from dynamo_tpu.engine.engine import EngineCore

    with pytest.raises(ValueError, match="share"):
        EngineCore(_engine_config(tmp_path, ep=2))
    with pytest.raises(ValueError, match="pipeline"):
        EngineCore(_engine_config(tmp_path, pp=2))
    (tmp_path / "config.json").write_text(json.dumps(
        {**TINY, "num_experts": 6}))
    from dynamo_tpu.utils.config import EngineConfig

    with pytest.raises(ValueError, match="divide"):
        EngineCore(EngineConfig(model=str(tmp_path),
                                allow_random_weights=True, num_blocks=64))


def test_kv_blocks_walked_by_hand():
    from dynamo_tpu.obs.compile_ledger import BucketSig
    from dynamo_tpu.obs.sched_ledger import step_counts

    # one decode row at position 700 (45 blocks of 16) and a 512 chunk at 300
    sig = BucketSig("mixed", 8, 512, 512, True, "bfloat16")
    batches = [(sig, [(None, 700, 1), (None, 300, 512)], None, None, None)]
    count = lambda windows, key: step_counts(batches, 16, windows)[key]
    assert count([0], "kv_blocks_live") == 44 + 51
    # full layer: what is held. Window 128: the decode row walks from the
    # block of position 573 (35) to 43, nine blocks; the chunk from the
    # block of position 173 (10) to 50, 41 blocks.
    assert count([0], "kv_blocks_walked") == 44 + 51
    assert count([128], "kv_blocks_walked") == 9 + 41
    assert count([128, 0, 128], "kv_blocks_walked") == 2 * 50 + 95
    assert count([128, 0, 128], "kv_blocks_live") == 44 + 51


# ---------------------------------------------------------------------------
# what the configuration's keys resolve to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mistral-7b-v0.3-l16", "mistral-nemo-12b-l10",
                                  "mistral-nemo-12b-tp4"])
def test_a_dense_configuration_resolves_to_what_it_did(name):
    """None of the new fields reads anything from a dense model's keys
    (``sliding_window: null``): one scan of identical full layers."""
    cfg = ModelConfig.from_hf_config(str(CONFIG_DIR.parent / name))
    plain = ModelConfig(
        name=cfg.name, vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_layers=cfg.num_layers,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_position_embeddings,
        tie_word_embeddings=cfg.tie_word_embeddings)
    assert cfg == plain
    assert _period_windows(cfg) == (0,) and not cfg.holds_share
    assert cfg.rope_theta == 1000000.0 and isinstance(cfg.rope_theta, float)


def test_the_published_configuration_resolves():
    """The catalog's keys, the cell's cut and the published depth."""
    model = json.loads((CONFIG_DIR / "config.json").read_text())
    cfg = ModelConfig.from_hf_config(str(CONFIG_DIR))
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size) == (
        6144, 64, 8, 128, 18432, 2048)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == (16, 128, 8, 1)
    assert cfg.holds_share and cfg.first_k_dense == 1 and cfg.num_layers == 5
    assert (cfg.router_scoring, cfg.router_bias, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, True, 2.5)
    assert _period_windows(cfg) == (128, 128, 0, 128)
    assert [cfg.window_of(i) for i in range(5)] == [128, 128, 128, 0, 128]
    assert (cfg.qk_norm, cfg.rope_scope, cfg.norm_placement) == (
        True, "sliding", "post")
    assert cfg.rope_theta == 1e6 and cfg.vocab_size == 19200
    # the published depth: layer 0, eleven periods and three layers more
    assert len(model["layer_types"]) == 48
    deep = dataclasses.replace(
        cfg, num_layers=48, layer_types=tuple(model["layer_types"]))
    assert _period_windows(deep) == (128, 128, 0, 128)
    assert (48 - 1) // 4 == 11 and (48 - 1) % 4 == 3
    # the parameter tree: a leading group beside the repeated one
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    stacks = llama.layer_stacks(shapes["layers"])
    lead, rep = stacks["lead"], stacks["rep"]
    assert lead["w_gate"].shape == (1, 6144, 18432)
    assert rep["w_gate"].shape == (4, 16, 6144, 2048)
    assert rep["router"].shape == (4, 6144, 128)
    assert rep["router_bias"].shape == (4, 128)
    assert rep["q_norm"].shape == (4, 128) and lead["wq"].shape == (1, 6144, 8192)
    assert shapes["lm_head"].shape == (6144, 19200)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    about = json.loads((CONFIG_DIR / "about.json").read_text())
    # norms and the bias beside the matrices that ``sizes`` counts
    assert 0 <= n - about["sizes"]["params_total"] < 100_000
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes)
