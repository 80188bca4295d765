"""Falcon-H1's block (Falcon-H1-34B-Instruct) at a small size on the CPU,
against the plain reference the benchmark's configuration brings
(``chipbench/configs/falcon-h1-34b-l6/reference.py``): in every layer
attention and a Mamba-2 mixer side by side under one norm and one add, then
a gated MLP; keys and values and recurrent state in every layer; the
published muP multipliers on the activations.

Float32 with seeded random weights wherever logits are compared. The
reference runs the recurrence token by token and every multiplier on its
activation; the program a blocked scan over chunks and a one-token update
through the state pool.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mamba
from dynamo_tpu.models.config import ModelConfig, resolve_model_config

ROOT = Path(__file__).resolve().parents[1]
CONFIG_DIR = ROOT / "chipbench" / "configs" / "falcon-h1-34b-l6"

# The language model's settings as Falcon-H1-34B-Instruct publishes them
# (https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json).
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}

# The same keys at CPU size: every multiplier as published but
# ``attention_in_multiplier``, which the 34B publishes as 1 (leaving it out
# could not be seen); 5 query heads a KV head as published; a block of the
# scan is 8 positions.
TINY = {
    **PUBLISHED, "num_hidden_layers": 3, "hidden_size": 64,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 96, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_ssm": 64, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_chunk_size": 8, "vocab_size": 128, "rope_theta": 10000,
    "attention_in_multiplier": 0.5,
}

# float32 against float32 over three layers of three mixers, sums in other
# orders (a blocked scan against a recurrence, an online softmax, a
# multiplier before or behind a rounding): 2e-6 of unit-scale logits read
# here. bf16 anywhere reads 1e-2 and more.
LOGIT_TOL = 1e-4
BS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "falcon_h1_reference", CONFIG_DIR / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(tmp_path, **over) -> tuple[ModelConfig, dict]:
    model = {**TINY, **over}
    (tmp_path / "config.json").write_text(json.dumps(model))
    cfg = ModelConfig.from_hf_config(str(tmp_path))
    return dataclasses.replace(cfg, dtype="float32"), model


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cfg, model = _config(tmp_path_factory.mktemp("falcon_h1"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


def _serve(cfg, params, tokens, cuts, *, slot=1, ssm=None, slots=3,
           attn_impl="dense"):
    """Logits [len(tokens), vocab] as a step computes them: the sequence
    in the chunks ``cuts`` (a chunk of one token is the decode program's
    shape), through a paged KV cache and row ``slot`` of a state pool, both
    of every layer, one row of a batch of two (the other is padding and
    names the trash row). Returns (logits, the pool)."""
    n = len(tokens)
    assert sum(cuts) == n
    nblk = -(-n // BS)
    shape = (cfg.attn_layers, nblk + 2, BS, cfg.num_kv_heads, cfg.head_dim)
    ck = jnp.zeros(shape, jnp.float32)
    cv = jnp.zeros(shape, jnp.float32)
    if ssm is None:
        ssm = mamba.zeros_state(cfg, slots)
    bt = jnp.zeros((2, nblk), jnp.int32).at[0].set(jnp.arange(1, nblk + 1))
    rows = jnp.asarray([slot, slots], jnp.int32)

    @jax.jit     # one program a chunk width, as a step is
    def step(ids, start, length, ck, cv, ssm):
        hid, ck, cv, ssm = llama.forward(
            params, cfg, ids, start, length, bt, ck, cv,
            attn_impl=attn_impl, return_all_hidden=True, ssm=ssm,
            ssm_slots=rows)
        return llama.logits_from_hidden(params, cfg, hid[0]), ck, cv, ssm

    out, start = [], 0
    for length in cuts:
        t = 1 if length == 1 else max(cuts)
        ids = np.zeros((2, t), np.int32)
        ids[0, :length] = tokens[start:start + length]
        logits, ck, cv, ssm = step(
            jnp.asarray(ids), jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32), ck, cv, ssm)
        out.append(np.asarray(logits[:length]))
        start += length
    return np.concatenate(out), ssm


# ---------------------------------------------------------------------------
# the configuration and its adapter
# ---------------------------------------------------------------------------

def test_the_published_config_resolves_to_the_plan(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps(PUBLISHED))
    cfg = resolve_model_config(str(tmp_path))
    assert cfg.layer_plan.split == (0, 1, 72, 0)
    kinds = [(m.kind, m.stack, m.place, m.layer, m.joined)
             for m in cfg.layer_plan.layers[7]]
    assert kinds == [("*", "rep", 7, 7, False), ("M", "rep", 7, 7, True),
                     ("-", "rep", 7, 7, False)]
    assert cfg.has_ssm and not cfg.hybrid_pattern
    assert (cfg.attn_layers, cfg.layers_of("M"), cfg.layers_of("-")) == (72,) * 3
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (4096, 5120)
    assert cfg.ssm_inner + cfg.ssm_conv_dim + cfg.mamba_num_heads == 9248
    columns = cfg.ssm_column_multipliers
    assert columns.shape == (9248,) and columns.dtype == np.float32
    edges = (0, 4096, 8192, 8704, 9216, 9248)        # z | x | B | C | dt
    for lo, hi, m in zip(edges, edges[1:], PUBLISHED["ssm_multipliers"]):
        assert (columns[lo:hi] == np.float32(m)).all()
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 4, 128)
    assert (cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_chunk) == (2, 256, 128)
    assert cfg.rope_theta == 1e11 and cfg.rope_scope == "all"
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 261120
    for key in ("embedding_multiplier", "lm_head_multiplier", "key_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier"):
        assert getattr(cfg, key) == PUBLISHED[key]
    assert cfg.ssm_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(PUBLISHED["mlp_multipliers"])


def test_the_benchmarks_cut_differs_in_depth_alone():
    """Every key of the source but the depth, to the digit, and no key
    beside them."""
    cut = json.loads((CONFIG_DIR / "config.json").read_text())
    assert {k for k in PUBLISHED if cut.get(k) != PUBLISHED[k]} == {
        "num_hidden_layers"}
    assert set(cut) == set(PUBLISHED)
    cfg = resolve_model_config(str(CONFIG_DIR))
    assert cfg.layer_plan.split == (0, 1, 6, 0)
    # a slot-layer of the pool: the float32 state and the bf16 tail
    assert mamba.slot_layer_bytes(cfg) == 32 * 128 * 256 * 4 + 3 * 5120 * 2 \
        == 4225024
    assert mamba.state_shapes(cfg, 64)["state"].shape == (6, 65, 32, 128, 256)
    # the parameters the equations imply (about.json)
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes["layers"].items()}
    assert "ssm_norm" not in sizes
    assert sum(sizes.values()) / 6 == pytest.approx(430.1e6, rel=1e-3)
    assert sizes["ssm_in"] // 6 == 5120 * 9248
    total = sum(sizes.values()) + 2 * 261120 * 5120 + 5120
    assert total == pytest.approx(5.255e9, rel=1e-3)


@pytest.mark.parametrize("key, value, says", [
    ("attention_bias", True, "attention_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("projectors_bias", True, "projectors_bias"),
    ("attn_layer_indices", [0, 2], "attn_layer_indices"),
    ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling"),
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate"),
    ("mamba_rms_norm", False, "mamba_rms_norm"),
    ("mamba_use_mlp", False, "mamba_use_mlp"),
    ("hidden_act", "gelu", "hidden_act"),
    ("mamba_d_ssm", 128, "mamba_d_ssm"),
    ("ssm_multipliers", [0.5, 0.5], "ssm_multipliers"),
    # the key of the families that mix dense and sparse MLPs: no such layer
    ("mlp_layer_types", ["dense", "sparse", "dense"], "mlp_layer_types"),
])
def test_the_adapter_refuses_by_key(tmp_path, key, value, says):
    with pytest.raises(ValueError, match=says):
        _config(tmp_path, **{key: value})


def test_a_config_of_another_family_passes_the_adapter_untouched():
    from dynamo_tpu.models.config import _falcon_h1_keys

    other = {"model_type": "llama", "embedding_multiplier": 12.0}
    assert _falcon_h1_keys(other) is other
    assert ModelConfig().embedding_multiplier == 1.0
    assert ModelConfig().init_gain == {}


def test_seeded_init_undoes_the_multipliers(tiny):
    """Each matrix is drawn at fan-in scale over the multipliers on its
    product, so a branch's output is unit scale, as in a model without
    multipliers, and the logits are: what lets the comparison see a branch
    (at plain fan-in scale the head's 1/128 leaves every logit within 0.01
    of 0)."""
    cfg, model, params = tiny
    layers = params["layers"]
    std = lambda a: float(np.asarray(a, np.float32).std())
    h = cfg.hidden_size
    assert std(layers["wk"]) == pytest.approx(
        h ** -0.5 / (0.5 * PUBLISHED["key_multiplier"]), rel=0.05)
    assert std(layers["wq"]) == pytest.approx(h ** -0.5 / 0.5, rel=0.05)
    assert std(layers["w_up"]) == pytest.approx(h ** -0.5, rel=0.05)
    assert std(params["lm_head"]) == pytest.approx(h ** -0.5 * 128, rel=0.05)
    d = cfg.ssm_inner
    for (lo, hi), m in zip([(0, d), (d, 2 * d)], PUBLISHED["ssm_multipliers"]):
        assert std(layers["ssm_in"][..., lo:hi]) == pytest.approx(
            h ** -0.5 / (0.25 * m), rel=0.05)
    assert (np.asarray(layers["ssm_D"]) == 1).all()
    tokens = np.random.default_rng(1).integers(0, 128, 24).tolist()
    logits = _reference().logits_at(params, model, tokens, list(range(24)))
    assert 0.5 < logits.std() < 2.0


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through both caches
# ---------------------------------------------------------------------------

N_TOKENS = 61     # no multiple of the scan's block (8) or of a chunk (16)
CUTS = {
    "one_chunk_then_decode": [32] + [1] * 29,
    "chunks_of_16_a_tail_and_decode": [16, 16, 16, 7] + [1] * 6,
    "chunks_of_13": [13, 13, 13, 13, 9],
    "token_by_token": [1] * 61,
}


@pytest.fixture(scope="module")
def served(tiny):
    cfg, model, params = tiny
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, N_TOKENS).tolist()
    ref = _reference().logits_at(params, model, tokens, list(range(N_TOKENS)))
    return tokens, ref


@pytest.mark.parametrize("attn_impl", ["dense", "pallas_interpret"])
@pytest.mark.parametrize("cuts", sorted(CUTS))
def test_prefill_then_decode_matches_the_reference(tiny, served, cuts,
                                                   attn_impl):
    """The reference's full forward pass against the step's, whatever the
    chunk boundaries (every cut crosses a block of the scan, all but the
    first a chunk boundary): keys and values and the state are carried from
    chunk to chunk and from the last chunk into decode, in every layer.
    Under "pallas_interpret" the one-token update is the kernel's
    (ops/ssm_update.py), interpreted, beside the attention kernel."""
    cfg, _model, params = tiny
    tokens, ref = served
    if attn_impl != "dense" and cuts == "token_by_token":
        pytest.skip("61 interpreted steps: the other cuts hold the kernel")
    got, _ = _serve(cfg, params, tokens, CUTS[cuts], attn_impl=attn_impl)
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) < LOGIT_TOL


def test_bf16_fails_the_tolerance(tiny, served):
    cfg, _model, params = tiny
    tokens, ref = served
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim > 2 else a, params)
    got, _ = _serve(cfg, low, tokens, CUTS["chunks_of_13"])
    assert np.max(np.abs(got - ref)) > 20 * LOGIT_TOL


def _without(cfg: ModelConfig, scalar: str) -> ModelConfig:
    """``cfg`` with one published scalar left out (1 in its place):
    ``key`` or ``key[i]``."""
    key, _, index = scalar.partition("[")
    if not index:
        return dataclasses.replace(cfg, **{key: 1.0})
    values = list(getattr(cfg, key))
    values[int(index[:-1])] = 1.0
    return dataclasses.replace(cfg, **{key: tuple(values)})


def _in_sequence(cfg: ModelConfig, params):
    """The two branches one after the other, each under the same norm's
    weights: ``h + a(norm(h))`` and then ``h + s(norm(h))`` of the new h."""
    plan = cfg.layer_plan
    wrong = dataclasses.replace(cfg)
    wrong.__dict__["layer_plan"] = plan._replace(layers=tuple(
        tuple(m._replace(joined=False) for m in layer)
        for layer in plan.layers))
    layers = {**params["layers"], "ssm_norm": params["layers"]["attn_norm"]}
    return wrong, {**params, "layers": layers}


SCALARS = ["embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "key_multiplier",
           "attention_out_multiplier", "ssm_in_multiplier",
           "ssm_out_multiplier", "mlp_multipliers[0]", "mlp_multipliers[1]",
           *(f"ssm_multipliers[{i}]" for i in range(5))]


@pytest.mark.parametrize("control", SCALARS + [
    "branches_in_sequence", "gate_after_the_norm",
    "state_not_carried_across_a_chunk_boundary"])
def test_a_control_fails_the_comparison(tiny, served, control, monkeypatch):
    """What the comparison has to see, each held to the tolerance the parity
    test passes (``LOGIT_TOL``, with two hundred times of room: a scalar
    left out moves a branch by a factor of 2 to 128, and the seeded init
    makes every branch a unit-scale part of the residual): each published
    scalar left out of the program (``key_multiplier`` left out is "k not
    scaled"); the two branches run in sequence instead of side by side; the
    reference gating after the group norm where the program gates before;
    a chunk that does not start a prompt starting its state from zeros."""
    cfg, model, params = tiny
    tokens, ref = served
    if control in SCALARS:
        cfg = _without(cfg, control)
    elif control == "branches_in_sequence":
        cfg, params = _in_sequence(cfg, params)
    elif control == "gate_after_the_norm":
        ref = _reference().logits_at(
            params, {**model, "mamba_norm_before_gate": True}, tokens,
            list(range(N_TOKENS)))
    else:
        real = mamba.mixer

        def broken(*args, lay, q_start, **kw):
            if lay.t > 1:
                q_start = jnp.zeros_like(q_start)
            return real(*args, lay=lay, q_start=q_start, **kw)

        monkeypatch.setattr(mamba, "mixer", broken)
    got, _ = _serve(cfg, params, tokens, CUTS["chunks_of_13"])
    if control.startswith("state_not_carried"):
        assert np.max(np.abs(got[:13] - ref[:13])) < LOGIT_TOL  # first chunk
        got, ref = got[13:], ref[13:]
    assert np.max(np.abs(got - ref)) > 200 * LOGIT_TOL


@pytest.mark.parametrize("attn_impl, cuts", [
    ("dense", [16, 4]), ("pallas_interpret", [16, 4]),
    ("pallas_interpret", [16, 1, 1])])
def test_a_padded_row_touches_the_trash_row_alone(tiny, served, attn_impl,
                                                  cuts):
    """Of the pool a step changes its live rows' slots and, for its padded
    rows, the trash row and nothing else; and of the trash row the
    convolution tail alone, in every layer."""
    cfg, _model, params = tiny
    tokens, _ref = served
    pool = jax.tree.map(lambda a: a + 7.0, mamba.zeros_state(cfg, 3))
    _, after = _serve(cfg, params, tokens[:sum(cuts)], cuts, slot=1, ssm=pool,
                      attn_impl=attn_impl)
    for leaf in ("state", "conv"):
        a = np.asarray(after[leaf])
        assert a.shape[0] == cfg.num_layers
        assert (a[:, [0, 2]] == 7.0).all()          # other sequences' rows
        assert not any((a[layer, 1] == 7.0).all()
                       for layer in range(cfg.num_layers))   # the live row's
    assert (np.asarray(after["state"])[:, 3] == 7.0).all()     # the trash row


@pytest.mark.parametrize("b, ones", [(4, (0, 2, 3)), (8, ()), (2, (1,))])
def test_the_update_kernel_at_the_published_head_shape(b, ones):
    """``[32, 128, 256]`` cut in heads alone (4 heads in 2 groups, head size
    128, state 256: a group's spread rows are 256 lanes, the state's tiles
    two lanes wide): the kernel, interpreted, against ``jax.numpy``'s
    gather, update and scatter, on the rows marked and no other."""
    rng = np.random.default_rng(b)
    m, slots, h, p, n, g = 2, 9, 4, 128, 256, 2
    pool = jnp.asarray(rng.standard_normal((m, slots + 1, h, p, n)),
                       jnp.float32)
    slot_of = jnp.asarray(rng.permutation(slots)[:b], jnp.int32)
    one = jnp.zeros((b,), bool).at[jnp.asarray(ones, jnp.int32)].set(True)
    a = jnp.asarray(rng.uniform(0.2, 1.0, (b, h)), jnp.float32)
    dx = jnp.asarray(rng.standard_normal((b, h, p)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, g, n)), jnp.float32)
    got, y = mamba._update_rows(pool, 1, slot_of, one, a, dx, bm, cm,
                                "pallas_interpret")
    keep = one[:, None]
    want, y_want = mamba._update_rows(
        pool, 1, slot_of, one, jnp.where(keep, a, 1.0),
        jnp.where(keep[..., None], dx, 0.0), bm, cm, "jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.where(keep[..., None], y_want, 0.0)),
        atol=2e-4, rtol=1e-5)


def test_the_update_kernel_asks_for_vmem_by_the_slot_layer():
    """The shape the kernel was written at keeps its 16 MiB, letter for
    letter; a 4 MiB slot-layer gets twice that."""
    from dynamo_tpu.ops import ssm_update

    assert ssm_update.vmem_limit_bytes(64, 64, 128) == \
        ssm_update.VMEM_LIMIT_BYTES == 16 * 2**20
    assert ssm_update.vmem_limit_bytes(32, 128, 256) == 32 * 2**20
    assert ssm_update.vmem_limit_bytes(4, 8, 16) == 16 * 2**20


# ---------------------------------------------------------------------------
# the normal path: AsyncJaxEngine.generate
# ---------------------------------------------------------------------------

def _engine_config(tmp_path, **kw):
    from dynamo_tpu.utils.config import EngineConfig

    (tmp_path / "config.json").write_text(json.dumps(TINY))
    base = dict(num_blocks=160, max_batch_size=4, max_model_len=512,
                prefill_chunk=32, decode_bucket=(2, 4))
    return EngineConfig(model=str(tmp_path), allow_random_weights=True,
                        **{**base, **kw})


def _float32_core(tmp_path, monkeypatch, **kw):
    """An ``EngineCore`` over the tiny configuration computing in float32
    (the configuration's dtype is bf16 on every real path): the logprobs it
    reports are then the reference's to rounding."""
    from dynamo_tpu.engine import engine as eng

    resolve = eng.resolve_model_config
    monkeypatch.setattr(
        eng, "resolve_model_config",
        lambda path: dataclasses.replace(resolve(path), dtype="float32"))
    return eng.EngineCore(_engine_config(tmp_path, **kw))


def _request(tokens, max_tokens):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


def _against_the_reference(params, req, toks, lps):
    """(the largest logprob difference, the largest gap between the
    reference's best logit and the chosen token's) over a request's
    generated tokens."""
    seq = req.token_ids + toks
    at = list(range(len(req.token_ids) - 1, len(seq) - 1))
    logits = _reference().logits_at(params, TINY, seq[:-1], at)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    return (max(abs(float(lp[j, t]) - lps[j]) for j, t in enumerate(toks)),
            max(float(logits[j].max() - logits[j, t])
                for j, t in enumerate(toks)))


def _generate_all(core, reqs):
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    async def run():
        engine = AsyncJaxEngine(core)

        async def one(r):
            toks, lps = [], []
            async for out in engine.generate(r):
                toks += out.token_ids
                lps += out.log_probs
            return toks, lps

        try:
            return await asyncio.gather(*(one(r) for r in reqs))
        finally:
            await engine.shutdown()

    return asyncio.run(run())


def test_generate_matches_the_reference_and_counts(tmp_path, monkeypatch):
    """Through ``AsyncJaxEngine.generate`` with the scheduler, the pools and
    the lattice as any model: four requests at once (a mixed step holds
    rows of different lengths, prompts of one to four chunks). The greedy
    tokens are the reference's best and their logprobs the reference's;
    both pools have every layer; the counts and the shapes ``stats()``
    states."""
    from dynamo_tpu.obs.sched_ledger import get_sched_ledger

    core = _float32_core(tmp_path, monkeypatch)
    assert core.runner.ssm is not None
    assert core.runner.spec.num_layers == 3        # K and V in every layer
    assert core.runner.ssm["state"].shape == (3, 5, 8, 8, 16)   # and state
    assert core.pool.enable_prefix_caching is False
    before = get_sched_ledger().snapshot()
    rng = np.random.default_rng(11)
    reqs = [_request(rng.integers(0, 128, n).tolist(), 6)
            for n in (100, 20, 70, 33)]
    outs = _generate_all(core, reqs)
    params = core.runner.params
    for r, (toks, lps) in zip(reqs, outs):
        assert len(toks) == 6
        d_lp, d_arg = _against_the_reference(params, r, toks, lps)
        assert d_lp < 1e-3 and d_arg < 1e-3
    after = get_sched_ledger().snapshot()
    d = {k: after[k] - before[k] for k in after if k.startswith("ssm_")}
    assert d["ssm_layer_steps_total"] > 0 and d["ssm_layer_steps_total"] % 3 == 0
    assert 223 + 4 * 5 <= d["ssm_live_tokens_total"] <= 223 + 4 * 6
    stats = core.metrics.snapshot(core.sched, core.pool)
    ssm = stats["ssm"]
    assert ssm["layers"] == 3 and ssm["slots"] == 4
    assert ssm["shapes"]["state"] == [3, 5, 8, 8, 16]
    assert (ssm["heads"], ssm["head_dim"], ssm["state_size"], ssm["groups"],
            ssm["conv_dim"]) == (8, 8, 16, 2, 128)
    # every sequence ended: none of the pool's rows is held
    assert ssm["slots_in_use"] == 0
    shapes = stats["step_shapes"]
    mixer = 64 * (64 + 128 + 8) + 64 * 64
    assert shapes["layers"] == shapes["dense_ffn_layers"] == 3
    assert shapes["ssm_layers"] == 3 and shapes["ssm_params"] == mixer
    # the MLP's three matrices and the mixer's two: every matrix a step reads
    assert shapes["dense_ffn_params"] == 3 * 64 * 96 + mixer
    assert shapes["attn_params"] == 2 * 64 * 80 + 2 * 64 * 16


def test_a_reused_slot_starts_from_zeros(tmp_path, monkeypatch):
    """One slot: the second sequence takes the row the first left its state
    in, and no host call cleared it. Its first chunk starts at 0, so the
    program starts it from zeros."""
    core = _float32_core(tmp_path, monkeypatch, max_batch_size=1,
                         decode_bucket=(1,))
    rng = np.random.default_rng(3)
    first = _request(rng.integers(0, 128, 50).tolist(), 8)
    second = _request(rng.integers(0, 128, 41).tolist(), 8)
    _generate_all(core, [first])
    left = np.asarray(core.runner.ssm["state"][:, 0])
    assert all(np.abs(left[layer]).max() > 0 for layer in range(3))
    (toks2, lps2), = _generate_all(core, [second])
    assert max(_against_the_reference(
        core.runner.params, second, toks2, lps2)) < 1e-3


def test_a_preempted_sequence_recomputes_to_the_same_logits(tmp_path,
                                                            monkeypatch):
    """A pool too small for three long outputs at once: one is preempted,
    loses its blocks and its slot, and is recomputed from its first token.
    Every sequence's logprobs are the reference's all the same."""
    core = _float32_core(tmp_path, monkeypatch, num_blocks=14)
    rng = np.random.default_rng(8)
    reqs = [_request(rng.integers(0, 128, n).tolist(), 40)
            for n in (30, 28, 26)]
    outs = _generate_all(core, reqs)
    assert core.sched.preemption_count > 0
    for r, (toks, lps) in zip(reqs, outs):
        assert len(toks) == 40
        assert max(_against_the_reference(
            core.runner.params, r, toks, lps)) < 1e-3


REFUSED = {
    "spec_ngram": dict(spec_ngram=2),
    "tp": dict(tp=2), "pp": dict(pp=3), "sp": dict(sp=2), "ep": dict(ep=2),
    "kv_dtype": dict(kv_dtype="int8"),
    "host_kv_blocks": dict(host_kv_blocks=8),
    "stream_ckpt_blocks": dict(stream_ckpt_blocks=2),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_engine_refuses_what_cannot_resume_or_shard_the_state(tmp_path, option):
    """By the plan (this model has no pattern string): its layers are
    identical and three divide over three stages, so nothing but the
    recurrent state refuses pp."""
    from dynamo_tpu.engine.engine import EngineCore

    with pytest.raises(ValueError, match="recurrent layers"):
        EngineCore(_engine_config(tmp_path, **REFUSED[option]))


def test_paths_that_move_blocks_alone_refuse_or_recompute(tmp_path):
    """Prefix matching gives nothing and commits nothing; session retention
    falls back to recomputing the prompt (no store); the disaggregated
    transfer's operations refuse by name."""
    from dynamo_tpu.engine.engine import EngineCore

    core = EngineCore(_engine_config(tmp_path, session_ttl=30.0))
    assert core.engine_cfg.enable_prefix_caching is False
    assert core.sessions is None
    core.pool.commit(3, 12345)
    assert core.pool.match_prefix([12345]) == []
    for op, args in (("export_blocks", ([1],)), ("import_blocks", ([],)),
                     ("stage_export", ("x", [1])),
                     ("stream_begin", ("x", "r", [1])),
                     ("prefetch_remote", ({"xfer_id": "x"},)),
                     ("import_remote", ({"xfer_id": "x"},))):
        with pytest.raises(ValueError, match="recurrent layers"):
            getattr(core, op)(*args)
