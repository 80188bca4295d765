"""Phi-4-mini-flash-reasoning (SambaY with differential attention) at a small
size on the CPU, against the plain reference the benchmark's configuration
brings (``chipbench/configs/phi-4-mini-flash-reasoning/reference.py``): a
self-decoder of Mamba-1 mixers and windowed differential attention by turns,
one more Mamba-1 mixer whose scan output is the step's memory, one full
attention layer, then a cross-decoder of gated memory units and attention
that rereads the full layer's keys and values.

The tiny preset has the plan of the published model at 8 layers (M W M W,
M, F, G X) and a window shorter than the prompts. Float32 with seeded random
weights wherever logits are compared. The reference runs the recurrence
token by token, the two softmaxes and their difference as the equations
have them, and every layer over every token; the program the pair view of
the paged kernel, one selective-scan kernel through the state pool, and the
cross-decoder over each row's last token.
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
CONFIG_DIR = ROOT / "chipbench" / "configs" / "phi-4-mini-flash-reasoning"

# The language model's settings as the catalog's row gives them
# (https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
# ... and what the configuration class defaults to, each a key of the
# benchmark's config.json and an entry of about.json's ``assumed``
ASSUMED = {
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 160, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": True, "rope_scope": "none",
    "ssm_state_dtype": "float32",
}

# The same keys at CPU size: 8 layers (M W M W, M, F, G X), two Q heads a
# KV head as published, a window of 12 under prompts of 45, d = 128. Six KV
# heads are three pairs, which no tile takes whole: the cache holds them as
# one head of 48, as the published ten pairs are two heads of 640 (a head
# size of its own key, where the family's is hidden_size / heads).
TINY = {
    **PUBLISHED, **ASSUMED, "num_hidden_layers": 8, "hidden_size": 64,
    "num_attention_heads": 12, "num_key_value_heads": 6, "head_dim": 8,
    "intermediate_size": 96, "sliding_window": 12, "vocab_size": 128,
    "mamba_dt_rank": 4, "max_position_embeddings": 4096,
}

# float32 against float32 over eight layers, sums in other orders (an online
# softmax over pairs of heads against two plain ones, a kernel's recurrence
# against a scan): 1e-5 of unit-scale logits read here. bf16 anywhere reads
# 1e-2 and more.
LOGIT_TOL = 1e-4
BS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "phi4_flash_reference", CONFIG_DIR / "reference.py")
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
    cfg, model = _config(tmp_path_factory.mktemp("phi4_flash"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


def _serve(cfg, params, tokens, cuts, *, slot=1, ssm=None, slots=3,
           attn_impl="dense", every_position=True):
    """Logits as a step computes them: the sequence in the chunks ``cuts``
    (a chunk of one token is the decode program's shape), through a paged KV
    cache of the writing attention layers and row ``slot`` of a state pool,
    one row of a batch of two (the other is padding and names the trash
    row). ``every_position``: logits at every token (the cross-decoder over
    every token, ``return_all_hidden``), else at each chunk's last token
    (the cross-decoder over the rows' last tokens, what a step serves).
    Returns (logits, the pool)."""
    n = len(tokens)
    assert sum(cuts) == n
    nblk = -(-n // BS)
    shape = (cfg.attn_layers, nblk + 2, BS, cfg.cache_kv_heads,
             cfg.cache_head_dim)
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
            attn_impl=attn_impl, return_all_hidden=every_position, ssm=ssm,
            ssm_slots=rows)
        return llama.logits_from_hidden(
            params, cfg, hid[0] if every_position else hid[:1]), ck, cv, ssm

    out, start = [], 0
    for length in cuts:
        t = 1 if length == 1 else max(cuts)
        ids = np.zeros((2, t), np.int32)
        ids[0, :length] = tokens[start:start + length]
        logits, ck, cv, ssm = step(
            jnp.asarray(ids), jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32), ck, cv, ssm)
        out.append(np.asarray(logits[:length] if every_position else logits))
        start += length
    return np.concatenate(out), ssm


# ---------------------------------------------------------------------------
# the configuration and its adapter
# ---------------------------------------------------------------------------

def test_the_benchmarks_configuration_is_the_source_whole():
    """Every key of the catalog's row to the digit, the assumed ones beside
    them and no other; nothing is cut."""
    stated = json.loads((CONFIG_DIR / "config.json").read_text())
    assert stated == {**PUBLISHED, **ASSUMED}
    about = json.loads((CONFIG_DIR / "about.json").read_text())
    assert about["reduced"] == {} and set(ASSUMED) <= set(about["assumed"])
    cfg = resolve_model_config(str(CONFIG_DIR))
    assert cfg.layer_plan.split == (0, 2, 8, 2, 2, 7, 0)
    assert cfg.layer_plan.last_from == 18
    kinds = "".join(layer[0].kind for layer in cfg.layer_plan.layers)
    assert kinds == "S*" * 9 + "GX" * 7
    assert all(layer[1].kind == "-" for layer in cfg.layer_plan.layers)
    assert cfg.layer_plan.layers[16][0].keeps
    assert [m.window for m, _ in cfg.layer_plan.layers if m.kind == "*"] \
        == [512] * 8 + [0]
    # the cross layers reread layer 17's keys and values: the cache's ninth
    assert {m.layer for m, _ in cfg.layer_plan.layers if m.kind == "X"} == {8}
    assert (cfg.attn_layers, cfg.layers_of("X"), cfg.layers_of("S"),
            cfg.layers_of("G")) == (9, 7, 9, 7)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (40, 20, 64)
    # ten pairs of KV heads lie in the cache as two heads of five pairs
    assert (cfg.cache_kv_heads, cfg.cache_head_dim) == (2, 640)
    assert (cfg.ssm_inner, cfg.ssm_state_size, cfg.mamba_dt_rank,
            cfg.conv_kernel) == (5120, 16, 160, 4)
    assert cfg.rope_scope == "none" and cfg.tie_word_embeddings
    assert cfg.norm_kind == "layer" and cfg.rms_norm_eps == 1e-5
    # the pools: 46,080 B of keys and values a token, 3,225,600 B a sequence
    from dynamo_tpu.engine.cache import KVCacheSpec

    spec = KVCacheSpec.for_model(cfg, 128, 16)
    assert spec.shape == (9, 128, 16, 2, 640)
    assert spec.bytes_per_block() // 16 == 46080
    assert mamba.state_shapes(cfg, 64)["state"].shape == (9, 65, 16, 40, 128)
    assert mamba.slot_layer_bytes(cfg) * 9 == 3225600
    assert mamba.state_bytes(cfg, 64) == 209664000
    # the parameters the equations imply (about.json): the published 3.85 B
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == about["published"]["params_counted_from_the_equations"] \
        == 3852562944
    lam = cfg.lambda_init("*")
    assert lam[0] == pytest.approx(0.8 - 0.6 * np.exp(-0.3))        # layer 1
    assert cfg.lambda_init("X")[-1] == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 31))


@pytest.mark.parametrize("key, value", [
    ("mb_per_layer", 1), ("num_hidden_layers", 7), ("sliding_window", None),
    ("sliding_window", [512, None]), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("mlp_bias", True), ("lm_head_bias", True),
    ("attention_bias", False), ("hidden_act", "gelu"), ("rope_scope", "all"),
    ("ssm_state_dtype", "bfloat16"), ("tie_word_embeddings", False),
])
def test_the_adapter_refuses_by_key(tmp_path, key, value):
    with pytest.raises(ValueError, match=key):
        _config(tmp_path, **{key: value})


def test_a_config_of_another_family_passes_the_adapter_untouched():
    from dynamo_tpu.models.config import _phi4flash_keys

    other = {"model_type": "llama", "sliding_window": 512}
    assert _phi4flash_keys(other) is other
    plain = ModelConfig()
    assert not (plain.decoder_layout or plain.diff_attention
                or plain.attention_bias) and plain.norm_kind == "rms"
    assert plain.layer_plan.last_from is None


def test_the_seeded_logits_are_unit_scale(tiny):
    cfg, model, params = tiny
    tokens = np.random.default_rng(1).integers(0, 128, 24).tolist()
    logits = _reference().logits_at(params, model, tokens, list(range(24)))
    assert 0.5 < logits.std() < 2.0
    # padded so that lengths share a program, as the probe pads: positions
    # past the window of every live key see nothing and change nothing (the
    # first chip run read NaN here: chip call 123, PR 56)
    padded = _reference().logits_at(params, model, tokens, list(range(24)),
                                    pad_to=64)
    assert np.max(np.abs(padded - logits)) < 1e-5


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through the cache and the state pool
# ---------------------------------------------------------------------------

N_TOKENS = 45     # no multiple of a block of the scan's copies (8) or a chunk
CUTS = {
    "one_chunk_then_decode": [24] + [1] * 21,
    "chunks_of_16_a_tail_and_decode": [16, 16, 7] + [1] * 6,
    "chunks_of_13": [13, 13, 13, 6],
    "token_by_token": [1] * 45,
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
    chunk boundaries: keys and values, the recurrent state and the
    convolution's tail are carried from chunk to chunk and into decode; the
    window (12) is shorter than the context from the second chunk on. Under
    "pallas_interpret" attention is the paged kernel in the pair view and
    the recurrence the selective-scan kernel, both interpreted."""
    cfg, _model, params = tiny
    tokens, ref = served
    if attn_impl != "dense" and cuts == "token_by_token":
        pytest.skip("45 interpreted steps: the other cuts hold the kernels")
    got, _ = _serve(cfg, params, tokens, CUTS[cuts], attn_impl=attn_impl)
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) < LOGIT_TOL


@pytest.mark.parametrize("attn_impl", ["dense", "pallas_interpret"])
def test_the_cross_decoder_over_the_last_tokens_alone(tiny, served, attn_impl):
    """The control that has to pass: the cross-decoder over every token and
    over each row's last token give the same logits at the last tokens (and
    both the reference's)."""
    cfg, _model, params = tiny
    tokens, ref = served
    cuts = CUTS["chunks_of_16_a_tail_and_decode"]
    last = np.cumsum(cuts) - 1
    got, _ = _serve(cfg, params, tokens, cuts, attn_impl=attn_impl,
                    every_position=False)
    assert got.shape == (len(cuts), cfg.vocab_size)
    assert np.max(np.abs(got - ref[last])) < LOGIT_TOL
    every, _ = _serve(cfg, params, tokens, cuts, attn_impl=attn_impl)
    assert np.max(np.abs(got - every[last])) < LOGIT_TOL


def test_bf16_fails_the_tolerance(tiny, served):
    cfg, _model, params = tiny
    tokens, ref = served
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim > 2 else a, params)
    got, _ = _serve(cfg, low, tokens, CUTS["chunks_of_13"])
    assert np.max(np.abs(got - ref)) > 20 * LOGIT_TOL


# ---------------------------------------------------------------------------
# leave one out: what the comparison has to see
# ---------------------------------------------------------------------------

def _zeroed(params, *names, to=0.0):
    layers = dict(params["layers"])
    for name in names:
        layers[name] = jnp.full_like(layers[name], to)
    return {**params, "layers": layers}


def _replanned(cfg, change):
    """``cfg`` with every first mixer ``m`` of its plan replaced by
    ``change(m)`` (``layer_plan`` is a cached property: the instance's
    ``__dict__`` holds it)."""
    out = dataclasses.replace(cfg)
    plan = cfg.layer_plan
    out.__dict__["layer_plan"] = plan._replace(layers=tuple(
        (change(layer[0]), *layer[1:]) for layer in plan.layers))
    return out


ATTN_BIASES = ("bq", "bk", "bv", "bo", "x_bq", "x_bo")
NORM_BIASES = ("attn_norm_b", "x_attn_norm_b", "ssm_norm_b", "gmu_norm_b",
               "mlp_norm_b")
LAMBDAS = tuple(p + v for p in ("diff_", "x_diff_")
                for v in ("lq1", "lk1", "lq2", "lk2"))
CONTROLS = [
    "a2_dropped", "lambda_fixed_at_lambda_init", "pair_norm_weight",
    "one_minus_lambda_init", "cross_reads_another_layer",
    "window_widened_to_the_context", "memory_taken_after_the_gate", "d_x",
    "convolution_bias", "dt_bias", "layer_norm_bias", "attention_biases",
    "state_not_carried_across_a_chunk_boundary",
    "tail_not_carried_across_a_chunk_boundary",
]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_fails_the_comparison(tiny, served, control, monkeypatch):
    """Each of these left out of, or changed in, the program has to fail
    the comparison the parity test passes (``LOGIT_TOL``, with a hundred
    times of room: the seeded init draws every bias, lambda vector, norm
    weight and ``D`` with a spread, so that each is a unit-scale part of
    its branch). The zero halves of the pair view's queries are no licence
    to drop a term: the second softmax, lambda, the norm over the pair and
    ``1 - lambda_init`` are each held here."""
    cfg, _model, params = tiny
    tokens, ref = served
    if control == "a2_dropped":
        real = llama._diff_combine
        monkeypatch.setattr(
            llama, "_diff_combine", lambda cfg, lp, attn, lam: real(
                cfg, lp, attn.reshape(
                    attn.shape[0], -1, 2, attn.shape[-1]).at[:, :, 1].set(0)
                .reshape(attn.shape), lam))
    elif control == "lambda_fixed_at_lambda_init":
        params = _zeroed(params, *LAMBDAS)       # exp(0) - exp(0) + init
    elif control == "pair_norm_weight":
        params = _zeroed(params, "diff_norm", "x_diff_norm", to=1.0)
    elif control == "one_minus_lambda_init":
        real = llama._diff_combine
        monkeypatch.setattr(
            llama, "_diff_combine", lambda cfg, lp, attn, lam: real(
                cfg, lp, attn, lam) / (1.0 - lam).astype(attn.dtype))
    elif control == "cross_reads_another_layer":
        cfg = _replanned(cfg, lambda m: m._replace(layer=m.layer - 1)
                         if m.kind == "X" else m)
    elif control == "window_widened_to_the_context":
        cfg = _replanned(cfg, lambda m: m._replace(window=0))
    elif control == "memory_taken_after_the_gate":
        real = mamba.mixer1

        def gated(cfg, lp, *args, **kw):
            out, pool, y = real(cfg, lp, *args, **kw)
            u = args[1]
            z = (u @ lp["ssm_in"])[:, cfg.ssm_inner:]
            return out, pool, y * jax.nn.silu(z)

        monkeypatch.setattr(mamba, "mixer1", gated)
    elif control == "d_x":
        params = _zeroed(params, "ssm_D")
    elif control == "convolution_bias":
        params = _zeroed(params, "ssm_conv_b")
    elif control == "dt_bias":
        params = _zeroed(params, "ssm_dt_bias")
    elif control == "layer_norm_bias":
        params = _zeroed(params, *NORM_BIASES)
        params = {**params,
                  "final_norm_b": jnp.zeros_like(params["final_norm_b"])}
    elif control == "attention_biases":
        params = _zeroed(params, *ATTN_BIASES)
    else:
        # A chunk that does not start a prompt starts its state, or the
        # convolution's tail, from zeros.
        real = mamba.mixer1
        leaf = "state" if control.startswith("state") else "conv"

        def broken(cfg, lp, layer, u, ssm, *, lay, **kw):
            if lay.t == 1:
                return real(cfg, lp, layer, u, ssm, lay=lay, **kw)
            out, new, y = real(cfg, lp, layer, u, {
                **ssm, leaf: jnp.zeros_like(ssm[leaf])}, lay=lay, **kw)
            # (the other rows' and layers' part of the pool stays)
            at = (layer, kw["slots"])
            return out, {**new, leaf: ssm[leaf].at[at].set(new[leaf][at])}, y

        monkeypatch.setattr(mamba, "mixer1", broken)
    got, _ = _serve(cfg, params, tokens, CUTS["chunks_of_13"])
    if control.endswith("chunk_boundary"):
        assert np.max(np.abs(got[:13] - ref[:13])) < LOGIT_TOL  # first chunk
        got, ref = got[13:], ref[13:]
    assert np.max(np.abs(got - ref)) > 100 * LOGIT_TOL


@pytest.mark.parametrize("attn_impl, cuts", [
    ("dense", [16, 4]), ("pallas_interpret", [16, 4]),
    ("pallas_interpret", [16, 1, 1])])
def test_a_padded_row_leaves_the_pool_alone(tiny, served, attn_impl, cuts):
    """Of the pool a step changes its live rows' slots and, for its padded
    rows, the trash row's convolution tail and nothing else."""
    cfg, _model, params = tiny
    tokens, _ref = served
    pool = jax.tree.map(lambda a: a + 7.0, mamba.zeros_state(cfg, 3))
    _, after = _serve(cfg, params, tokens[:sum(cuts)], cuts, slot=1, ssm=pool,
                      attn_impl=attn_impl)
    for leaf in ("state", "conv"):
        a = np.asarray(after[leaf])
        assert a.shape[0] == cfg.layers_of("S") == 3
        assert (a[:, [0, 2]] == 7.0).all()          # other sequences' rows
        assert not any((a[layer, 1] == 7.0).all() for layer in range(3))
    assert (np.asarray(after["state"])[:, 3] == 7.0).all()     # the trash row


def test_every_position_or_a_refusal_by_name(tiny, served):
    """A caller that needs every position (``return_all_hidden``: the
    speculative verify step) gets every layer over every position."""
    cfg, _model, params = tiny
    tokens, ref = served
    got, _ = _serve(cfg, params, tokens[:24], [24])
    assert got.shape == (24, cfg.vocab_size)
    assert np.max(np.abs(got - ref[:24])) < LOGIT_TOL


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
    the lattice as any model: four requests at once (a mixed step holds rows
    of different lengths, prompts of one to four chunks, longer than the
    window). The greedy tokens are the reference's best and their logprobs
    the reference's; the KV cache has the writing attention layers alone;
    the step's counts say what ran."""
    from dynamo_tpu.obs.sched_ledger import get_sched_ledger

    core = _float32_core(tmp_path, monkeypatch)
    assert core.runner.spec.num_layers == 3         # W, W, F of 8 layers
    assert core.runner.spec.shape[3:] == (1, 48)    # three pairs, one head
    assert core.runner.ssm["state"].shape == (3, 5, 16, 1, 128)
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
    d = {k: after[k] - before[k] for k in after
         if isinstance(after[k], int) and not isinstance(after[k], bool)}
    live = d["live_tokens_total"]
    assert 223 + 4 * 5 <= live <= 223 + 4 * 6
    # the cross-decoder took one token a row a step, never a prompt's others
    rows = d["ssm_state_rows_total"] // 3
    assert d["cross_tokens_total"] == rows < live
    assert d["ssm_scan_rows_total"] == 3 * rows
    assert d["ssm_scan_positions_total"] == d["ssm_live_tokens_total"] * 3 \
        == 3 * live
    assert d["ssm_update_rows_given_total"] == 0     # no one-token kernel
    # four walks a row a step: two windowed, the full one, the cross layer's
    assert 0 < d["kv_blocks_walked_shared_total"] < d["kv_blocks_walked_total"]
    stats = core.metrics.snapshot(core.sched, core.pool)
    ssm = stats["ssm"]
    assert (ssm["layers"], ssm["slots"], ssm["recurrence"]) == (3, 4, "mamba1")
    assert ssm["shapes"]["state"] == [3, 5, 16, 1, 128]
    # five operations an element of a [128, 16] state, as ssm_counts prices it
    assert ssm["heads"] * ssm["head_dim"] * ssm["state_size"] == 128 * 16
    assert ssm["conv_dim"] == 128 and ssm["slots_in_use"] == 0
    shapes = stats["step_shapes"]
    mixer = 64 * 256 + 128 * 36 + 4 * 128 + 128 * 64
    ffn = 3 * 64 * 96
    # layers 0-5 under the fixed terms, their sum the self-decoder's ...
    assert shapes["layers"] == 3 and shapes["dense_ffn_layers"] == 6
    assert shapes["layers"] * shapes["attn_params"] \
        + shapes["dense_ffn_layers"] * shapes["dense_ffn_params"] \
        == 3 * (2 * 64 * 96 + 2 * 64 * 48) + 3 * mixer + 6 * ffn
    # ... and the cross-decoder's beside the head's, computed for logit rows
    assert shapes["head_params"] == 128 * 64 + shapes["last_token_params"]
    assert shapes["last_token_params"] == 2 * 64 * 128 + 2 * 64 * 96 + 2 * ffn
    assert (shapes["num_heads"], shapes["head_dim"]) == (12, 8)


def test_a_reused_slot_starts_from_zeros(tmp_path, monkeypatch):
    core = _float32_core(tmp_path, monkeypatch, max_batch_size=1,
                         decode_bucket=(1,))
    rng = np.random.default_rng(3)
    first = _request(rng.integers(0, 128, 50).tolist(), 8)
    second = _request(rng.integers(0, 128, 41).tolist(), 8)
    for req in (first, second):
        (toks, lps), = _generate_all(core, [req])
        d_lp, _ = _against_the_reference(core.runner.params, req, toks, lps)
        assert d_lp < 1e-3
