"""NemotronH's block (NVIDIA-Nemotron-3-Nano-30B-A3B) at a small size on the
CPU, against the plain reference the benchmark's configuration brings
(``chipbench/configs/nemotron-3-nano-30b-a3b-ep8-l34/reference.py``): layers
of one mixer each in a pattern (a leading group, a scanned period, a
remainder), Mamba-2 layers whose state lives in a pool beside the paged KV
cache, attention without positions, sigmoid-routed experts of two matrices
and a squared ReLU, 4 of 8 held.

Float32 with seeded random weights wherever logits are compared. The
reference runs the recurrence token by token; the program a blocked scan
over chunks and a one-token update through the state pool.
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

from dynamo_tpu.models import llama, mamba, moe
from dynamo_tpu.models.config import ModelConfig, resolve_model_config

ROOT = Path(__file__).resolve().parents[1]
CONFIG_DIR = (ROOT / "chipbench" / "configs"
              / "nemotron-3-nano-30b-a3b-ep8-l34")

# The published config's keys at CPU size. The pattern has every kind of
# layer, a leading group ("M"), three periods "EM*" and a remainder "ME";
# a block of the scan is 8 positions.
PATTERN = "M" + "EM*" * 3 + "ME"
TINY = {
    "model_type": "nemotron_h", "hybrid_override_pattern": PATTERN,
    "num_hidden_layers": len(PATTERN), "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "vocab_size": 128, "intermediate_size": 32,
    "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "use_bias": False, "attention_bias": False,
    "mlp_bias": False, "use_conv_bias": True, "rope_theta": 10000,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "expert_act": "relu2", "expert_gated": False, "rope_scope": "none",
    "ssm_state_dtype": "float32",
}

# float32 against float32 over twelve layers, sums in other orders (a
# blocked scan against a recurrence, grouped matmuls, an online softmax):
# rounding, 1e-6 of unit-scale logits a layer. bf16 anywhere reads 1e-2.
LOGIT_TOL = 2e-4
BS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference", CONFIG_DIR / "reference.py")
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
    cfg, model = _config(tmp_path_factory.mktemp("nemotron_h"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


def _serve(cfg, params, tokens, cuts, *, slot=1, ssm=None, slots=3,
           attn_impl="dense"):
    """Logits [len(tokens), vocab] as a step computes them: the sequence
    in the chunks ``cuts`` (a chunk of one token is the decode program's
    shape), through a paged KV cache of the attention layers and row
    ``slot`` of a state pool, one row of a batch of two (the other is
    padding and names the trash row). Returns (logits, the pool)."""
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
        hid, ck, cv, ssm, counts = llama.forward(
            params, cfg, ids, start, length, bt, ck, cv,
            attn_impl=attn_impl, moe_impl="held", return_all_hidden=True,
            moe_counts=True, ssm=ssm, ssm_slots=rows)
        return llama.logits_from_hidden(params, cfg, hid[0]), ck, cv, ssm, counts

    out, start = [], 0
    for length in cuts:
        t = 1 if length == 1 else max(cuts)
        ids = np.zeros((2, t), np.int32)
        ids[0, :length] = tokens[start:start + length]
        logits, ck, cv, ssm, counts = step(
            jnp.asarray(ids), jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32), ck, cv, ssm)
        assert counts.shape == (3,) and int(counts[0]) > 0
        out.append(np.asarray(logits[:length]))
        start += length
    return np.concatenate(out), ssm


# ---------------------------------------------------------------------------
# the configuration and its adapter
# ---------------------------------------------------------------------------

def test_the_published_configuration_resolves():
    cfg = resolve_model_config(str(CONFIG_DIR))
    assert cfg.hybrid_pattern == "MEMEM*" + "EMEMEM*" * 4
    assert cfg.layer_plan.split == (6, 7, 4, 0)
    assert (cfg.layers_of("M"), cfg.layers_of("E"), cfg.attn_layers) == (15, 14, 5)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok) == (16, 128, 6)
    assert (cfg.expert_act, cfg.expert_gated, cfg.rope_scope) == ("relu2", False, "none")
    assert cfg.router_scoring == "sigmoid" and cfg.router_bias
    assert cfg.routed_scaling_factor == 2.5
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_groups) == (4096, 6144, 8)
    assert cfg.shared_expert_width == 3712 and cfg.moe_intermediate_size == 1856
    assert cfg.expert_store_width == 1920      # 15 lane tiles, zeros behind 1856
    assert mamba.slot_layer_bytes(cfg) == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert mamba.state_shapes(cfg, 64)["conv"].shape == (15, 65, 3 * 6144)
    # the parameters the equations imply, a kind of layer (about.json)
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes["layers"].items()}
    per = lambda names, n: sum(sizes[k] for k in names) / n
    assert per(mamba.LEAVES, 15) == pytest.approx(38.74e6, rel=1e-3)
    assert per(("wq", "wk", "wv", "wo", "attn_norm"), 5) == pytest.approx(23.40e6, rel=1e-3)


@pytest.mark.parametrize("pattern, groups", [
    (PATTERN, (1, 3, 3)),
    ("MEMEM*" + "EMEMEM*" * 4, (6, 7, 4)),
    # the whole model: "MEMEM", five periods "*EMEMEM", twelve layers one by one
    ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", (5, 7, 5)),
    ("M*E", (3, 1, 0)),                  # nothing repeats: every layer traced
    ("MMMM", (0, 1, 4)),
])
def test_a_patterns_leading_group_and_period(pattern, groups):
    cfg = ModelConfig(num_layers=len(pattern), hybrid_pattern=pattern,
                      mamba_num_heads=2, mamba_head_dim=4, ssm_state_size=4)
    assert cfg.layer_plan.split[:3] == groups


@pytest.mark.parametrize("key, value, says", [
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("use_bias", True, "use_bias"),
    ("attention_bias", True, "attention_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("n_group", 2, "n_group"),
    ("hybrid_override_pattern", "M-" + PATTERN[2:], "'-'"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("rope_scope", "all", "rope_scope"),
    ("ssm_state_dtype", "bfloat16", "float32"),
])
def test_the_adapter_refuses_by_key(tmp_path, key, value, says):
    with pytest.raises(ValueError, match=says):
        _config(tmp_path, **{key: value})


def test_a_config_of_another_family_passes_the_adapter_untouched():
    from dynamo_tpu.models.config import _nemotron_h_keys

    other = {"model_type": "llama", "use_bias": True}
    assert _nemotron_h_keys(other) is other


def test_seeded_init_draws_the_recurrence_as_published(tiny):
    """dt within [time_step_min, time_step_max] through the inverse
    softplus, A in [1, 16], D ones: a state that decays over tens to
    thousands of tokens, not in one."""
    layers = tiny[2]["layers"]
    dt = np.asarray(jax.nn.softplus(layers["ssm_dt_bias"]))
    assert dt.min() >= 0.001 - 1e-6 and dt.max() <= 0.1 + 1e-6
    a = np.exp(np.asarray(layers["ssm_A_log"]))
    assert a.min() >= 1 and a.max() <= 16
    assert (np.asarray(layers["ssm_D"]) == 1).all()
    decay = np.exp(-dt * a)                   # a token's decay of the state
    assert decay.min() > 0.15 and decay.max() < 1


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
    chunk boundaries: the state is carried from chunk to chunk and from the
    last chunk into decode. Under "pallas_interpret" the one-token update is
    the kernel's (ops/ssm_update.py), interpreted, beside the attention
    kernel."""
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


def test_state_not_carried_across_a_chunk_boundary_fails(tiny, served,
                                                         monkeypatch):
    """The control the chip's probe is held to as well: a chunk that does
    not start a prompt starts from zeros."""
    cfg, _model, params = tiny
    tokens, ref = served
    real = mamba.mixer

    def broken(*args, lay, q_start, **kw):
        if lay.t > 1:
            q_start = jnp.zeros_like(q_start)
        return real(*args, lay=lay, q_start=q_start, **kw)

    monkeypatch.setattr(mamba, "mixer", broken)
    got, _ = _serve(cfg, params, tokens, CUTS["chunks_of_13"])
    assert np.max(np.abs(got[:13] - ref[:13])) < LOGIT_TOL      # first chunk
    assert np.max(np.abs(got[13:] - ref[13:])) > 0.05


@pytest.mark.parametrize("control", ["gated_by_silu", "rotary_positions",
                                     "no_selection_bias"])
def test_a_control_fails_the_comparison(tiny, served, control):
    cfg, _model, params = tiny
    tokens, ref = served
    if control == "gated_by_silu":
        wrong = dataclasses.replace(cfg, expert_act="silu")
    elif control == "rotary_positions":
        wrong = dataclasses.replace(cfg, rope_scope="all")
    else:
        wrong = dataclasses.replace(cfg, router_bias=False)
    got, _ = _serve(wrong, params, tokens, CUTS["chunks_of_13"])
    assert np.max(np.abs(got - ref)) > 0.05


@pytest.mark.parametrize("t, block", [(5, 8), (8, 8), (13, 8), (29, 8),
                                      (29, 128), (64, 16)])
def test_blocked_scan_equals_the_recurrence(t, block):
    """``_scan_blocks`` against ``_scan_one`` token by token, from a state
    that is not zero, two rows of different lengths (the shorter's padded
    positions are the identity: its state is what its last live token
    left)."""
    rng = np.random.default_rng(t * 131 + block)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, t, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, t, g, n)), jnp.float32)
    live = np.arange(t)[None, :] < np.asarray([t, max(t - 3, 1)])[:, None]
    dt = jnp.asarray(np.where(live[..., None],
                              rng.uniform(0.001, 0.3, (b, t, h)), 0.0),
                     jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, p, n)), jnp.float32)
    y, s1 = mamba._scan_blocks(x, bm, cm, dt, a_log, s0, block)
    s, ys = s0, []
    for i in range(t):
        yi, s = mamba._scan_one(
            s, jnp.exp(dt[:, i] * -jnp.exp(a_log)), dt[:, i, :, None] * x[:, i],
            bm[:, i], cm[:, i])
        ys.append(yi)
    want = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y)[live], want[live], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("attn_impl, cuts", [
    ("dense", [16, 4]), ("pallas_interpret", [16, 4]),
    ("pallas_interpret", [16, 1, 1])])
def test_a_padded_row_touches_the_trash_row_alone(tiny, served, attn_impl,
                                                  cuts):
    """Of the pool a step changes its live rows' slots and, for its padded
    rows, the trash row and nothing else; and of the trash row the
    convolution tail alone: its state is what it was, whether the one-token
    update is the kernel's (which moves the live rows' state and no other:
    PR 49), in a chunk program or a decode program, or ``jax.numpy``'s (the
    identity written back)."""
    cfg, _model, params = tiny
    tokens, _ref = served
    pool = jax.tree.map(lambda a: a + 7.0, mamba.zeros_state(cfg, 3))
    _, after = _serve(cfg, params, tokens[:sum(cuts)], cuts, slot=1, ssm=pool,
                      attn_impl=attn_impl)
    for leaf in ("state", "conv"):
        a = np.asarray(after[leaf])
        assert (a[:, [0, 2]] == 7.0).all()          # other sequences' rows
        assert not (a[:, 1] == 7.0).all()           # the live row's
    assert (np.asarray(after["state"])[:, 3] == 7.0).all()     # the trash row


# ---------------------------------------------------------------------------
# experts of two matrices
# ---------------------------------------------------------------------------

def test_ungated_held_rows_equal_the_all_experts_form(tiny):
    cfg = tiny[0]
    lp = {k: v[0] for k, v in tiny[2]["layers"].items()
          if k not in mamba.LEAVES and not k.startswith(("wq", "wk", "wv", "wo", "attn"))}
    assert "w_gate" not in lp and "shared_gate" not in lp
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (21, cfg.hidden_size)), jnp.float32)
    live = jnp.arange(21) < 13
    y, counts = moe.moe_mlp_held(x, lp, cfg, live)
    want = llama.moe_mlp(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(y)[:13], np.asarray(want)[:13],
                               atol=2e-5)
    assert int(counts[0]) <= 13 * cfg.num_experts_per_tok
    assert not moe.streams_experts(8, 2688, 1856, 2) or jax.default_backend() == "tpu"


def test_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Eight chips hold 2 of 16 ungated experts each; their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are the whole layer's result: the case of
    ``test_kexaone.test_the_shares_add_up_to_the_uncut_layer`` for an expert
    without a gate and a shared expert of its own width."""
    cfg = dataclasses.replace(tiny[0], num_experts=2, num_experts_published=16,
                              num_experts_per_tok=6)
    whole = dataclasses.replace(cfg, num_experts=16, num_experts_published=0)
    layers = llama.init_params(whole, jax.random.key(9))["layers"]
    lp = {k: layers[k][0] for k in ("mlp_norm", "router", "router_bias",
                                    "w_up", "w_down", "shared_up", "shared_down")}
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (37, cfg.hidden_size)), jnp.float32)
    uncut = llama.moe_mlp(x, lp, whole)
    shared = moe.ungated_ffn(x, lp["shared_up"], lp["shared_down"],
                             moe.gate_act(cfg))
    total, rows = jnp.zeros_like(x), 0
    for chip in range(8):
        order = np.roll(np.arange(16), -2 * chip)
        mine = {**lp, "router": lp["router"][:, order],
                "router_bias": lp["router_bias"][order],
                **{k: lp[k][2 * chip:2 * chip + 2] for k in ("w_up", "w_down")}}
        part, counts = moe.moe_mlp_held(x, mine, cfg)
        total = total + (part - shared)
        rows += int(counts[0])
    assert rows == 37 * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               atol=5e-5)


def test_expert_width_is_stored_in_whole_lane_tiles():
    """1,856 columns are stored as 1,920 with zeros behind them, which add
    nothing: the grouped matmul then reads the stack where it lies."""
    cfg = ModelConfig(
        num_layers=2, hybrid_pattern="ME", hidden_size=16, num_experts=2,
        num_experts_per_tok=1, moe_intermediate_size=200, expert_gated=False,
        expert_act="relu2", mamba_num_heads=2, mamba_head_dim=4,
        ssm_state_size=4, dtype="float32")
    assert cfg.expert_store_width == 256
    layers = llama.init_params(cfg, jax.random.key(0))["layers"]
    assert layers["w_up"].shape == (1, 2, 16, 256)
    assert layers["w_down"].shape == (1, 2, 256, 16)
    assert (np.asarray(layers["w_up"])[..., 200:] == 0).all()
    assert (np.asarray(layers["w_down"])[:, :, 200:] == 0).all()
    assert np.asarray(layers["w_up"])[..., :200].std() > 0.1
    assert dataclasses.replace(cfg, moe_intermediate_size=64).expert_store_width == 64


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


def _logprob_diffs(params, req, toks, lps):
    seq = req.token_ids + toks
    at = list(range(len(req.token_ids) - 1, len(seq) - 1))
    logits = _reference().logits_at(params, TINY, seq[:-1], at)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    return [abs(float(lp[j, t]) - lps[j]) for j, t in enumerate(toks)]


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
    """Through ``AsyncJaxEngine.generate`` with the scheduler, the pool and
    the lattice as any model: four requests at once (a mixed step holds
    rows of different lengths, prompts of one to four chunks), then a fifth
    that takes a finished sequence's slot. Logprobs against the reference's
    full forward pass; the new counts; what is off, said in stats()."""
    from dynamo_tpu.obs.sched_ledger import get_sched_ledger

    core = _float32_core(tmp_path, monkeypatch)
    assert core.runner.ssm is not None
    assert core.runner.spec.num_layers == 3        # the attention layers alone
    assert core.pool.enable_prefix_caching is False
    before = get_sched_ledger().snapshot()
    rng = np.random.default_rng(11)
    reqs = [_request(rng.integers(0, 128, n).tolist(), 6)
            for n in (100, 20, 70, 33)]
    outs = _generate_all(core, reqs)
    params = core.runner.params
    for r, (toks, lps) in zip(reqs, outs):
        assert len(toks) == 6
        assert max(_logprob_diffs(params, r, toks, lps)) < 1e-3
    after = get_sched_ledger().snapshot()
    d = {k: after[k] - before[k] for k in after if k.startswith("ssm_")}
    m_layers = PATTERN.count("M")
    assert d["ssm_layer_steps_total"] > 0
    assert d["ssm_layer_steps_total"] % m_layers == 0
    # every prompt token once, and a decode input a generated token but the
    # last (the pipelined loop may have enqueued that one too)
    assert 223 + 4 * 5 <= d["ssm_live_tokens_total"] <= 223 + 4 * 6
    assert d["ssm_scanned_positions_total"] > d["ssm_live_tokens_total"]
    assert d["ssm_state_rows_total"] % m_layers == 0
    stats = core.metrics.snapshot(core.sched, core.pool)
    assert stats["ssm"]["layers"] == m_layers and stats["ssm"]["slots"] == 4
    assert stats["ssm"]["shapes"]["state"] == [m_layers, 5, 8, 8, 16]
    assert stats["ssm"]["prefix_matching"].startswith("off")
    assert stats["moe"]["expert_matrices"] == 2
    assert stats["step_shapes"]["layers"] == 3
    assert stats["step_shapes"]["dense_ffn_layers"] == m_layers
    assert stats["step_shapes"]["dense_ffn_params"] == \
        stats["step_shapes"]["ssm_params"] == 64 * (64 + 128 + 8) + 64 * 64
    assert stats["step_shapes"]["expert_params"] == 2 * 64 * 32


def test_a_reused_slot_starts_from_zeros(tmp_path, monkeypatch):
    """One slot: the second sequence takes the row the first left its state
    in, and no host call cleared it. Its first chunk starts at 0, so the
    program starts it from zeros."""
    core = _float32_core(tmp_path, monkeypatch, max_batch_size=1,
                         decode_bucket=(1,))
    rng = np.random.default_rng(3)
    first = _request(rng.integers(0, 128, 50).tolist(), 8)
    second = _request(rng.integers(0, 128, 41).tolist(), 8)
    (toks1, lps1), = _generate_all(core, [first])
    left = np.asarray(core.runner.ssm["state"][:, 0])
    assert np.abs(left).max() > 0
    (toks2, lps2), = _generate_all(core, [second])
    assert max(_logprob_diffs(core.runner.params, second, toks2, lps2)) < 1e-3


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
        assert max(_logprob_diffs(core.runner.params, r, toks, lps)) < 1e-3


REFUSED = {
    "spec_ngram": dict(spec_ngram=2),
    "tp": dict(tp=2), "pp": dict(pp=2), "sp": dict(sp=2), "ep": dict(ep=2),
    "kv_dtype": dict(kv_dtype="int8"),
    "host_kv_blocks": dict(host_kv_blocks=8),
    "stream_ckpt_blocks": dict(stream_ckpt_blocks=2),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_engine_refuses_what_cannot_resume_or_shard_the_state(tmp_path, option):
    from dynamo_tpu.engine.engine import EngineCore

    # (ep meets an older refusal first: a share of the experts is not
    # divided again; pp the plan's: the stages' layers are not of one kind)
    with pytest.raises(ValueError, match="recurrent layers|one chip's share"
                                         "|pipeline stages"):
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
    assert core.evacuate_sessions() == {"sessions": 0, "blocks": 0, "bytes": 0}
    for op, args in (("export_blocks", ([1],)), ("import_blocks", ([],)),
                     ("stage_export", ("x", [1])),
                     ("stream_begin", ("x", "r", [1])),
                     ("prefetch_remote", ({"xfer_id": "x"},)),
                     ("import_remote", ({"xfer_id": "x"},))):
        with pytest.raises(ValueError, match="recurrent layers"):
            getattr(core, op)(*args)
