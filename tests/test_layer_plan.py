"""The one plan of a model's layers (``ModelConfig.layer_plan``) and the one
runner over it (models/llama.py ``_run_layers``).

The plan: where the scan stands in every configuration the benchmark has
(that is program text: a split that moves is another program), and what each
reader of the layers' kinds reads from it. The runner: how many layer bodies
one trace of ``forward`` makes (set-up time is paid by the traced equation),
and that the scanned program is the plan's layers run one by one.
"""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mamba
from dynamo_tpu.models.config import (
    MODEL_PRESETS,
    ModelConfig,
    resolve_model_config,
)

CONFIGS = Path(__file__).resolve().parents[1] / "chipbench" / "configs"
BS = 16


def _resolve(name: str) -> ModelConfig:
    return resolve_model_config(
        str(CONFIGS / name) if (CONFIGS / name).is_dir() else name)


# (lead, period, trips) of each scanned run and then rest
# (``LayerPlan.split``), then what the readers of the plan read:
# the KV cache's layers, the routed layers, each attention layer's window
PLANS = {
    "mistral-7b-v0.3-l16": ((0, 1, 16, 0), 16, 0, (0,) * 16),
    "mistral-nemo-12b-l10": ((0, 1, 10, 0), 10, 0, (0,) * 10),
    # the dense layer leads; L L G L is stated, and scanned though once
    "k-exaone-236b-a23b-ep8-l5": ((1, 4, 1, 0), 5, 4,
                                  (128, 128, 128, 0, 128)),
    "smallthinker-21b-a3b-l12": ((0, 4, 3, 0), 12, 12,
                                 (0, 4096, 4096, 4096) * 3),
    # MEMEM* then EMEMEM* x 4
    "nemotron-3-nano-30b-a3b-ep8-l34": ((6, 7, 4, 0), 5, 14, (0,) * 5),
    "llama-3-8b-lite": ((0, 1, 8, 0), 8, 0, (0,) * 8),
    # attention and a Mamba-2 mixer joined, then the FFN, in every layer
    "falcon-h1-34b-l6": ((0, 1, 6, 0), 6, 0, (0,) * 6),
    # SambaY whole: (Mamba-1, window) x 8 scanned, the Mamba-1 layer that
    # keeps the memory and the full layer traced between, (memory unit,
    # cross) x 7 scanned: two runs. The cache has the nine layers that write
    "phi-4-mini-flash-reasoning": ((0, 2, 8, 2, 2, 7, 0), 9, 0,
                                   (512,) * 8 + (0,)),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_where_the_scan_stands(name):
    plan = _resolve(name).layer_plan
    assert plan.split == PLANS[name][0]
    assert sum(r.lead + r.period * r.trips for r in plan.runs) + plan.rest \
        == len(plan.layers)
    # the first run's are the plan's own: all of a one-run plan
    assert (plan.lead, plan.period, plan.trips) == plan.split[:3]
    assert sum(n * max(trips, 1) for _at, n, trips in plan.spans) \
        == len(plan.layers)
    assert [a for a, _n, _t in plan.spans] == sorted(
        a for a, _n, _t in plan.spans)
    if name == "phi-4-mini-flash-reasoning":
        # layers 16 and 17 stand between the two runs, traced one by one,
        # and the layers that run over the last tokens alone begin a run
        assert plan.spans == ((0, 2, 8), (16, 2, 0), (18, 2, 7))
        assert plan.last_from == 18 and len(plan.bodies) == 6
    else:
        assert len(plan.runs) == 1 and plan.last_from is None


@pytest.mark.parametrize("name", sorted(PLANS))
def test_the_mixers_cover_the_model_once(name):
    cfg = _resolve(name)
    layers = cfg.layer_plan.layers
    assert len(layers) == cfg.num_layers
    _split, attn, routed, windows = PLANS[name]
    assert (cfg.attn_layers, cfg.layers_of("E"), cfg.attn_windows) == (
        attn, routed, windows)
    mixers = [m for layer in layers for m in layer]
    # a layer is attention then an FFN (a Mamba-2 mixer joined to attention
    # between them where the model has one beside it), or one letter of a
    # hybrid pattern
    assert {len(layer) for layer in layers} == (
        {1} if cfg.hybrid_pattern else
        {3} if cfg.ssm_beside_attention else {2})
    if cfg.decoder_layout:      # SambaY: a mixer of four kinds, then an FFN
        assert {layer[1].kind for layer in layers} == {"-"}
        assert {layer[0].kind for layer in layers} == set("S*GX")
    if cfg.hybrid_pattern:
        assert "".join(m.kind for m in mixers) == cfg.hybrid_pattern
    # recurrent state: read off the plan, pattern string or none
    assert cfg.has_ssm == any(m.kind in "MS" for m in mixers)
    # a joined mixer stands behind the one it joins, in its stack and place
    for layer in layers:
        for before, m in zip(layer, layer[1:]):
            if m.joined:
                assert (before.kind, m.kind) == ("*", "M")
                assert (before.stack, before.place) == (m.stack, m.place)
        assert not layer[0].joined
    assert any(m.joined for m in mixers) == cfg.ssm_beside_attention
    for key in {(m.kind, m.stack) for m in mixers}:
        places = [m.place for m in mixers if (m.kind, m.stack) == key]
        assert places == list(range(len(places))), key
    # the buffers' layers: the KV cache counts every attention layer, the
    # state pool and the experts' stack their own
    for kind in "*MES":
        at = [m.layer for m in mixers if m.kind == kind]
        assert at == list(range(cfg.layers_of(kind))), kind
    # ... and a cross mixer rereads the last layer that writes, and no other
    assert {m.layer for m in mixers if m.kind == "X"} <= {cfg.attn_layers - 1}
    assert [m.keeps for m in mixers if m.kind == "S"][:-1].count(True) == 0
    assert all(m.window == 0 for m in mixers if m.kind != "*")
    # what shares a stack within a layer shares the place
    assert all(len({m.place for m in layer if m.stack == s}) == 1
               for layer in layers for s in {m.stack for m in layer})
    # the parameters' stacks are as long as the plan's places say
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    stacks = llama.layer_stacks(shapes["layers"])
    for stack in {m.stack for m in mixers}:
        n = 1 + max(m.place for m in mixers if m.stack == stack)
        assert {x.shape[0] for x in jax.tree.leaves(stacks[stack])} == {n}


# ---------------------------------------------------------------------------
# the runner, on a tiny configuration of each family
# ---------------------------------------------------------------------------

_TINY = dict(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
             vocab_size=256, dtype="float32", tie_word_embeddings=False)


def _lead_window(layers: int) -> ModelConfig:
    """K-EXAONE's block: a leading dense layer, then routed layers whose
    attention runs L L L G (stated), post-norm, sigmoid routing, a share."""
    kinds = ("sliding_attention",) * 3 + ("full_attention",)
    return ModelConfig(
        **_TINY, num_layers=layers, intermediate_size=96, first_k_dense=1,
        layer_types=(kinds * layers)[:layers], sliding_window=40,
        pattern_len=4, num_experts=4, num_experts_published=16,
        num_experts_per_tok=4, moe_intermediate_size=32, num_shared_experts=1,
        router_scoring="sigmoid", router_bias=True, routed_scaling_factor=2.5,
        qk_norm=True, rope_scope="sliding", norm_placement="post")


FAMILIES = {
    "dense": dataclasses.replace(
        MODEL_PRESETS["tiny-llama"], num_layers=3, dtype="float32"),
    "lead_window": _lead_window(10),
    # SmallThinker's: G L L L stated by no key, the router reads the state
    # that enters attention
    "routed_before_attention": ModelConfig(
        **_TINY, num_layers=8, intermediate_size=0,
        layer_types=("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",) + ("sliding_attention",) * 3,
        sliding_window=24, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=32, rope_scope="sliding", expert_act="relu",
        router_input="attn_norm"),
    # NemotronH's: one mixer a layer, "M" then "EM*" x 3 then "ME"
    "hybrid": ModelConfig(
        **_TINY, num_layers=12, hybrid_pattern="M" + "EM*" * 3 + "ME",
        mamba_num_heads=8, mamba_head_dim=8, ssm_groups=2, ssm_state_size=16,
        ssm_chunk=8, num_experts=4, num_experts_published=8,
        num_experts_per_tok=2, moe_intermediate_size=32, num_shared_experts=1,
        shared_expert_intermediate_size=48, router_scoring="sigmoid",
        router_bias=True, routed_scaling_factor=2.5, expert_act="relu2",
        expert_gated=False, rope_scope="none"),
    # Falcon-H1's: attention and a Mamba-2 mixer under one norm and one add,
    # then a gated FFN, multipliers on the activations
    "side_by_side": ModelConfig(
        **_TINY, num_layers=4, intermediate_size=96,
        ssm_beside_attention=True, mamba_num_heads=8, mamba_head_dim=8,
        ssm_groups=2, ssm_state_size=16, ssm_chunk=8,
        embedding_multiplier=5.5, lm_head_multiplier=0.25,
        attention_in_multiplier=0.5, key_multiplier=0.3,
        attention_out_multiplier=0.7, ssm_in_multiplier=0.25,
        ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35),
        ssm_out_multiplier=0.6, mlp_multipliers=(0.18, 0.4)),
}

# (split, bodies one trace of forward makes: attention, FFN, Mamba; since
# PR 54 a description the program holds more than once is traced once, so
# these are the distinct ones: of lead_window's six bodies the dense lead,
# the windowed and the full routed layer; of the hybrid's six one a kind)
TRACED = {
    "dense": ((0, 1, 3, 0), (1, 1, 0)),
    "lead_window": ((1, 4, 2, 1), (3, 3, 0)),
    "routed_before_attention": ((0, 4, 2, 0), (2, 2, 0)),
    "hybrid": ((1, 3, 3, 2), (1, 1, 1)),
    "side_by_side": ((0, 1, 4, 0), (1, 1, 1)),
}


def _step(cfg: ModelConfig, seed: int):
    """A mixed step's operands for the runner: two rows deep in a context,
    a warm cache and, where a layer is recurrent, a warm state pool."""
    rng = np.random.default_rng(seed)
    b, t = 2, 24

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    shape = (cfg.attn_layers, 8, BS, cfg.num_kv_heads, cfg.head_dim)
    q_start = jnp.asarray([50, 3], jnp.int32)
    q_len = jnp.asarray([t, t], jnp.int32)
    bt = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0]], jnp.int32)
    lay, valid = llama.token_layout(q_len, b, t, b * t)
    positions, slot = llama._positions_and_slots(lay, valid, q_start, bt, BS)
    ssm = {k: normal(*s.shape).astype(s.dtype)
           for k, s in mamba.state_shapes(cfg, 3).items()} \
        if cfg.has_ssm else None
    state = (normal(b * t, cfg.hidden_size), normal(*shape), normal(*shape),
             ssm)
    kw = dict(lay=lay, positions=positions, slot=slot, block_tables=bt,
              q_start=q_start, q_len=q_len, kv_lens=q_start + q_len,
              live=valid, ssm_slots=jnp.asarray([1, 2], jnp.int32),
              moe_impl="held")
    return state, kw


def _one_by_one(cfg, layers, h, ck, cv, ssm, *, lay, q_start, q_len, live,
                ssm_slots, moe_impl, **attn):
    """The plan's layers, each mixer written out, at static places and with
    no scan: what ``_run_layers`` has to equal."""
    stacks = llama.layer_stacks(layers)
    counts = jnp.zeros((3,), jnp.int32)
    post = cfg.norm_placement == "post"
    for mixers in cfg.layer_plan.layers:
        routing = None
        for m, after in zip(mixers, (*mixers[1:], None)):
            lp = jax.tree.map(lambda a: a[m.place], stacks[m.stack])
            if not m.joined:    # (a joined mixer reads what the one before read)
                norm = lp[{"*": "attn_norm", "M": "ssm_norm"}.get(
                    m.kind, "mlp_norm")]
                x = h if post else llama.rms_norm(h, norm, cfg.rms_norm_eps)
            if m.kind == "*":
                if cfg.router_input == "attn_norm" and "router" in lp:
                    from dynamo_tpu.models.moe import route

                    routing = route(x, lp, cfg)
                out, ck, cv = llama._attention(
                    cfg, lp, m.layer, x, ck, cv, lay=lay, q_start=q_start,
                    window=m.window, **attn)
            elif m.kind == "M":
                out, ssm = mamba.mixer(
                    cfg, lp, m.layer, x, ssm, lay=lay, slots=ssm_slots,
                    q_start=q_start, q_len=q_len, live=live)
            else:
                out, c = llama._ffn(cfg, lp, x, routing, moe_impl, None, live)
                counts = counts + (0 if c is None else c)
            if m.joined:
                out = beside + out
            if after is not None and after.joined:
                beside = out        # added with the mixer beside it
                continue
            h = h + (llama.rms_norm(out, norm, cfg.rms_norm_eps) if post
                     else out)
    return h, ck, cv, ssm, counts


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_forward_traces_a_period_once(monkeypatch, family):
    """The bodies one trace of ``forward`` makes: of the leading layers,
    one period and the rest, each distinct description once. Counted at the
    mixers' own functions, wrapped here: the program has no hook."""
    cfg = FAMILIES[family]
    split, bodies = TRACED[family]
    assert cfg.layer_plan.split == split
    calls = {"attention": 0, "ffn": 0, "mamba": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(llama, "_attention",
                        counted("attention", llama._attention))
    monkeypatch.setattr(llama, "_ffn", counted("ffn", llama._ffn))
    monkeypatch.setattr(mamba, "mixer", counted("mamba", mamba.mixer))
    (_h, ck, cv, ssm), kw = _step(cfg, 0)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    state = {"ssm": ssm, "ssm_slots": kw["ssm_slots"]} if cfg.has_ssm else {}
    jax.eval_shape(lambda p, ck, cv, state: llama.forward(
        p, cfg, jnp.zeros((2, 24), jnp.int32), kw["q_start"], kw["q_len"],
        kw["block_tables"], ck, cv, moe_impl="held", moe_counts=True,
        **state), params, ck, cv, state)
    assert tuple(calls.values()) == bodies


@pytest.mark.parametrize("case", [*sorted(FAMILIES), "lead_window-5",
                                  "lead_window-8", "lead_window-48"])
def test_the_scanned_layers_equal_the_layers_one_by_one(case):
    """Leading layers, whole periods scanned, a remainder, against the same
    layers with no scan: the hidden state, K, V, the state pool and the
    routed layers' counts. The K-EXAONE block at three more depths:
    5 = 1 + one period; 8 = 1 + one period + 3; the published 48 = 1 + 11
    periods + 3."""
    family, _, depth = case.partition("-")
    cfg = _lead_window(int(depth)) if depth else FAMILIES[family]
    layers = llama.init_params(cfg, jax.random.key(cfg.num_layers))["layers"]
    state, kw = _step(cfg, cfg.num_layers)
    got = jax.jit(lambda: llama._run_layers(
        cfg, cfg.layer_plan, layers, *state, **kw))()
    want = jax.jit(lambda: _one_by_one(cfg, layers, *state, **kw))()
    if cfg.is_moe:
        assert int(got[4][0]) > 0
        np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))
    else:
        assert got[4] is None
    # The same float32 operations in another program: rounding alone, which
    # grows with the depth as the hidden state does (a post-norm residual
    # adds a unit-norm vector a sub-layer: |h| ~ 10 after 48 layers, where
    # 4e-5 was read).
    for a, w in zip(jax.tree.leaves(got[:4]), jax.tree.leaves(want[:4])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5 + 2e-6 * cfg.num_layers)
