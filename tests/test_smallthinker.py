"""SmallThinker-21BA3B's block at a small size on the CPU, against the plain
reference the benchmark's configuration brings
(``chipbench/configs/smallthinker-21b-a3b-l12/reference.py``): every expert
held on one chip and computed by groups, ReLU-gated experts, a router that
reads the state entering attention, one full layer without positions before
three windowed rotary ones.

Everything here is float32 with seeded random weights. The tolerances say
why they are what they are; each is tight enough that computing in bf16
where float32 is stated fails it (``test_bf16_fails_the_tolerance``), and
five controls that change one piece of the mathematics each fail it too.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import MODEL_PRESETS, ModelConfig

ROOT = Path(__file__).resolve().parents[1]
CONFIG_DIR = ROOT / "chipbench" / "configs" / "smallthinker-21b-a3b-l12"

# The published config cut to CPU size: the same keys, tiny widths. Eight
# layers are two periods G L L L; 100 tokens pass the window four times.
TINY = {
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 4096,
    "model_name": "smallthinker_tiny", "moe_ffn_hidden_size": 32,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1] * 2, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "sliding_window_size": 24, "tie_word_embeddings": False,
    "vocab_size": 256,
    "expert_act": "relu", "router_input": "attn_norm",
    "attention_bias": False,
}

# float32 against float32 over eight layers: the two sides sum in other
# orders (a grouped matmul, an online softmax) and differ by rounding,
# 1e-6 of unit-scale logits a layer; 2e-4 leaves an order of magnitude.
# bf16 anywhere on the path reads 1e-2 or more.
LOGIT_TOL = 2e-4
N_TOKENS = 100


def _period_windows(cfg) -> tuple[int, ...]:
    """The attention windows of the layers of one period of the plan."""
    p = cfg.layer_plan
    return tuple(layer[0].window
                 for layer in p.layers[p.lead:p.lead + p.period])


def _reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference", CONFIG_DIR / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(tmp_path, **over) -> tuple[ModelConfig, dict]:
    model = {**TINY, **over}
    (tmp_path / "config.json").write_text(json.dumps(model))
    cfg = ModelConfig.from_hf_config(str(tmp_path))
    return dataclasses.replace(cfg, dtype="float32"), model


def _serve(cfg, params, tokens, *, attn_impl="dense", dtype=None):
    """Logits [len(tokens), vocab] as the engine's step computes them
    (``test_kexaone._serve``: prefill in chunks, here of 32, then one token
    at a time through a paged cache, the routed layers by groups)."""
    from test_kexaone import _serve as serve

    return serve(cfg, params, tokens, attn_impl=attn_impl, chunk=32,
                 dtype=dtype)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cfg, model = _config(tmp_path_factory.mktemp("smallthinker"))
    params = llama.init_params(cfg, jax.random.key(3))
    return cfg, model, params


@pytest.fixture(scope="module")
def served(tiny):
    """The reference's logits of a sequence that crosses the window (24), a
    block (16) and three 32-token chunks."""
    cfg, model, params = tiny
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, N_TOKENS).tolist()
    ref = _reference().logits_at(params, model, tokens, list(range(N_TOKENS)))
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
    got = _serve(cfg, params, tokens, dtype="bfloat16")
    assert np.max(np.abs(got - ref)) > 20 * LOGIT_TOL


def _unnormalised_route(xt, lp, cfg):
    """The control's router: the softmax over all experts, the chosen
    ones' shares not renormalised."""
    logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    topv, topi = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    return topi, jnp.exp(topv - jax.nn.logsumexp(logits, axis=-1, keepdims=True))


# One piece of the layer's mathematics changed in the program and the
# reference left as it is: each must read far outside the tolerance.
CONTROLS = {
    "silu_experts": {"expert_act": "silu"},
    "router_reads_the_ffn_input": {"router_input": "mlp_norm"},
    "rotary_on_the_full_layers": {"rope_scope": "all"},
    # (a window wider than the sequence hides nothing; the layers' kinds,
    # and with them which carry rotary positions, stay)
    "window_left_off": {"sliding_window": 10 * N_TOKENS},
    "softmax_over_all_not_renormalised": {},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison(tiny, served, control, monkeypatch):
    cfg, _model, params = tiny
    tokens, ref = served
    if control == "softmax_over_all_not_renormalised":
        monkeypatch.setattr(moe, "route", _unnormalised_route)
    wrong = _serve(dataclasses.replace(cfg, **CONTROLS[control]), params,
                   tokens)
    # a changed activation, routing state, position or mask moves unit-scale
    # logits by tenths and more; 0.05 is 250 tolerances
    assert np.max(np.abs(wrong - ref)) > 0.05


def test_routing_is_carried_past_attention(tiny):
    """The choice is a function of the state that enters attention: with the
    attention's output replaced, the experts chosen stay and the FFN's
    input moves."""
    cfg, _model, params = tiny
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (9, cfg.hidden_size)), jnp.float32)
    before = moe.route(llama.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps),
                       lp, cfg)
    after = moe.route(llama.rms_norm(x + 1.0, lp["mlp_norm"], cfg.rms_norm_eps),
                      lp, cfg)
    assert (np.asarray(before[0]) != np.asarray(after[0])).any()
    moved = x + 1.0
    y, counts = moe.moe_mlp_held(moved, lp, cfg, routing=before)
    want = llama.moe_mlp(moved, lp, cfg, routing=before)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    own = llama.moe_mlp(moved, lp, cfg)
    assert not np.allclose(np.asarray(own), np.asarray(want), atol=1e-3)
    assert counts.tolist()[0] == 9 * cfg.num_experts_per_tok


# ---------------------------------------------------------------------------
# the grouped form with every expert held is the all-experts form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("routing", ["even", "skewed"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_whole_held_rows_equal_the_all_experts_form(tiny, act, routing):
    cfg = dataclasses.replace(tiny[0], expert_act=act,
                              router_input="mlp_norm")
    lp = jax.tree.map(lambda a: a[0], tiny[2]["layers"])
    if routing == "skewed":
        # every token's first choice is expert 5: one group holds a row of
        # every token, most others few or none
        skew = np.zeros((cfg.hidden_size, cfg.num_experts), np.float32)
        skew[:, 5] = 10.0
        lp = {**lp, "router": jnp.asarray(skew) + 0.1 * lp["router"]}
    x = jnp.asarray(np.abs(np.random.default_rng(4).standard_normal(
        (21, cfg.hidden_size))), jnp.float32)
    live = jnp.arange(21) < 13            # a bucket of 21 with 13 live tokens
    y, counts = moe.moe_mlp_held(x, lp, cfg, live)
    want = llama.moe_mlp(x, lp, cfg)
    # float32 sums of three experts' parts in another order
    np.testing.assert_allclose(np.asarray(y[:13]), np.asarray(want[:13]),
                               atol=2e-5)
    assert np.asarray(y[13:] == 0).all()       # padding computes nothing
    topi = np.asarray(moe.route(x, lp, cfg)[0])[:13]
    sizes = np.bincount(topi.reshape(-1), minlength=cfg.num_experts)
    assert counts.tolist() == [13 * cfg.num_experts_per_tok,
                               int((sizes > 0).sum()), int(sizes.max())]
    if routing == "skewed":
        assert sizes[5] == 13
    # and the other activation is another result
    other = dataclasses.replace(cfg, expert_act={"relu": "silu",
                                                 "silu": "relu"}[act])
    assert not np.allclose(np.asarray(moe.moe_mlp_held(x, lp, other, live)[0]),
                           np.asarray(y), atol=1e-3)


@pytest.mark.parametrize("impl", ["dropless_ep2", "dropless_ep4"])
def test_the_sharded_forms_take_the_activation_and_the_routing(tiny, impl):
    """``moe_mlp_dropless`` computes ReLU-gated experts from a routing made
    elsewhere, as ``held_rows`` does: it neither applies SiLU nor routes
    again from its own input."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = tiny[0]
    lp = jax.tree.map(lambda a: a[2], tiny[2]["layers"])
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((40, cfg.hidden_size)), jnp.float32)
    routing = moe.route(jnp.asarray(rng.standard_normal(
        (40, cfg.hidden_size)), jnp.float32), lp, cfg)
    want = llama.moe_mlp(x, lp, cfg, routing)
    mesh = make_mesh(MeshConfig(ep=int(impl[-1])))
    got = jax.jit(lambda x, w, r: moe.moe_mlp_dropless(
        x, w, cfg, mesh=mesh, routing=r))(x, lp, routing)
    # float32 partial sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    silu = llama.moe_mlp(x, lp, dataclasses.replace(cfg, expert_act="silu"),
                         routing)
    assert not np.allclose(np.asarray(silu), np.asarray(want), atol=1e-3)


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer(tiny):
    """The deployment's other cut (the guide's section 4) in this model's
    form: four chips hold 2 of the 8 experts each, every chip routes over
    all 8 from the state that entered attention, and the parts add up to
    the whole layer. (No shared expert: nothing is counted twice.)"""
    cfg = tiny[0]
    share = dataclasses.replace(cfg, num_experts=2, num_experts_published=8)
    assert share.holds_share and share.router_width == 8
    lp = jax.tree.map(lambda a: a[3], tiny[2]["layers"])
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((37, cfg.hidden_size)), jnp.float32)
    m = jnp.asarray(rng.standard_normal((37, cfg.hidden_size)), jnp.float32)
    routing = moe.route(a, lp, cfg)               # before attention, 8 wide
    uncut = llama.moe_mlp(m, lp, cfg, routing)
    total, rows = jnp.zeros_like(m), 0
    for chip in range(4):
        # Chip ``chip`` holds experts 2*chip, 2*chip+1; the layer holds
        # "the first ``held``", so it is handed the routing renumbered.
        mine = {**lp, **{k: lp[k][2 * chip:2 * chip + 2]
                         for k in ("w_gate", "w_up", "w_down")}}
        part, counts = moe.moe_mlp_held(
            m, mine, share, routing=((routing[0] - 2 * chip) % 8, routing[1]))
        total = total + part
        rows += int(counts[0])
    assert rows == 37 * cfg.num_experts_per_tok
    # float32 sums of four parts in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)


# ---------------------------------------------------------------------------
# what the configuration's keys resolve to
# ---------------------------------------------------------------------------

# The ``config`` of the catalog's row ``SmallThinker-21BA3B-Instruct`` (the
# ``model-configs`` guide's ``architectures.jsonl``; the source's
# ``config.json``), key for key.
CATALOG_CONFIG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}


def test_the_catalog_rows_config_resolves_verbatim(tmp_path):
    """The 52-layer model's fields, from the source's own keys alone: no
    ``intermediate_size`` (no layer is dense), and without this
    configuration's ``assumed`` keys the defaults."""
    (tmp_path / "config.json").write_text(json.dumps(CATALOG_CONFIG))
    cfg = ModelConfig.from_hf_config(str(tmp_path))
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (52, 2560, 28, 4, 128, 151936)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.num_shared_experts, cfg.first_k_dense, cfg.router_width) == (
        64, 6, 768, 0, 0, 64)
    assert cfg.intermediate_size == 0 and not cfg.holds_share
    assert (cfg.router_scoring, cfg.router_bias) == ("softmax", False)
    assert _period_windows(cfg) == (0, 4096, 4096, 4096)
    assert [cfg.window_of(i) for i in (0, 1, 4, 51)] == [0, 4096, 0, 4096]
    assert cfg.rope_scope == "sliding" and cfg.rope_theta == 1.5e6
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_word_embeddings
    assert cfg.max_position_embeddings == 16384
    assert (cfg.expert_act, cfg.router_input) == ("silu", "mlp_norm")


def test_the_cells_configuration_is_the_catalog_row_cut_in_depth():
    model = json.loads((CONFIG_DIR / "config.json").read_text())
    row = CATALOG_CONFIG
    changed = {k for k, v in row.items() if model.get(k) != v}
    # the two layouts are copied whole, as a nested group is: the program
    # and the reference read their first ``num_hidden_layers`` entries
    assert changed == {"num_hidden_layers"}
    assert set(model) - set(row) - {"_name_or_path", "assumed"} \
        == set(model["assumed"])
    cfg = ModelConfig.from_hf_config(str(CONFIG_DIR))
    assert cfg.num_layers == 12 and _period_windows(cfg) == (0, 4096, 4096, 4096)
    assert (cfg.expert_act, cfg.router_input) == ("relu", "attn_norm")
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    assert shapes["layers"]["w_gate"].shape == (12, 64, 2560, 768)
    assert shapes["layers"]["router"].shape == (12, 2560, 64)
    assert shapes["lm_head"].shape == (2560, 151936)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    about = json.loads((CONFIG_DIR / "about.json").read_text())
    # the norms beside the matrices that ``sizes`` counts
    assert 0 <= n - about["sizes"]["params_total"] < 100_000
    assert about["sizes"]["weight_bytes_bf16"] == 2 * about["sizes"]["params_total"]
    assert list(about["reduced"]) == ["num_hidden_layers"]
    assert set(about["assumed"]) == set(model["assumed"])
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes)


REFUSED = {
    "moe_primary_router_apply_softmax": ({"moe_primary_router_apply_softmax": False},
                                         "moe_primary_router_apply_softmax"),
    "rope_layout": ({"rope_layout": [1, 0, 1, 1] * 2}, "rope_layout"),
    "secondary_experts": ({"moe_num_secondary_experts": 4},
                          "moe_num_secondary_experts"),
    "norm_topk_prob": ({"norm_topk_prob": False}, "norm_topk_prob"),
    "attention_bias": ({"attention_bias": True}, "attention_bias"),
    "expert_act": ({"expert_act": "gelu"}, "expert_act"),
    "router_input": ({"router_input": "residual"}, "router_input"),
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_a_key_that_cannot_be_served_is_refused_by_name(tmp_path, key):
    over, match = REFUSED[key]
    with pytest.raises(ValueError, match=match):
        _config(tmp_path, **over)


def test_keys_that_say_nothing_are_accepted(tmp_path):
    """A secondary-expert count of zero and rotary on every layer are
    served: the first is the 21B model's own state, the second is
    ``rope_scope: "all"``."""
    cfg, _ = _config(tmp_path, moe_num_secondary_experts=0,
                     rope_layout=[1] * 8)
    assert cfg.rope_scope == "all" and cfg.num_experts == 8


# ---------------------------------------------------------------------------
# the random init: what a seed gives, and a stack too large to draw whole
# ---------------------------------------------------------------------------

def _digest(params) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)
                 .tobytes())
    return h.hexdigest()[:16]


def _tiny_kexaone(tmp_path) -> ModelConfig:
    from test_kexaone import TINY as KEXAONE

    (tmp_path / "config.json").write_text(json.dumps(KEXAONE))
    return ModelConfig.from_hf_config(str(tmp_path))


# sha256 over every leaf's name, type, shape and bits at seed 0, taken on
# the parent of PR 41 (commit d3f6b01): ("threefry2x32", the engine's own
# init; "rbg", the benchmark's ``sut.build``).
PINNED = {
    ("tiny-llama", "threefry2x32"): "0111a4136b40f811",
    ("tiny-moe", "threefry2x32"): "532b9b7f79b9d827",
    ("tiny-kexaone", "threefry2x32"): "269940e7f70d1761",
    ("tiny-llama", "rbg"): "f3109eb1c2209e11",
    ("tiny-moe", "rbg"): "792684ea98b0b9e2",
    ("tiny-kexaone", "rbg"): "a07c66e22963771e",
}


@pytest.mark.parametrize("name,impl", sorted(PINNED))
def test_a_seed_gives_the_weights_it_always_gave(tmp_path, name, impl):
    """The accepted cells' probe digits repeat only while their weights do
    (PERF.md section 7): ``init_params`` draws every leaf under
    ``INIT_WHOLE_MAX`` as it always did."""
    cfg = (_tiny_kexaone(tmp_path) if name == "tiny-kexaone"
           else MODEL_PRESETS[name])
    params = llama.init_params(cfg, jax.random.key(0, impl=impl))
    assert _digest(params) == PINNED[name, impl]


def test_a_stack_over_the_limit_is_drawn_a_layer_at_a_time(tiny, monkeypatch):
    cfg = dataclasses.replace(tiny[0], num_layers=4,
                              layer_types=tiny[0].layer_types[:4])
    whole = llama.init_params(cfg, jax.random.key(1))
    # the experts' stacks (4 x 8 x 64 x 32 = 65,536 elements) over the
    # limit, every other leaf under it
    monkeypatch.setattr(llama, "INIT_WHOLE_MAX", 40_000)
    by_layer = jax.jit(lambda: llama.init_params(cfg, jax.random.key(1)))()
    for name, leaf in by_layer["layers"].items():
        same = np.array_equal(np.asarray(leaf), np.asarray(whole["layers"][name]))
        assert same == (name not in ("w_gate", "w_up", "w_down")), name
    np.testing.assert_array_equal(np.asarray(by_layer["embed"]),
                                  np.asarray(whole["embed"]))
    stack = np.asarray(by_layer["layers"]["w_gate"], np.float32)
    assert stack.shape == (4, 8, 64, 32)
    # unit-variance normals over fan-in 64 in every layer, no layer another's
    assert abs(stack.std() * 8 - 1) < 0.02
    assert not np.array_equal(stack[0], stack[1])
    # the published stack is over the limit, K-EXAONE's share under it
    assert 12 * 64 * 2560 * 768 > 2**30 > 4 * 16 * 6144 * 2048


# ---------------------------------------------------------------------------
# the normal path: EngineCore, default flags
# ---------------------------------------------------------------------------

def _engine_config(tmp_path, **kw):
    from dynamo_tpu.utils.config import EngineConfig

    (tmp_path / "config.json").write_text(json.dumps(TINY))
    return EngineConfig(model=str(tmp_path), allow_random_weights=True,
                        num_blocks=160, max_batch_size=8, max_model_len=1024,
                        prefill_chunk=64, decode_bucket=(4, 8), **kw)


@pytest.mark.parametrize("attn_impl, experts", [
    ("dense", "grouped"), ("pallas_interpret", "grouped"),
    ("dense", "streamed")])
def test_engine_serves_it_and_counts(tmp_path, monkeypatch, attn_impl, experts):
    """Through ``EngineCore`` as any model: the scheduler, the pool, the
    lattice. The engine computes in bf16, so the logprobs it reports are
    held to the reference loosely here (the chip's probe has the limits);
    the counters are exact. ``streamed``: the decode programs' experts by
    the streaming kernel (ops/moe_stream.py, interpreted; the predicate
    bent to this size and backend), the chunk programs' by groups, as on
    the chip."""
    import functools

    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.ops import moe_stream

    if experts == "streamed":
        monkeypatch.setattr(moe, "streams_experts", lambda n, *shape: n <= 8)
        monkeypatch.setattr(moe_stream, "stream_rows", functools.partial(
            moe_stream.stream_rows, interpret=True))
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
    # mask, position, window, routing state or activation is off by tenths
    # to whole units at every position.
    assert float(np.median(diffs)) < 0.05
    after = get_sched_ledger().snapshot()
    d = {k: after[k] - before[k] for k in after
         if k.startswith(("moe_", "kv_blocks_", "live_tokens"))}
    routed, k, held = 8, 3, 8
    assert d["moe_layer_steps_total"] > 0
    assert d["moe_layer_steps_total"] % routed == 0
    # every expert is held: each live token's every choice is a row computed
    # here, in every routed layer
    assert d["moe_rows_total"] == d["live_tokens_total"] * k * routed
    assert 0 < d["moe_experts_touched_total"] <= held * d["moe_layer_steps_total"]
    assert d["moe_largest_group_total"] <= d["moe_rows_total"]
    # the layer steps of the programs the predicate says yes to: none on the
    # CPU; bent, the decode programs' and not the chunk programs'
    streamed = d["moe_streamed_layer_steps_total"]
    assert streamed % routed == 0
    assert (0 < streamed < d["moe_layer_steps_total"]
            if experts == "streamed" else streamed == 0)
    # six of the eight layers slide (window 24): they walk less than they hold
    assert d["kv_blocks_live_total"] * 2 < d["kv_blocks_walked_total"] \
        < d["kv_blocks_live_total"] * 8
    facts = core.metrics.snapshot(core.sched, core.pool)["moe"]
    assert (facts["experts_held"], facts["router_width"],
            facts["experts_per_token"], facts["routed_layers"]) == (8, 8, 3, 8)
    assert (facts["expert_act"], facts["router_input"]) == ("relu", "attn_norm")


def test_every_routed_model_on_one_chip_runs_the_grouped_form(tmp_path):
    """``tiny-moe`` (all 8 experts held, SiLU, routed from the FFN's input)
    is served by groups too, and a dense model's programs return no counts."""
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    kw = dict(allow_random_weights=True, num_blocks=64, max_batch_size=4,
              max_model_len=256)
    core = EngineCore(EngineConfig(model="tiny-moe", **kw))
    assert core.runner.moe_impl == "held"
    facts = core.metrics.snapshot(core.sched, core.pool)["moe"]
    assert (facts["experts_held"], facts["router_width"]) == (8, 8)
    assert (facts["expert_act"], facts["router_input"]) == ("silu", "mlp_norm")
    dense = EngineCore(EngineConfig(model="tiny-llama", **kw))
    assert dense.runner.moe_impl == "dense"
    assert "moe" not in dense.metrics.snapshot(dense.sched, dense.pool)
    assert EngineCore(EngineConfig(model="tiny-moe", ep=2, **kw)
                      ).runner.moe_impl == "ep"


def _tiny_moe_tokens(**mesh):
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.utils.config import EngineConfig

    core = EngineCore(EngineConfig(
        model="tiny-moe", allow_random_weights=True, num_blocks=64,
        max_batch_size=4, max_model_len=256, **mesh))
    rng = np.random.default_rng(1)
    reqs = [PreprocessedRequest(
        token_ids=rng.integers(0, 500, n).tolist(),
        stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0)) for n in (40, 9)]
    for r in reqs:
        core.add_request(r)
    toks = {r.request_id: [] for r in reqs}
    for _ in range(100):
        if not core.has_work():
            break
        for rid, out in core.step().items():
            toks[rid] += out.token_ids
    return core.runner.moe_impl, [toks[r.request_id] for r in reqs]


@pytest.mark.parametrize("mesh,impl", [({"tp": 2}, "held"), ({"pp": 2}, "held"),
                                       ({"ep": 2}, "ep")],
                         ids=["tp2", "pp2", "ep2"])
def test_the_grouped_form_serves_on_a_mesh_too(mesh, impl):
    """A routed model on a "model" or a "pipe" mesh is ``ep == 1``: the
    grouped form under GSPMD and inside the stages (whose counts are not
    gathered), greedy tokens as one device gives them."""
    one = _tiny_moe_tokens()
    assert one[0] == "held" and all(len(t) == 6 for t in one[1])
    assert _tiny_moe_tokens(**mesh) == (impl, one[1])
