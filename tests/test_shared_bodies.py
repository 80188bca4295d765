"""A step program traces and lowers each distinct layer body once (PR 54).

``_run_layers`` runs a layer whose description (``models/config.py
body_of``: each mixer's kind, stack, window, joined) stands more than once
among the program's bodies through one ``jax.jit``-wrapped body, its places
int32 operands. Held here, on tiny plans of each shape: the program computes
what the unshared form computes, bit for bit; a traced step holds one body a
repeated description and none where nothing repeats; the compile ledger
sums what the plan says; a two-way mesh runs a shared body.
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mamba
from dynamo_tpu.models.config import LayerPlan, ModelConfig, body_of
from dynamo_tpu.obs.compile_ledger import BucketSig, CompileLedger

from test_layer_plan import BS, FAMILIES, _TINY, _step

PLANS = {
    **FAMILIES,
    # Falcon-H1's block under two kinds of attention: a joined mixer in a
    # body that repeats (G L L L, twice)
    "joined_windows": ModelConfig(
        **_TINY, num_layers=8, intermediate_size=96,
        ssm_beside_attention=True, mamba_num_heads=8, mamba_head_dim=8,
        ssm_groups=2, ssm_state_size=16, ssm_chunk=8,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 2,
        sliding_window=24),
    # Nemotron's cut in small: a lead and a period that hold every kind
    # more than once between them (E M * then M E M * twice)
    "nemotron_like": dataclasses.replace(
        FAMILIES["hybrid"], num_layers=11,
        hybrid_pattern="EM*" + "MEM*" * 2),
}

# (bodies a program holds, distinct descriptions, bodies behind a shared
# function: the call sites of the wrappers)
BODIES = {
    "dense": (1, 1, 0),                     # period 1: the scan over xs
    "side_by_side": (1, 1, 0),
    "lead_window": (6, 3, 4),               # *- | L L G L | L: L four times
    "routed_before_attention": (4, 2, 3),   # G L L L
    "hybrid": (6, 3, 5),                    # M | E M * | M E: * once, as it is
    "joined_windows": (4, 2, 3),
    "nemotron_like": (7, 3, 7),             # E M * | M E M * : every kind
}


class _Unshared(LayerPlan):
    """The same plan with no body said to repeat: the form every layer had
    before, each body traced where it stands."""
    __slots__ = ()

    @property
    def bodies(self):
        return ()


def _run(cfg, plan, layers, state, kw):
    return jax.jit(lambda: llama._run_layers(
        cfg, plan, layers, *state, **kw))


@pytest.mark.parametrize("family", sorted(PLANS))
def test_the_plan_says_which_bodies_repeat(family):
    plan = PLANS[family].layer_plan
    bodies = plan.bodies
    held, distinct, _ = BODIES[family]
    assert (len(bodies), len(set(bodies))) == (held, distinct)
    assert len(bodies) == plan.lead + plan.period * bool(plan.trips) + plan.rest
    # a description has no place: two layers of one kind at other places
    # are one body, and the buffer's layer is kept as its distance
    for layer in plan.layers:
        assert all(m.place == 0 for m in body_of(layer))
        assert [m.layer for m in body_of(layer)] == [
            m.layer - m.place for m in layer]


@pytest.mark.parametrize("family", sorted(PLANS))
def test_a_traced_step_holds_one_body_a_repeated_description(monkeypatch,
                                                             family):
    """One ``jax.jit`` wrapper a description that repeats (3 for a pattern
    like Nemotron's, none for a period-1 plan), entered once a layer that
    has it, and the mixers' own functions run once a distinct body: counted
    at ``jax.jit`` and at the mixers, wrapped here."""
    cfg = PLANS[family]
    layers = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))["layers"]
    state, kw = _step(cfg, 0)
    held, distinct, calls = BODIES[family]
    repeated = {b for b in cfg.layer_plan.bodies
                if cfg.layer_plan.bodies.count(b) > 1}
    made, entered, bodies = [], [], []
    real = jax.jit

    def counting_jit(fn, **kw):
        jitted = real(fn, **kw)
        if fn.__name__ != "layer":
            return jitted
        made.append(kw)

        def enter(*a):
            entered.append(fn)
            return jitted(*a)
        return enter

    def counted(fn):
        def wrapper(*a, **kw):
            bodies.append(fn.__name__)
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(jax, "jit", counting_jit)
    monkeypatch.setattr(llama, "_attention", counted(llama._attention))
    monkeypatch.setattr(llama, "_ffn", counted(llama._ffn))
    monkeypatch.setattr(mamba, "mixer", counted(mamba.mixer))
    jax.eval_shape(lambda layers: llama._run_layers(
        cfg, cfg.layer_plan, layers, *state, **kw), layers)
    assert (len(made), len(entered)) == (len(repeated), calls)
    # a body runs its mixers' functions once, however many layers enter it
    assert len(bodies) == sum(len(b) for b in set(cfg.layer_plan.bodies))
    assert len(set(cfg.layer_plan.bodies)) == distinct


def test_a_plan_without_a_repeat_has_no_wrapper_in_its_text():
    """A model of identical layers, and Falcon-H1's block: the program's
    text is what it was (no call, no private function of a layer)."""
    for family in ("dense", "side_by_side"):
        cfg = PLANS[family]
        layers = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0)))["layers"]
        state, kw = _step(cfg, 0)
        text = [jax.jit(lambda layers: llama._run_layers(
            cfg, plan, layers, *state, **kw)).lower(layers).as_text()
            for plan in (cfg.layer_plan, _Unshared(*cfg.layer_plan))]
        assert text[0] == text[1]
        assert "@layer" not in text[0]


@pytest.mark.parametrize("family", sorted(PLANS))
def test_the_shared_program_computes_what_the_unshared_one_does(family):
    """Hidden state, K, V, the state pool and the routed layers' counts,
    bit for bit: the same equations, a body written once and called."""
    cfg = PLANS[family]
    layers = llama.init_params(cfg, jax.random.key(cfg.num_layers))["layers"]
    state, kw = _step(cfg, cfg.num_layers)
    got = _run(cfg, cfg.layer_plan, layers, state, kw)()
    want = _run(cfg, _Unshared(*cfg.layer_plan), layers, state, kw)()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    if cfg.is_moe:
        assert int(got[4][0]) > 0


def _unshared(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with a plan that says nothing repeats (``layer_plan`` is a
    cached property: the copy is handed its value)."""
    out = dataclasses.replace(cfg)
    out.__dict__["layer_plan"] = _Unshared(*cfg.layer_plan)
    return out


@pytest.mark.parametrize("family", ["hybrid", "lead_window"])
def test_a_decode_step_through_forward_is_the_unshared_one(family):
    """``forward`` on a decode batch (one token a row): the hidden state
    the head reads, every buffer and the counts, bit for bit."""
    cfg = PLANS[family]
    params = llama.init_params(cfg, jax.random.key(3))
    (_h, ck, cv, ssm), kw = _step(cfg, 3)
    tokens = jnp.asarray([[7], [11]], jnp.int32)
    state = {"ssm": ssm, "ssm_slots": kw["ssm_slots"]} if cfg.has_ssm else {}

    def step(cfg):
        return jax.jit(lambda: llama.forward(
            params, cfg, tokens, kw["q_start"], jnp.ones((2,), jnp.int32),
            kw["block_tables"], ck, cv, moe_impl="held", moe_counts=True,
            **state))()

    got, want = step(cfg), step(_unshared(cfg))
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_a_shared_body_runs_on_a_two_way_mesh():
    """Two kinds of layer with a repeat, heads and FFN split over "model":
    an inner jit's operands keep the shardings the step gives them. The
    shared program equals the unshared one on the mesh bit for bit, and
    the one-device program within the all-reduce's rounding."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh, shard_params

    cfg = ModelConfig(
        **_TINY, num_layers=8, intermediate_size=96,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 2,
        sliding_window=24)
    assert (len(cfg.layer_plan.bodies), len(set(cfg.layer_plan.bodies))) \
        == (4, 2)
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    params = llama.init_params(cfg, jax.random.key(5))
    (_h, ck, cv, _ssm), kw = _step(cfg, 5)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 24)),
        jnp.int32)

    def step(cfg, params, mesh):
        return jax.jit(lambda p: llama.forward(
            p, cfg, tokens, kw["q_start"], kw["q_len"], kw["block_tables"],
            ck, cv, mesh=mesh))(params)

    with mesh:
        placed = shard_params(params, llama.param_logical_axes(cfg), mesh)
        got = step(cfg, placed, mesh)
        want = step(_unshared(cfg), placed, mesh)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    alone = step(cfg, params, None)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(alone)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=2e-4)


# ---------------------------------------------------------------------------
# the counter: what the compile ledger sums over the programs it recorded
# ---------------------------------------------------------------------------

def test_the_ledger_sums_bodies_over_the_programs_it_recorded():
    led = CompileLedger()
    assert (led.snapshot()["layer_bodies"],
            led.snapshot()["layer_bodies_traced"]) == (0, 0)
    for b in (8, 16, 32):
        led.record(BucketSig("decode", b, 1, 512, True, "bfloat16"), 2.0,
                   source="warmup", bodies=(13, 3))
    led.record(BucketSig("mixed", 8, 16, 512, True, "bfloat16"), 3.0,
               source="warmup", bodies=(13, 3))
    snap = led.snapshot()
    assert (snap["layer_bodies"], snap["layer_bodies_traced"]) == (52, 12)
    assert snap["cache_entries"] == 4
    # a caller that says nothing (a program with no layers of a plan)
    led.record(BucketSig("embed", 1, 16, 0, True, "bfloat16"), 1.0)
    assert led.snapshot()["layer_bodies"] == 52
    led.reset()
    assert led.snapshot()["layer_bodies"] == 0


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_the_runner_hands_the_ledger_its_plans_counts(monkeypatch, family):
    """Each program ``ModelRunner`` warms is recorded with the bodies its
    model's plan holds and the distinct among them."""
    from dynamo_tpu.engine import engine as eng
    from dynamo_tpu.obs.compile_ledger import get_compile_ledger
    from dynamo_tpu.utils.config import EngineConfig

    cfg = PLANS[family]
    held, distinct, _ = BODIES[family]
    monkeypatch.setattr(eng, "resolve_model_config", lambda path: cfg)
    core = eng.EngineCore(EngineConfig(
        model="tiny-llama", block_size=BS, num_blocks=24, max_batch_size=2,
        max_model_len=64, prefill_chunk=16, decode_bucket=(2,)))
    assert core.runner._bodies == (held, distinct)
    led = get_compile_ledger()
    before = led.snapshot()
    nblk = core.runner.max_nblk
    done = core.runner.warmup([
        BucketSig("decode", 2, 1, nblk, True, "bfloat16"),
        BucketSig("mixed", 2, 16, nblk, True, "bfloat16")])
    assert (done["compiled"], done["failed"]) == (2, 0)
    after = led.snapshot()
    assert after["layer_bodies"] - before["layer_bodies"] == 2 * held
    assert after["layer_bodies_traced"] - before["layer_bodies_traced"] \
        == 2 * distinct


# ---------------------------------------------------------------------------
# the one-body configurations: their step programs keep their text
# ---------------------------------------------------------------------------

_ROOT = Path(__file__).resolve().parents[1]
STEP_TEXT = json.loads(
    (_ROOT / "tests" / "data" / "step_text_one_body.json").read_text())


@pytest.mark.parametrize("config", sorted(STEP_TEXT))
def test_a_one_body_configurations_step_text_is_what_it_was(config, tmp_path):
    """The two Mistral cuts, the tp=4 configuration and Falcon-H1's have one
    body a program: no wrapper, and the lowered text of a decode and a
    mixed program (``tools/step_text.py``: packed inputs, the kernel, the
    described v5e) is the text the parent of PR 54 lowered, by its sha256
    (``tests/data/step_text_one_body.json``, taken from the parent's
    output). A PR that means to change these programs says so by writing
    the file again; ``diff`` of the tool's output on two checkouts shows
    what moved."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    done = subprocess.run(
        [sys.executable, str(_ROOT / "tools" / "step_text.py"), "--root",
         str(_ROOT), "--config", config, "--out", str(tmp_path), "--rows",
         "8", "--chunks", "1,16"],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    got = {p.stem: hashlib.sha256(p.read_bytes()).hexdigest()
           for p in sorted(tmp_path.glob("*.txt"))}
    assert got == STEP_TEXT[config]
