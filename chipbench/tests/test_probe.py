"""The comparison that decides ``correct`` is found by the configuration
(CPU, no engine; no number here is a measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider

``harness/probe.py`` resolves the reference (``<config dir>/reference.py``,
else ``harness/reference.py``) and the two tolerances (``about.json``'s
``probe`` block, else 0.1 / 0.05); ``manifest.probe_faults`` holds a block to
its two readings and a reference to its contract without importing it.
``rehearse.py`` drives the whole command through both hooks; this file
checks the resolution and each fault on configuration directories it builds.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from harness import manifest, probe  # noqa: E402

DEFAULT = BENCH / "harness" / "reference.py"
TINY_MOE = BENCH / "rehearsal" / "tiny-moe"
ACCEPTED = manifest.load_benchmark()["configs"]
# A block that stands: both tolerances above what the configuration read as
# stated, and the argmax one below what it read one precision down.
SOUND = {
    "logprob_tol": 0.12, "argmax_tol": 0.09,
    "readings": {
        "as_stated": {"worst_logprob_diff": 0.05, "worst_argmax_gap": 0.07},
        "one_precision_down": {"worst_logprob_diff": 0.11,
                               "worst_argmax_gap": 0.3}},
    "why": "40 layers over four chips"}
GOOD_REFERENCE = "import numpy as np\n\n\ndef logits_at(params, model, " \
    "tokens, positions, pad_to=0):\n    return np.zeros((len(positions), " \
    "model['vocab_size']), np.float32)\n"


def config_dir(tmp_path: Path, probe_block=None, reference: str | None = None):
    about = {"source": "none", "reduced": {}, "seed": 0, "engine": {}}
    if probe_block is not None:
        about["probe"] = probe_block
    (tmp_path / "about.json").write_text(json.dumps(about))
    (tmp_path / "config.json").write_text(json.dumps({"vocab_size": 16}))
    if reference is not None:
        (tmp_path / "reference.py").write_text(reference)
    return tmp_path, about


GONE = object()


def edited(block: dict, value, *path: str) -> dict:
    """A copy of ``block`` with ``value`` at ``path``, or without that key
    (``GONE``)."""
    block = json.loads(json.dumps(block))
    d = block
    for key in path[:-1]:
        d = d[key]
    if value is GONE:
        del d[path[-1]]
    else:
        d[path[-1]] = value
    return block


@pytest.mark.parametrize("cfg", ACCEPTED, ids=lambda c: c["name"])
def test_accepted_configurations_keep_their_comparison(cfg):
    """No accepted configuration brings a reference or a block: each is
    compared with ``harness/reference.py`` at 0.1 / 0.05, as before."""
    d = (manifest.ROOT / cfg["file"]).parent
    about = json.loads((d / "about.json").read_text())
    assert probe.reference_path(d) == DEFAULT
    assert probe.tolerances(about) == (0.1, 0.05)
    assert "probe" not in about and not (d / "reference.py").exists()
    assert manifest.probe_faults(d, about) == []


def test_reference_resolution(tmp_path):
    assert probe.reference_path(tmp_path) == DEFAULT           # none of its own
    assert callable(probe.load_reference(DEFAULT).logits_at)
    d, _ = config_dir(tmp_path, reference=GOOD_REFERENCE)
    assert probe.reference_path(d) == d / "reference.py"       # its own
    own = probe.load_reference(d / "reference.py")
    assert own.logits_at(None, {"vocab_size": 16}, [1, 2], [0, 1]).shape == (2, 16)
    (d / "reference.py").write_text("def logits(params):\n    return 0\n")
    with pytest.raises(AttributeError, match="logits_at"):
        probe.load_reference(d / "reference.py")


def test_the_rehearsals_routed_configuration_brings_its_own_reference():
    about = json.loads((TINY_MOE / "about.json").read_text())
    assert probe.reference_path(TINY_MOE) == TINY_MOE / "reference.py"
    assert probe.tolerances(about) == (0.1, 0.05)
    assert manifest.probe_faults(TINY_MOE, about) == []
    assert probe.reference_path(BENCH / "rehearsal" / "tiny") == DEFAULT


def test_tolerance_resolution():
    assert probe.tolerances({}) == (probe.LOGPROB_TOL, probe.ARGMAX_TOL) == (0.1, 0.05)
    assert probe.tolerances({"probe": SOUND}) == (0.12, 0.09)
    assert probe.tolerances({"probe": {"logprob_tol": 1, "argmax_tol": 2}}) == (1.0, 2.0)


def test_a_sound_block_and_reference_have_no_fault(tmp_path):
    d, about = config_dir(tmp_path, SOUND, GOOD_REFERENCE)
    assert manifest.probe_faults(d, about) == []
    # Only one of the two has to fail one precision down.
    d, about = config_dir(tmp_path, edited(
        SOUND, 0.01, "readings", "one_precision_down", "worst_logprob_diff"))
    assert manifest.probe_faults(d, about) == []


@pytest.mark.parametrize("block, said", [
    (edited(SOUND, GONE, "why"), "no why"),
    (edited(SOUND, "", "why"), "no why"),
    (edited(SOUND, GONE, "readings"), "no readings.as_stated"),
    (edited(SOUND, GONE, "readings", "as_stated"), "no readings.as_stated"),
    (edited(SOUND, GONE, "readings", "one_precision_down"),
     "no readings.one_precision_down"),
    (edited(SOUND, GONE, "argmax_tol"), "argmax_tol is not a number"),
    (edited(SOUND, "0.1", "logprob_tol"), "logprob_tol is not a number"),
    (edited(SOUND, GONE, "readings", "as_stated", "worst_argmax_gap"),
     "readings.as_stated.worst_argmax_gap is not a number"),
    (edited(SOUND, 0.05, "logprob_tol"), "logprob_tol 0.05 is not above"),
    (edited(SOUND, 0.06, "argmax_tol"), "argmax_tol 0.06 is not above"),
    (edited(SOUND, 0.09, "readings", "one_precision_down", "worst_argmax_gap"),
     "neither tolerance is below"),
    (edited(SOUND, 0.4, "argmax_tol"), "neither tolerance is below"),
])
def test_each_fault_of_a_probe_block(tmp_path, block, said):
    d, about = config_dir(tmp_path, block)
    faults = manifest.probe_faults(d, about)
    assert any(said in f for f in faults), faults


@pytest.mark.parametrize("source, said", [
    ("def logits(params):\n    return 0\n", "defines no logits_at"),
    ("class logits_at:\n    pass\n", "defines no logits_at"),
    ("import dynamo_tpu\n" + GOOD_REFERENCE, "imports dynamo_tpu"),
    ("from dynamo_tpu.models import llama\n" + GOOD_REFERENCE,
     "imports dynamo_tpu"),
    (GOOD_REFERENCE.replace("    return", "    import dynamo_tpu.models.llama"
                            " as m\n    return"), "imports dynamo_tpu"),
], ids=["other-name", "not-a-function", "import", "from-import", "inside"])
def test_each_fault_of_a_reference(tmp_path, source, said):
    d, about = config_dir(tmp_path, reference=source)
    faults = manifest.probe_faults(d, about)
    assert any(said in f for f in faults), faults


def test_manifest_check_reports_a_configurations_faults(tmp_path):
    """``manifest.check`` runs ``probe_faults`` for every configuration of
    the manifest, and is empty on the tree."""
    assert manifest.check() == []
    d, _ = config_dir(tmp_path, edited(SOUND, 0.01, "logprob_tol"),
                      "import dynamo_tpu\n")
    bench = manifest.load_benchmark()
    bench = dict(bench, configs=bench["configs"] + [{
        "name": "tmp", "source": "none", "reduced": [],
        "file": str(d / "config.json")}])
    faults = manifest.check(bench)
    assert len(faults) == 3 and all(f.startswith("config tmp: ") for f in faults)
    assert {f.split(": ")[1].split(" ")[0] for f in faults} == {
        "probe", "reference.py"}


def test_the_default_references_file_keeps_the_contract():
    """What ``probe_faults`` asks of a configuration's reference holds for
    ``harness/reference.py`` and the rehearsal's too."""
    for d in (DEFAULT.parent, TINY_MOE):
        assert manifest.probe_faults(d, {}) == []


def test_the_routed_references_experts_are_the_programs_in_float32():
    """The rehearsal's reference writes the routed layer independently; in
    float32 on both sides no rounding reaches a routing tie, and it agrees
    with the program's ``moe_mlp`` to rounding. (The engine itself computes
    in bf16: there a tie flips an expert, ``about.json``'s ``seed_why``.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import resolve_model_config

    cfg = resolve_model_config(str(TINY_MOE))
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.num_shared_experts) == (8, 2, 64, 1)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          llama.init_params(cfg, jax.random.key(3)))
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.key(4), (1, 96, cfg.hidden_size), jnp.float32)
    ref = probe.load_reference(TINY_MOE / "reference.py")
    with jax.default_matmul_precision("highest"):
        got = ref._experts(x[0], lp, cfg.num_experts_per_tok, True)
        want = llama.moe_mlp(x, lp, cfg)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
