"""The comparison that decides ``correct`` is found by the configuration
(CPU, no engine; no number here is a measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider

``harness/probe.py`` resolves the reference (``<config dir>/reference.py``,
else ``harness/reference.py``) and the limits (``about.json``'s ``probe``
block, else 0.1 / 0.05 / 0.033); ``manifest.probe_faults`` holds a block to
its two readings and a reference to its contract without importing it.
``rehearse.py`` drives the whole command through both hooks; this file
checks the resolution, each fault on configuration directories it builds,
and the rule itself (``probe.decide``: tied positions, the steady
statistic) on hand-made differences. ``test_controls.py`` serves the
rehearsal's routed configuration for the controls.
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
# A routed configuration's: the reference names tied positions (here the
# first of each request) and the block says how close is tied.
ROUTED_REFERENCE = GOOD_REFERENCE + "\n\ndef routing_margin_at(params, " \
    "model, tokens, positions, pad_to=0):\n    return np.where(np.arange(" \
    "len(positions)) < 1, 0.01, 1.0).astype(np.float32)\n"
SOUND_ROUTED = {
    "logprob_tol": 0.12, "argmax_tol": 0.09, "rms_tol": 0.04,
    "margin": 0.05, "max_tied_share": 0.3,
    "readings": {
        "as_stated": {"worst_logprob_diff": 0.05, "worst_argmax_gap": 0.07,
                      "rms_logprob_diff": 0.02, "tied_share": 0.14},
        "one_precision_down": {"worst_logprob_diff": 0.11,
                               "worst_argmax_gap": 0.08,
                               "rms_logprob_diff": 0.07, "tied_share": 0.16}},
    "why": "8 routed experts, 2 a token"}


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
    compared with ``harness/reference.py`` at 0.1 / 0.05 at every position,
    as before, and at 0.033 over them all; none is tied."""
    d = (manifest.ROOT / cfg["file"]).parent
    about = json.loads((d / "about.json").read_text())
    assert probe.reference_path(d) == DEFAULT
    assert probe.limits(about) == probe.Limits(0.1, 0.05, 0.033, None, None)
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
    ref = probe.load_reference(TINY_MOE / "reference.py")
    assert callable(ref.routing_margin_at)
    lim = probe.limits(about)
    assert lim.margin > 0 and 0 < lim.max_tied_share < 1
    assert (lim.logprob_tol, lim.argmax_tol) == (0.1, 0.05)
    assert manifest.probe_faults(TINY_MOE, about) == []
    assert probe.reference_path(BENCH / "rehearsal" / "tiny") == DEFAULT


def test_limit_resolution():
    assert probe.limits({}) == probe.Limits(
        probe.LOGPROB_TOL, probe.ARGMAX_TOL, probe.RMS_TOL) == probe.Limits(
        0.1, 0.05, 0.033, None, None)
    # a block may leave rms_tol to the default
    assert probe.limits({"probe": SOUND}) == probe.Limits(0.12, 0.09, 0.033)
    assert probe.limits({"probe": {"logprob_tol": 1, "argmax_tol": 2}}) == \
        probe.Limits(1.0, 2.0, 0.033)
    assert probe.limits({"probe": SOUND_ROUTED}) == probe.Limits(
        0.12, 0.09, 0.04, 0.05, 0.3)


def test_a_sound_block_and_reference_have_no_fault(tmp_path):
    d, about = config_dir(tmp_path, SOUND, GOOD_REFERENCE)
    assert manifest.probe_faults(d, about) == []
    # Only one of the two has to fail one precision down.
    d, about = config_dir(tmp_path, edited(
        SOUND, 0.01, "readings", "one_precision_down", "worst_logprob_diff"))
    assert manifest.probe_faults(d, about) == []
    # A routed configuration's: the function and the block's two keys. Here
    # the steady statistic alone fails one precision down.
    d, about = config_dir(tmp_path, SOUND_ROUTED, ROUTED_REFERENCE)
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
     "no tolerance is below"),
    (edited(SOUND, 0.4, "argmax_tol"), "no tolerance is below"),
    # the steady statistic: the block's own limit, or the default where the
    # block gives the readings alone
    (edited(SOUND_ROUTED, 0.02, "rms_tol"), "rms_tol 0.02 is not above"),
    (edited(SOUND_ROUTED, "x", "rms_tol"), "rms_tol is not a number"),
    (edited(SOUND_ROUTED, GONE, "readings", "as_stated", "rms_logprob_diff"),
     "readings.as_stated.rms_logprob_diff is not a number"),
    (edited(edited(SOUND_ROUTED, GONE, "rms_tol"), 0.034,
            "readings", "as_stated", "rms_logprob_diff"),
     "rms_tol 0.033 is not above"),
    (edited(SOUND_ROUTED, 0.03, "readings", "one_precision_down",
            "rms_logprob_diff"), "no tolerance is below"),
    # tied positions
    (edited(SOUND_ROUTED, "0.05", "margin"), "margin is not a number"),
    (edited(SOUND_ROUTED, GONE, "margin"), "margin is not a number"),
    (edited(SOUND_ROUTED, None, "max_tied_share"),
     "max_tied_share is not a number"),
    (edited(SOUND_ROUTED, GONE, "readings", "as_stated", "tied_share"),
     "no readings.as_stated.tied_share"),
    (edited(SOUND_ROUTED, 0.31, "readings", "as_stated", "tied_share"),
     "readings.as_stated.tied_share 0.31 is over max_tied_share 0.3"),
    (edited(SOUND_ROUTED, 0.5, "readings", "one_precision_down", "tied_share"),
     "readings.one_precision_down.tied_share 0.5 is over max_tied_share"),
    (edited(SOUND_ROUTED, 1.0, "max_tied_share"), "ties nothing or everything"),
    (edited(SOUND_ROUTED, 0.0, "margin"), "ties nothing or everything"),
])
def test_each_fault_of_a_probe_block(tmp_path, block, said):
    routed = any(k in block for k in manifest.TIE_KEYS)
    d, about = config_dir(tmp_path, block,
                          ROUTED_REFERENCE if routed else None)
    faults = manifest.probe_faults(d, about)
    assert any(said in f for f in faults), faults


@pytest.mark.parametrize("block, reference, said", [
    (SOUND_ROUTED, GOOD_REFERENCE, "without a routing_margin_at"),
    (SOUND_ROUTED, None, "without a routing_margin_at"),
    (edited(SOUND, 0.5, "max_tied_share"), GOOD_REFERENCE,
     "without a routing_margin_at"),
    (SOUND, ROUTED_REFERENCE, "gives no margin and max_tied_share"),
    (None, ROUTED_REFERENCE, "gives no margin and max_tied_share"),
], ids=["margin-no-function", "margin-no-reference", "share-no-function",
        "function-no-margin", "function-no-block"])
def test_margin_and_function_come_together(tmp_path, block, reference, said):
    d, about = config_dir(tmp_path, block, reference)
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


LIM = probe.Limits(0.1, 0.05, 0.033)
TIE = probe.Limits(0.1, 0.05, 0.033, margin=0.05, max_tied_share=0.25)
QUIET = [0.01] * 32           # positions well inside every limit


def _decide(d_lp, d_arg=None, margins=None, lim=LIM):
    n = len(d_lp)
    return probe.decide([f"p{i}" for i in range(n)], d_lp,
                        d_arg or [0.0] * n, margins, lim)


def _far(n: int, last: float, rest: float = 1.0) -> list[float]:
    """``n`` margins: ``rest``, and ``last`` at the last position."""
    return [rest] * (n - 1) + [last]


@pytest.mark.parametrize("d_lp, d_arg, margins, lim, said, compared, tied", [
    # nothing is tied: as it was, every position held
    (QUIET, None, None, LIM, [], 32, 0),
    (QUIET[:31] + [0.11], None, None, LIM, ["p31: logprob off by 0.1100"], 32, 0),
    (QUIET, _far(32, 0.06, 0.0), None, LIM, ["p31: logprob off"], 32, 0),
    # a tied position over tolerance passes and is counted
    (QUIET[:31] + [2.6], _far(32, 1.9, 0.0), _far(32, 0.003), TIE, [], 31, 1),
    # an untied one fails, whatever the others' margins
    (QUIET[:30] + [0.11, 2.6], None, [1.0] * 30 + [0.2, 0.003], TIE,
     ["p30: logprob off by 0.1100, 0.0000 under the reference's best "
      "(routing margin 0.2000, not tied)"], 31, 1),
    # a margin exactly at ``margin`` is not under it
    (QUIET[:31] + [0.11], None, _far(32, 0.05), TIE, ["p31: logprob off"], 32, 0),
    # too many tied: the probe compares too little, though nothing is off
    (QUIET, None, [0.01] * 9 + [1.0] * 23, TIE,
     ["9 of 32 positions are tied (margin under 0.05): more than "
      "max_tied_share 0.25"], 23, 9),
    (QUIET, None, [0.0] * 32, TIE, ["32 of 32 positions are tied"], 0, 32),
    # the steady statistic fails alone: every position inside 0.1
    ([0.04] * 32, None, None, LIM, ["rms_logprob_diff 0.0400 over 32"], 32, 0),
    ([0.04] * 32, None, [1.0] * 32, TIE, ["rms_logprob_diff 0.0400"], 32, 0),
    # ... and is taken over the compared positions alone
    ([0.02] * 31 + [1.0], None, _far(32, 0.0), TIE, [], 31, 1),
    # far enough off, an untied position fails both ways
    (QUIET[:31] + [0.3], None, _far(32, 0.2), TIE,
     ["p31: logprob off by 0.3000", "rms_logprob_diff 0.0539"], 32, 0),
    # NaN from either side, or in a margin, is a fault
    (QUIET[:31] + [float("nan")], None, None, LIM,
     ["p31: logprob off by nan", "rms_logprob_diff nan"], 32, 0),
    (QUIET, None, _far(32, float("nan")), TIE,
     ["p31: routing margin is NaN"], 32, 0),
], ids=["quiet", "logprob-over", "argmax-over", "tied-over-passes",
        "untied-over-fails", "at-the-margin", "too-many-tied", "all-tied",
        "rms-alone", "rms-alone-routed", "rms-over-compared", "untied-far-off",
        "nan-difference", "nan-margin"])
def test_the_rule_on_hand_made_differences(d_lp, d_arg, margins, lim, said,
                                           compared, tied):
    v = _decide(d_lp, d_arg, margins, lim)
    assert (v["compared"], v["tied"]) == (compared, tied)
    assert len(v["faults"]) == len(said), v["faults"]
    for fault, part in zip(v["faults"], said):
        assert part in fault


def test_the_worst_numbers_are_of_the_compared_positions():
    v = _decide(QUIET[:6] + [0.07, 2.6], [0.0] * 6 + [0.02, 1.9],
                _far(8, 0.003), TIE)
    assert (v["worst_logprob_diff"], v["worst_argmax_gap"]) == (0.07, 0.02)
    assert (v["worst_tied_logprob_diff"], v["worst_tied_argmax_gap"]) == (2.6, 1.9)
    assert v["tied_over_tolerance"] == 1 and v["tied_share"] == 0.125
    # without margins they are the worst of all, and the rms of all
    v = _decide([0.03, 0.04], [0.01, 0.0])
    assert (v["worst_logprob_diff"], v["worst_argmax_gap"]) == (0.04, 0.01)
    assert v["rms_logprob_diff"] == pytest.approx((0.0025 / 2) ** 0.5)


def _served(d: Path, about: dict, n_req: int = 4, n_tok: int = 4):
    """A cell on ``d`` and hand-made records that agree with
    ``GOOD_REFERENCE`` (all logits 0: every logprob is -log(vocab))."""
    import math

    from harness.loadgen import Record
    from harness.traffic import Request

    cell = manifest.Cell(name="t", chips=1, config_name="t", config_dir=d,
                         model={"vocab_size": 16}, about=about, traffic={},
                         end_to_end=[], per_layer=[])
    reqs = [Request(i, 0.0, (1, 2, 3), n_tok, 0) for i in range(n_req)]
    recs = [Record(i, 0.0, 3, n_tok, tokens=[5] * n_tok,
                   logprobs=[-math.log(16)] * n_tok, finish="length")
            for i in range(n_req)]
    return cell, reqs, recs


def _compare(tmp_path, block, reference):
    import asyncio

    d, about = config_dir(tmp_path, block, reference)
    cell, reqs, recs = _served(d, about)
    return asyncio.run(probe.compare_probe(None, cell, reqs, recs))


MARGIN = "\n\ndef routing_margin_at(params, model, tokens, positions, pad_to=0):\n"


@pytest.mark.parametrize("margin_body, said", [
    ("    return np.zeros(len(positions), np.float32)\n",
     "16 of 16 positions are tied"),
    ("    return np.ones(len(positions) + 1, np.float32)\n",
     "gave routing margins (5,), not (4,)"),
    ("    return np.full(len(positions), np.nan, np.float32)\n",
     "routing margin is NaN"),
    ("    raise KeyError('router')\n", "failed: KeyError: 'router'"),
], ids=["all-tied", "wrong-shape", "nan", "raises"])
def test_a_margin_function_that_is_wrong_is_a_fault(tmp_path, margin_body, said):
    pr = _compare(tmp_path, SOUND_ROUTED, GOOD_REFERENCE + MARGIN + margin_body)
    assert pr["faults"] and any(said in f for f in pr["faults"]), pr["faults"]


def test_a_sound_margin_function_ties_what_it_names(tmp_path):
    pr = _compare(tmp_path, SOUND_ROUTED, ROUTED_REFERENCE)
    assert pr["faults"] == []
    assert (pr["positions"], pr["compared"], pr["tied"]) == (16, 12, 4)
    # the log line carries the limits used
    assert (pr["margin"], pr["max_tied_share"], pr["rms_tol"]) == (0.05, 0.3, 0.04)


@pytest.mark.parametrize("block, reference", [
    (SOUND_ROUTED, GOOD_REFERENCE), (SOUND, ROUTED_REFERENCE),
    (None, ROUTED_REFERENCE)], ids=["margin-no-function", "function-no-margin",
                                    "function-no-block"])
def test_a_run_says_what_the_manifest_says(tmp_path, block, reference):
    pr = _compare(tmp_path, block, reference)
    assert any("all three or none" in f for f in pr["faults"]), pr["faults"]
    assert pr["tied"] == 0 and pr["compared"] == 16


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
    assert manifest.probe_faults(DEFAULT.parent, {}) == []
    about = json.loads((TINY_MOE / "about.json").read_text())
    assert manifest.probe_faults(TINY_MOE, about) == []


def test_the_routed_references_experts_are_the_programs_in_float32():
    """The rehearsal's reference writes the routed layer independently; in
    float32 on both sides no rounding reaches a routing tie, and it agrees
    with the program's ``moe_mlp`` to rounding. (The engine itself computes
    in bf16: there a tie flips an expert, which ``routing_margin_at`` names.)"""
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
        got, _ = ref._experts(x[0], lp, cfg.num_experts_per_tok, True, 8)
        want = llama.moe_mlp(x, lp, cfg)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
