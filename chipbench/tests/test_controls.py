"""The controls of the comparison that decides ``correct``, on the
rehearsal's routed configuration served by the engine (CPU; no number here is
a measurement of a device).

    python3 -m pytest chipbench/tests/test_controls.py -q -p no:cacheprovider

One engine serves ``rehearsal/tiny-moe`` (8 routed experts, 2 a token, 2
layers) at several weight seeds (``readings.Seeds``: the probe's own prompts,
the weights swapped under the compiled programs). As stated the engine's
bf16 flips an expert at a few positions and the old rule, every position
inside 0.1 / 0.05, read ``correct`` false in 21 of the weight seeds 0-40
(``OLD_RULE_FAILED``); the reference names those positions as tied and the
run is correct. What has to stay not correct: every matrix rounded to int8;
a wrong routed layer that is no matter of rounding (two experts' matrices
exchanged: what an off-by-one in the held experts' offset computes; the last
chosen expert dropped), which has to fail through a position that is not
tied or through the steady statistic. A margin function that calls
everything tied is in ``test_probe.py``; ``rehearse.py`` runs one seed of
each through the whole command. All 41 seeds, either way:
``readings.py --config-dir chipbench/rehearsal/tiny-moe --seeds 0,...,40
--allow-cpu [--weights int8]`` (PERF.md, PR 30).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import readings  # noqa: E402
from harness import probe  # noqa: E402

TINY_MOE = BENCH / "rehearsal" / "tiny-moe"
# Weight seeds 0-40 at which some probed position is over 0.1 / 0.05 as
# stated (my CPU run, PR 30; each such position is tied).
OLD_RULE_FAILED = (0, 2, 4, 5, 6, 7, 8, 10, 16, 18, 19, 22, 24, 25, 28, 29,
                   30, 31, 32, 34, 35)
# Served here: the configuration's own seed (18); the two whose off
# positions are context positions attended to (22, 28); the seed nearest
# each limit as stated (35: 0.0798 of 0.1, 14: 0.043 of 0.05, 28: rms 0.0182
# of 0.0209) and with int8 weights (11: rms 0.0240; 2: 0.0258); and some
# that the old rule passed.
SEEDS = (18, 0, 2, 5, 22, 28, 35, 1, 9, 11, 14, 33)
WRONG_LAYER_SEEDS = (18, 22, 9, 11, 33)


class Served:
    """The probe's requests served once a (weights, seed), compared as often
    as a test asks."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.cell = readings.reading_cell(TINY_MOE)
        self.seeds = readings.Seeds(self.cell, lambda *a, **k: None)
        self.kept: dict = {}

    def at(self, weights: str, seed: int):
        if (weights, seed) not in self.kept:
            self.seeds.weights = weights     # the same programs serve both
            self.kept[weights, seed] = self.loop.run_until_complete(
                self.seeds.serve(seed))
        return self.kept[weights, seed]

    def compare(self, weights: str, seed: int, params=None, model=None) -> dict:
        stated, reqs, recs = self.at(weights, seed)
        cell = self.cell if model is None else dataclasses.replace(
            self.cell, model=model)
        return self.loop.run_until_complete(probe.compare_probe(
            stated if params is None else params, cell, reqs, recs))

    def close(self):
        self.loop.run_until_complete(self.seeds.close())
        self.loop.close()


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.close()


def _through_a_compared_position(pr: dict) -> bool:
    """``correct`` is false, and not because too much was tied: through a
    position that is not tied, or through the steady statistic."""
    return any("not tied" in f or "rms_logprob_diff" in f for f in pr["faults"])


@pytest.mark.parametrize("seed", SEEDS)
def test_as_stated_is_correct_and_every_off_position_is_tied(served, seed):
    pr = served.compare("stated", seed)
    assert pr["faults"] == []
    assert pr["tied_share"] <= pr["max_tied_share"] < 1
    assert pr["compared"] + pr["tied"] == pr["positions"] == 64
    # what the old rule read: a position over 0.1 / 0.05, which is tied
    assert (pr["tied_over_tolerance"] > 0) == (seed in OLD_RULE_FAILED)
    assert all(m < pr["margin"] for m in pr["margins_over_tolerance"])
    assert readings.old_rule(pr) == (seed not in OLD_RULE_FAILED)


def test_the_rehearsals_own_seed_is_one_the_old_rule_failed():
    about = json.loads((TINY_MOE / "about.json").read_text())
    assert about["seed"] in OLD_RULE_FAILED and about["seed"] in SEEDS
    assert "seed_why" not in about


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_weights_are_not_correct(served, seed):
    pr = served.compare("int8", seed)
    assert pr["faults"] and _through_a_compared_position(pr), pr["faults"]
    assert pr["tied_share"] <= pr["max_tied_share"]


def exchanged(params, a: int = 0, b: int = 1):
    """The parameters with the matrices of experts ``a`` and ``b``
    exchanged in every layer, the router as it was."""
    import jax.numpy as jnp

    order = jnp.arange(params["layers"]["w_gate"].shape[1])
    order = order.at[a].set(b).at[b].set(a)
    layers = {k: v[:, order] if k in ("w_gate", "w_up", "w_down") else v
              for k, v in params["layers"].items()}
    return {**params, "layers": layers}


@pytest.mark.parametrize("seed", WRONG_LAYER_SEEDS)
def test_exchanged_experts_are_not_correct(served, seed):
    stated, _, _ = served.at("stated", seed)
    pr = served.compare("stated", seed, params=exchanged(stated))
    assert _through_a_compared_position(pr), pr["faults"]
    assert pr["tied_share"] <= pr["max_tied_share"]


@pytest.mark.parametrize("seed", WRONG_LAYER_SEEDS)
def test_a_dropped_expert_is_not_correct(served, seed):
    model = dict(served.cell.model)
    model["num_experts_per_tok"] -= 1
    pr = served.compare("stated", seed, model=model)
    assert _through_a_compared_position(pr), pr["faults"]
    assert pr["tied_share"] <= pr["max_tied_share"]
