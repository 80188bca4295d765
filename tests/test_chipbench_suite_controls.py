import pytest

from chipbench.tests import test_controls as _bench
from chipbench.tests.test_controls import *  # noqa: F401,F403

# ``chipbench/tests/test_controls.py`` pins, seed by seed, which of the
# rehearsal's ``tiny-moe`` weight seeds have a tied position over the old
# rule's 0.1 / 0.05 (``OLD_RULE_FAILED``): which expert a near-tie falls to
# under bf16 is a matter of rounding. Since PR 41 the engine serves
# ``tiny-moe`` by groups (``moe_impl`` "held" for every routed model on one
# chip), whose sums round in another order than the all-experts form the
# list was read on, and seed 9 now has such a position: still tied, the
# verdict still ``correct``. That file is the benchmark's own: the repair
# (9 joins ``OLD_RULE_FAILED``) takes a ``benchmark`` PR (PERF.md section 7).
# Until it lands the pinned case stays in the run as a strict expected
# failure, and everything in it but the two lines that read the pinned list
# is held for that seed by the case below.

MOVED = 9      # the seed whose near-tie fell the other way


@pytest.fixture(autouse=True)
def _pinned_seed_whose_near_tie_fell_the_other_way(request):
    if (request.node.originalname
            == "test_as_stated_is_correct_and_every_off_position_is_tied"
            and request.node.callspec.id == str(MOVED)):
        request.applymarker(pytest.mark.xfail(
            strict=True,
            reason="chipbench/tests/test_controls.py: seed 9 joins "
                   "OLD_RULE_FAILED under the grouped path (a benchmark "
                   "PR's repair)"))


def test_the_moved_seed_is_correct_and_its_off_position_is_tied(served):
    """Every assertion of the pinned case for the seed it waives, with the
    pinned list read the other way: the run is ``correct`` with no fault,
    the tied share is under its cap, all 64 positions are accounted for, and
    the position that is over the old rule's tolerances is a tied one (its
    margin under ``margin``)."""
    assert MOVED in _bench.SEEDS and MOVED not in _bench.OLD_RULE_FAILED
    pr = served.compare("stated", MOVED)
    assert pr["faults"] == []
    assert pr["tied_share"] <= pr["max_tied_share"] < 1
    assert pr["compared"] + pr["tied"] == pr["positions"] == 64
    assert pr["tied_over_tolerance"] > 0 and pr["over_tolerance"] == \
        pr["tied_over_tolerance"]
    assert pr["margins_over_tolerance"]
    assert all(m < pr["margin"] for m in pr["margins_over_tolerance"])
    assert not _bench.readings.old_rule(pr)
