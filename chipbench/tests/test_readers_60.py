"""``kv.block_write_tokens_pct`` (PR 60) on hand-made contexts: the share of
the window's live tokens whose K and V went into the paged cache by blocks,
off the scheduling ledger's two snapshots (CPU, no engine; no number here is
a measurement).

    python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from harness import manifest, measure  # noqa: E402

NAME = "kv.block_write_tokens_pct"
CELL = "mistral-7b.longprompt"


def _ctx(first: dict | None, last: dict | None) -> measure.Context:
    c0, c1 = ({} if s is None else {"sched": s} for s in (first, last))
    return measure.Context(window=(100.0, 151.0), window_wall=(1e9, 1e9 + 51),
                           chips=1, records=[], counters=(c0, c1))


def _sched(live: int, by_blocks: int | None) -> dict:
    s = {"live_tokens_total": live, "sched_tokens_total": live + 900,
         "rect_tokens_total": live + 900}
    if by_blocks is not None:
        s["kv_block_written_tokens_total"] = by_blocks
    return s


@pytest.mark.parametrize("ctx, expect", [
    # the long-prompt cell: 40,960 prompt tokens in chunk steps that carried
    # 310 decode rows' tokens too, 1,100 tokens in decode programs
    (_ctx(_sched(9_000, 8_400), _sched(9_000 + 42_370, 8_400 + 41_270)),
     100.0 * 41_270 / 42_370),
    # a window of decode programs alone: every token kept the scatter
    (_ctx(_sched(500, 400), _sched(2_500, 400)), 0.0),
    # a quantized pool, the dense gather, rows split over "data": the
    # counter is there and stands at 0
    (_ctx(_sched(0, 0), _sched(5_000, 0)), 0.0),
    # every step of the window a chunk step
    (_ctx(_sched(0, 0), _sched(4_096, 4_096)), 100.0),
    # the parent of PR 60 has no such count; no step ran; no ledger
    (_ctx(_sched(100, None), _sched(5_000, None)), None),
    (_ctx(_sched(700, 600), _sched(700, 600)), None),
    (_ctx(None, None), None),
], ids=["long_prompts", "decode_only", "keeps_the_scatter", "chunks_only",
        "parent", "no_step", "no_ledger"])
def test_block_write_tokens_pct_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader(NAME).read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))
    assert (NAME in measure.per_layer(ctx, [NAME])) == (expect is not None)


def test_the_manifest_enters_it_as_the_reader_says():
    """One entry, what the reader declares, under the layer the benchmark
    named for ``device.cache_copy_pct``, in the long-prompt cell alone (the
    one cell that judges ``ttft_mean_ms``)."""
    bench = manifest.load_benchmark()
    assert manifest.check() == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    reader = measure.load_reader(NAME)
    assert entries[NAME] == {
        "name": NAME, "unit": reader.unit, "better": "higher",
        "source": "program_counter", "layer": reader.layer,
        "moves": "ttft_mean_ms", "workloads": [CELL]}
    assert reader.source == "program_counter"
    assert reader.layer == entries["device.cache_copy_pct"]["layer"]
    for w in bench["workloads"]:
        judged, layer = manifest.cell_metrics(bench, w["name"])
        assert (NAME in layer) == (w["name"] == CELL)
        if w["name"] == CELL:
            assert "ttft_mean_ms" in judged
