"""The benchmark's per-layer readers (``chipbench/layers/``) and the trace
events they read (``chipbench/harness/xevents.py``): the manifest against its
files, the counter readers on hand-made contexts, the trace readers on a
small trace recorded on the chip. No number here is a measurement."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import manifest, measure, xevents  # noqa: E402

REHEARSAL = ROOT / "chipbench" / "rehearsal"
# mistral-nemo-12b.chat on the v5e with this PR's program (PR 25, my chip run
# 1): 0.25 s of the traced slice (six decode steps, one arriving prompt and
# its mixed step), cut to the device's three lines and the host's spans.
CHIP_TRACE = REHEARSAL / "v5e-nemo-chat-named-0.25s.xplane.pb"
# mistral-7b.chat on PR 24's program: no named step programs, no engine spans.
OLD_TRACE = REHEARSAL / "v5e-chat-0.3s.xplane.pb"
CPU_TRACE = REHEARSAL / "cpu-5-steps.xplane.pb"

BENCH = manifest.load_benchmark()
NEW_COUNTER = ("engine.inbox_wait_mean_ms", "sched.queue_wait_mean_ms",
               "engine.prefill_mean_ms", "engine.host_ms_per_step",
               "engine.record_ms_per_step", "engine.idle_wait_pct",
               "engine.device_wait_pct")
NEW_TRACE = ("engine.decode_step_dev_ms", "engine.mixed_step_dev_ms",
             "device.cache_copy_pct", "device.attention_pct",
             "mesh.collective_exposed_pct")
POOL = [10, 2837, 16, 8, 128]            # the recorded cell's pool


def test_manifest_names_files_that_exist():
    assert manifest.check() == []
    names = {m["name"] for m in BENCH["per_layer"]}
    # What needs a four-chip cell is absent exactly while no cell has four
    # chips (PERF.md section 7), and such cells stay few: a quarter of the
    # cells at most, one always allowed.
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert set(NEW_COUNTER + NEW_TRACE) - names == (
        set() if four else {"mesh.collective_exposed_pct"})
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_four_chip_cells_files_are_ready_for_their_entries():
    """``mistral-nemo-12b.tp4.chat`` was measured in PR 25; until
    ``BENCHMARK.json`` holds its entries (PERF.md section 7 gives them),
    they are laid over it here. Its files are data: with the entries, the
    manifest finds them and has no fault."""
    about = ROOT / "chipbench/configs/mistral-nemo-12b-tp4/about.json"

    def with_entry(key, entry):
        have = {e["name"] for e in BENCH[key]}
        return BENCH[key] + ([] if entry["name"] in have else [entry])

    bench = dict(
        BENCH,
        configs=with_entry("configs", {
            "name": "mistral-nemo-12b-tp4",
            "source": json.loads(about.read_text())["source"],
            "file": "chipbench/configs/mistral-nemo-12b-tp4/config.json",
            "reduced": []}),
        workloads=with_entry("workloads", {
            "name": "mistral-nemo-12b.tp4.chat",
            "config": "mistral-nemo-12b-tp4", "traffic": "chat", "chips": 4}),
        per_layer=with_entry("per_layer", {
            "name": "mesh.collective_exposed_pct", "moves": "itl_p95_ms",
            "workloads": ["mistral-nemo-12b.tp4.chat"]}))
    assert manifest.check(bench) == []
    cell = manifest.load_cell("mistral-nemo-12b.tp4.chat", bench)
    assert cell.about["engine"]["tp"] == 4 and cell.about["reduced"] == {}
    assert cell.model["num_hidden_layers"] == 40 and cell.chips == 4
    assert cell.traffic["rate_per_s"] == 0.8
    assert "mesh.collective_exposed_pct" in cell.per_layer
    assert "mesh.collective_exposed_pct" not in \
        manifest.load_cell("mistral-7b.chat", bench).per_layer
    sweep = json.loads((ROOT / "chipbench/sweeps/"
                        "mistral-nemo-12b.tp4.chat.json").read_text())
    assert sweep["knee_per_s_all_chips"] == 1.0
    assert cell.traffic["rate_per_s"] == 0.8 * sweep["knee_per_s_all_chips"]
    mod = measure.load_reader("mesh.collective_exposed_pct")
    assert (mod.unit, mod.moves, mod.source) == \
        ("%", "itl_p95_ms", "device_trace")


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_file_agrees_with_its_entry(entry):
    mod = measure.load_reader(entry["name"])
    for key in ("name", "unit", "layer", "moves", "source"):
        assert getattr(mod, key) == entry[key], key
    assert callable(mod.read) and mod.__doc__


def _loop(**over) -> dict:
    keys = ("engine.idle_wait", "engine.inbox", "engine.plan",
            "engine.dispatch", "engine.finalize.wait", "engine.finalize.host",
            "engine.record", "engine.post", "engine.compile")
    return {k: float(over.get(k, 0.0)) for k in keys}


def _ctx(c0: dict, c1: dict, seconds: float = 50.0, chips: int = 1,
         trace: dict | None = None) -> measure.Context:
    return measure.Context(window=(100.0, 100.0 + seconds),
                           window_wall=(1e9, 1e9 + seconds), chips=chips,
                           records=[], counters=(c0, c1), trace=trace)


def _counters() -> tuple[dict, dict]:
    c0 = {"num_steps": 100, "ttft_count": 10, "ttft_inbox_s": 1.0,
          "ttft_queue_s": 2.0, "ttft_prefill_s": 5.0, "loop": _loop()}
    c1 = {"num_steps": 1100, "ttft_count": 50, "ttft_inbox_s": 1.8,
          "ttft_queue_s": 3.0, "ttft_prefill_s": 15.0,
          "kv_cache_shape": POOL,
          "loop": _loop(**{"engine.idle_wait": 5.0, "engine.inbox": 0.1,
                           "engine.plan": 0.5, "engine.dispatch": 2.4,
                           "engine.compile": 0.4,
                           "engine.finalize.wait": 40.0,
                           "engine.finalize.host": 0.6,
                           "engine.record": 0.3, "engine.post": 0.5})}
    return c0, c1


@pytest.mark.parametrize("name, expect", [
    ("engine.inbox_wait_mean_ms", 20.0),       # 0.8 s over 40 sequences
    ("sched.queue_wait_mean_ms", 25.0),
    ("engine.prefill_mean_ms", 250.0),
    # (0.1 + 0.5 + 2.4 - 0.4 + 0.6 + 0.3 + 0.5) s over 1000 steps
    ("engine.host_ms_per_step", 4.0),
    ("engine.record_ms_per_step", 0.3),
    ("engine.idle_wait_pct", 10.0),            # 5 s of 50
    ("engine.device_wait_pct", 80.0),
])
def test_counter_reader_on_a_hand_made_context(name, expect):
    value = measure.load_reader(name).read(_ctx(*_counters()))
    assert value == pytest.approx(expect)


@pytest.mark.parametrize("name", NEW_COUNTER + NEW_TRACE)
def test_reader_finds_nothing_in_a_program_without_the_counters(name,
                                                                 monkeypatch):
    """The parent commit has no loop clock, no TTFT parts, no cache shape
    and no named programs: every new reader returns None, none raises."""
    monkeypatch.setattr(xevents, "newest_xplane", lambda *a, **k: OLD_TRACE)
    old = ({"num_steps": 1}, {"num_steps": 9})
    trace = {"busy_s": 0.2, "window_s": 0.3}
    assert measure.load_reader(name).read(_ctx(*old, trace=trace)) is None
    assert measure.load_reader(name).read(_ctx(*old, chips=4, trace=trace)) \
        in (None, 0.0)        # one chip's trace holds no collective


def test_ttft_parts_with_no_first_token_in_the_window():
    c0, c1 = _counters()
    c1["ttft_count"] = c0["ttft_count"]
    assert measure.load_reader("sched.queue_wait_mean_ms").read(
        _ctx(c0, c1)) is None


def test_hlo_text_helpers():
    hlo = ("%constant_dynamic-update-slice_fusion.4 = bf16[16,2183,16,8,128]"
           "{4,3,2,1,0:T(8,128)(2,1)} fusion(bf16[16,2183,16,8,128] %p)")
    assert xevents.result_shape(hlo) == (16, 2183, 16, 8, 128)
    assert xevents.instruction(hlo) == \
        "%constant_dynamic-update-slice_fusion.4"
    start = ("%copy-start = (bf16[34928,8,128]{2,1,0}, bf16[34928,8,128]"
             "{2,1,0:S(1)}, u32[]{:S(2)}) copy-start(bf16[34928,8,128] %x)")
    assert xevents.result_shape(start) == (34928, 8, 128)
    assert xevents.result_shape("%tuple.1 = () tuple()") is None
    assert xevents.result_shape("%c = s32[] constant(0)") == ()
    for name in ("%all-reduce.7", "all-gather", "%all-reduce-start.2",
                 "%collective-permute-done", "%reduce-scatter.11",
                 "%all-to-all.3"):
        assert xevents.COLLECTIVE.match(name), name
    for name in ("%fusion.3", "%all-reduce_fusion.1", "%paged_attention.1"):
        assert not xevents.COLLECTIVE.match(name), name


def test_subtract_intervals():
    assert xevents.subtract([(0, 10)], []) == 10
    assert xevents.subtract([(0, 10)], [(2, 4), (6, 7)]) == 7
    assert xevents.subtract([(0, 4), (8, 12)], [(3, 9)]) == 6
    assert xevents.subtract([(5, 6)], [(0, 10)]) == 0


def test_collective_exposed_on_hand_made_events():
    """A synchronous all-reduce alone on the core is all exposed; an
    asynchronous one is exposed only where nothing runs beside it."""
    ar = "%all-reduce.1 = bf16[8,5120]{1,0} all-reduce(bf16[8,5120] %x)"
    st = "%all-gather-start.2 = (f32[8], f32[32]) all-gather-start(f32[8] %y)"
    dn = "%all-gather-done.5 = f32[32] all-gather-done(%all-gather-start.2)"
    ops = [("%while.1 = (s32[]) while(%t)", 0, 100),
           ("%fusion.1 = bf16[8] fusion(%a)", 0, 10),
           (ar, 10, 30),                       # 20 exposed
           ("%fusion.2 = bf16[8] fusion(%b)", 30, 40),
           (st, 40, 41),
           ("%fusion.3 = bf16[8] fusion(%c)", 41, 50),
           (dn, 50, 60)]                       # 40..60 less 40..50 = 10
    ev = xevents.Events(ops=[ops], async_ops=[[]])
    assert xevents.collective_exposed_ns(ev) == 30
    assert xevents.collective_exposed_ns(xevents.Events()) is None
    # The same asynchronous pair seen on the Async XLA Ops line alone: the
    # instant in which the core issued it (40..41) now holds nothing else.
    ev = xevents.Events(ops=[[o for o in ops if o[0] not in (st, dn)]],
                        async_ops=[[(st, 40, 60)]])
    assert xevents.collective_exposed_ns(ev) == 31
    reader = measure.load_reader("mesh.collective_exposed_pct")
    assert reader.read(_ctx({}, {}, chips=4, trace=None)) is None


def test_recorded_chip_trace_has_names_and_engine_spans():
    ev = xevents.load(CHIP_TRACE)
    assert len(ev.ops) == 1 and len(ev.ops[0]) > 500
    programs = {name.split("(")[0] for name, _, _ in ev.modules}
    assert {"jit_step_decode_b8_n64", "jit_step_mixed_b8_t256_n64"} <= programs
    assert any(xevents.instruction(hlo).startswith("%paged_attention")
               for hlo, _, _ in ev.ops[0])
    spans = {name for name, *_ in ev.host}
    assert {"engine.plan", "engine.dispatch", "engine.finalize.wait",
            "engine.finalize.host", "engine.record", "engine.post"} <= spans
    dispatch = next(a for n, _, _, a in ev.host if n == "engine.dispatch")
    assert {"kind", "b", "t", "nblk", "rows"} <= set(dispatch)
    assert 0 < ev.busy_ns() <= max(e for _, _, e in ev.ops[0])


def test_trace_readers_on_the_recorded_chip_trace(monkeypatch):
    monkeypatch.setattr(xevents, "newest_xplane", lambda *a, **k: CHIP_TRACE)
    ctx = _ctx(*_counters(), trace={"busy_s": 0.2, "window_s": 0.25})
    read = lambda name: measure.load_reader(name).read(ctx)
    assert 5.0 < read("engine.decode_step_dev_ms") < 100.0
    copy, attn = read("device.cache_copy_pct"), read("device.attention_pct")
    assert 20.0 < copy < 80.0 and 0.0 < attn < 40.0 and copy + attn < 100.0
    assert read("engine.mixed_step_dev_ms") > \
        2 * read("engine.decode_step_dev_ms")
    assert read("mesh.collective_exposed_pct") is None       # one chip
    # Another pool size: nothing in this trace has that shape.
    ctx.counters[1]["kv_cache_shape"] = [10, 999, 16, 8, 128]
    assert read("device.cache_copy_pct") == 0.0


def test_cpu_trace_has_no_device_plane(monkeypatch):
    monkeypatch.setattr(xevents, "newest_xplane", lambda *a, **k: CPU_TRACE)
    ev = xevents.current()
    assert ev.ops == [] and ev.modules == []
    ctx = _ctx(*_counters(), trace=None)
    for name in NEW_TRACE:
        assert measure.load_reader(name).read(ctx) is None, name


def test_newest_xplane_takes_only_this_runs_trace(tmp_path):
    assert xevents.newest_xplane(tmp_path) is None
    old = tmp_path / "a" / "old.xplane.pb"
    new = tmp_path / "b" / "plugins" / "new.xplane.pb"
    for p in (old, new):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
    import os
    os.utime(old, (1000.0, 1000.0))
    assert xevents.newest_xplane(tmp_path, since=2000.0) == new
    assert xevents.newest_xplane(tmp_path, since=0.0) == new
    assert xevents.process_start() > 0


# ---------------------------------------------------------------------------
# the routed configuration's cell and its four readers (PR 39)
# ---------------------------------------------------------------------------

ROUTED_CELL = "k-exaone-236b.reasoning"
# Both routed cells: one chip's share of a wider router (PR 39) and a model
# whose every expert is held here (PR 41). What each states of itself.
ROUTED_CELLS = {
    ROUTED_CELL: {
        "config": "k-exaone-236b-a23b-ep8-l5",
        "experts": ("num_experts", 16, "num_experts_published", 128,
                    "num_experts_per_tok", 8)},
    "smallthinker-21b.reasoning": {
        "config": "smallthinker-21b-a3b-l12",
        "experts": ("moe_num_primary_experts", 64, None, None,
                    "moe_num_active_primary_experts", 6)},
}
ROUTED = ("moe.rows_per_expert_step", "device.moe_pct",
          "moe.expert_gemm_roofline_pct", "attn.blocks_walked_pct",
          "moe.experts_touched_pct", "moe.streamed_layer_steps_pct")
MOE = {"experts_held": 16, "router_width": 128, "experts_per_token": 8,
       "routed_layers": 4, "hidden_size": 6144, "expert_width": 2048,
       "bytes_per_param": 2,
       "shapes": [[16, 6144, 2048], [16, 2048, 6144], [6144, 128],
                  [6144, 2048], [2048, 6144]]}


def _routed_counters() -> tuple[dict, dict]:
    """A window of 1,000 steps of 4 routed layers at 32 rows: 4,000
    layer-steps, 32 rows and 14 experts touched a layer-step; 5 layers, one
    full, walking a fifth of what they hold and all of it."""
    sched0 = {"moe_layer_steps_total": 400, "moe_rows_total": 10_000,
              "moe_experts_touched_total": 5_000,
              "moe_largest_group_total": 2_000,
              "kv_blocks_live_total": 1_000, "kv_blocks_walked_total": 1_500}
    sched1 = {"moe_layer_steps_total": 4_400, "moe_rows_total": 138_000,
              "moe_experts_touched_total": 61_000,
              "moe_largest_group_total": 22_000,
              "kv_blocks_live_total": 101_000,
              "kv_blocks_walked_total": 141_500}
    dev = {"device_kind": "TPU v5 lite"}
    return ({"sched": sched0}, {"sched": sched1, "moe": MOE, "device": dev,
                                "kv_cache_shape": [5, 19291, 16, 8, 128]})


@pytest.mark.parametrize("name", sorted(ROUTED_CELLS))
def test_the_routed_cell_reports_what_it_is_judged_on(name):
    facts = ROUTED_CELLS[name]
    cell = manifest.load_cell(name)
    assert cell.chips == 1 and cell.config_name == facts["config"]
    assert cell.end_to_end == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    assert set(ROUTED) <= set(cell.per_layer)
    for other in ("mistral-7b.chat", "mistral-7b.longprompt",
                  "mistral-nemo-12b.chat"):
        assert not set(ROUTED) & set(manifest.load_cell(other).per_layer)
    tr = cell.traffic
    assert tr["prompt_tokens"]["max"] + tr["output_tokens"]["max"] <= 6144
    assert tr["max_rows"] <= 64 and tr["rate_per_s"] > 0
    # The cell follows its sweep by the issue's rule: 0.8 x the highest rate
    # that kept up, and the decode bucket above the rows in flight at that
    # rate in the cell's own window: no row bucket is warmed that the
    # traffic does not reach.
    from harness import sut

    from dynamo_tpu.obs.compile_ledger import sig_for_rows
    sweep = json.loads((ROOT / "chipbench/sweeps" / f"{name}.json").read_text())
    kept_up = [r["rate_per_s"] for r in sweep["rates"] if r["kept_up"]]
    assert sweep["knee_per_s"] == max(kept_up)
    assert tr["rate_per_s"] == pytest.approx(0.8 * sweep["knee_per_s"])
    at = sweep["at_cell_rate_51s"]
    assert at["rate_per_s"] == tr["rate_per_s"] and not at["failed"]
    ec = sut.engine_config(cell.config_dir, cell.about)
    assert tr["max_rows"] == sig_for_rows(
        "decode", at["in_flight_max"], 1, 1, ec).b
    # the experts held, the router's width and the choices a token, as
    # config.json states them under the model's own keys
    held_key, held, wide_key, wide, k_key, k = facts["experts"]
    assert (cell.model[held_key], cell.model[k_key]) == (held, k)
    assert wide_key is None or cell.model[wide_key] == wide
    assert sorted(cell.about["reduced"]) == sorted(
        next(c for c in BENCH["configs"]
             if c["name"] == cell.config_name)["reduced"])
    assert set(cell.model["assumed"]) == set(cell.about["assumed"])


def test_every_cell_reports_what_its_per_layer_metrics_move():
    """A per-layer entry names one end-to-end metric, and every cell it is
    read in has to report that metric (``manifest.cell_metrics``): the new
    cell's name in a ``workloads`` list is held to it like the others."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for w in BENCH["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.per_layer, w["name"]
        for metric in cell.per_layer:
            assert entries[metric]["moves"] in cell.end_to_end, (
                w["name"], metric)
        listed = {m for m, e in entries.items()
                  if w["name"] in e.get("workloads", ())}
        assert listed <= set(cell.per_layer), w["name"]


def _touched(steps: int, touched: int | None, held: int | None = 64):
    sched0 = {"moe_layer_steps_total": 100, "moe_experts_touched_total": 700}
    sched1 = {"moe_layer_steps_total": 100 + steps}
    if touched is not None:
        sched1["moe_experts_touched_total"] = 700 + touched
    return _ctx({"sched": sched0},
                {"sched": sched1, **({"moe": {"experts_held": held}}
                                     if held else {})})


@pytest.mark.parametrize("ctx, expect", [
    # 1,200 layer-steps of 64 held experts: none, 51 of 64, every one
    (_touched(1_200, 0), 0.0),
    (_touched(1_200, 61_200), 100.0 * 51 / 64),
    (_touched(1_200, 76_800), 100.0),
    # no routed layer-step in the window; a program without the counter; a
    # model without a routed layer
    (_touched(0, 0), None),
    (_touched(1_200, None), None),
    (_touched(1_200, 61_200, held=None), None),
], ids=["none", "a_fraction", "all", "no_steps", "no_counter", "no_facts"])
def test_experts_touched_share_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("moe.experts_touched_pct").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def _streamed(steps: int, streamed: int | None):
    sched0 = {"moe_layer_steps_total": 100}
    sched1 = {"moe_layer_steps_total": 100 + steps}
    if streamed is not None:
        sched0["moe_streamed_layer_steps_total"] = 60
        sched1["moe_streamed_layer_steps_total"] = 60 + streamed
    return _ctx({"sched": sched0}, {"sched": sched1})


@pytest.mark.parametrize("ctx, expect", [
    # 1,200 layer-steps: none of a program that streams its experts (every
    # expert too large, or every step a chunk's), all but the five chunk
    # steps' twelve layers, every one
    (_streamed(1_200, 0), 0.0),
    (_streamed(1_200, 1_140), 95.0),
    (_streamed(1_200, 1_200), 100.0),
    # no routed layer-step in the window; a program without the counter
    # (the parent's); a model without a routed layer
    (_streamed(0, 0), None),
    (_streamed(1_200, None), None),
    (_ctx({"sched": {}}, {"sched": {}}), None),
], ids=["none", "a_fraction", "all", "no_steps", "no_counter", "no_routed_layer"])
def test_streamed_layer_steps_share_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("moe.streamed_layer_steps_pct").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


@pytest.mark.parametrize("name, expect", [
    # 128,000 rows over 4,000 layer-steps x 16 experts held
    ("moe.rows_per_expert_step", 2.0),
    # 140,000 blocks walked of 100,000 held x 5 layers
    ("attn.blocks_walked_pct", 28.0),
    # 56,000 experts touched of 4,000 layer-steps x 16 held
    ("moe.experts_touched_pct", 87.5),
])
def test_routed_counter_reader_on_a_hand_made_context(name, expect):
    value = measure.load_reader(name).read(_ctx(*_routed_counters()))
    assert value == pytest.approx(expect)


def _routed_events():
    """Two decode steps of one routed layer each: route, three grouped
    matmuls with their metadata op, the activation between them, the shared
    expert, and a dense matmul and a kernel call that are not the layer's."""
    step = [
        ("%fusion.1 = f32[32,128]{1,0} fusion(bf16[32,6144]{1,0} %x, "
         "bf16[4,6144,128]{2,1,0} %router)", 10),
        ("%ragged-dot-metadata.1 = (s32[65]{0}, s32[8]{0}) custom-call("
         "s32[64]{0} %gs)", 2),
        ("%ragged-dot-none.1 = bf16[256,2048]{1,0} custom-call(bf16[256,6144]"
         "{1,0} %xs, bf16[64,6144,2048]{2,1,0} %w)", 100),
        ("%ragged-dot-none.2 = bf16[256,2048]{1,0} custom-call(bf16[256,6144]"
         "{1,0} %xs, bf16[64,6144,2048]{2,1,0} %w)", 100),
        ("%multiply_fusion.3 = bf16[256,2048]{1,0} fusion(bf16[256,2048]{1,0} "
         "%ragged-dot-none.1, bf16[256,2048]{1,0} %ragged-dot-none.2)", 8),
        ("%ragged-dot-none.3 = bf16[256,6144]{1,0} custom-call(bf16[256,2048]"
         "{1,0} %act, bf16[64,2048,6144]{2,1,0} %w)", 98),
        ("%fusion.9 = bf16[32,2048]{1,0} fusion(bf16[32,6144]{1,0} %x, "
         "bf16[1,6144,2048]{2,1,0} %shared_gate)", 30),
        ("%fusion.11 = bf16[32,18432]{1,0} fusion(bf16[32,6144]{1,0} %x, "
         "bf16[6144,18432]{1,0} %w_gate)", 400),
        ("%paged_attention.1 = bf16[32,8,8,128]{3,2,1,0} custom-call()", 252),
    ]
    ops, at = [], 0
    for _ in range(2):
        for hlo, ns in step:
            ops.append((hlo, at, at + ns))
            at += ns
    return xevents.Events(ops=[ops], async_ops=[[]])


def test_routed_trace_readers_on_hand_made_events(monkeypatch):
    monkeypatch.setattr(xevents, "current", _routed_events)
    ctx = _ctx(*_routed_counters(), trace={"busy_s": 1.0, "window_s": 5.0})
    # route 10 + metadata 2 + matmuls 298 + activation 8 + shared 30 of 1,000
    assert measure.load_reader("device.moe_pct").read(ctx) == \
        pytest.approx(34.8)
    reader = measure.load_reader("moe.expert_gemm_roofline_pct")
    # 300 ns of grouped matmuls a layer-step in the slice (metadata with them)
    assert reader.gemm_seconds_per_layer_step(_routed_events()) == \
        pytest.approx(300e-9)
    # 32 rows and 14 experts touched a layer-step, by the counting function:
    nbytes, flop = reader.counts.layer_step(32, 14, 6144, 2048, 2)
    assert nbytes == (14 * 3 * 6144 * 2048 + 32 * 3 * (6144 + 2048)) * 2
    assert flop == 32 * 3 * 2 * 6144 * 2048
    ideal = max(nbytes / 819e9, flop / 197e12)
    assert reader.read(ctx) == pytest.approx(100.0 * ideal / 300e-9)
    # a slab of another layer count, or one layer cut out, is the same matrix
    keep = measure.load_reader("device.moe_pct").matcher(MOE["shapes"])
    assert keep("%copy.1 = bf16[1,16,6144,2048]{3,2,1,0} copy(%p)")
    assert keep("%f = f32[576,128]{1,0} fusion(bf16[4,6144,128]{2,1,0} %r)")
    assert not keep("%f = bf16[32,6144]{1,0} fusion(bf16[6144,8192]{1,0} %wq)")
    assert not keep("%f = bf16[32,19200]{1,0} fusion(bf16[6144,19200] %head)")


@pytest.mark.parametrize("name", ROUTED)
def test_routed_reader_finds_nothing_on_a_program_without_the_counters(
        name, monkeypatch):
    """The parent commit has none of the counters, and a dense model's
    program none of the routed layer's: every reader returns None."""
    monkeypatch.setattr(xevents, "newest_xplane", lambda *a, **k: CHIP_TRACE)
    trace = {"busy_s": 0.2, "window_s": 0.25}
    reader = measure.load_reader(name)
    assert reader.read(_ctx({"num_steps": 1}, {"num_steps": 9},
                            trace=trace)) is None
    assert reader.read(_ctx(*_counters(), trace=trace)) is None
    c0, c1 = _routed_counters()     # the counters, and a trace without the ops
    if name == "moe.expert_gemm_roofline_pct":
        assert reader.read(_ctx(c0, c1, trace=trace)) is None


def _placed(steps: int, placed: int | None):
    c0, c1 = {"num_steps": 100}, {"num_steps": 100 + steps}
    if placed is not None:
        c0["placed_inputs"], c1["placed_inputs"] = 1_300, 1_300 + placed
    return _ctx(c0, c1)


@pytest.mark.parametrize("ctx, expect", [
    # 1,000 steps: thirteen arrays a step (what the counter would have read
    # before the inputs were packed), one packed array a step, a window in
    # which one step in ten was cut into two programs and one in twenty had
    # a row that samples
    (_placed(1_000, 13_000), 13.0),
    (_placed(1_000, 1_000), 1.0),
    (_placed(1_000, 1_150), 1.15),
    # no step in the window; a program without the counter (the parent's)
    (_placed(0, 0), None),
    (_placed(1_000, None), None),
], ids=["thirteen", "one", "cut_and_sampled", "no_steps", "no_counter"])
def test_placed_inputs_per_step_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("engine.placed_inputs_per_step").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def _update_rows(given: int | None, moved: int = 0):
    c0 = {"sched": {}} if given is None else {"sched": {
        "ssm_update_rows_given_total": 1_200,
        "ssm_update_rows_moved_total": 900}}
    c1 = {"sched": {}} if given is None else {"sched": {
        "ssm_update_rows_given_total": 1_200 + given,
        "ssm_update_rows_moved_total": 900 + moved}}
    return _ctx(c0, c1)


@pytest.mark.parametrize("ctx, expect", [
    # 1,000 programs of 15 layers: 16-row buckets with 9 rows of one token;
    # full 8-row buckets; chunks alone, every row passed by
    (_update_rows(16 * 15_000, 9 * 15_000), 100.0 * 7 / 16),
    (_update_rows(8 * 15_000, 8 * 15_000), 0.0),
    (_update_rows(8 * 15_000, 0), 100.0),
    # no program with recurrent layers in the window (every other cell:
    # the counts are there and stay 0); a program without the counts
    (_update_rows(0, 0), None),
    (_update_rows(None), None),
], ids=["nine_of_sixteen", "full", "chunks_alone", "no_ssm", "no_counter"])
def test_update_rows_skipped_pct_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("ssm.update_rows_skipped_pct").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def _slots(edges: tuple | None, in_flight: tuple = (), slots: int = 64):
    """A window with ``edges`` rows of the state pool held at its start and
    end and ``in_flight`` requests running or waiting at its samples."""
    c0, c1 = ({"ssm": {"slots": slots} if edges is None else
               {"slots": slots, "slots_in_use": n}} for n in edges or (0, 0))
    ctx = _ctx(c0, c1)
    ctx.in_flight = list(in_flight)
    return ctx


@pytest.mark.parametrize("ctx, expect", [
    # a sample above both edges; an edge above every sample; no sample at
    # all; more requests in flight than the pool has rows: some waited
    (_slots((14, 19), (15, 21, 18)), 100.0 * 21 / 64),
    (_slots((14, 9), (12, 11)), 100.0 * 14 / 64),
    (_slots((7, 5)), 100.0 * 7 / 64),
    (_slots((60, 64), (63, 70)), 100.0),
    # a program without the count (the parent's), or without a state pool
    (_slots(None, (3, 4)), None),
    (_ctx({}, {}), None),
], ids=["sample_above", "edge_above", "no_sample", "some_waited",
        "no_counter", "no_pool"])
def test_slots_peak_usage_pct_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("ssm.slots_peak_usage_pct").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def _q_positions(handed: int | None, live: int = 0):
    c0 = {"sched": {}} if handed is None else {"sched": {
        "rect_tokens_total": 50_000, "live_tokens_total": 7_000}}
    c1 = {"sched": {}} if handed is None else {"sched": {
        "rect_tokens_total": 50_000 + handed,
        "live_tokens_total": 7_000 + live}}
    return _ctx(c0, c1)


@pytest.mark.parametrize("ctx, expect", [
    # 100 b8 t512 steps of 513 live tokens beside 1,000 decode steps of one
    # row in eight: the rectangle (4,096 positions a mixed step) and the
    # token bucket (520)
    (_q_positions(100 * 4_096 + 8_000, 100 * 513 + 1_000),
     100.0 * (1 - 52_300 / 417_600)),
    (_q_positions(100 * 520 + 8_000, 100 * 513 + 1_000),
     100.0 * (1 - 52_300 / 60_000)),
    # every position a live token
    (_q_positions(8_000, 8_000), 0.0),
    # no step in the window; a program without the count
    (_q_positions(0, 0), None),
    (_q_positions(None), None),
], ids=["rectangle", "token_bucket", "full", "no_steps", "no_counter"])
def test_q_padding_pct_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("attn.q_padding_pct").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


# ---------------------------------------------------------------------------
# the step programs' build (PR 54): seconds a warmed program, and the share
# of the programs' layer bodies that their builds traced
# ---------------------------------------------------------------------------

def _build(programs: int | None, seconds: float = 0.0, serve: float = 0.0,
           bodies: tuple | None = None):
    """Warm-up is over before the window's first edge: both snapshots hold
    the same ledger, and the readers take the last."""
    led = {} if programs is None else {
        "cache_entries": programs, "compile_seconds_total": seconds,
        "serve_stall_seconds": serve, "events_total": programs}
    if bodies is not None:
        led["layer_bodies"], led["layer_bodies_traced"] = bodies
    return _ctx({"compile": dict(led)}, {"compile": led})


@pytest.mark.parametrize("name, ctx, expect", [
    # the hybrid cell's 21 programs: 3.5 s each with 13 of 13 bodies traced
    # (what the parent's counts would read, had it them), 2.2 s with 3 of 13
    ("engine.warmup_s_per_program", _build(21, 73.5), 3.5),
    ("engine.warmup_s_per_program", _build(21, 46.2, bodies=(273, 63)), 2.2),
    # a program the serving path had to build is no warmed program's second
    ("engine.warmup_s_per_program", _build(14, 16.0, serve=2.0), 1.0),
    ("engine.layer_bodies_traced_pct", _build(21, 46.2, bodies=(273, 63)),
     100.0 * 3 / 13),
    ("engine.layer_bodies_traced_pct", _build(21, 40.0, bodies=(105, 63)),
     60.0),
    ("engine.layer_bodies_traced_pct", _build(21, 40.0, bodies=(84, 42)),
     50.0),
    # a model of identical layers: one body a program, traced
    ("engine.layer_bodies_traced_pct", _build(14, 11.0, bodies=(14, 14)),
     100.0),
    # the parent's ledger: its seconds are there, its bodies are not
    ("engine.layer_bodies_traced_pct", _build(21, 73.5), None),
    # no program recorded; a ledger switched off (no "compile" in stats)
    ("engine.warmup_s_per_program", _build(0, 0.0, bodies=(0, 0)), None),
    ("engine.layer_bodies_traced_pct", _build(0, 0.0, bodies=(0, 0)), None),
    ("engine.warmup_s_per_program", _build(None), None),
    ("engine.layer_bodies_traced_pct", _build(None), None),
], ids=["parent_hybrid", "hybrid", "serve_compile_left_out", "3_of_13",
        "3_of_5", "2_of_4", "one_body", "parent_no_counts", "no_programs_s",
        "no_programs_pct", "no_ledger_s", "no_ledger_pct"])
def test_build_reader_on_a_hand_made_context(name, ctx, expect):
    value = measure.load_reader(name).read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def test_build_readers_with_no_compile_key_at_all():
    ctx = _ctx({"num_steps": 1}, {"num_steps": 9})
    for name in ("engine.warmup_s_per_program",
                 "engine.layer_bodies_traced_pct"):
        assert measure.load_reader(name).read(ctx) is None


def test_every_cell_reports_the_build_metrics():
    """They move ``setup_s``, which every cell reports: no ``workloads``."""
    for w in BENCH["workloads"]:
        _judged, layer = manifest.cell_metrics(BENCH, w["name"])
        assert {"engine.warmup_s_per_program",
                "engine.layer_bodies_traced_pct"} <= set(layer)


# ---------------------------------------------------------------------------
# PR 56: the cross-decoder's two counters
# ---------------------------------------------------------------------------

def _cross(shared: int | None, walked: int = 0, cross: int = 0, live: int = 0):
    c0 = {"sched": {}} if shared is None else {"sched": {
        "kv_blocks_walked_total": 9_000, "kv_blocks_walked_shared_total": 4_000,
        "cross_tokens_total": 300, "live_tokens_total": 5_000}}
    c1 = {"sched": {}} if shared is None else {"sched": {
        "kv_blocks_walked_total": 9_000 + walked,
        "kv_blocks_walked_shared_total": 4_000 + shared,
        "cross_tokens_total": 300 + cross,
        "live_tokens_total": 5_000 + live}}
    return _ctx(c0, c1)


@pytest.mark.parametrize("name, ctx, expect", [
    # 1,000 decode steps of 20 rows at 3,000 tokens: 8 walks of 33 blocks
    # and 8 of 188 a row, 7 of the latter the cross layers'
    ("attn.shared_kv_walk_pct",
     _cross(20_000 * 7 * 188, 20_000 * (8 * 33 + 8 * 188)),
     100.0 * 7 * 188 / (8 * 33 + 8 * 188)),
    # a model without cross layers walks none of them; nothing walked at all
    ("attn.shared_kv_walk_pct", _cross(0, 50_000), 0.0),
    ("attn.shared_kv_walk_pct", _cross(0, 0), None),
    ("attn.shared_kv_walk_pct", _cross(None), None),
    # 1,000 decode steps of 20 rows and 30 chunks of 512 beside them: one
    # token a row a step entered the cross-decoder
    ("xdec.tokens_pct", _cross(0, 0, 20_000 + 30, 20_000 + 30 * 512),
     100.0 * 20_030 / (20_000 + 30 * 512)),
    ("xdec.tokens_pct", _cross(0, 0, 20_000, 20_000), 100.0),
    # a model without a cross-decoder; a program without the counter
    ("xdec.tokens_pct", _cross(0, 0, 0, 20_000), None),
    ("xdec.tokens_pct", _cross(None), None),
], ids=["decode_at_3000", "no_cross_layers", "nothing_walked", "no_counter",
        "chunks_beside", "decode_alone", "no_cross_decoder", "no_counter2"])
def test_cross_decoder_counter_on_a_hand_made_context(name, ctx, expect):
    value = measure.load_reader(name).read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry["workloads"] == ["phi-4-mini-flash.reasoning"]
    assert entry["source"] == "program_counter"


# ---------------------------------------------------------------------------
# PR 61: the latent (MLA) cache's three readers and their counting function
# ---------------------------------------------------------------------------

LATENT_CELL = "glm-4.7-flash.longdoc"
LATENT = {"cache_kind": "latent", "pools": 1, "row_stored": 640,
          "row_useful": 576, "value_width": 512, "bytes_per_token": 10240,
          "layers": 8, "heads": 20}


def _latent(rows: int | None, ctx_tokens: int = 0):
    c0 = {} if rows is None else {"attn": {
        **LATENT, "chunk_rows": 40, "chunk_ctx_tokens": 300_000}}
    c1 = {} if rows is None else {"attn": {
        **LATENT, "chunk_rows": 40 + rows,
        "chunk_ctx_tokens": 300_000 + ctx_tokens}}
    return _ctx(c0, c1)


@pytest.mark.parametrize("ctx, expect", [
    # a 16,384-token prompt in 32 chunks of 512: the chunks end at 512,
    # 1,024, ... 16,384, a mean of 8,448
    (_latent(32, 512 * 32 * 33 // 2), 8448.0),
    (_latent(0, 0), None),          # a window in which no chunk ran
    (_latent(None), None),          # a program without the counters
], ids=["one_prompt", "no_chunk", "no_counter"])
def test_chunk_context_on_a_hand_made_context(ctx, expect):
    value = measure.load_reader("mla.chunk_ctx_tokens").read(ctx)
    assert value == (None if expect is None else pytest.approx(expect))


def test_latent_counts_are_the_useful_widths():
    counts = measure.load_module(
        ROOT / "chipbench" / "layers" / "mla_counts.py", "mla_counts")
    # one chunk of 512 tokens at a context of 16,384 over 8 layers: 1,024
    # blocks a layer; a query at position p sees p + 1 keys
    pairs = 8 * (512 * (16_384 - 512) + 512 * 513 // 2)
    nbytes, flop = counts.step(8 * 1024, pairs, 512, LATENT, 16)
    assert nbytes == 8 * 1024 * 16 * 576 * 2 + 512 * 8 * 20 * (576 + 512) * 2
    assert flop == pairs * 20 * 2 * (576 + 512)
    # compute-bound by far: 2.9 TFLOP against 0.24 GB
    from harness import peaks
    pk = peaks.peaks_for("TPU v5 lite")
    assert counts.ideal_seconds(8 * 1024, pairs, 512, LATENT, 16, pk) == \
        pytest.approx(flop / pk.flops_bf16)
    assert flop / pk.flops_bf16 > 10 * nbytes / pk.hbm_bytes_per_s


def test_latent_trace_readers_on_a_hand_made_join(monkeypatch):
    """``device.mla_pct`` by phase and ``attn.latent_roofline_pct`` by the
    kernel's name over one matched chunk step."""
    join = measure.load_module(
        ROOT / "chipbench" / "layers" / "step_join.py", "step_join")
    pairs = 8 * (512 * (16_384 - 512) + 512 * 513 // 2)
    step = join.Step(7, {"kv_blocks_walked": 8 * 1024, "attn_q_ctx": pairs,
                         "live_tokens": 512}, programs=[0])
    ops = [(0, "fusion.1", "mla_down", 2e6), (0, "fusion.2", "mla_absorb", 1e6),
           (0, "scatter.3", "mla_write", 1e6),
           (0, "paged_attention.4", "mla_walk", 40e6),
           (0, "fusion.5", "mla_unabsorb", 1e6), (0, "fusion.6", "proj", 5e6),
           (0, "ragged-dot.7", "moe_experts", 50e6)]
    j = join.Joined([("step_mixed_b8_t512", 0.0, 100e6)], [step], 1.0, ops,
                    {"step_mixed_b8_t512": {}})

    class Events:
        path = None

        def busy_ns(self):
            return 100e6
    readers = {name: measure.load_reader(name)
               for name in ("device.mla_pct", "attn.latent_roofline_pct")}
    for reader in readers.values():
        monkeypatch.setattr(reader.join, "current", lambda: j)
    monkeypatch.setattr(xevents, "current", Events)
    c1 = {"attn": LATENT, "step_shapes": {"block_size": 16},
          "device": {"device_kind": "TPU v5 lite"}}
    ctx = _ctx({}, c1, trace={"busy_s": 0.1, "window_s": 5.0})
    assert readers["device.mla_pct"].read(ctx) == pytest.approx(45.0)
    ideal = pairs * 20 * 2 * (576 + 512) / 197e12
    assert readers["attn.latent_roofline_pct"].read(ctx) == \
        pytest.approx(100.0 * ideal / 40e-3, rel=1e-3)
    # a program that states no latent cache: nothing to read
    plain = _ctx({}, {"step_shapes": {"block_size": 16}}, trace=ctx.trace)
    assert readers["attn.latent_roofline_pct"].read(plain) is None


@pytest.mark.parametrize("name", ["device.mla_pct", "attn.latent_roofline_pct",
                                  "mla.chunk_ctx_tokens"])
def test_latent_reader_is_the_new_cells_alone(name, monkeypatch):
    """Each is read in the new cell alone and moves what it is judged on; on
    the parent's program (no counters, no phases) it returns None."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry["workloads"] == [LATENT_CELL]
    assert entry["moves"] == "itl_p95_ms"
    monkeypatch.setattr(xevents, "newest_xplane", lambda *a, **k: CHIP_TRACE)
    assert measure.load_reader(name).read(_ctx(
        {"num_steps": 1}, {"num_steps": 9},
        trace={"busy_s": 0.2, "window_s": 0.25})) is None


def test_the_long_document_slice_may_hold_no_decode_step():
    """The new cell's traced slice is chunk steps end to end (busy 97 %), so
    the reader of the ``jit_step_decode_*`` executions finds none there: its
    entry lists the accepted cells, which all report it, and the cell is held
    to every other metric that has no list and moves what it reports."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}["engine.decode_step_dev_ms"]
    accepted = [w["name"] for w in BENCH["workloads"] if w["name"] != LATENT_CELL]
    assert entry["workloads"] == accepted
    cell = manifest.load_cell(LATENT_CELL)
    assert "engine.decode_step_dev_ms" not in cell.per_layer
    unlisted = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m and m["moves"] in cell.end_to_end}
    assert unlisted <= set(cell.per_layer)


def test_the_latent_configuration_brings_its_reference():
    cfg = {c["name"]: c for c in BENCH["configs"]}["glm-4.7-flash-l5"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    ref = (ROOT / cfg["file"]).parent / "reference.py"
    _tree, defined = manifest._functions(ref)
    assert {"logits_at", "routing_margin_at"} <= defined
    assert "dynamo_tpu" not in ref.read_text().replace(
        "``dynamo_tpu/models/llama.py", "")
    about = json.loads(((ROOT / cfg["file"]).parent / "about.json").read_text())
    assert manifest.probe_faults(ref.parent, about) == []
    cell = manifest.load_cell(LATENT_CELL)
    assert cell.end_to_end == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    assert cell.traffic["prompt_tokens"] == {
        "median": 16384, "sigma": 0.4, "min": 8192, "max": 30720}
    assert cell.traffic["output_tokens"] == {
        "median": 128, "sigma": 0.4, "min": 64, "max": 256}
    assert cell.about["engine"]["max_model_len"] == 32768
