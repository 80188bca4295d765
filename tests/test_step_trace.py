"""A step names its own work and its own phases (PR 43): the phase table
read off a step program's compiled text, the join of a step's programs to
their device events on hand-made events, the pricing of a step's one count
by hand for the benchmark's five configurations, and the new readers where
there is nothing to read. No number here is a measurement."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import manifest, measure, peaks, xevents  # noqa: E402

from dynamo_tpu.obs import costmodel as cm  # noqa: E402
from dynamo_tpu.obs.profiler import (  # noqa: E402
    DEVICE_PHASES,
    innermost_phase,
    phase_table,
)
from dynamo_tpu.obs.sched_ledger import step_counts  # noqa: E402

REHEARSAL = ROOT / "chipbench" / "rehearsal"
join = measure.load_module(ROOT / "chipbench/layers/step_join.py", "step_join")
work = measure.load_module(ROOT / "chipbench/layers/step_work_counts.py",
                           "step_work_counts")
BENCH = manifest.load_benchmark()
NEW = ("engine.step_mfu_pct", "engine.step_roofline_pct",
       "attn.kernel_roofline_pct", "device.moe_experts_pct",
       "moe.experts_roofline_pct", "device.unscoped_pct",
       "engine.dispatch_fill_ms_per_step",
       "engine.dispatch_launch_ms_per_step", "engine.unphased_ms_per_step")
V5E = peaks.peaks_for("TPU v5 lite")


# ---------------------------------------------------------------------------
# the phase table (on programs compiled for a v5e: tests/test_ops.py, the one
# file that describes the chip)
# ---------------------------------------------------------------------------

def test_innermost_phase_reads_scopes_not_primitives():
    assert innermost_phase("jit(step)/while/body/layer/moe_experts/add") \
        == "moe_experts"
    assert innermost_phase("jit(step)/while/body/layer/mul") == "layer"
    # a gather or a scatter outside every phase is not the phase of that name
    assert innermost_phase("jit(step)/jit(take_along_axis)/gather") is None
    assert innermost_phase("jit(step)/layer/scatter/scatter") == "scatter"
    assert innermost_phase("ragged-dot-none") is None


@pytest.mark.parametrize("experts", ["another_grouped_matmul",
                                     "the_streaming_kernel"])
def test_another_grouped_matmul_under_the_scope_keeps_the_phase(monkeypatch,
                                                                experts):
    """``lax.ragged_dot`` in ``moe.held_rows`` replaced by another grouped
    matmul (each row against its group's matrix, gathered), or the whole of
    ``held_rows`` by the streaming kernel (``ops/moe_stream.py``,
    interpreted here: its custom call on a v5e is held to the phase in
    tests/test_ops.py): the phase table's ``moe_experts`` set is still
    there, and names no ``ragged-dot``: the new readers do not depend on
    the instruction's name."""
    import functools

    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.models import moe
    from dynamo_tpu.ops import moe_stream
    from tests.test_engine import tiny_config

    def grouped(xs, w, sizes):
        ends = jnp.cumsum(sizes)
        gid = jnp.sum(jnp.arange(xs.shape[0])[:, None] >= ends[None, :], axis=1)
        return jnp.einsum("rk,rkn->rn", xs,
                          w[jnp.clip(gid, 0, w.shape[0] - 1)])

    if experts == "another_grouped_matmul":
        monkeypatch.setattr(moe.lax, "ragged_dot", grouped)
    else:
        monkeypatch.setattr(moe, "streams_experts", lambda *shape: True)
        monkeypatch.setattr(moe_stream, "stream_rows", functools.partial(
            moe_stream.stream_rows, interpret=True))
    runner = EngineCore(tiny_config(model="tiny-moe")).runner
    assert runner.moe_impl == "held"
    fn = runner._build_step_fn(4, 1, 4, fast_greedy=True)
    text = fn.lower(
        runner.params, runner.cache_k, runner.cache_v, runner.counts,
        runner.keys, runner.slot_toks,
        *runner._padding_inputs(4, 1, 4, True)).compile().as_text()
    assert "ragged" not in text
    table = phase_table(text)
    experts = {n for n, p in table.items() if p == "moe_experts"}
    assert experts and {"moe_route", "moe_shared"} <= set(table.values())


# ---------------------------------------------------------------------------
# the join, on hand-made events (times in ns)
# ---------------------------------------------------------------------------

MS = 1e6
DEC, MIX = "jit_step_decode_b8_n512", "jit_step_mixed_b8_t512_k520_n512"


def _program(step, index, program, start, dur=0.3 * MS):
    return ("engine.program", start, start + dur,
            {"step": str(step), "program": program})


def _wait(step, end):
    return ("engine.finalize.wait", end - MS, end, {"step": str(step)})


def _record(step, at, programs=1, **counts):
    attrs = {"step": step, "programs": programs, "live_tokens": 2,
             "logit_rows": 2, "attn_q_ctx": 100, "kv_blocks_walked": 10,
             **counts}
    return ("engine.record", at, at + 0.2 * MS,
            {k: str(v) for k, v in attrs.items()})


def _events(modules, host, ops=None):
    ops = ops or [(f"%fusion.{i} = bf16[8] fusion(%a)", s, e)
                  for i, (_n, s, e) in enumerate(modules)]
    return xevents.Events(modules=modules, ops=[ops], async_ops=[[]],
                          host=sorted(host, key=lambda x: x[1]))


def test_join_a_step_of_two_programs():
    """Step 6 overflowed one token bucket and went out as two programs of
    one name: both are its own, its device time is their sum, and its counts
    are its record's."""
    modules = [(f"{DEC}(11)", 10 * MS, 20 * MS),
               (f"{MIX}(22)", 20 * MS, 50 * MS), (f"{MIX}(22)", 50 * MS, 75 * MS),
               (f"{DEC}(11)", 75 * MS, 85 * MS)]
    host = [_program(5, 0, DEC, 9 * MS),
            _program(6, 0, MIX, 12 * MS), _program(6, 1, MIX, 13 * MS),
            _wait(5, 20.1 * MS), _record(5, 20.5 * MS),
            _program(7, 0, DEC, 21 * MS),
            _wait(6, 50.1 * MS), _wait(6, 75.1 * MS),
            _record(6, 75.5 * MS, programs=2, live_tokens=900),
            _wait(7, 85.1 * MS), _record(7, 85.5 * MS)]
    j = join.build(_events(modules, host), {})
    assert j.matched_share == 1.0
    steps = {s.step: s for s in j.steps}
    assert set(steps) == {5, 6, 7}
    assert steps[6].programs == [1, 2] and steps[6].device_ns == 55 * MS
    assert steps[6].counts["live_tokens"] == "900"
    assert steps[5].device_ns == steps[7].device_ns == 10 * MS


def test_join_a_slice_that_cuts_a_step_at_each_end():
    """The trace began after step 3 was enqueued (an event, no span); it
    ended before step 7 ran (a span, no event) and before step 6 was
    recorded. The ends are dropped, nothing shifts: steps 4 and 5 are whole.
    Where the device's lines begin later than the host's, a span's event is
    not held either, and a later event of its name does not take it."""
    modules = [(f"{DEC}(11)", 1 * MS, 5 * MS),          # step 3: no span
               (f"{DEC}(11)", 5 * MS, 15 * MS),         # step 4
               (f"{DEC}(11)", 16 * MS, 26 * MS),        # step 5
               (f"{DEC}(11)", 26 * MS, 36 * MS)]        # step 6
    host = [_program(4, 0, DEC, 1.5 * MS),
            _program(5, 0, DEC, 6 * MS),
            _wait(4, 15.1 * MS), _record(4, 15.5 * MS),
            _program(6, 0, DEC, 17 * MS),
            _wait(5, 26.1 * MS), _record(5, 26.5 * MS),
            _program(7, 0, DEC, 27 * MS), _wait(6, 36.1 * MS)]
    j = join.build(_events(modules, host), {})
    assert j.matched_share == 1.0
    assert [(s.step, s.programs) for s in j.steps] == [(4, [1]), (5, [2])]
    assert all(s.device_ns == 10 * MS for s in j.steps)
    # Steps 3 and 4 ran before the device's lines began: step 4's span has
    # no event, and step 5's event does not take it, because the host had
    # step 4's tokens before step 5's program ended.
    j = join.build(_events(modules[2:], host), {})
    assert j.matched_share == 1.0
    assert [(s.step, s.programs) for s in j.steps] == [(5, [0])]
    # the same slice with most of its spans gone is no join at all
    few = [h for h in host if h[0] != "engine.program" or h[3]["step"] == "5"]
    more = modules + [(f"{DEC}(11)", (40 + 10 * i) * MS, (50 + 10 * i) * MS)
                      for i in range(3)]
    few.append(_program(9, 0, DEC, 59 * MS))      # 2 of 5 between them
    assert join.build(_events(more, few), {}) is None


def test_join_a_foreign_program_between_and_the_phases_of_each_program():
    """A program that is no step's (``reset_slot``'s) between two steps: it
    takes no span, its operations have no phase; two step programs both have
    a ``%fusion.83``, in different phases, and each takes its own."""
    modules = [(f"{DEC}(11)", 10 * MS, 20 * MS),
               ("jit__advance_key_data(7)", 20 * MS, 21 * MS),
               (f"{MIX}(22)", 21 * MS, 51 * MS)]
    ops = [("%while.1 = (s32[]) while(%t)", 10 * MS, 20 * MS),
           ("%fusion.83 = bf16[8,14336] fusion(%a)", 10 * MS, 16 * MS),
           ("%paged_attention.2 = bf16[8,8,4,128] custom-call(%q)", 16 * MS,
            19 * MS),
           ("%ragged-dot-none.1 = bf16[48,768] custom-call(%x)", 19 * MS,
            20 * MS),
           ("%fusion.1 = u32[2] fusion(%k)", 20 * MS, 21 * MS),
           ("%fusion.83 = bf16[520,4096] fusion(%a)", 21 * MS, 51 * MS)]
    host = [_program(1, 0, DEC, 9 * MS), _program(2, 0, MIX, 12 * MS),
            _wait(1, 20.1 * MS), _record(1, 20.5 * MS),
            _wait(2, 51.1 * MS), _record(2, 51.5 * MS)]
    tables = {DEC: {"fusion.83": "mlp", "paged_attention.2": "attention",
                    "ragged-dot-none.1": "moe_experts"},
              MIX: {"fusion.83": "proj"}}
    j = join.build(_events(modules, host, ops), tables)
    assert j.matched_share == 1.0 and [s.step for s in j.steps] == [1, 2]
    by_phase = {}
    for _mod, _ins, phase, ns in j.ops:
        by_phase[phase] = by_phase.get(phase, 0.0) + ns
    assert by_phase == {"mlp": 6 * MS, "attention": 3 * MS,
                        "moe_experts": 1 * MS, "proj": 30 * MS,
                        None: 1 * MS}                # the while itself: 0
    assert j.self_ns(lambda ins, _p: ins.startswith("paged_attention"),
                     {0}) == 3 * MS
    assert j.self_ns(lambda _i, p: p == "mlp", {2}) == 0.0


def test_match_takes_the_earliest_span_of_its_name_that_began_before():
    spans = [(DEC, 0.0, 1e18), (MIX, 1.0, 1e18), (DEC, 2.0, 1e18)]
    events = [(DEC, 0.5, 1.5), (DEC, 1.6, 1.9), (MIX, 3.0, 4.0),
              (DEC, 5.0, 6.0)]
    # the second decode event ran before the next decode span began; the
    # last finds its span behind the mixed one's, which is passed already
    assert join.match(events, spans) == [0, None, 1, 2]
    assert join.matched_share([None, 0, None, 1, None]) == pytest.approx(2 / 3)
    assert join.program_of("jit_step_decode_b8_n512(123)") == DEC


# ---------------------------------------------------------------------------
# the pricing of a step's count, by hand, for the five configurations
# ---------------------------------------------------------------------------

def _shapes(config: str) -> dict:
    from dynamo_tpu.models.config import resolve_model_config

    return cm.step_shapes(
        resolve_model_config(str(ROOT / "chipbench/configs" / config)),
        block_size=16)


def _decode(rows=1, experts=0, layers=0, **over):
    return {"programs": 1, "live_tokens": rows, "logit_rows": rows,
            "attn_q_ctx": 0, "kv_blocks_walked": 0,
            "moe_experts_touched": experts * layers,
            "moe_rows": 0, **over}


@pytest.mark.parametrize("config, params, experts, layers", [
    # 16 x (wq, wo 2 x 4096 x 4096, wk, wv 2 x 4096 x 1024, MLP 3 x 4096 x
    # 14336) + the head 32768 x 4096: 7.25 GB (PERF.md section 5)
    ("mistral-7b-v0.3-l16",
     16 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
     + 32768 * 4096, 0, 0),
    # 10 x (2 x 5120 x 4096 + 2 x 5120 x 1024 + 3 x 5120 x 14336) + the head
    # 131072 x 5120: 6.79 GB
    ("mistral-nemo-12b-l10",
     10 * (2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336)
     + 131072 * 5120, 0, 0),
    # the same matrices a chip, all 40 layers: the tp=4 files
    ("mistral-nemo-12b-tp4",
     40 * (2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336)
     + 131072 * 5120, 0, 0),
    # 5 x attention (64 x 128 query, 8 x 128 key and value columns), the
    # leading dense FFN of 18432, 4 x (shared expert of 2048 + the router's
    # 128 columns), 10 of the 16 held experts a layer, the 19200-row head
    ("k-exaone-236b-a23b-ep8-l5",
     5 * (2 * 6144 * 8192 + 2 * 6144 * 1024) + 3 * 6144 * 18432
     + 4 * (3 * 6144 * 2048 + 6144 * 128) + 4 * 10 * 3 * 6144 * 2048
     + 19200 * 6144, 10, 4),
    # 12 x (attention 20.97 M + the router's 64 columns + 28 experts of
    # 5.898 M) + the head 151936 x 2560: 5.25 GB at 28 experts a layer
    ("smallthinker-21b-a3b-l12",
     12 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
           + 28 * 3 * 2560 * 768) + 151936 * 2560, 28, 12),
])
def test_a_decode_steps_bytes_by_hand(config, params, experts, layers):
    shapes = _shapes(config)
    nbytes, flop = work.step(shapes, _decode(1, experts, layers))
    assert nbytes == 2 * params + 2 * shapes["hidden_size"]
    gb = {"mistral-7b-v0.3-l16": 7.25, "mistral-nemo-12b-l10": 6.79,
          "smallthinker-21b-a3b-l12": 5.25}.get(config)
    if gb:
        assert nbytes / 1e9 == pytest.approx(gb, abs=0.005)
    # The program prices the same count the same way (the operator's gauges
    # and the benchmark's shares disagree only by what they divide by).
    own = cm.step_work(shapes, _decode(1, experts, layers),
                       (layers, 0, experts * layers) if layers else None)
    assert (own.hbm_bytes, own.flops) == (nbytes, flop)
    # One token's FLOP: 2 a parameter that is no routed expert's.
    dense = params - experts * layers * shapes["expert_params"]
    assert flop == 2 * dense


def test_a_chunk_step_is_bound_by_its_flop_and_a_decode_step_by_its_bytes():
    shapes = _shapes("mistral-7b-v0.3-l16")
    # a T=512 chunk at depth 1024 beside nothing: 3.7 TFLOP (PERF.md s. 5)
    pairs = 16 * sum(p + 1 for p in range(1024, 1536))
    chunk = _decode(1, live_tokens=512, attn_q_ctx=pairs,
                    kv_blocks_walked=16 * 96)
    nbytes, flop = work.step(shapes, chunk)
    assert flop / 1e12 == pytest.approx(3.7, abs=0.1)
    assert work.ideal_seconds((nbytes, flop), V5E) == flop / 197e12
    # 16 layers x 96 blocks x (K and V: 2 x 16 x 8 x 128 x 2 B) of KV
    assert nbytes - work.step(shapes, {**chunk, "kv_blocks_walked": 0})[0] \
        == 16 * 96 * 65536
    dec = work.step(shapes, _decode(2))
    assert work.ideal_seconds(dec, V5E) == dec[0] / 819e9
    # the kernel's own work: the blocks, q in and the output back; its FLOP
    kb, kf = work.kernel(shapes, chunk)
    assert kb == 16 * 96 * 65536 + 2 * 512 * 4096 * 2 * 16
    assert kf == 4 * 32 * 128 * pairs
    # a second program of the step reads the weights again
    two = work.step(shapes, _decode(2, programs=2))[0]
    assert two - dec[0] == pytest.approx(7.25e9, rel=1e-3)


def test_the_routed_rows_are_priced_from_the_devices_counts():
    shapes = _shapes("k-exaone-236b-a23b-ep8-l5")
    assert (shapes["routed_layers"], shapes["dense_ffn_layers"]) == (4, 1)
    assert (shapes["experts_held"], shapes["router_width"]) == (16, 128)
    counts = _decode(16)
    # what the device returned: 4 layer-steps, 20 rows and 9 experts
    exact = cm.step_work(shapes, counts, (4, 20, 9, 5))
    guess = cm.step_work(shapes, counts)          # 16 x 8 / 8 rows a layer
    e = shapes["expert_params"]
    assert guess.hbm_bytes - exact.hbm_bytes == (4 * 16 - 9) * e * 2
    assert guess.flops - exact.flops == 2 * (4 * 16 - 20) * e
    both = {**counts, "moe_rows": 20, "moe_experts_touched": 9}
    assert work.step(shapes, both) == (exact.hbm_bytes, exact.flops)


# ---------------------------------------------------------------------------
# the one count of a step's rows
# ---------------------------------------------------------------------------

def test_the_one_count_on_windowed_rows():
    """One decode row at position 700 and a 512 chunk at 300 (blocks of
    16), as ``tests/test_kexaone.py::test_kv_blocks_walked_by_hand`` has
    them: the one count gives what ``kv_blocks_live`` / ``kv_blocks_walked``
    gave at dispatch until PR 43 (44 + 51 held; 9 + 41 walked behind a window
    of 128), and the pairs a window hides are not counted."""
    from dynamo_tpu.obs.compile_ledger import BucketSig

    sig = BucketSig("mixed", 8, 512, 512, True, "bfloat16")
    batches = [(sig, [(None, 700, 1), (None, 300, 512)], None, None, None)]
    for windows, walked in (((0,), 44 + 51), ((128,), 9 + 41),
                            ((128, 0, 128), 2 * 50 + 95)):
        c = step_counts(batches, 16, windows, dec_rows=1)
        assert c["kv_blocks_live"] == 44 + 51
        assert c["kv_blocks_walked"] == walked
    c = step_counts(batches, 16, (128, 0, 128), dec_rows=1)
    assert (c["programs"], c["live_tokens"], c["logit_rows"]) == (1, 513, 2)
    assert (c["decode_rows"], c["prefill_rows"]) == (1, 1)
    assert (c["decode_tokens"], c["prefill_tokens"]) == (1, 512)
    assert (c["sched_tokens"], c["rect_tokens"]) == (520, 8 * 512)
    # ... and where the kernel takes a packed step's tokens as they lie
    # (PR 50), attention is handed the token bucket and no rectangle.
    tokens = step_counts(batches, 16, (128, 0, 128), dec_rows=1,
                         attn_tokens=True)
    assert tokens["rect_tokens"] == 520
    assert {k: v for k, v in tokens.items() if k != "rect_tokens"} == {
        k: v for k, v in c.items() if k != "rect_tokens"}
    full = 701 + sum(p + 1 for p in range(300, 812))
    slid = 128 + 512 * 128             # every query sees its window, full
    assert c["attn_q_ctx"] == full + 2 * slid
    # a chunk from 0 of 200 under a window of 128: the first 128 queries see
    # 1..128 keys, the rest 128 each
    c = step_counts([(sig, [(None, 0, 200)], None, None, None)], 16, (128,))
    assert c["attn_q_ctx"] == 128 * 129 // 2 + 72 * 128
    assert c["table_blocks"] == 8 * 512 and c["kinds"] == ("mixed",)


def test_the_one_count_of_a_cross_decoders_step():
    """PR 56: a mixed program of SambaY's plan (8 windows of 512, a full
    layer, 7 cross layers, 9 Mamba-1 layers). The windows' walks begin at
    the oldest key a row's first query sees; the full layer and the cross
    layers walk the row's whole context, the cross layers for the row's last
    token alone; one token a row enters the cross-decoder; the scan's kernel
    runs the live rows and positions and the one-token kernel none."""
    from dynamo_tpu.obs.compile_ledger import BucketSig

    sig = BucketSig("mixed", 8, 512, 512, True, "bfloat16")
    rows = [(None, 2999, 1), (None, 1024, 300)]
    c = step_counts([(sig, rows, None, None, None)], 16, (512,) * 8 + (0,),
                    dec_rows=1, ssm_layers=9, scan_layers=9, cross_layers=7)
    used = (188, 83)                     # ceil(3000 / 16), ceil(1324 / 16)
    first = (2488 // 16, 513 // 16)      # the oldest key seen: start - 511
    windows = sum(8 * (u - f) for u, f in zip(used, first))
    assert c["kv_blocks_walked_shared"] == 7 * sum(used)
    assert c["kv_blocks_walked"] == windows + sum(used) + 7 * sum(used)
    assert c["cross_tokens"] == c["logit_rows"] == 2
    assert c["live_tokens"] == 301
    full = 3000 + sum(1025 + i for i in range(300))
    assert c["attn_q_ctx"] == 8 * (512 + 300 * 512) + full + 7 * (3000 + 1324)
    assert (c["ssm_scan_rows"], c["ssm_scan_positions"]) == (2 * 9, 301 * 9)
    assert c["ssm_update_rows_given"] == c["ssm_update_rows_moved"] == 0
    assert c["ssm_state_rows"] == 2 * 9 and c["ssm_layer_steps"] == 9
    # the mixer computes the bucket's tokens: no blocked scan's t beside them
    assert c["ssm_scanned_positions"] == sig.n
    # ... and a model without any of it counts none of it
    plain = step_counts([(sig, rows, None, None, None)], 16, (0,) * 9,
                        dec_rows=1)
    assert plain["cross_tokens"] == plain["kv_blocks_walked_shared"] == 0
    assert plain["kv_blocks_walked"] == 9 * sum(used)


@pytest.mark.parametrize("kind, b, t, rows, ssm_layers, given, moved", [
    # a decode program of 16 rows with 9 live: the grid has 16 rows a
    # layer, the kernel moves 9
    ("decode", 16, 1, [(None, 40 + i, 1) for i in range(9)], 15,
     16 * 15, 9 * 15),
    # a mixed program of 8 rows: three decode rows lead, two chunks follow,
    # then a prompt's last chunk of one token, which does not lead and is
    # moved all the same; the chunks are the blocked scan's
    ("mixed", 8, 64, [(None, 700, 1), (None, 90, 1), (None, 5, 1),
                      (None, 0, 64), (None, 64, 37), (None, 128, 1)], 15,
     8 * 15, 4 * 15),
    # chunks alone: the grid is given its rows and moves none
    ("mixed", 8, 64, [(None, 0, 64), (None, 64, 20)], 15, 8 * 15, 0),
    # a model without recurrent layers: both are zero, as SSM_COUNTS are
    ("decode", 16, 1, [(None, 40 + i, 1) for i in range(9)], 0, 0, 0),
    ("mixed", 8, 64, [(None, 700, 1), (None, 0, 64)], 0, 0, 0),
], ids=["decode", "mixed", "chunks_alone", "no_ssm_decode", "no_ssm_mixed"])
def test_the_one_count_of_the_update_rows(kind, b, t, rows, ssm_layers,
                                          given, moved):
    """PR 49: what the one-token update's grid was given (a program's
    bucket of rows x the recurrent layers) and what it moved (the rows of
    one token x the layers), in the step's one count, and summed by the
    ledger beside the four other counts of the recurrent layers."""
    from dynamo_tpu.obs.compile_ledger import BucketSig
    from dynamo_tpu.obs.sched_ledger import SSM_COUNTS, SchedLedger

    sig = BucketSig(kind, b, t, 512, True, "bfloat16")
    dec = sum(1 for r in rows if r[2] == 1)
    c = step_counts([(sig, rows, None, None, None)], 16, (0,) * 5,
                    dec_rows=dec, ssm_layers=ssm_layers)
    assert c["ssm_update_rows_given"] == given
    assert c["ssm_update_rows_moved"] == moved
    assert c["ssm_state_rows"] == len(rows) * ssm_layers
    assert SSM_COUNTS[4:6] == ("ssm_update_rows_given",
                               "ssm_update_rows_moved")
    # (PR 56: behind them what the Mamba-1 kernel ran, 0 for these models)
    assert SSM_COUNTS[6:] == ("ssm_scan_rows", "ssm_scan_positions")
    assert c["ssm_scan_rows"] == c["ssm_scan_positions"] == 0
    led = SchedLedger()
    for _ in range(2):
        led.record_step(wall_s=0.01, kinds=c["kinds"],
                        ssm=tuple(c[k] for k in SSM_COUNTS))
    snap = led.snapshot()
    assert snap["ssm_update_rows_given_total"] == 2 * given
    assert snap["ssm_update_rows_moved_total"] == 2 * moved
    led.reset()
    assert led.snapshot()["ssm_update_rows_given_total"] == 0


# ---------------------------------------------------------------------------
# the readers where there is nothing to read, and on hand-made contexts
# ---------------------------------------------------------------------------

def _loop(**over) -> dict:
    from dynamo_tpu.obs.profiler import LOOP_PHASES

    return {k: float(over.get(k, 0.0)) for k in LOOP_PHASES}


def _ctx(c0, c1, seconds=50.0):
    return measure.Context(window=(100.0, 100.0 + seconds),
                           window_wall=(1e9, 1e9 + seconds), chips=1,
                           records=[], counters=(c0, c1), trace=None)


def test_host_ms_per_step_is_unchanged_by_the_nested_phases():
    """``engine.host_ms_per_step`` sums phases by name: the parts nested in
    ``engine.dispatch`` and ``engine.unphased`` are beside it, not in it,
    and the parts are at most the whole."""
    was = {"engine.idle_wait": 5.0, "engine.inbox": 0.1, "engine.plan": 0.5,
           "engine.dispatch": 2.4, "engine.compile": 0.4,
           "engine.finalize.wait": 40.0, "engine.finalize.host": 0.6,
           "engine.record": 0.3, "engine.post": 0.5}
    now = {**was, "engine.dispatch.reset": 0.1, "engine.dispatch.fill": 0.7,
           "engine.dispatch.place": 0.5, "engine.dispatch.launch": 1.0,
           "engine.unphased": 0.2}
    read = lambda name, loop: measure.load_reader(name).read(_ctx(
        {"num_steps": 100, "loop": _loop()},
        {"num_steps": 1100, "loop": loop}))
    old = {k: v for k, v in _loop(**was).items() if k in was}
    assert read("engine.host_ms_per_step", _loop(**now)) \
        == read("engine.host_ms_per_step", old) == pytest.approx(4.0)
    assert read("engine.dispatch_fill_ms_per_step", _loop(**now)) \
        == pytest.approx(1.2)
    assert read("engine.dispatch_launch_ms_per_step", _loop(**now)) \
        == pytest.approx(0.6)                  # less the compile inside it
    assert read("engine.unphased_ms_per_step", _loop(**now)) \
        == pytest.approx(0.2)
    parts = sum(v for k, v in now.items() if k.startswith("engine.dispatch."))
    assert parts <= now["engine.dispatch"]
    # a program from before PR 43 has none of them: nothing, and no raise
    for name in NEW[-3:]:
        assert read(name, old) is None, name


@pytest.mark.parametrize("trace", ["v5e-nemo-chat-named-0.25s.xplane.pb",
                                   "v5e-chat-0.3s.xplane.pb",
                                   "cpu-5-steps.xplane.pb", None])
def test_new_readers_find_nothing_on_the_rehearsals_traces(monkeypatch, trace):
    """The recorded traces are of programs that wrote no ``engine.program``
    span (and the CPU's has no device plane): every new reader returns None
    and none raises, with the new program's counters beside them or the
    parent's."""
    monkeypatch.setattr(xevents, "newest_xplane",
                        lambda *a, **k: REHEARSAL / trace if trace else None)
    shapes = _shapes("mistral-nemo-12b-l10")
    moe = {"hidden_size": 6144, "expert_width": 2048, "bytes_per_param": 2}
    new = {"num_steps": 9, "loop": {}, "step_shapes": shapes, "moe": moe,
           "device": {"device_kind": "TPU v5 lite"}}
    for counters in (new, {"num_steps": 9}):
        ctx = _ctx({"num_steps": 1, "loop": {}}, counters)
        for name in NEW:
            assert measure.load_reader(name).read(ctx) is None, name


def test_the_new_entries_and_their_cells():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(entries)
    gated = {"k-exaone-236b.reasoning", "smallthinker-21b.reasoning",
             "glm-4.7-flash.longdoc"}       # (PR 61: 64 gated experts, whole)
    # (PR 45) experts without a gate: the phase's share is read there, its
    # roofline share by a count of its own (moe.ungated_experts_roofline_pct:
    # moe_gemm_counts.py prices three matrices an expert)
    routed = gated | {"nemotron-3-nano-30b.reasoning"}
    for name in NEW:
        e = entries[name]
        assert e["moves"] == "itl_p95_ms"
        if name == "device.moe_experts_pct":
            assert set(e["workloads"]) == routed
        elif name == "moe.experts_roofline_pct":
            assert set(e["workloads"]) == gated
        else:
            assert "workloads" not in e
    assert entries["moe.ungated_experts_roofline_pct"]["workloads"] == [
        "nemotron-3-nano-30b.reasoning"]
    for w in BENCH["workloads"]:
        cell = manifest.load_cell(w["name"])
        want = set(NEW) - {"device.moe_experts_pct", "moe.experts_roofline_pct"}
        if w["name"] in routed:
            want |= {"device.moe_experts_pct"}
        if w["name"] in gated:
            want |= {"moe.experts_roofline_pct"}
        assert want <= set(cell.per_layer)
        assert not (set(NEW) - want) & set(cell.per_layer)
    assert manifest.check() == []


def test_the_readers_on_a_hand_made_trace(monkeypatch, tmp_path):
    """Two decode steps of 10 ms of the 7B cut, each reading its 7.25 GB:
    8.85 ms at 819 GB/s, 88.5 % of its roofline; one kernel call a step."""
    shapes = _shapes("mistral-7b-v0.3-l16")
    modules = [(f"{DEC}(1)", 10 * MS, 20 * MS), (f"{DEC}(1)", 25 * MS, 35 * MS)]
    ops = [("%fusion.1 = bf16[8,14336] fusion(%a)", 10 * MS, 18.5 * MS),
           ("%fusion.7 = bf16[8,4096] fusion(%n)", 18.5 * MS, 19 * MS),
           ("%paged_attention.2 = bf16[8,8,4,128] custom-call(%q)", 19 * MS,
            19.5 * MS),
           ("%copy.9 = bf16[8] copy(%z)", 19.5 * MS, 20 * MS),
           ("%fusion.1 = bf16[8,14336] fusion(%a)", 25 * MS, 33.5 * MS),
           ("%fusion.7 = bf16[8,4096] fusion(%n)", 33.5 * MS, 34 * MS),
           ("%paged_attention.2 = bf16[8,8,4,128] custom-call(%q)", 34 * MS,
            34.5 * MS),
           ("%copy.9 = bf16[8] copy(%z)", 34.5 * MS, 35 * MS)]
    counts = dict(live_tokens=2, logit_rows=2, kv_blocks_walked=16 * 64,
                  attn_q_ctx=16 * 1000)
    host = [_program(1, 0, DEC, 9 * MS), _wait(1, 20.1 * MS),
            _record(1, 20.5 * MS, **counts),
            _program(2, 0, DEC, 24 * MS), _wait(2, 35.1 * MS),
            _record(2, 35.5 * MS, **counts)]
    ev = _events(modules, host, ops)
    ev.path = tmp_path / "hand.xplane.pb"
    tables = {DEC: {"fusion.1": "mlp", "fusion.7": "layer",
                    "paged_attention.2": "attention"}}
    monkeypatch.setattr(xevents, "current", lambda: ev)
    ctx = _ctx({}, {"step_shapes": shapes,
                    "device": {"device_kind": "TPU v5 lite"}})

    def reader(name):
        mod = measure.load_reader(name)
        monkeypatch.setattr(mod.join, "current",
                            lambda: join.build(ev, tables))
        return mod.read(ctx)

    nbytes, flop = work.step(shapes, {"programs": 1, **counts})
    assert reader("engine.step_roofline_pct") == pytest.approx(
        100 * (nbytes / 819e9) / 0.010)
    assert 88.0 < reader("engine.step_roofline_pct") < 90.0
    assert reader("engine.step_mfu_pct") == pytest.approx(
        100 * flop / (0.010 * 197e12))
    kb, kf = work.kernel(shapes, counts)
    assert reader("attn.kernel_roofline_pct") == pytest.approx(
        100 * max(kb / 819e9, kf / 197e12) / 0.0005)
    # The copies, which no table names, and the layer's rest, which no inner
    # scope does: 0.5 ms each of a step's 10.
    assert reader("device.unscoped_pct") == pytest.approx(10.0)
    assert reader("device.moe_experts_pct") is None              # no such phase
    assert reader("moe.experts_roofline_pct") is None


# ---------------------------------------------------------------------------
# PR 45: the recurrent layers' readers and the count of an expert's matrices
# ---------------------------------------------------------------------------

SSM = {"layers": 15, "slots": 64, "slot_layer_bytes": 2_134_016,
       "token_bytes": (2 * 4096 + 6144 + 64) * 2, "heads": 64, "head_dim": 64,
       "state_size": 128, "groups": 8, "conv_kernel": 4, "conv_dim": 6144}
UNGATED = {"hidden_size": 2688, "expert_width": 1856, "bytes_per_param": 2,
           "expert_matrices": 2}
PR45 = ("device.ssm_pct", "ssm.scan_roofline_pct", "ssm.padding_pct",
        "moe.ungated_experts_roofline_pct")


def test_the_recurrent_readers_on_a_hand_made_trace(monkeypatch, tmp_path):
    """Two decode steps of 10 ms at 16 rows: 1.5 ms of Mamba projections,
    0.5 ms of convolution, 2 ms of the state's update (16 rows x 15 layers
    of 2.13 MB read and written: 1.25 ms at 819 GB/s), 3 ms of experts
    without a gate (14 layers, 96 rows over 10 experts of two matrices)."""
    ssm_counts = measure.load_module(
        ROOT / "chipbench/layers/ssm_counts.py", "ssm_counts")
    ungated = measure.load_module(
        ROOT / "chipbench/layers/moe_ungated_counts.py", "moe_ungated_counts")
    gated = measure.load_module(
        ROOT / "chipbench/layers/moe_gemm_counts.py", "moe_gemm_counts")
    ops, modules, host = [], [], []
    counts = dict(live_tokens=16, logit_rows=16, kv_blocks_walked=16 * 64 * 5,
                  attn_q_ctx=16 * 1000 * 5, ssm_layer_steps=15,
                  ssm_live_tokens=16, ssm_scanned_positions=16,
                  ssm_state_rows=16 * 15, moe_layer_steps=14,
                  moe_rows=14 * 12, moe_experts_touched=14 * 10)
    for step, t0 in ((1, 10 * MS), (2, 25 * MS)):
        modules.append((f"{DEC}(1)", t0, t0 + 10 * MS))
        ops += [("%fusion.1 = bf16[16,10304] fusion(%a)", t0, t0 + 1.5 * MS),
                ("%fusion.2 = bf16[16,6144] fusion(%b)", t0 + 1.5 * MS,
                 t0 + 2 * MS),
                ("%ssm_update.3 = f32[15,65,64,64,128] custom-call(%s)",
                 t0 + 2 * MS, t0 + 4 * MS),
                ("%moe_stream.4 = f32[16,2688] custom-call(%x)", t0 + 4 * MS,
                 t0 + 7 * MS),
                ("%fusion.5 = bf16[16,16384] fusion(%h)", t0 + 7 * MS,
                 t0 + 10 * MS)]
        host += [_program(step, 0, DEC, t0 - MS), _wait(step, t0 + 10.1 * MS),
                 _record(step, t0 + 10.5 * MS, **counts)]
    ev = _events(modules, host, ops)
    ev.path = tmp_path / "hand.xplane.pb"
    tables = {DEC: {"fusion.1": "ssm_proj", "fusion.2": "ssm_conv",
                    "ssm_update.3": "ssm_scan", "moe_stream.4": "moe_experts",
                    "fusion.5": "logits"}}
    monkeypatch.setattr(xevents, "current", lambda: ev)
    sched0 = {"ssm_live_tokens_total": 100, "ssm_scanned_positions_total": 400}
    sched1 = {"ssm_live_tokens_total": 700, "ssm_scanned_positions_total": 1600}
    full = {"ssm": SSM, "moe": UNGATED, "sched": sched1,
            "device": {"device_kind": "TPU v5 lite"}}

    def reader(name, c1=full, c0=None):
        mod = measure.load_reader(name)
        if hasattr(mod, "join"):
            monkeypatch.setattr(mod.join, "current",
                                lambda: join.build(ev, tables))
        return mod.read(_ctx({"sched": sched0} if c0 is None else c0, c1))

    assert reader("device.ssm_pct") == pytest.approx(40.0)      # 4 ms of 10
    nbytes, flop = ssm_counts.step(16 * 15, 16, SSM)
    assert nbytes == 16 * 15 * 2 * 2_134_016 + 16 * 15 * SSM["token_bytes"]
    assert flop == 16 * 15 * (5 * 64 * 64 * 128 + 2 * 4 * 6144)
    assert reader("ssm.scan_roofline_pct") == pytest.approx(
        100 * (nbytes / 819e9) / 0.0025)
    assert 49.0 < reader("ssm.scan_roofline_pct") < 51.0
    assert reader("ssm.padding_pct") == pytest.approx(50.0)     # 600 of 1200
    b2, f2 = ungated.layer_step(12, 10, 2688, 1856, 2)
    b3, f3 = gated.layer_step(12, 10, 2688, 1856)
    assert ungated.layer_step(12, 10, 2688, 1856, 3) == (b3, f3)
    assert f2 * 3 == f3 * 2 and b2 < b3
    assert reader("moe.ungated_experts_roofline_pct") == pytest.approx(
        100 * 14 * (b2 / 819e9) / 0.003)
    # a program without the facts (the parent's, another model's): nothing
    # (device.ssm_pct reads the phases alone: nothing where no table has one)
    for name in PR45[1:]:
        assert reader(name, {"device": full["device"], "sched": {},
                             "moe": {k: v for k, v in UNGATED.items()
                                     if k != "expert_matrices"}},
                      {"sched": {}}) is None, name
    tables = {DEC: {"fusion.1": "proj", "fusion.5": "logits"}}
    assert reader("device.ssm_pct") is None
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in PR45:
        # (the recurrent layer's three also in the cells PR 52 and PR 56 added)
        assert entries[name]["workloads"] == ["nemotron-3-nano-30b.reasoning"] \
            + ["falcon-h1-34b.reasoning", "phi-4-mini-flash.reasoning"] \
            * name.startswith(("device.ssm", "ssm."))
        assert entries[name]["moves"] == "itl_p95_ms"


# ---------------------------------------------------------------------------
# PR 56: the Mamba-1 recurrence's kernel by its own name
# ---------------------------------------------------------------------------

SSM1 = {"layers": 9, "slots": 64, "slot_layer_bytes": 358_400,
        "token_bytes": (2 * 5120 + 5120 + 5120) * 2, "heads": 5120,
        "head_dim": 1, "state_size": 16, "groups": 1, "conv_kernel": 4,
        "conv_dim": 5120, "recurrence": "mamba1"}


def test_the_selective_scan_reader_on_a_hand_made_trace(monkeypatch, tmp_path):
    """Two decode steps at 20 rows: the kernel's nine calls take 0.3 ms of
    a step, beside 0.2 ms of casts and the gate under the same phase. The
    ideal is the rows' states read and written (20 x 9 x 2 x 327,680 B:
    0.144 ms at 819 GB/s) and a position's operands; the share is of the
    kernel's calls by name, the phase's rest is ``ssm.scan_roofline_pct``'s."""
    counts_mod = measure.load_module(
        ROOT / "chipbench/layers/selective_scan_counts.py",
        "selective_scan_counts")
    ops, modules, host = [], [], []
    counts = dict(live_tokens=20, logit_rows=20, kv_blocks_walked=20 * 16 * 40,
                  attn_q_ctx=20 * 16 * 600, ssm_layer_steps=9,
                  ssm_live_tokens=20, ssm_scanned_positions=20,
                  ssm_state_rows=20 * 9, ssm_scan_rows=20 * 9,
                  ssm_scan_positions=20 * 9, cross_tokens=20,
                  kv_blocks_walked_shared=20 * 7 * 40)
    for step, t0 in ((1, 10 * MS), (2, 25 * MS)):
        modules.append((f"{DEC}(1)", t0, t0 + 10 * MS))
        ops += [("%fusion.1 = f32[20,40,128] fusion(%a)", t0, t0 + 0.2 * MS),
                ("%selective_scan.2 = f32[9,65,16,40,128] custom-call(%s)",
                 t0 + 0.2 * MS, t0 + 0.5 * MS),
                ("%fusion.5 = bf16[20,16384] fusion(%h)", t0 + 0.5 * MS,
                 t0 + 10 * MS)]
        host += [_program(step, 0, DEC, t0 - MS), _wait(step, t0 + 10.1 * MS),
                 _record(step, t0 + 10.5 * MS, **counts)]
    ev = _events(modules, host, ops)
    ev.path = tmp_path / "hand.xplane.pb"
    tables = {DEC: {"fusion.1": "ssm_scan", "selective_scan.2": "ssm_scan",
                    "fusion.5": "logits"}}
    monkeypatch.setattr(xevents, "current", lambda: ev)
    mod = measure.load_reader("ssm.selective_scan_roofline_pct")
    monkeypatch.setattr(mod.join, "current", lambda: join.build(ev, tables))
    full = {"ssm": SSM1, "sched": {}, "device": {"device_kind": "TPU v5 lite"}}
    nbytes, ops_ = counts_mod.step(180, 180, SSM1)
    assert nbytes == 180 * 2 * 16 * 5120 * 4 + 180 * (5120 * 8 + 64)
    assert ops_ == 180 * 6 * 16 * 5120
    got = mod.read(_ctx({"sched": {}}, full))
    assert got == pytest.approx(100 * (nbytes / 819e9) / 0.0003)
    assert 40.0 < got < 60.0
    # the phase's share, beside it, is of all 0.5 ms
    ssm_counts = measure.load_module(
        ROOT / "chipbench/layers/ssm_counts.py", "ssm_counts")
    phase = measure.load_reader("ssm.scan_roofline_pct")
    monkeypatch.setattr(phase.join, "current", lambda: join.build(ev, tables))
    assert phase.read(_ctx({"sched": {}}, full)) == pytest.approx(
        100 * ssm_counts.ideal_seconds(180, 20, SSM1, peaks.peaks_for(
            "TPU v5 lite")) / 0.0005)
    # nothing on a program without the kernel (the parent's: no such call,
    # no such count) or without a state pool
    tables = {DEC: {"fusion.1": "ssm_scan", "fusion.5": "logits"}}
    ev2 = _events(modules, host, [o for o in ops if "selective" not in o[0]])
    ev2.path = tmp_path / "hand2.xplane.pb"
    monkeypatch.setattr(mod.join, "current", lambda: join.build(ev2, tables))
    assert mod.read(_ctx({"sched": {}}, full)) is None
    assert mod.read(_ctx({"sched": {}}, {"sched": {}})) is None
    entry = {m["name"]: m for m in BENCH["per_layer"]}[mod.name]
    assert entry["workloads"] == ["phi-4-mini-flash.reasoning"]
    assert (entry["moves"], entry["source"]) == ("itl_p95_ms", "device_trace")
