"""What is warmed is what is reached.

The benchmark warms the step programs that ``chipbench/harness/sut.py
reachable_buckets`` enumerates from the program's device-free mirror
(``obs/compile_ledger.py sig_for_rows``). Held here, for each cell of
``BENCHMARK.json``: a replay of the cell's own trace through the real
scheduler, cut into programs as ``EngineCore`` cuts it (``pack_rows``) and
bucketed by ``ModelRunner.bucket_of`` (what dispatch() calls), mints no
``(kind, b, t, nblk, N)`` outside that set; the set is no larger than it
was; no program is sent more live tokens than its bucket N; and the
scheduling ledger counts N a program. Held on both attention paths: the
kernel walks a row's live blocks whatever the table's width, so every
program has the one width ``max_nblk`` and a cell warms 14 (the routed
cell, which says ``max_rows`` 32 for its long outputs, 21: three row
buckets); the dense gather pays for every entry and keeps the pow2 ladder
of widths (64 / 48 / 64; 99). Nothing here runs a model: no number is a measurement.
"""

from __future__ import annotations

import dataclasses
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import manifest, sut, traffic  # noqa: E402

from dynamo_tpu.engine.engine import ModelRunner  # noqa: E402
from dynamo_tpu.engine.prefix_pool import PrefixPool  # noqa: E402
from dynamo_tpu.engine.scheduler import Scheduler, Seq  # noqa: E402
from dynamo_tpu.models.config import resolve_model_config  # noqa: E402
from dynamo_tpu.obs.compile_ledger import (  # noqa: E402
    attends_tokens,
    pack_rows,
    sig_for_rows,
    token_bucket,
)
from dynamo_tpu.obs.sched_ledger import step_geometry  # noqa: E402
from dynamo_tpu.protocols.common import (  # noqa: E402
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

# Warmed programs a cell under the dense gather's ladder of table widths, as
# the accepted benchmark had them on the chip until PR 35 (PERF.md), and
# under the kernel: (rows 8, 16) x (decode + six chunk buckets), one width.
WARMED = {"mistral-7b.chat": 64, "mistral-7b.longprompt": 48,
          "mistral-nemo-12b.chat": 64, "k-exaone-236b.reasoning": 99,
          "smallthinker-21b.reasoning": 66,
          "nemotron-3-nano-30b.reasoning": 99,
          "falcon-h1-34b.reasoning": 99,
          "phi-4-mini-flash.reasoning": 99,
          # (PR 61: tables of 512-2,048 blocks, prompts of 8,192 and up)
          "glm-4.7-flash.longdoc": 40}
# ... rows (8, 16), or (8, 16, 32) where the cell's ``max_rows`` is 32.
WARMED_KERNEL = {"mistral-7b.chat": 14, "mistral-7b.longprompt": 14,
                 "mistral-nemo-12b.chat": 14, "k-exaone-236b.reasoning": 21,
                 "smallthinker-21b.reasoning": 14,
                 "nemotron-3-nano-30b.reasoning": 21,
                 "falcon-h1-34b.reasoning": 21,
                 "phi-4-mini-flash.reasoning": 21,
                 "glm-4.7-flash.longdoc": 14}
# What EngineCore resolves EngineConfig.attn_impl to: on a TPU, elsewhere.
PATHS = {"kernel": "pallas", "gather": "dense"}
# Seconds a step takes in the replay: (a decode step, each chunk token on
# top). Two clocks, because which prompts coincide in a step depends on how
# long the steps before took: about the parent's step and about this one.
CLOCKS = {"fast": (0.015, 0.00015), "slow": (0.015, 0.0004)}
# The routed cell's requests last a thousand steps, so how many are in flight
# (and with it the row bucket) hangs on the step time far more than in the
# other cells: its clocks are about what the chip read (a decode step 11.9
# ms, a mixed step 43.1 ms; PERF.md, PR 39) and a third slower. At the old
# 0.4 ms a chunk token the replay holds more than the cell's ``max_rows``.
CLOCKS_OF = {"k-exaone-236b.reasoning": {"fast": (0.011, 0.0001),
                                         "slow": (0.015, 0.00015)},
             # (PR 41: a decode step 14-16 ms at 7-10 rows, a 512-token
             # chunk step 72 ms)
             "smallthinker-21b.reasoning": {"fast": (0.014, 0.00011),
                                            "slow": (0.018, 0.00015)},
             # (PR 45: a decode step 11-15 ms at 10-20 rows, a 512-token
             # chunk step ~90 ms)
             "nemotron-3-nano-30b.reasoning": {"fast": (0.011, 0.00012),
                                               "slow": (0.015, 0.00018)},
             # (PR 52: a step period of 14.8 ms at 15-24 rows, a mixed step
             # 25 ms in the mean over chunk buckets; and 7 % slower, all the
             # room this cell has: at 0.8 x its knee 26-27 of the 32 warmed
             # rows are in flight, and the replay passes 32 at steps 10 %
             # slower in order 4 and 15 % slower in the cell's own.
             # ``engine.compiles_in_window.chat`` reads it in a traced run)
             "falcon-h1-34b.reasoning": {"fast": (0.0148, 0.00004),
                                         "slow": (0.0158, 0.000043)},
             # (PR 56: a step period of 17.6 ms at 13-20 rows at 0.8 req/s,
             # 18.4 at 20 rows at 1.0; a mixed step 33 ms in the mean over
             # chunk buckets, ~0.05 ms a chunk token: a prompt's tokens run
             # half the depth; and a seventh slower: 31 rows were in flight
             # at most at 1.0 req/s over 150 s, so the 32-row programs have
             # that room at the cell's 0.8)
             "phi-4-mini-flash.reasoning": {"fast": (0.0176, 0.00005),
                                            "slow": (0.0200, 0.000057)},
             # (PR 61, chip call 177: a decode step 5-8 ms at 8 rows and
             # 6.5-12 at 16 over 1k-30k of latent context, a 512-token chunk
             # step 20-55 ms by the context under it, ~0.06 ms a chunk
             # token in the mean; and a third slower)
             "glm-4.7-flash.longdoc": {"fast": (0.008, 0.000055),
                                       "slow": (0.009, 0.00006)}}
POOL_BLOCKS = 6000
# ... and where a cell's prompts would not fit that: about the chip's pool
POOL_BLOCKS_OF = {"glm-4.7-flash.longdoc": 20000}


def _replay(cell, ec, order: int, clock: tuple[float, float]):
    """Every program the cell's trace dispatches: (signature, live tokens),
    and every step's batches as ``PendingStep`` holds them."""
    tr = cell.traffic
    ramp_s, rate = float(tr["ramp_s"]), float(tr["rate_per_s"])
    vocab = cell.model["vocab_size"]
    reqs = traffic.schedule(tr, vocab, ramp_s, 1, rate, 1, order)
    reqs = [(r.due_s, r) for r in reqs] + [
        (ramp_s + r.due_s, r)
        for r in traffic.schedule(tr, vocab, 51.0, 1, rate, 0, order)]
    sched = Scheduler(PrefixPool(POOL_BLOCKS_OF.get(cell.name, POOL_BLOCKS),
                                 ec.block_size),
                      ec.max_batch_size, ec.prefill_chunk, ec.max_model_len,
                      ec.max_tokens_per_step)
    runner = types.SimpleNamespace(
        engine_cfg=ec, max_nblk=-(-ec.max_model_len // ec.block_size))
    now, nxt, programs, steps = 0.0, 0, [], []
    while nxt < len(reqs) or sched.has_work():
        while nxt < len(reqs) and reqs[nxt][0] <= now:
            r = reqs[nxt][1]
            sched.add(Seq(req=PreprocessedRequest(
                token_ids=list(r.prompt),
                stop_conditions=StopConditions(max_tokens=r.max_tokens,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0)),
                block_size=ec.block_size))
            nxt += 1
        plan = sched.plan()
        if plan.empty:
            now = reqs[nxt][0]
            continue
        # EngineCore._dispatch_plan: decode rows, then the chunks.
        rows = ([(s, s.num_computed, 1) for s in plan.decode]
                + [(w.seq, w.start, w.length) for w in plan.prefill])
        batches, lo = [], 0
        for k in pack_rows([r[2] for r in rows], ec):
            run, lo = rows[lo:lo + k], lo + k
            sig = ModelRunner.bucket_of(runner, run)
            programs.append(((sig.kind, sig.b, sig.t, sig.nblk, sig.n),
                             sum(r[2] for r in run)))
            batches.append((sig, run, [True] * k, None, None))
        steps.append((batches, len(plan.decode)))
        for seq, start, length in rows:
            samples = start + length >= seq.prefill_target()
            seq.num_computed = start + length
            if samples:
                seq.tokens.append(1)
                if (seq.num_output_tokens
                        >= seq.req.stop_conditions.max_tokens):
                    sched.finish(seq, FinishReason.LENGTH)
        now += clock[0] + clock[1] * sum(w.length for w in plan.prefill)
    return programs, steps


@pytest.fixture(scope="module", params=sorted(PATHS))
def cells(request):
    bench = manifest.load_benchmark()
    out = {}
    for name in WARMED:
        cell = manifest.load_cell(name, bench)
        ec = dataclasses.replace(sut.engine_config(cell.config_dir, cell.about),
                                 attn_impl=PATHS[request.param])
        warmed = {(s.kind, s.b, s.t, s.nblk, s.n)
                  for s in sut.reachable_buckets(cell.traffic, ec)}
        out[name] = (cell, ec, warmed)
    out["path"] = request.param
    return out


def test_benchmark_has_the_cells_held_here():
    names = {w["name"] for w in manifest.load_benchmark()["workloads"]}
    assert names == set(WARMED)


@pytest.mark.parametrize("name", sorted(WARMED))
def test_a_cell_warms_no_more_programs_than_before(cells, name):
    cell, ec, warmed = cells[name]
    assert len(warmed) == len(sut.reachable_buckets(cell.traffic, ec))
    max_nblk = -(-ec.max_model_len // ec.block_size)
    if cells["path"] == "kernel":
        assert len(warmed) == WARMED_KERNEL[name]
        assert {s[3] for s in warmed} == {max_nblk}
    else:
        assert len(warmed) == WARMED[name]
        assert len({s[3] for s in warmed}) > 3       # the ladder of widths
    # N follows from (kind, b, t): the lattice has no dimension for it.
    assert len({s[:4] for s in warmed}) == len(warmed)


@pytest.mark.parametrize("clock", sorted(CLOCKS))
@pytest.mark.parametrize("order", [1, 2, 3, 4])
@pytest.mark.parametrize("name", sorted(WARMED))
def test_replayed_trace_reaches_only_warmed_programs(cells, name, order,
                                                     clock):
    cell, ec, warmed = cells[name]
    seconds = CLOCKS_OF.get(name, CLOCKS)[clock]    # (a step, a chunk token)
    programs, steps = _replay(cell, ec, order, seconds)
    assert len(programs) > 500
    cold = sorted({sig for sig, _ in programs} - warmed)
    assert not cold, cold
    # No program is sent more tokens than its dense layers hold, and the
    # mirror names the same program from the same rows.
    assert all(live <= sig[4] for sig, live in programs)
    # Long prompts overlap: steps with two full chunks, which overflow one
    # token bucket and go out as two programs (7-24 a run; chat has 0-4).
    # How many follows from what the cell states: an arriving prompt finds
    # another in prefill with probability rate x a median prompt's prefill
    # time, and then about half its chunks run beside the other's (the
    # second routed cell's 31 requests at 0.44 req/s: 1.5-2.1 likely, 2-4
    # seen; every other cell 5 or more likely).
    tr = cell.traffic
    median = tr["prompt_tokens"]["median"]
    if median > ec.prefill_chunk:
        requests = (float(tr["ramp_s"]) + 51.0) * float(tr["rate_per_s"])
        likely = (requests * float(tr["rate_per_s"]) * median * seconds[1]
                  * -(-median // ec.prefill_chunk) / 2)
        assert sum(len(batches) > 1 for batches, _ in steps) >= max(
            1, min(5, int(likely)))
    model_cfg = resolve_model_config(str(cell.config_dir))
    for batches, dec_rows in steps[::7]:
        g = step_geometry(model_cfg, ec, batches, dec_rows=dec_rows)
        runs = [_bucket_of_batch(ec, batch) for batch in batches]
        assert runs == [sig for sig, *_ in batches]
        assert g["sched_tokens"] == sum(s.n for s in runs)
        # what attention is handed: a packed step's tokens under the
        # kernel (PR 50), the b x t rectangle under the dense gather
        assert g["rect_tokens"] == sum(
            s.n if attends_tokens(ec) else s.b * s.t for s in runs)
        assert g["live_tokens"] == sum(
            length for _, rows, *_ in batches for _, _, length in rows)
        assert g["decode_rows"] == dec_rows


def _bucket_of_batch(ec, batch):
    _, rows, *_ = batch
    need = max(-(-(start + length) // ec.block_size)
               for _, start, length in rows)
    return sig_for_rows("mixed", len(rows), max(r[2] for r in rows), need, ec)


@pytest.mark.parametrize("lengths, want", [
    ([1, 1, 1], [3]),                      # a decode batch is one run
    ([1, 1, 512], [3]),                    # one chunk and its decoders
    ([1, 512, 512], [2, 1]),               # two full chunks: two programs
    ([1, 300, 100, 90], [4]),              # short chunks share one
    ([1] * 7 + [512, 30], [8, 1]),         # the ninth row would not fit
    ([512] * 3, [1, 1, 1]),                # chunks alone: b=8, t=512 holds 520
    ([9, 9, 3], [3]),                      # b=8, t=16 holds 24: 21 fit
    ([9, 9, 7], [2, 1]),                   # 25 do not
])
def test_pack_rows_cuts_a_step_at_the_token_bucket(lengths, want):
    from dynamo_tpu.utils.config import EngineConfig

    ec = EngineConfig(model="tiny-llama")
    assert pack_rows(lengths, ec) == want


@pytest.mark.parametrize("kind, b, t, want", [
    ("decode", 8, 1, 8), ("mixed", 8, 1, 8), ("mixed", 8, 512, 520),
    ("mixed", 16, 16, 32), ("mixed", 64, 512, 576), ("mixed", 1, 512, 512),
    ("mixed", 4, 16, 20), ("verify", 8, 4, 32), ("embed", 2, 16, 32),
])
def test_token_bucket_follows_from_the_signature(kind, b, t, want):
    assert token_bucket(kind, b, t) == want
