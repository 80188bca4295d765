"""A step's inputs as one packed array (two where a row samples):
``pack_step_inputs`` / ``unpack_step_inputs`` (dynamo_tpu/engine/engine.py)
are inverse field by field, ``_fill_inputs`` and ``_padding_inputs`` make the
same packed form, the step program gives what its thirteen-array call gives
bit for bit, ``placed_inputs`` counts the placements, and the thirteen-array
call (``chipbench/aot_check.py compile_bucket``) still lowers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.engine import (
    EngineCore,
    pack_step_inputs,
    unpack_step_inputs,
)

from tests.test_engine import make_req, tiny_config

FIELDS = ("tokens", "q_start", "q_len", "bt", "slots", "temp", "top_k",
          "top_p", "fp", "pp", "rp", "do_sample", "from_slot")
FLOATS = ("temp", "top_p", "fp", "pp", "rp")


def _random_rows(b: int, t: int, nblk: int, seed: int = 0) -> tuple:
    """Thirteen per-row arrays in the program's order and dtypes, every
    value drawn: what any fill could make, and more."""
    rng = np.random.default_rng(seed)
    i32 = lambda hi, *shape: rng.integers(0, hi, shape).astype(np.int32)  # noqa: E731
    f32 = lambda *shape: rng.random(shape).astype(np.float32)            # noqa: E731
    flag = lambda: rng.integers(0, 2, (b,)).astype(bool)                 # noqa: E731
    return (i32(2**31 - 1, b, t), i32(4096, b), i32(t + 1, b),
            i32(2**20, b, nblk), i32(65, b), f32(b) * 2, i32(100, b), f32(b),
            f32(b) - 0.5, f32(b) - 0.5, f32(b) + 0.5, flag(), flag())


@pytest.mark.parametrize("field", FIELDS)
@pytest.mark.parametrize("b, t, nblk, greedy", [
    (8, 1, 16, True), (8, 1, 16, False), (4, 32, 8, True), (4, 32, 8, False),
], ids=["decode-greedy", "decode-sampling", "mixed-greedy", "mixed-sampling"])
def test_unpack_inverts_pack(b, t, nblk, greedy, field):
    rows = _random_rows(b, t, nblk)
    packed = pack_step_inputs(*rows, greedy=greedy)
    assert len(packed) == (1 if greedy else 2)
    assert packed[0].shape == (b, 5 + t + nblk) and packed[0].dtype == np.int32
    if not greedy:
        assert packed[1].shape == (b, 5) and packed[1].dtype == np.float32
    back = dict(zip(FIELDS, unpack_step_inputs(t, *packed)))
    want = dict(zip(FIELDS, rows))[field]
    if greedy and field in FLOATS:
        # a fast_greedy program has no sampling options to read
        assert back[field] is None
        return
    assert back[field].dtype == want.dtype and back[field].shape == want.shape
    np.testing.assert_array_equal(back[field], want)


def _drive(core: EngineCore, reqs, on_fill=None, max_steps=200):
    """Run ``reqs`` to their end; returns {rid: (tokens, logprobs)}, the
    number of programs dispatched in each step and ``placed_inputs`` after
    it. ``on_fill(rows, result)`` sees every ``_fill_inputs`` call."""
    runner = core.runner
    fill, dispatch = runner._fill_inputs, runner.dispatch
    calls = []

    def spy_fill(rows, sample_rows, masks):
        out = fill(rows, sample_rows, masks)
        if on_fill is not None:
            on_fill(rows, out)
        return out

    def spy_dispatch(*a, **kw):
        calls.append(1)
        return dispatch(*a, **kw)

    runner._fill_inputs, runner.dispatch = spy_fill, spy_dispatch
    for r in reqs:
        core.add_request(r)
    got = {r.request_id: ([], []) for r in reqs}
    steps = []
    for _ in range(max_steps):
        if not core.has_work():
            break
        before = len(calls)
        for rid, out in core.step().items():
            got[rid][0].extend(out.token_ids)
            got[rid][1].extend(out.log_probs or [])
        steps.append((len(calls) - before,
                      core.metrics.snapshot(core.sched, core.pool)[
                          "placed_inputs"]))
    assert not core.has_work()
    return got, steps


def _greedy_reqs(tag=""):
    # a 40-token prompt takes two chunks of 32: mixed steps, then decode
    return [make_req(rid=f"{tag}long", prompt=list(range(3, 43)), max_tokens=6),
            make_req(rid=f"{tag}a", prompt=[20, 30, 40], max_tokens=9),
            make_req(rid=f"{tag}b", max_tokens=4)]


def _sampled_reqs(tag=""):
    return [make_req(rid=f"{tag}s0", temperature=0.8, seed=7, top_k=20,
                     top_p=0.9, frequency_penalty=0.3, presence_penalty=0.2,
                     repetition_penalty=1.1, max_tokens=10),
            make_req(rid=f"{tag}s1", prompt=list(range(50, 90)),
                     temperature=1.2, seed=11, max_tokens=6),
            # a greedy row beside them rides the sampling program
            make_req(rid=f"{tag}g", prompt=[9, 8, 7], max_tokens=8)]


def _guided_reqs(tag=""):
    return [make_req(rid=f"{tag}json", prompt=list(range(40, 52)),
                     max_tokens=12, guided_json={}),
            make_req(rid=f"{tag}plain", max_tokens=5)]


def _mm_reqs(tag=""):
    from tests.test_multimodal import mm_req

    emb = np.random.default_rng(3).standard_normal((4, 64)).astype(np.float32)
    return [mm_req(emb, f"{tag}mm", max_tokens=4)]


CASES = {"greedy": _greedy_reqs, "sampling": _sampled_reqs,
         "logit_mask": _guided_reqs, "multimodal": _mm_reqs}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fill_and_padding_make_the_same_packed_form(case):
    """Every step of a run: the fill's packed arrays have the shapes and
    dtypes of the padding's for the same (b, t, nblk, greedy), the
    multimodal pair and the logit mask ride behind them as separate arrays,
    and what the program will cut out of them is what the rows said."""
    core = EngineCore(tiny_config())
    runner, cfg = core.runner, core.model_cfg
    seen = {"decode": 0, "mixed": 0, "packed": set(), "mm": 0, "masked": 0}

    def on_fill(rows, out):
        sig, _sp, arrays, mm, masked = out
        b, t, nblk = sig.b, sig.t, sig.nblk
        n = 1 if sig.greedy else 2
        seen["decode" if t == 1 else "mixed"] += 1
        seen["packed"].add(n)
        seen["mm"] += mm
        seen["masked"] += masked
        pad = runner._padding_inputs(b, t, nblk, sig.greedy)
        assert len(arrays) == n + 2 * mm + masked and len(pad) == n
        for x, p in zip(arrays, pad):
            assert (x.shape, x.dtype) == (p.shape, p.dtype)
        rest = arrays[n:]
        if mm:
            emb, emb_mask = rest[:2]
            assert emb.shape == (b, t, cfg.hidden_size)
            assert (emb_mask.shape, emb_mask.dtype) == ((b, t), bool)
        if masked:
            assert rest[-1].shape == (b, cfg.vocab_size)
            assert not sig.greedy
        f = dict(zip(FIELDS, unpack_step_inputs(t, *arrays[:n])))
        for i, (seq, start, length) in enumerate(rows):
            assert (f["q_start"][i], f["q_len"][i]) == (start, length)
            assert f["slots"][i] == max(seq.slot, 0)
            if not f["from_slot"][i]:
                assert list(f["tokens"][i, :length]) == \
                    seq.tokens[start:start + length]
            ids = seq.block_ids[:nblk]
            assert list(f["bt"][i, :len(ids)]) == ids
            so = seq.req.sampling_options
            assert f["top_k"][i] == (so.top_k or 0)
            if not sig.greedy:
                assert f["temp"][i] == np.float32(so.temperature)
                assert f["rp"][i] == np.float32(so.repetition_penalty or 1.0)
        # rows beyond the live ones are padding, as _padding_inputs has them
        live = len(rows)
        np.testing.assert_array_equal(arrays[0][live:],
                                      np.asarray(pad[0])[live:])
        if not sig.greedy:
            np.testing.assert_array_equal(arrays[1][live:],
                                          np.asarray(pad[1])[live:])

    _drive(core, CASES[case](), on_fill)
    assert seen["decode"] and seen["mixed"]
    assert seen["packed"] == ({1} if case in ("greedy", "multimodal")
                              else {1, 2} if case == "logit_mask" else {2})
    assert bool(seen["mm"]) == (case == "multimodal")
    assert bool(seen["masked"]) == (case == "logit_mask")


@pytest.mark.parametrize("case", ["greedy", "sampling"])
def test_packed_call_gives_what_the_thirteen_array_call_gives(case, monkeypatch):
    """The same requests through two engines of the same weights: one as
    served (the packed inputs, cut apart inside the program), one whose
    packer hands the thirteen arrays on one by one (the program's other
    trace, what ``aot_check.compile_bucket`` lowers). Tokens and logprobs
    are the same bits: the same values reach the model and the sampler in
    the same dtypes."""
    packed, steps = _drive(EngineCore(tiny_config()), CASES[case]())
    per_program = 1 if case == "greedy" else 2
    assert steps[-1][1] == per_program * sum(n for n, _ in steps)

    monkeypatch.setattr(engine_mod, "pack_step_inputs",
                        lambda *rows, greedy: rows)
    thirteen, steps13 = _drive(EngineCore(tiny_config()), CASES[case]())
    assert steps13[-1][1] == 13 * sum(n for n, _ in steps13)
    assert [n for n, _ in steps] == [n for n, _ in steps13]
    assert packed.keys() == thirteen.keys()
    for rid in packed:
        toks, lps = packed[rid]
        assert toks and len(lps) == len(toks), rid
        assert toks == thirteen[rid][0], rid
        # the very bits, not a tolerance
        assert np.asarray(lps, np.float64).tobytes() == \
            np.asarray(thirteen[rid][1], np.float64).tobytes(), rid


@pytest.mark.parametrize("case, per_program", [("greedy", 1), ("sampling", 2)])
def test_placed_inputs_grows_by_the_arrays_a_step_places(case, per_program):
    """``stats()["placed_inputs"]``: one array a greedy step program, two a
    sampling one, step by step; the warm-up's and the probe's padding
    inputs are not counted."""
    core = EngineCore(tiny_config())
    assert core.metrics.snapshot(core.sched, core.pool)["placed_inputs"] == 0
    _got, steps = _drive(core, CASES[case]())
    assert len(steps) > 5
    last = 0
    for programs, placed in steps:
        assert placed - last == per_program * programs
        last = placed
    assert core.metrics.num_steps == len([n for n, _ in steps if n])


@pytest.mark.parametrize("t", [1, 16], ids=["decode", "mixed"])
@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_the_thirteen_array_call_still_lowers(t, greedy):
    """What ``chipbench/aot_check.py compile_bucket`` does (a file of the
    benchmark's, not edited): the function ``_build_step_fn`` returns
    lowered from thirteen abstract per-row arrays passed one by one. The
    packed call of the same function is another trace of the same body,
    with the same outputs."""
    runner = EngineCore(tiny_config()).runner
    b, nblk = 4, 4
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    thirteen = (
        sds((b, t), i32), sds((b,), i32), sds((b,), i32), sds((b, nblk), i32),
        sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
        sds((b,), f32), sds((b,), f32), sds((b,), f32), sds((b,), bool),
        sds((b,), bool))
    state = (runner.params, runner.cache_k, runner.cache_v, runner.counts,
             runner.keys, runner.slot_toks)
    fn = runner._build_step_fn(b, t, nblk, fast_greedy=greedy)
    old = fn.lower(*state, *thirteen)
    new = fn.lower(*state, *runner._padding_inputs(b, t, nblk, greedy))
    assert old.out_info == new.out_info
    assert len(old.args_info[0]) == 6 + 13
    assert len(new.args_info[0]) == 6 + (1 if greedy else 2)
    # a packed call with the wrong number of arrays is refused when traced
    with pytest.raises(TypeError, match="packed per-row inputs"):
        fn.lower(*state, *runner._padding_inputs(b, t, nblk, not greedy))
