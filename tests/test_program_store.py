"""The program store (PR 57): a warm start loads its step programs instead of
tracing and lowering them again.

``ModelRunner.step_fn`` looks in ``<compile cache dir>/programs/`` before it
builds a program, under a key read off the runner with no trace
(``engine/program_store.py``). The store is on where the persistent compile
cache is (so off on the CPU backend); these tests reach it through
``EngineCore(..., program_dir=...)``. Held here: a second engine over the
same directory loads every program the first built and serves the same
tokens; each part of the key, changed alone, is a miss; a damaged entry falls
back, serves correctly and is written again; two writers leave one whole
file; ``phase_tables`` answers for a loaded program; old code digests go and
jax's own cache entries stay.

The CPU backend cannot serialise an executable that sorts ("`LessThan` is not
serializable": the routed layers' grouping, the sampler's top-k), so the
routed configuration's case holds the other half here: nothing is written,
the next engine builds and serves the same tokens. The chip loads them (three
of the benchmark's cells are routed).
"""

import os
import pickle
import threading
import time
from pathlib import Path

import jax
import pytest

from dynamo_tpu.engine import engine as eng
from dynamo_tpu.engine import program_store
from dynamo_tpu.engine.program_store import KEEP_DIGESTS, ProgramStore
from dynamo_tpu.models.config import MODEL_PRESETS
from dynamo_tpu.obs.compile_ledger import (
    BucketSig,
    CompileLedger,
    get_compile_ledger,
)
from dynamo_tpu.utils.config import EngineConfig

from test_engine import make_req, run_to_completion
from test_layer_plan import FAMILIES

ENGINE = dict(model="tiny-llama", block_size=16, num_blocks=24,
              max_batch_size=2, max_model_len=64, prefill_chunk=16,
              decode_bucket=(2,))
MODELS = {"dense": MODEL_PRESETS["tiny-llama"],
          "routed": MODEL_PRESETS["tiny-moe"],
          # attention and a Mamba-2 mixer in every layer: the ``ssm=``
          # keyword and the donated state pool
          "recurrent": FAMILIES["side_by_side"]}
DECODE = (2, 1, 4, False, True, False, False)
MIXED = (2, 16, 4, False, True, False, False)
PROMPT = list(range(5, 25))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    get_compile_ledger().reset()
    yield
    get_compile_ledger().reset()


def _core(directory, monkeypatch=None, family="dense", params=None, **over):
    if family == "mesh":        # the dense model over a two-way "model" axis
        family, over = "dense", {**over, "tp": 2}
    if family != "dense":
        monkeypatch.setattr(eng, "resolve_model_config",
                            lambda path: MODELS[family])
    return eng.EngineCore(EngineConfig(**{**ENGINE, **over}), params=params,
                          program_dir=directory)


def _sigs(core):
    nblk = core.runner.max_nblk
    return [BucketSig("decode", 2, 1, nblk, True, "bfloat16"),
            BucketSig("mixed", 2, 16, nblk, True, "bfloat16")]


def _tokens(core):
    out, finished = run_to_completion(
        core, [make_req(PROMPT, max_tokens=6, rid="r")])
    assert finished == {"r"}
    return out["r"]


def _spy_on_builds(monkeypatch):
    built = []
    real = eng.ModelRunner._build_step_fn

    def spy(self, *a, **kw):
        built.append(a)
        return real(self, *a, **kw)

    monkeypatch.setattr(eng.ModelRunner, "_build_step_fn", spy)
    return built


def _entries(directory):
    return sorted(p for p in Path(directory).rglob("*.bin")
                  if "_memory-" not in p.name)


# ---------------------------------------------------------------------------
# a second engine loads what the first built
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family, stored", [
    ("dense", True), ("recurrent", True), ("mesh", True), ("routed", False)])
def test_a_second_engine_loads_what_the_first_built(tmp_path, monkeypatch,
                                                    family, stored):
    first = _core(tmp_path, monkeypatch, family)
    done = first.runner.warmup(_sigs(first))
    assert (done["compiled"], done["failed"]) == (2, 0)
    snap = get_compile_ledger().snapshot()
    assert (snap["cache_entries"], snap["programs_loaded"]) == (2, 0)
    assert len(_entries(tmp_path)) == (2 if stored else 0)
    want = _tokens(first)

    get_compile_ledger().reset()
    built = _spy_on_builds(monkeypatch)
    second = _core(tmp_path, monkeypatch, family)
    done = second.runner.warmup(_sigs(second))
    assert (done["compiled"], done["failed"]) == (2, 0)
    snap = get_compile_ledger().snapshot()
    assert snap["cache_entries"] == 2
    if stored:
        assert built == []
        assert snap["programs_loaded"] == 2
        assert snap["layer_bodies_traced"] == 0
        assert snap["layer_bodies"] == 2 * second.runner._bodies[0]
        assert all(isinstance(fn, jax.stages.Compiled)
                   for fn in second.runner._step_fns.values())
    else:
        assert len(built) == 2 and snap["programs_loaded"] == 0
    assert _tokens(second) == want
    # nothing was built behind the warm-up either
    assert len(built) == (0 if stored else 2)


def test_a_lazy_engine_loads_inside_its_serving_path(tmp_path, monkeypatch):
    """No warm-up: the first dispatch of each bucket finds its program in
    the store. The ledger files the load as the serving path's stall it is
    (``source`` "serve", its seconds the load's and the first call's) and
    says where the program came from beside that."""
    want = _tokens(_core(tmp_path))
    get_compile_ledger().reset()
    built = _spy_on_builds(monkeypatch)
    assert _tokens(_core(tmp_path)) == want
    assert built == []
    snap = get_compile_ledger().snapshot(events=True)
    assert snap["programs_loaded"] == snap["cache_entries"] == 2
    assert [(e["source"], e["loaded"]) for e in snap["events"]] \
        == [("serve", True)] * 2
    assert snap["serve_stall_seconds"] == snap["compile_seconds_total"] > 0


def test_the_store_is_off_where_the_compile_cache_is(tmp_path):
    """On the CPU backend ``configure_compile_cache`` returns None: no
    store, unless a caller names a directory."""
    core = eng.EngineCore(EngineConfig(**ENGINE))
    assert core.runner._store is None
    assert core.runner._load_program(DECODE) is None
    core.runner._keep_program(DECODE, ())        # nothing, and no error
    assert _core(tmp_path).runner._store.root == tmp_path


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """A directory holding the dense configuration's decode program, and
    the runner that wrote it."""
    directory = tmp_path_factory.mktemp("programs")
    core = _core(directory)
    done = core.runner.warmup(_sigs(core)[:1])
    assert (done["compiled"], done["failed"]) == (1, 0)
    assert len(_entries(directory)) == 1
    return directory, core


def _changed(part, stored_core, monkeypatch, directory):
    """A runner over ``directory`` that differs from the one that wrote it
    in ``part`` alone."""
    if part == "dtype":
        params = jax.tree.map(lambda x: x, stored_core.runner.params)
        params["final_norm"] = params["final_norm"].astype("float32")
        return _core(directory, params=params)
    if part == "pool":
        return _core(directory, num_blocks=ENGINE["num_blocks"] + 1)
    if part == "engine_field":
        return _core(directory, pp_microbatches=3)
    if part == "digest":
        monkeypatch.setattr(program_store, "code_digest", lambda: "f" * 64)
    if part == "jax_version":
        monkeypatch.setattr(jax, "__version__", "0.0.0+another")
    if part == "xla_flags":
        monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_enable_fast_min_max=false")
    if part == "seed":
        return _core(directory, seed=7)
    return _core(directory)


@pytest.mark.parametrize("part", [
    "nothing", "seed", "bucket", "dtype", "pool", "engine_field", "digest",
    "jax_version", "xla_flags"])
def test_each_part_of_the_key_changed_alone_is_a_miss(stored, monkeypatch,
                                                      part):
    """The same runner again, and one with another weight seed (values of
    arguments, nothing of a program), load the entry; a runner that differs
    in one part of the key finds nothing, and builds."""
    directory, wrote = stored
    core = _changed(part, wrote, monkeypatch, directory)
    key = MIXED if part == "bucket" else DECODE
    name, store_key = core.runner._program_key(key)
    hit = part in ("nothing", "seed")
    assert (store_key == wrote.runner._program_key(DECODE)[1]) == hit
    fn = core.runner._load_program(key)
    assert (fn is not None) == hit
    if hit:
        assert name == "step_decode_b2_n4"
        assert isinstance(fn, jax.stages.Compiled)
        core.runner._step_fns[key] = fn
        assert core.runner._was_loaded(key)


def test_a_tree_with_one_edited_line_has_another_digest(tmp_path):
    digest = program_store.code_digest.__wrapped__
    for tree in ("a", "b"):
        (tmp_path / tree / "models").mkdir(parents=True)
        (tmp_path / tree / "models" / "llama.py").write_text("x = 1\n")
        (tmp_path / tree / "engine.py").write_text("y = 2\n")
        (tmp_path / tree / "notes.txt").write_text(tree)      # no module
    assert digest(tmp_path / "a") == digest(tmp_path / "b")
    (tmp_path / "b" / "models" / "llama.py").write_text("x = 1 \n")
    assert digest(tmp_path / "a") != digest(tmp_path / "b")
    # the real one: every module of the package, the same twice
    assert program_store.code_digest() == digest(program_store._PACKAGE)
    # and a store opened under another digest shares no directory
    assert ProgramStore(tmp_path / "p", "0" * 64).dir \
        != ProgramStore(tmp_path / "p", "1" * 64).dir


def test_a_file_of_another_key_is_never_unpickled(tmp_path):
    """The key stands before the pickle: a file that says another key, or
    nothing, is refused before any byte of it is unpickled."""
    class Boom:
        def __reduce__(self):
            return (pytest.fail, ("unpickled a foreign file",))

    store = ProgramStore(tmp_path, "0" * 64)
    key, other = "a" * 64, "b" * 64
    store.write("p", other, pickle.dumps(Boom()))
    os.replace(store.path("p", other), store.path("p", key))
    assert store.read("p", key) is None
    assert store.load("p", key, jax.devices()[:1]) is None
    store.path("p", key).write_bytes(pickle.dumps(Boom()))
    assert store.load("p", key, jax.devices()[:1]) is None


# ---------------------------------------------------------------------------
# a damaged entry falls back, serves correctly and is rewritten
# ---------------------------------------------------------------------------

def _truncate(path):
    path.write_bytes(path.read_bytes()[:len(path.read_bytes()) // 2])


def _another_pickle(path):
    header = path.read_bytes().split(b"\n", 2)[:2]
    path.write_bytes(b"\n".join(header) + b"\n"
                     + pickle.dumps({"not": "a program"}))


def _empty(path):
    path.write_bytes(b"")


@pytest.mark.parametrize("damage", [_truncate, _another_pickle, _empty])
def test_a_damaged_entry_falls_back_and_is_rewritten(tmp_path, monkeypatch,
                                                     damage):
    first = _core(tmp_path)
    first.runner.warmup(_sigs(first))
    want = _tokens(first)
    decode, mixed = _entries(tmp_path)
    whole = decode.read_bytes()
    damage(decode)

    get_compile_ledger().reset()
    built = _spy_on_builds(monkeypatch)
    second = _core(tmp_path)
    done = second.runner.warmup(_sigs(second))
    assert (done["compiled"], done["failed"]) == (2, 0)
    assert [a[:2] for a in built] == [(2, 1)]      # the decode program alone
    assert get_compile_ledger().snapshot()["programs_loaded"] == 1
    assert _tokens(second) == want
    # written again, whole: the next engine loads both
    assert len(decode.read_bytes()) == pytest.approx(len(whole), rel=0.05)
    get_compile_ledger().reset()
    third = _core(tmp_path)
    third.runner.warmup(_sigs(third))
    assert get_compile_ledger().snapshot()["programs_loaded"] == 2
    assert len(built) == 1


def test_two_writers_of_one_entry_leave_one_whole_file(tmp_path):
    store = ProgramStore(tmp_path, "0" * 64)
    key = "c" * 64
    bodies = [bytes([i]) * (1 << 20) for i in range(8)]
    start = threading.Barrier(len(bodies))

    def write(body):
        start.wait(timeout=30)
        for _ in range(4):
            ProgramStore(tmp_path, "0" * 64).write("p", key, body)

    threads = [threading.Thread(target=write, args=(b,)) for b in bodies]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert store.read("p", key) in bodies
    assert [p.name for p in store.dir.iterdir()] == [store.path("p", key).name]


def test_a_store_that_cannot_be_written_costs_nothing(tmp_path):
    blocked = tmp_path / "file"
    blocked.write_text("in the way")
    store = ProgramStore(blocked / "programs", "0" * 64)     # warns
    store.write("p", "d" * 64, b"body")                      # warns
    assert store.read("p", "d" * 64) is None


# ---------------------------------------------------------------------------
# phase tables, the pool's probe
# ---------------------------------------------------------------------------

def test_phase_tables_answers_for_a_loaded_program(tmp_path):
    first = _core(tmp_path)
    first.runner.warmup(_sigs(first))
    want = first.runner.phase_tables()
    second = _core(tmp_path)
    second.runner.warmup(_sigs(second))
    assert all(map(second.runner._was_loaded, (DECODE, MIXED)))
    got = second.runner.phase_tables()
    assert sorted(got) == ["jit_step_decode_b2_n4",
                           "jit_step_mixed_b2_t16_k18_n4"]
    assert got == want
    assert {"attention", "mlp", "sampling"} <= set(
        got["jit_step_decode_b2_n4"].values())
    assert second.runner.phase_tables({"jit_step_decode_b2_n4"}).keys() \
        == {"jit_step_decode_b2_n4"}


def test_the_pools_probe_is_kept_under_the_same_kind_of_key(tmp_path,
                                                            monkeypatch):
    """``_fit_pool`` lowers and compiles the widest step at two pool sizes;
    a second engine over the same directory reads both answers and builds
    nothing, and sizes the same pool. A damaged answer is measured again."""
    monkeypatch.setattr(eng.ModelRunner, "_auto_num_blocks",
                        lambda self: self._fit_pool(64 << 20))
    first = _core(tmp_path, num_blocks=0)
    kept = sorted(tmp_path.rglob("*_memory-*.bin"))
    assert len(kept) == 2
    built = _spy_on_builds(monkeypatch)
    second = _core(tmp_path, num_blocks=0)
    assert built == []
    assert second.runner.spec.num_blocks == first.runner.spec.num_blocks > 64
    assert second.runner.step_copy_bytes_per_block \
        == first.runner.step_copy_bytes_per_block
    header = kept[0].read_bytes().split(b"\n", 2)[:2]
    kept[0].write_bytes(b"\n".join(header) + b"\n[1, ")
    third = _core(tmp_path, num_blocks=0)
    assert len(built) == 1
    assert third.runner.spec.num_blocks == first.runner.spec.num_blocks


# ---------------------------------------------------------------------------
# old digests go; nothing of jax's own cache does
# ---------------------------------------------------------------------------

def test_opening_a_store_drops_all_but_the_newest_digests(tmp_path):
    cache = tmp_path / "jax_cache"
    root = cache / "programs"
    root.mkdir(parents=True)
    jax_own = {cache / "jit_step-abc-cache": b"executable",
               cache / "jit_step-abc-atime": b"12345678"}
    for path, body in jax_own.items():
        path.write_bytes(body)
    now = time.time()
    for age, digest in enumerate("abcd"):            # "a" the newest
        old = root / (digest * 16)
        old.mkdir()
        (old / "step_decode_b8_n512-0123.bin").write_bytes(b"body")
        os.utime(old, (now - 100 * (age + 1),) * 2)
    used = ProgramStore(root, "9" * 64)
    assert sorted(d.name for d in root.iterdir()) == sorted(
        [used.dir.name] + [c * 16 for c in "abcd"[:KEEP_DIGESTS - 1]])
    assert used.dir.name == "9" * 16
    for path, body in jax_own.items():
        assert path.read_bytes() == body
    # a stray file beside the digests is no digest and is left alone
    (root / "README").write_text("x")
    ProgramStore(root, "8" * 64)
    assert (root / "README").read_text() == "x"


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

def test_the_ledger_counts_the_programs_that_were_loaded():
    led = CompileLedger()
    assert led.snapshot()["programs_loaded"] == 0
    sigs = [BucketSig("decode", b, 1, 512, True, "bfloat16")
            for b in (8, 16, 32)]
    led.record(sigs[0], 2.0, source="warmup", bodies=(13, 3))
    for sig in sigs[1:]:
        led.record(sig, 0.3, source="warmup", bodies=(13, 0), loaded=True)
    snap = led.snapshot(events=True)
    assert (snap["cache_entries"], snap["programs_loaded"]) == (3, 2)
    assert (snap["layer_bodies"], snap["layer_bodies_traced"]) == (39, 3)
    assert [e["loaded"] for e in snap["events"]] == [False, True, True]
    # one program recorded twice is one program
    led.record(sigs[1], 0.3, source="warmup", loaded=True)
    assert led.snapshot()["programs_loaded"] == 2
    led.reset()
    assert led.snapshot()["programs_loaded"] == 0
