"""The rules every engine starts under (engine/device.py): which backend it
may run on and where compiled programs are cached."""

from pathlib import Path

import jax
import pytest

from dynamo_tpu.engine import device


@pytest.mark.parametrize("platform,requested,refused", [
    ("cpu", "cpu", False),        # tests, local work: asked for outright
    ("cpu", None, True),          # JAX found no accelerator and dropped here
    ("cpu", "", True),
    ("cpu", "tpu,cpu", True),     # the chip machine's setting, chip gone
    ("tpu", "tpu,cpu", False),
    ("tpu", None, False),
])
def test_cpu_backend_must_be_asked_for(platform, requested, refused):
    if refused:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            device.require_backend(platform, requested)
    else:
        device.require_backend(platform, requested)


# Every program is kept, whatever its compile time or size (PERF.md, PR 32:
# small step programs compile in under JAX's one-second floor, and a
# restart that finds them missing compiles them again).
KEEP_ALL = [("jax_persistent_cache_min_compile_time_secs", 0),
            ("jax_persistent_cache_min_entry_size_bytes", -1)]


@pytest.mark.parametrize("backend,env_dir,updates", [
    # Set from outside: JAX has read it, the directory is not set in code.
    ("tpu", "/somewhere/else", KEEP_ALL),
    # Unset: the fixed <checkout>/.jax_cache.
    ("tpu", None, [("jax_compilation_cache_dir",
                    str(Path(__file__).resolve().parents[1] / ".jax_cache")),
                   *KEEP_ALL]),
    # The CPU backend: off, wherever the variable points.
    ("cpu", "/somewhere/else", [("jax_enable_compilation_cache", False)]),
    ("cpu", None, [("jax_enable_compilation_cache", False)]),
])
def test_compile_cache_placement(monkeypatch, backend, env_dir, updates):
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv(device.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(device.CACHE_ENV, env_dir)
    device.configure_compile_cache()
    assert seen == updates


def test_persistence_floors_are_real_jax_options():
    """The two names are options this JAX has, and 0 / -1 mean "no floor"
    there: a typo would be a silent no-op under the mock above."""
    assert jax.config.jax_persistent_cache_min_compile_time_secs >= 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes >= -1
    for name, _ in KEEP_ALL:
        assert hasattr(jax.config, name)


@pytest.mark.parametrize("kv_dtype,num_blocks,tp,refusal", [
    ("bfloat16", 0, 1, None),
    ("int8", 449, 1, None),                     # what every test pool was
    ("int8", 36000, 1, "scale sidecars"),       # what device memory suggests
    ("int4", 0, 1, "explicit --num-blocks"),    # auto would size from memory
    ("bfloat16", 0, 3, "does not divide tp=3"),  # 8 KV heads over 3 chips
])
def test_tpu_construction_refuses_what_the_kernel_cannot_serve(
        monkeypatch, kv_dtype, num_blocks, tp, refusal):
    """On a TPU, a quantized pool whose sidecars cannot fit SMEM and a mesh
    the kernel does not divide fail at construction, not at the first
    request (and not by a quiet swap to the dense path)."""
    from types import SimpleNamespace

    from dynamo_tpu.engine.engine import ModelRunner
    from dynamo_tpu.models.config import MODEL_PRESETS
    from dynamo_tpu.utils.config import EngineConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner = object.__new__(ModelRunner)
    runner.cfg = MODEL_PRESETS["llama-3-8b-lite"]
    runner.engine_cfg = EngineConfig(
        model="llama-3-8b-lite", kv_dtype=kv_dtype, num_blocks=num_blocks)
    runner.mesh = SimpleNamespace(shape={"model": tp}) if tp > 1 else None
    runner.attn_impl, runner.max_nblk = "pallas", 512
    if refusal is None:
        runner._check_kernel_fits()
    else:
        with pytest.raises(ValueError, match=refusal):
            runner._check_kernel_fits()
