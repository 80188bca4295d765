"""The device under the engine: which backend it may run on, where compiled
programs are cached, and what it reports about both.

Everything that builds an engine goes through ``EngineCore.__init__``, which
calls :func:`require_backend` and :func:`configure_compile_cache` before its
first ``jit`` — so the launcher, the worker, the benchmark and the tests
all get the same two rules.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — fixed, because a restart only hits the cache
#: if it looks where the last run wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def require_backend(platform: str, requested: str | None) -> None:
    """Refuse a CPU backend nobody asked for. With ``JAX_PLATFORMS`` unset
    JAX drops to the CPU when it finds no accelerator, and a server built
    for a TPU would then come up, pick the dense attention path and serve
    at CPU speed with nothing in its output to say so. ``requested`` is
    ``jax.config.jax_platforms`` (``JAX_PLATFORMS``); it has to name the
    CPU first — in ``tpu,cpu`` the CPU is what is left when the TPU is
    gone, not what was asked for."""
    if platform == "cpu" and (requested or "").lower().split(",")[0] != "cpu":
        raise RuntimeError(
            "JAX found no accelerator and fell back to the CPU backend. "
            "Set JAX_PLATFORMS=cpu to run the engine on the CPU on purpose "
            "(tests, local work); on a TPU host, find out why the chip is "
            "not visible (another process holding it?)")


def configure_compile_cache() -> str | None:
    """Place JAX's persistent compilation cache; returns the directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    the directory is left alone; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``.

    Every compiled program is kept, whatever its compile time or size. JAX
    by itself writes an entry only for a compile of a second or more, and a
    small step program (a ``T=16`` mixed step, a narrow decode step)
    compiles in less: left out of the cache, it is compiled again by every
    restart, which costs more than loading it and is what a warm start is
    there to avoid (PERF.md section 6, PR 32: twelve to sixteen such
    programs a cell, 5-11 s of set-up). A setting in code, the same for
    every caller.

    On the CPU backend the cache is switched off and None returned. There
    a program compiles in a second or two, so a restart gains little, and
    XLA:CPU's loader greets every cached executable with an error about
    its own ``+prefer-no-scatter`` flag ("could lead to execution errors
    such as SIGILL"). Tests and local work compile exactly as they did
    before there was a cache, and write nothing into the checkout."""
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def describe(mesh, **engine_fields) -> dict:
    """What is running, for the construction log line and ``stats()``:
    the device as JAX reports it, the mesh, the engine's own choices
    (``attn_impl``, pool size, cache directory) passed by the caller, and
    the bytes in use on each local device once all of that is resident —
    on a mesh, the evidence that no device holds more than its share. (The
    CPU backend reports no memory statistics: an empty list.)"""
    devices = jax.devices()
    stats = [d.memory_stats() for d in jax.local_devices()]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": ({k: v for k, v in mesh.shape.items() if v > 1}
                 if mesh is not None else {}),
        **engine_fields,
        "bytes_in_use": [st["bytes_in_use"] for st in stats if st],
    }
