"""Compiled step programs kept beside JAX's persistent compile cache, under a
key that takes no trace.

JAX's own cache is keyed by the lowered module, so a start that finds every
executable on disk still traces and lowers every step program to learn its
key: jax's Python, 0.5 s of a Mistral program's 0.65 s and 1.5 s of a hybrid
program's 2.2 s (PERF.md section 5). This store holds the same executables
(``jax.experimental.serialize_executable``) under a key made of what a step
program is built from, read off the runner before anything is traced
(:func:`program_key`): ``ModelRunner.step_fn`` looks here first and calls
what it finds in the jitted function's place.

Layout: ``<compile cache dir>/programs/<code digest>/<program>-<key>.bin``.
The code digest (:func:`code_digest`) is the bytes of every ``*.py`` under
``dynamo_tpu/``: an edit to any of them starts a new directory, and opening
a store deletes all but the ``KEEP_DIGESTS`` most recently used ones, which
is what bounds the store over code edits. Nothing outside ``programs/`` is
touched, and deleting ``programs/`` (or any part of it) is always safe: a
missing, foreign, truncated or unloadable entry is a miss, the program is
built the ordinary way and the entry written again. An entry is written to
a temporary file and renamed into place, so a reader never sees half of one
and two writers leave one whole file.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import os
import pickle
import shutil
import tempfile
from pathlib import Path

import jax
import jaxlib
from jax.experimental import serialize_executable

from dynamo_tpu.utils.logging import get_logger

log = get_logger("engine.program_store")

#: Code digests whose programs are kept: the one in use and the one before
#: it (a rolling upgrade's two versions over one cache directory).
KEEP_DIGESTS = 2

_MAGIC = b"dynamo-tpu step program 1\n"
_PACKAGE = Path(__file__).resolve().parents[1]


@functools.cache
def code_digest(root: Path = _PACKAGE) -> str:
    """sha256 over the path and bytes of every ``*.py`` under ``root``
    (``dynamo_tpu/``): every module a step program can trace, and more.
    Read once a process."""
    h = hashlib.sha256()
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()


def runtime_facts() -> tuple:
    """What of the process decides a compiled program beside its source and
    arguments: the versions of jax, jaxlib and the device's runtime (libtpu's
    build on a TPU), the device kind, the compiler's flags from the
    environment, and every jax flag as set (the cache's own aside, which
    say where programs are kept and not what they are)."""
    dev = jax.devices()[0]
    flags = sorted((k, repr(v)) for k, v in jax.config.values.items()
                   if "cache" not in k)
    return (jax.__version__, jaxlib.__version__, dev.client.platform_version,
            dev.device_kind, os.environ.get("XLA_FLAGS", ""),
            os.environ.get("LIBTPU_INIT_ARGS", ""), flags)


def program_key(name: str, args, facts) -> str:
    """The key of the program ``name`` called on ``args`` (any pytree:
    the positional and keyword arguments of the serving call), hashed:
    each leaf's place in the tree with its shape, dtype and sharding, and
    ``facts``, whatever else the caller's build closes over, by its
    ``repr``. A numpy leaf (a step's packed inputs before they are placed)
    has no sharding of its own; how the caller places it belongs in
    ``facts``."""
    leaves = [(jax.tree_util.keystr(path), tuple(x.shape), str(x.dtype),
               repr(getattr(x, "sharding", None)))
              for path, x in jax.tree_util.tree_flatten_with_path(args)[0]]
    return hashlib.sha256(repr((name, leaves, facts)).encode()).hexdigest()


class ProgramStore:
    """The entries of one code digest under ``root`` (``.../programs``)."""

    def __init__(self, root: str | Path, digest: str | None = None):
        self.root = Path(root)
        self.digest = digest or code_digest()
        self.dir = self.root / self.digest[:16]
        try:
            self.dir.mkdir(parents=True, exist_ok=True)
            os.utime(self.dir)           # used now: the newest digest
            self._drop_old_digests()
        except OSError:
            log.warning("program store at %s cannot be written", self.dir,
                        exc_info=True)

    def _drop_old_digests(self) -> None:
        """Delete every digest's directory but the ``KEEP_DIGESTS`` most
        recently used (a directory is used when a store is opened on it or
        an entry is written into it)."""
        digests = sorted((d for d in self.root.iterdir() if d.is_dir()),
                         key=lambda d: d.stat().st_mtime, reverse=True)
        for old in digests[KEEP_DIGESTS:]:
            log.info("program store: dropping the programs of %s", old.name)
            shutil.rmtree(old, ignore_errors=True)

    def path(self, name: str, key: str) -> Path:
        return self.dir / f"{name}-{key[:24]}.bin"

    def read(self, name: str, key: str) -> bytes | None:
        """The body of the entry ``name`` of ``key``, or None: no entry, or
        a file that does not say it is this key's (never unpickled)."""
        path = self.path(name, key)
        try:
            with open(path, "rb") as f:
                if f.read(len(_MAGIC)) == _MAGIC and \
                        f.readline().strip() == key.encode():
                    return f.read()
            log.warning("program store: %s is not this key's entry", path)
        except FileNotFoundError:
            pass
        except OSError:
            log.warning("program store: %s not read", path, exc_info=True)
        return None

    def write(self, name: str, key: str, body: bytes) -> None:
        """Write ``body`` as the entry ``name`` of ``key``, whole or not at
        all (a temporary file, renamed). A store that cannot be written
        costs the next start its time and this one nothing."""
        path = self.path(name, key)
        tmp = None
        try:
            self.dir.mkdir(parents=True, exist_ok=True)
            with tempfile.NamedTemporaryFile(
                    dir=self.dir, suffix=".tmp", delete=False) as f:
                tmp = f.name
                f.write(_MAGIC + key.encode() + b"\n" + body)
            os.replace(tmp, path)
        except OSError:
            log.warning("program store: %s not written", path, exc_info=True)
            if tmp is not None:
                with contextlib.suppress(OSError):
                    os.unlink(tmp)

    def load(self, name: str, key: str, devices):
        """The stored program ``name`` of ``key`` loaded onto ``devices``
        (a ``jax.stages.Compiled``), or None: no entry, a damaged one, or a
        loader that refuses (another device assignment)."""
        body = self.read(name, key)
        if body is None:
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(body)
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, backend=devices[0].client,
                execution_devices=devices)
        except Exception:
            log.warning("program store: %s does not load; building it",
                        self.path(name, key), exc_info=True)
            return None

    def save(self, name: str, key: str, compiled) -> None:
        """Write ``compiled`` (a ``jax.stages.Compiled``) as the entry
        ``name`` of ``key``; a program the backend cannot serialise (the
        CPU's, where it sorts) is left out, with a warning."""
        try:
            body = pickle.dumps(serialize_executable.serialize(compiled))
        except Exception:
            log.warning("program store: %s does not serialise", name,
                        exc_info=True)
            return
        self.write(name, key, body)
