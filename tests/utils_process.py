"""ManagedProcess test harness.

Fills the role of the reference's ManagedProcess
(reference: tests/utils/managed_process.py:591): spawn a component as a real
subprocess, gate on a readiness line, capture logs for assertions, terminate
cleanly on exit.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

BASE_ENV = {
    **os.environ,
    "PYTHONPATH": str(REPO),
    "PYTHONUNBUFFERED": "1",
    "JAX_PLATFORMS": "cpu",
    "DYN_LOG": "info",
}


class ManagedProcess:
    def __init__(self, args: list[str], name: str = "proc", env: dict | None = None):
        self.name = name
        self.args = [sys.executable, "-u", *args]
        self.env = {**BASE_ENV, **(env or {})}
        self.proc: subprocess.Popen | None = None
        self._lines: list[str] = []

    def start(self) -> "ManagedProcess":
        self.proc = subprocess.Popen(
            self.args, env=self.env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        # Drain continuously so (a) the child never blocks on a full pipe and
        # (b) logs() captures everything, not just pre-readiness output.
        self._drain = threading.Thread(target=self._drain_loop, daemon=True)
        self._drain.start()
        return self

    def _drain_loop(self) -> None:
        assert self.proc and self.proc.stdout
        for line in self.proc.stdout:
            self._lines.append(line)

    def wait_for_line(self, needle: str, timeout: float = 30.0) -> str:
        """Block until any captured line contains ``needle``; returns it."""
        assert self.proc
        deadline = time.time() + timeout
        scanned = 0
        while time.time() < deadline:
            lines = self._lines
            while scanned < len(lines):
                if needle in lines[scanned]:
                    return lines[scanned]
                scanned += 1
            if self.proc.poll() is not None and scanned >= len(self._lines):
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode}:\n" + "".join(self._lines[-50:]))
            time.sleep(0.02)
        raise TimeoutError(f"{self.name}: no {needle!r} within {timeout}s:\n" + "".join(self._lines[-50:]))

    def kill_hard(self) -> None:
        """SIGKILL — simulates sudden worker death (fault-tolerance tests)."""
        if self.proc and self.proc.poll() is None:
            self.proc.kill()

    def stop(self, grace: float = 5.0) -> None:
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(grace)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(5)

    def logs(self) -> str:
        return "".join(self._lines)

    def __enter__(self) -> "ManagedProcess":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def free_port() -> int:
    """Bind-probe an ephemeral port (shared by the e2e suites)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
