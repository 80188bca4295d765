"""Topology benchmark driver: spin up a serving stack, run the load
generator against it, report the BASELINE.md target metric.

Fills the role of the reference's recipe perf jobs
(reference: recipes/llama-3-70b/vllm/{agg,disagg-single-node}/perf.yaml —
genai-perf against a deployed topology; benchmarks/profiler/profile_sla.py
sweeps), but self-contained: this script owns process lifecycle too.

Topologies:
  agg            single process, ``launch.run in=http`` (StaticFull path)
  distributed    coordinator + N workers + frontend (KV routing)
  disagg         coordinator + prefill worker + decode worker + frontend

Examples:
    # CPU smoke (tiny model)
    python -m benchmarks.serve_bench --topology agg --platform cpu \
        --model tiny-llama --isl 64 --osl 16 --concurrency 4 --requests 16

    # one real TPU chip, default model
    python -m benchmarks.serve_bench --topology agg --model llama-3-8b-lite
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Proc:
    """Minimal managed subprocess with readiness-line gating (the test
    harness equivalent lives in tests/utils_process.py; this one honors the
    ambient platform env so it can drive the real TPU)."""

    def __init__(self, args: list[str], name: str, env: dict):
        self.name = name
        self.proc = subprocess.Popen(
            [sys.executable, "-u", *args], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        self.lines: list[str] = []
        assert self.proc.stdout is not None
        # Binary + non-blocking: text-mode streams can't be read
        # non-blockingly (the codec layer chokes on the None short-read).
        os.set_blocking(self.proc.stdout.fileno(), False)
        self._buf = b""

    def _pump(self) -> list[str]:
        try:
            chunk = self.proc.stdout.read()  # type: ignore[union-attr]
        except BlockingIOError:
            chunk = None
        if not chunk:
            return []
        self._buf += chunk
        *done, self._buf = self._buf.split(b"\n")
        fresh = [ln.decode("utf-8", errors="replace") for ln in done]
        self.lines.extend(fresh)
        return fresh

    def wait_for(self, needle: str, timeout: float) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if any(needle in ln for ln in self._pump()):
                return
            if self.proc.poll() is not None:
                self._pump()
                raise RuntimeError(f"{self.name} exited rc={self.proc.returncode}:\n"
                                   + "\n".join(self.lines[-40:]))
            time.sleep(0.05)
        raise TimeoutError(f"{self.name}: no {needle!r} in {timeout}s:\n"
                           + "\n".join(self.lines[-40:]))

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def base_env(platform: str) -> dict:
    env = {**os.environ, "PYTHONPATH": str(REPO), "PYTHONUNBUFFERED": "1"}
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def wait_http(url: str, timeout: float) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                body = json.loads(resp.read())
                if body.get("data"):
                    return
        except Exception:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"no models at {url} within {timeout}s")


def engine_flags(ns) -> list[str]:
    return ["--model", ns.model, "--block-size", str(ns.block_size),
            "--max-batch-size", str(ns.max_batch_size),
            "--max-model-len", str(ns.max_model_len),
            "--num-blocks", str(ns.num_blocks)]


def launch_topology(ns, env: dict) -> tuple[list[Proc], str, int]:
    """Returns (procs newest-first, base_url, chips). A stack that does not
    come up (a worker that dies or never reports ready) is torn down before
    the error leaves: nothing stays behind holding a chip."""
    procs: list[Proc] = []
    try:
        return _launch(ns, env, procs)
    except BaseException:
        for p in reversed(procs):
            p.stop()
        raise


def _launch(ns, env: dict, procs: list[Proc]) -> tuple[list[Proc], str, int]:
    http_port = free_port()
    if ns.topology == "agg":
        p = Proc(["-m", "dynamo_tpu.launch.run", "in=http", "out=jax",
                  "--host", "127.0.0.1", "--port", str(http_port), *engine_flags(ns)],
                 "serve", env)
        procs.append(p)
        chips = 1
    else:
        coord_port = free_port()
        url = f"tcp://127.0.0.1:{coord_port}"
        procs.append(Proc(["-m", "dynamo_tpu.transports.coordinator",
                           "--host", "127.0.0.1", "--port", str(coord_port)],
                          "coordinator", env))
        time.sleep(1.0)
        if ns.topology == "distributed":
            workers = [
                Proc(["-m", "dynamo_tpu.components.worker", "--engine", "jax",
                      "--coordinator", url, *engine_flags(ns)], f"worker{i}", env)
                for i in range(ns.workers)
            ]
            chips = ns.workers
        elif ns.topology == "disagg":
            workers = [
                Proc(["-m", "dynamo_tpu.components.worker", "--engine", "jax",
                      "--coordinator", url, "--component", "prefill",
                      "--disagg", "prefill", *engine_flags(ns)], "prefill", env),
                Proc(["-m", "dynamo_tpu.components.worker", "--engine", "jax",
                      "--coordinator", url, "--disagg", "decode",
                      *engine_flags(ns)], "decode", env),
            ]
            chips = 2
        else:
            raise SystemExit(f"unknown topology {ns.topology}")
        procs.extend(workers)
        for w in workers:
            w.wait_for("WORKER_READY", ns.start_timeout)
        procs.append(Proc(["-m", "dynamo_tpu.components.frontend",
                           "--coordinator", url, "--host", "127.0.0.1",
                           "--port", str(http_port), "--router-mode", "kv"],
                          "frontend", env))
        procs[-1].wait_for("FRONTEND_READY", 60)
    return procs, f"http://127.0.0.1:{http_port}", chips


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--topology", choices=["agg", "distributed", "disagg"],
                    default="agg")
    ap.add_argument("--platform", choices=["ambient", "cpu"], default="ambient",
                    help="'ambient' inherits the env (the TPU on a chip "
                         "machine); 'cpu' forces JAX_PLATFORMS=cpu")
    ap.add_argument("--model", default="tiny-llama")
    ap.add_argument("--workers", type=int, default=2, help="distributed only")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0)
    ap.add_argument("--max-batch-size", type=int, default=32)
    ap.add_argument("--max-model-len", type=int, default=512)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--isl", type=int, default=128)
    ap.add_argument("--osl", type=int, default=32)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--start-timeout", type=float, default=600.0,
                    help="worker readiness gate (TPU cold start is slow)")
    ap.add_argument("--out", default=None)
    ns = ap.parse_args(argv)

    env = base_env(ns.platform)
    procs, base_url, chips = launch_topology(ns, env)
    try:
        wait_http(base_url + "/v1/models", ns.start_timeout)
        from benchmarks.loadgen import run_load
        import asyncio

        load = asyncio.run(run_load(base_url, ns.model, ns.concurrency,
                                    ns.requests, ns.isl, ns.osl, ns.warmup))
    finally:
        for p in reversed(procs):
            p.stop()

    result = {
        "topology": ns.topology,
        "model": ns.model,
        "chips": chips,
        "output_tok_s_per_chip": round(load["output_tok_s"] / chips, 2),
        **load,
    }
    print(json.dumps(result))
    if ns.out:
        with open(ns.out, "w") as f:
            json.dump(result, f, indent=2)
    return result


if __name__ == "__main__":
    main()
