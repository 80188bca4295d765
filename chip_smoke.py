#!/usr/bin/env python3
"""Chip smoke: does the serving path start and answer on a TPU?

Starts the server a user would start,

    python -m dynamo_tpu.launch.run in=http out=jax --model llama-3-8b-lite

with every engine flag at its default (real Llama-3-8B widths, 8 layers,
seeded random weights; the KV pool sized by the engine itself), sends it a
handful of ``/v1/chat/completions`` requests over HTTP, stops it, and then
starts it a second time against the compile cache the first start filled.
``--chips 4`` then does the same once more for full-depth ``llama-3-8b``
with ``--tp 4``.

This process never imports JAX: a process that has touched JAX holds the
chip, and the server — its child — is the one that needs it. Everything it
knows about the device it reads from the server (``/engine_stats``, which
repeats the engine's construction log line).

It fails, with the reason and a non-zero exit code, unless

- the server reports ``platform == "tpu"``, a device kind, the expected
  device count and mesh, and ``attn_impl == "pallas"``;
- every response carries the requested number of completion tokens and a
  ``finish_reason`` of ``length`` or ``stop``; the stream ends in
  ``[DONE]``; the long prompt was longer than one prefill chunk; requests
  in flight together shared a step (a mixed step with decode rows, and a
  decode step with several rows);
- the server's log shows no failed step and no kernel giving way to the
  dense path, and the server exits 0 when told to stop;
- on four chips, no chip holds more than its share after start-up.

What it prints besides are plain facts of this run (seconds spent in
first calls of buckets, programs in the compile cache, time to first token
cold and warm), not metrics. The last line
of stdout is one JSON object naming the device as JAX reported it to the
server. The same facts go to ``<out>/chip_smoke.json``, the servers' logs
beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

HERE = Path(__file__).resolve().parent

EXPECT_PLATFORM = "tpu"
ONE_CHIP = {"model": "llama-3-8b-lite", "flags": [], "mesh": {}}
FOUR_CHIPS = {"model": "llama-3-8b", "flags": ["--tp", "4"],
              "mesh": {"model": 4}}
PREFILL_CHUNK = 512          # the server's default --prefill-chunk
START_TIMEOUT_S = 600.0      # process start + weight init + pool probe
REQUEST_TIMEOUT_S = 600.0    # a cold request compiles its buckets first

#: Server log lines that fail the smoke: the step loop's catch-all
#: (engine.py AsyncJaxEngine._run) and the model's kernel→dense warnings
#: (models/llama.py forward / forward_pp).
FORBIDDEN_LOG = ("engine step failed", "device error is fatal",
                 "serving the dense gather path",
                 "dense-attention pipeline")

TTFT_SUM = re.compile(
    r"^dynamo_frontend_time_to_first_token_seconds_sum\{[^}]*\} (\S+)$",
    re.MULTILINE)
TTFT_COUNT = re.compile(
    r"^dynamo_frontend_time_to_first_token_seconds_count\{[^}]*\} (\S+)$",
    re.MULTILINE)
COMPILING = re.compile(
    r"compiling step fn B=(\d+) T=(\d+) NBLK=(\d+) .*?greedy=(True|False)")


class SmokeFailure(Exception):
    """A check of the smoke did not hold; the message is the reason."""


def check(cond: bool, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


# ---------------------------------------------------------------------------
# HTTP (stdlib only)
# ---------------------------------------------------------------------------

def http_get(url: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def chat(base: str, model: str, content: str, max_tokens: int,
         stream: bool = False, **sampling) -> dict:
    """One ``/v1/chat/completions`` request, checked: the requested number
    of completion tokens, a ``finish_reason`` of length or stop, and for a
    stream the ``[DONE]`` terminator. Returns the usage it reported."""
    body = {"model": model, "max_tokens": max_tokens, "ignore_eos": True,
            "messages": [{"role": "user", "content": content}], **sampling}
    if stream:
        body.update(stream=True, stream_options={"include_usage": True})
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    what = (f"{'stream' if stream else 'request'} "
            f"({len(content)} chars, {max_tokens} tokens, {sampling})")
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as resp:
            raw = resp.read().decode()
    except urllib.error.HTTPError as exc:
        raise SmokeFailure(
            f"{what}: HTTP {exc.code}: {exc.read().decode()[:500]}") from exc
    if stream:
        events = [ln[len("data: "):] for ln in raw.splitlines()
                  if ln.startswith("data: ")]
        check(bool(events) and events[-1] == "[DONE]",
              f"{what}: stream did not end in [DONE]: {raw[-300:]!r}")
        chunks = [json.loads(e) for e in events[:-1]]
        check(not any("error" in c for c in chunks),
              f"{what}: error in stream: {raw[-500:]!r}")
        finish = [c["choices"][0].get("finish_reason") for c in chunks
                  if c.get("choices")]
        finish = [f for f in finish if f]
        usage = next((c["usage"] for c in chunks if c.get("usage")), None)
    else:
        doc = json.loads(raw)
        check("choices" in doc, f"{what}: no choices: {raw[:500]}")
        finish = [doc["choices"][0].get("finish_reason")]
        usage = doc.get("usage")
    check(finish[-1:] in (["length"], ["stop"]),
          f"{what}: finish_reason {finish!r}")
    check(usage is not None and usage["completion_tokens"] == max_tokens,
          f"{what}: usage {usage!r}, wanted {max_tokens} completion tokens")
    return usage


def ttft_observed(base: str) -> tuple[float, int]:
    """(sum, count) of the server's own time-to-first-token histogram."""
    text = http_get(base + "/metrics")
    return (sum(float(v) for v in TTFT_SUM.findall(text)),
            sum(int(float(v)) for v in TTFT_COUNT.findall(text)))


def timed_first_token(base: str, model: str, content: str) -> float:
    """Time to first token of one greedy stream, as the server measured it
    (the byte tokenizer of a weightless preset decodes most of a 128k
    vocabulary to nothing, so the client sees no content chunk to time)."""
    before, _ = ttft_observed(base)
    chat(base, model, content, 16, stream=True, temperature=0)
    after, _ = ttft_observed(base)
    return round(after - before, 3)


# ---------------------------------------------------------------------------
# The server child
# ---------------------------------------------------------------------------

class Server:
    """``dynamo_tpu.launch.run in=http`` as a child in its own process
    group, its output in ``log_path``; always stopped on exit."""

    def __init__(self, model: str, flags: list[str], port: int,
                 log_path: Path):
        self.model, self.log_path = model, log_path
        self.base = f"http://127.0.0.1:{port}"
        self.args = [sys.executable, "-u", "-m", "dynamo_tpu.launch.run",
                     "in=http", "out=jax", "--model", model,
                     "--host", "127.0.0.1", "--port", str(port), *flags]
        self.proc: subprocess.Popen | None = None

    def __enter__(self) -> "Server":
        env = {**os.environ, "PYTHONUNBUFFERED": "1", "DYN_LOG": "info"}
        self._log = open(self.log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            self.args, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        return self

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            self._signal(signal.SIGKILL)
            self.proc.wait()
        self._log.close()

    def _signal(self, sig: int) -> None:
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass

    def log(self) -> str:
        return self.log_path.read_text(errors="replace")

    def alive(self) -> None:
        rc = self.proc.poll()
        check(rc is None, f"server exited with code {rc} on its own; log "
                          f"tail:\n{self.log()[-3000:]}")

    def wait_ready(self) -> float:
        """Poll ``/v1/models`` until it answers; seconds since start."""
        while time.monotonic() - self.started < START_TIMEOUT_S:
            self.alive()
            try:
                http_get(self.base + "/v1/models", timeout=2.0)
                return round(time.monotonic() - self.started, 1)
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
        raise SmokeFailure(
            f"server not ready after {START_TIMEOUT_S:.0f}s; log tail:\n"
            f"{self.log()[-3000:]}")

    def stats(self) -> dict:
        return json.loads(http_get(self.base + "/engine_stats"))[self.model]

    def stop(self) -> None:
        """SIGTERM; the server must exit 0 by itself."""
        self.alive()
        self._signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not exit within 60s of SIGTERM")
        check(rc == 0, f"server exited with code {rc} after SIGTERM; log "
                       f"tail:\n{self.log()[-2000:]}")


# ---------------------------------------------------------------------------
# One start of the server, and everything asked of it
# ---------------------------------------------------------------------------

def drive(srv: Server) -> dict:
    """The requests of the smoke, in an order that keeps the number of
    compiled buckets small; returns the facts they established."""
    base, model = srv.base, srv.model
    facts: dict = {}
    # Greedy stream first: its time to first token is the cold one (it
    # compiles the first prefill bucket); a second prompt of the same
    # shape gives the warm one.
    facts["ttft_first_s"] = timed_first_token(base, model, "Tell me a story.")
    facts["ttft_repeat_s"] = timed_first_token(base, model, "Sing me a song!")
    chat(base, model, "What is a TPU?", 16, temperature=0)
    chat(base, model, "Pick a number.", 16, temperature=0.7, top_p=0.9,
         seed=7)
    long_prompt = " ".join(f"item {i}" for i in range(80))
    usage = chat(base, model, long_prompt, 8, temperature=0)
    check(usage["prompt_tokens"] > PREFILL_CHUNK,
          f"long prompt was {usage['prompt_tokens']} tokens, not more than "
          f"one prefill chunk ({PREFILL_CHUNK})")
    facts["long_prompt_tokens"] = usage["prompt_tokens"]

    # In flight together: three streams decode, then three more arrive, so
    # their prefill has to share a step with rows that are decoding.
    victims0 = srv.stats()["sched"]["hol_victims_total"]
    _, seen0 = ttft_observed(base)
    max_running = 0
    with ThreadPoolExecutor(max_workers=6) as pool:
        def send(i: int):
            return chat(base, model, f"Count from {i}.", 128, temperature=0)

        first = [pool.submit(send, i) for i in range(3)]
        deadline = time.monotonic() + REQUEST_TIMEOUT_S
        while ttft_observed(base)[1] < seen0 + 3:
            srv.alive()
            check(time.monotonic() < deadline,
                  "first wave of concurrent requests got no first token")
            time.sleep(0.05)
        second = [pool.submit(send, i) for i in range(3, 6)]
        while not all(f.done() for f in first + second):
            max_running = max(max_running, srv.stats()["num_running"])
            time.sleep(0.05)
        for f in first + second:
            f.result()
    victims = srv.stats()["sched"]["hol_victims_total"] - victims0
    check(victims > 0,
          "no step carried a prefill chunk beside decoding rows: the "
          "unified mixed step never ran with decode rows")
    check(max_running > 1,
          f"never more than {max_running} request running at once: no "
          "decode step with several rows")
    facts["max_running"] = max_running
    facts["decode_rows_beside_a_prefill_chunk"] = victims
    return facts


def serve_once(cfg: dict, label: str, port: int, out_dir: Path,
               device_count: int | None) -> dict:
    """Start the server, check what it says it runs on, drive it, stop it,
    read its log. ``device_count`` None = whatever the machine has."""
    log_path = out_dir / f"server_{label}.log"
    print(f"[{label}] starting: {cfg['model']} {' '.join(cfg['flags'])}",
          flush=True)
    with Server(cfg["model"], cfg["flags"], port, log_path) as srv:
        ready_s = srv.wait_ready()
        dev = srv.stats()["device"]
        print(f"[{label}] ready in {ready_s}s on {json.dumps(dev)}",
              flush=True)
        check(dev["platform"] == EXPECT_PLATFORM,
              f"server runs on platform {dev['platform']!r}, not "
              f"{EXPECT_PLATFORM!r}")
        check(bool(dev["device_kind"]), "server reported no device_kind")
        check(device_count is None or dev["device_count"] == device_count,
              f"server sees {dev['device_count']} devices, expected "
              f"{device_count}")
        check(dev["mesh"] == cfg["mesh"],
              f"mesh {dev['mesh']}, expected {cfg['mesh']}")
        check(dev["attn_impl"] == "pallas",
              f"attn_impl {dev['attn_impl']!r}, not 'pallas'")
        check(dev["pool_blocks"] > 0, "engine reported no KV pool")
        used = sorted(dev["bytes_in_use"],
                      reverse=True)[:cfg["mesh"].get("model", 1)]
        if len(used) > 1:
            # Weights and cache are sharded evenly and the rest is
            # replicated, so the chips of the mesh should hold the same.
            check(used[0] <= 1.05 * used[-1],
                  f"bytes in use differ across the mesh: {used}")
        facts = drive(srv)
        stats = srv.stats()
        srv.stop()
        log = srv.log()
    for needle in FORBIDDEN_LOG:
        check(needle not in log,
              f"server log contains {needle!r}: see {log_path}")
    buckets = sorted({(int(b), int(t), int(n), g == "True")
                      for b, t, n, g in COMPILING.findall(log)})
    summary = {
        "label": label, "model": cfg["model"], "flags": cfg["flags"],
        "device": dev, "ready_s": ready_s,
        "buckets_compiled": stats["compile"]["cache_entries"],
        "compile_seconds": round(stats["compile"]["compile_seconds_total"], 1),
        "buckets": [f"B={b} T={t} NBLK={n}" + ("" if g else " sampled")
                    for b, t, n, g in buckets],
        "requests_finished": stats["requests_finished"],
        # Programs in the persistent cache now (JAX stores those that took
        # at least 1 s to compile): a warm start that adds none compiled
        # nothing of any size again.
        "cache_entries": len(list(Path(dev["compile_cache_dir"]).iterdir())),
        **facts,
    }
    print(f"[{label}] ok: pool {dev['pool_blocks']} blocks "
          f"({dev['pool_bytes'] / 1e9:.2f} GB), "
          f"{summary['buckets_compiled']} buckets in "
          f"{summary['compile_seconds']}s, first token "
          f"{facts['ttft_first_s']}s then {facts['ttft_repeat_s']}s, "
          f"bytes in use {dev['bytes_in_use']}", flush=True)
    return summary


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: after the one-chip smoke, llama-3-8b --tp 4")
    ap.add_argument("--port", type=int, default=8391)
    ap.add_argument("--out", type=Path,
                    default=HERE / "chiprun_out" / "chip_smoke")
    ns = ap.parse_args(argv)
    # Stopped from outside (a time limit): leave through the with-blocks,
    # which stop the server.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    runs: list[dict] = []
    try:
        check(not platforms or EXPECT_PLATFORM in platforms.split(","),
              f"JAX_PLATFORMS={platforms} names no {EXPECT_PLATFORM}: the "
              "smoke runs on the chip or not at all")
        ns.out.mkdir(parents=True, exist_ok=True)
        # One chip, twice: the second start finds the first one's programs
        # in the compile cache and should compile (almost) nothing.
        need = None if ns.chips == 1 else ns.chips
        runs.append(serve_once(ONE_CHIP, "cold", ns.port, ns.out, need))
        runs.append(serve_once(ONE_CHIP, "warm", ns.port, ns.out, need))
        if ns.chips == 4:
            runs.append(serve_once(FOUR_CHIPS, "tp4", ns.port, ns.out, 4))
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    cold, warm = runs[0], runs[1]
    print(f"seconds in the first call of each bucket (trace, lower, then "
          f"compile or load): {cold['compile_seconds']} cold, "
          f"{warm['compile_seconds']} against the warm cache; "
          f"{warm['device']['compile_cache_dir']} held "
          f"{cold['cache_entries']} programs after the cold start and "
          f"{warm['cache_entries']} after the warm one; ready in "
          f"{cold['ready_s']}s cold, {warm['ready_s']}s warm", flush=True)
    dev = runs[-1]["device"]
    result = {"ok": True,
              "device": {"platform": dev["platform"],
                         "kind": dev["device_kind"],
                         "count": dev["device_count"]}}
    (ns.out / "chip_smoke.json").write_text(json.dumps(
        {**result, "runs": runs, "claim": None}, indent=1) + "\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
