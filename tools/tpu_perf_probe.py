"""On-chip perf attribution for the decode step.

Runs ONE experiment per invocation: each builds its own engine, and a
process holds the chip for as long as it lives, so experiments run one
after another as separate processes in one chip call. A hard in-process
deadline lets the probe print its JSON line before an outer timeout would
cut it off with nothing on stdout.

Experiments (pick with MODE):
  baseline   — production pipelined loop, defaults (pallas + general sampling)
  dense      — attention impl forced to the dense gather path
  greedy     — fast_greedy step variant (argmax-only sampling)
  window1    — no fused window (per-step dispatch; isolates dispatch overhead)
  profile    — 3 windows under jax.profiler.trace (writes /tmp/tpu_trace)

Env knobs: B (batch, 32), W (window, 8), PROMPT (128), DECODE (64),
DEADLINE (seconds, 420). Prints one JSON line:
  {"mode": ..., "tok_s": ..., "ms_per_step": ..., "steps": N, ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_START = time.monotonic()
MODE = os.environ.get("MODE", "baseline")
B = int(os.environ.get("B", "32"))
W = int(os.environ.get("W", "8"))
PROMPT = int(os.environ.get("PROMPT", "128"))
DECODE = int(os.environ.get("DECODE", "64"))
DEADLINE = float(os.environ.get("DEADLINE", "420"))


def left() -> float:
    return DEADLINE - (time.monotonic() - _START)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def main() -> None:
    import jax

    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.utils.config import EngineConfig

    window = 1 if MODE == "window1" else W
    attn = "dense" if MODE == "dense" else "auto"
    # greedy mode IS the default dispatch choice now; "baseline" forces the
    # general path by tagging one request with a temperature.
    core = EngineCore(EngineConfig(
        model=os.environ.get("MODEL", "llama-3-8b-lite"), block_size=16,
        num_blocks=B * ((PROMPT + DECODE) // 16 + 2) + 1,
        max_batch_size=B, max_model_len=PROMPT + DECODE + 16,
        prefill_chunk=PROMPT, decode_bucket=(B,), decode_window=window,
        allow_random_weights=True, enable_prefix_caching=False,
        attn_impl=attn,
    ))
    force_general = MODE in ("baseline", "dense", "window1")
    for i in range(B):
        toks = [(7 * i + 11 * j) % 32000 + 5 for j in range(PROMPT)]
        so = SamplingOptions(temperature=0.0)
        if force_general and i == 0:
            # one sampled row pushes the whole batch onto the general
            # sampling path (fast_greedy needs an all-greedy batch)
            so = SamplingOptions(temperature=0.7, seed=1)
        core.add_request(PreprocessedRequest(
            token_ids=toks,
            stop_conditions=StopConditions(max_tokens=DECODE, ignore_eos=True),
            sampling_options=so))

    while core.metrics.num_decode_tokens == 0 and core.has_work() and left() > 60:
        core.step()
    base = core.metrics.num_decode_tokens
    if base == 0:
        emit({"mode": MODE, "error": "no decode within deadline"})
        sys.exit(1)

    tracing = MODE == "profile"
    if tracing:
        jax.profiler.start_trace("/tmp/tpu_trace")

    pending = None
    t0 = time.perf_counter()
    budget = 3 if tracing else 10 ** 9
    dispatched = 0
    while ((core.has_work() or pending is not None)
           and left() > 45 and dispatched < budget):
        nxt = core.step_begin() if core.has_work() else None
        if pending is not None:
            core.step_finalize(pending)
        pending = nxt
        dispatched += 1
    if pending is not None:
        core.step_finalize(pending)
    dt = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()
    measured = core.metrics.num_decode_tokens - base
    steps = measured // B
    fast = core.runner.used_fast_greedy()
    emit({
        "mode": MODE, "batch": B, "window": window,
        "attn_impl": core.runner.attn_impl,
        "tok_s": round(measured / dt, 1) if dt > 0 else None,
        "ms_per_step": round(dt / steps * 1e3, 2) if steps else None,
        "steps": steps,
        "fast_greedy_used": fast,
        "device": jax.devices()[0].device_kind,
        "trace": "/tmp/tpu_trace" if tracing else None,
    })


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # noqa: BLE001 - JSON contract on any failure
        emit({"mode": MODE, "error": f"{type(exc).__name__}: {exc}"})
        sys.exit(1)
