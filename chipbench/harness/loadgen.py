"""The open-loop load generator: one asyncio loop in the benchmark's own
process feeds ``AsyncJaxEngine.generate`` with token ids on a fixed schedule
and reads the streams where the HTTP frontend would.

Each request is timed from the instant it was due, not from when it was
sent, so a stall's cost to later arrivals is counted; how late the
generator itself ran is reported (``late_s``).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    index: int
    due: float                    # perf_counter clock
    prompt_len: int
    max_tokens: int
    sent: float = 0.0
    deltas: list = field(default_factory=list)   # (time, tokens in delta)
    tokens: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)
    finish: str | None = None
    error: str | None = None

    @property
    def first_token(self) -> float | None:
        return next((t for t, k in self.deltas if k > 0), None)


def make_request(req, sampling: dict, model: str, tag: str):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    temp = float(sampling.get("temperature") or 0.0)
    return PreprocessedRequest(
        token_ids=list(req.prompt), model=model,
        request_id=f"{tag}-{req.index}",
        stop_conditions=StopConditions(max_tokens=req.max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=temp, top_p=sampling.get("top_p"),
            top_k=sampling.get("top_k"),
            seed=req.seed if temp > 0.0 else None),
        eos_token_ids=[])


async def _one(engine, preq, rec: Record) -> None:
    delay = rec.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.sent = time.perf_counter()
    try:
        async for out in engine.generate(preq):
            now = time.perf_counter()
            if out.token_ids:
                rec.deltas.append((now, len(out.token_ids)))
                rec.tokens.extend(out.token_ids)
                rec.logprobs.extend(out.log_probs or [])
            if out.error:
                rec.error = out.error
            if out.finish_reason is not None:
                rec.finish = str(out.finish_reason)
    except Exception as exc:  # noqa: BLE001 - a failed request is a result
        rec.error = f"{type(exc).__name__}: {exc}"


async def run_schedule(engine, reqs, sampling: dict, model: str, t0: float,
                       end_by: float, tag: str,
                       marks: list | None = None) -> list[Record]:
    """Offer ``reqs`` (due ``t0 + due_s`` each) and wait for every stream,
    at most until ``end_by``; what has not finished then is cancelled and
    keeps ``finish`` None. ``marks`` are ``(time, callback)`` pairs run on
    the loop at those instants (window edges, counter snapshots)."""
    loop = asyncio.get_running_loop()
    off = loop.time() - time.perf_counter()
    for when, cb in marks or []:
        loop.call_at(when + off, cb)
    recs = [Record(r.index, t0 + r.due_s, len(r.prompt), r.max_tokens)
            for r in reqs]
    preqs = [make_request(r, sampling, model, tag) for r in reqs]
    tasks = [asyncio.ensure_future(_one(engine, p, rec))
             for p, rec in zip(preqs, recs)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(end_by - time.perf_counter(), 0.0))
    for task in pending:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs
