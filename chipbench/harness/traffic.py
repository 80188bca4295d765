"""One general traffic generator, driven by a traffic file and a rate.

A traffic file (``chipbench/traffic/<traffic>.json``) gives two clipped
lognormals (prompt and output lengths) and sampling options; the cell's
file gives the rate. For a horizon of H seconds the generator makes
N = round(rate * H) requests whose lengths are the quantiles (i+0.5)/N of
the stated distributions and whose inter-arrival gaps are the same quantiles
of the exponential, scaled to sum to H, and permutes lengths and gaps once,
by ``ORDER``. ``--seed`` draws the token ids (and the requests' sampling
seeds) and nothing else.

**So a cell is one trace.** Every seed offers the same requests at the same
instants with other contents, and with random weights, no end-of-sequence
token and exact ``max_tokens`` the contents do not change the work: runs
with different seeds are replicates, and their spread is the machine's
noise. Why: measured on the chip (PERF.md, PR 24), the same multiset in six
orders spread ``ttft_p90_ms`` by 21-24 %, ``tokens_per_s`` by 9 % and
``itl_p95_ms`` by 5 %, while two runs of one order agreed within 1 %, 0.1 %
and 0.3 %: which prompts coincide in a step decides its padded shape. A
bound of at most 10 % cannot be held across orders. What a gain has to
exceed is therefore that spread across orders, not the bound: ``run.py
--order <n>`` and ``sweep.py --orders a,b,c`` offer the same multiset in
another order, for the held-out check a claim needs (PERF.md, section 2).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

MAX_SEED = 2**32
ORDER = 1     # the one order the benchmark's cells are measured on


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # seconds after the schedule's start
    prompt: tuple[int, ...]
    max_tokens: int
    seed: int


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> list[int]:
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(median * math.exp(sigma * z)), lo), hi)))
    return out


def exponential_gaps(n: int, horizon_s: float) -> list[float]:
    """The quantiles (i+0.5)/n of the exponential, scaled to sum to the
    horizon: a Poisson process's gaps with the sampling noise taken out."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = horizon_s / sum(raw)
    return [g * scale for g in raw]


def schedule(traffic: dict, vocab: int, horizon_s: float, seed: int,
             rate: float | None = None, phase: int = 0,
             order: int = ORDER) -> list[Request]:
    """The requests of one phase (0 the window, 1 the ramp) of a run, in
    arrival order."""
    rate = traffic["rate_per_s"] if rate is None else rate
    n = max(int(round(rate * horizon_s)), 1)
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    gaps = exponential_gaps(n, horizon_s)
    perm = np.random.default_rng([int(order), phase])
    prompts = [prompts[i] for i in perm.permutation(n)]
    outputs = [outputs[i] for i in perm.permutation(n)]
    gaps = [gaps[i] for i in perm.permutation(n)]
    rng = np.random.default_rng([seed % MAX_SEED, phase])
    # The first request is due after the first gap, the last at the horizon
    # less nothing: shift by half the first gap so that no request is due
    # at the very end, where it could not be served inside any window.
    due = np.cumsum(gaps) - gaps[0] / 2.0
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab, size=prompts[i], dtype=np.int64)
        reqs.append(Request(i, float(due[i]), tuple(ids.tolist()),
                            outputs[i], int(rng.integers(0, 2**31 - 1))))
    return reqs


def summary(reqs: list[Request]) -> dict:
    """What a schedule offers, for the log: counts and totals."""
    pl = sorted(len(r.prompt) for r in reqs)
    ol = sorted(r.max_tokens for r in reqs)
    return {"requests": len(reqs), "prompt_tokens": sum(pl),
            "output_tokens": sum(ol),
            "prompt_min_med_max": [pl[0], pl[len(pl) // 2], pl[-1]],
            "output_min_med_max": [ol[0], ol[len(ol) // 2], ol[-1]]}
