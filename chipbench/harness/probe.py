"""The comparison that decides ``correct``.

(a) Every request of the run that finished returned exactly its
``max_tokens`` tokens, each id inside the vocabulary, finish reason
``length``.

(b) Seeded prompts are served greedily on the idle engine and the
configuration's plain reference recomputes the logits at the generated
positions from the same parameters. Logits are compared, not tokens: with
random weights the largest logit changes on rounding.

The reference and the two tolerances are found by the configuration, like
the rest of a cell: ``<config dir>/reference.py`` if the configuration
brings one (another architecture's layer equations), else ``reference.py``
here; ``about.json``'s ``probe`` block if it has one (another depth, mesh,
routing or cache precision; each tolerance set from two readings on the
chip, which ``manifest.check`` holds it to), else the two constants below.

Tolerances (natural-log units; random weights give logits of unit scale, so
these are absolute errors of the logits themselves):

- ``LOGPROB_TOL``: the engine's reported logprob of each chosen token
  against the reference's. The engine computes in bf16 (8 bits of mantissa)
  through every layer and the reference in float32; on the chip the largest
  difference seen over the 64 probe positions of either configuration was
  0.046 (PERF.md, findings of PR 24), so the bound is about twice what bf16 itself costs.
- ``ARGMAX_TOL``: the chosen token's reference logit against the
  reference's maximum: greedy decoding must pick a token the reference
  ranks within rounding of its best (largest seen on the chip: 0.031).

Int8 weights or an 8- or 4-bit KV cache carry several times bf16's
rounding (7 bits against a channel's or block's largest value, where bf16
keeps 8 bits of every value), so over 64 positions they pass twice bf16's
worst; a wrong mask, position or block table is off by whole units.
"""

from __future__ import annotations

import asyncio
import time
import traceback
from pathlib import Path

import numpy as np

from .loadgen import Record, run_schedule
from .manifest import ROOT
from .measure import load_module
from .traffic import Request

LOGPROB_TOL = 0.1      # a configuration without a ``probe`` block: the
ARGMAX_TOL = 0.05      # 16- and 10-layer one-chip cuts these were set on
DEFAULT_REFERENCE = Path(__file__).with_name("reference.py")
PROBE_LENGTHS = (48, 300, 700, 1500)   # the last two cross a 512 chunk
PROBE_TOKENS = 16
PROBE_SEED = 20240924


def check_counts(records: list[Record], vocab: int) -> list[str]:
    faults = []
    for r in records:
        if r.finish is None or r.error:
            continue      # counted in ``failed``, not a wrong answer
        if (len(r.tokens) != r.max_tokens or r.finish != "length"
                or any(t < 0 or t >= vocab for t in r.tokens)):
            faults.append(
                f"request {r.index}: {len(r.tokens)} tokens of "
                f"{r.max_tokens}, finish {r.finish}")
    return faults


def reference_path(config_dir: Path) -> Path:
    own = Path(config_dir) / "reference.py"
    return own if own.is_file() else DEFAULT_REFERENCE


def load_reference(path: Path):
    """The reference in the file at ``path``; it has to define
    ``logits_at``."""
    mod = load_module(path, "reference_" + Path(path).parent.name)
    if not callable(getattr(mod, "logits_at", None)):
        raise AttributeError(f"{path} defines no logits_at(params, model, "
                             "tokens, positions, pad_to=0)")
    return mod


def tolerances(about: dict) -> tuple[float, float]:
    """(logprob, argmax): the configuration's own, else the defaults."""
    block = about.get("probe")
    if not block:
        return LOGPROB_TOL, ARGMAX_TOL
    return float(block["logprob_tol"]), float(block["argmax_tol"])


def _shown(path: Path) -> str:
    path = Path(path).resolve()
    return str(path.relative_to(ROOT)) if path.is_relative_to(ROOT) else str(path)


async def run_probe(sut, cell, lengths=PROBE_LENGTHS,
                    n_tokens: int = PROBE_TOKENS) -> dict:
    model = cell.model
    ref_path = reference_path(cell.config_dir)
    logprob_tol, argmax_tol = tolerances(cell.about)
    used = {"reference": _shown(ref_path), "logprob_tol": logprob_tol,
            "argmax_tol": argmax_tol}
    reference = load_reference(ref_path)
    rng = np.random.default_rng(PROBE_SEED)
    vocab = model["vocab_size"]
    reqs = [Request(i, 0.0, tuple(rng.integers(0, vocab, size=n).tolist()), n_tokens, 0)
            for i, n in enumerate(lengths)]
    recs: list[Record] = []
    for r in reqs:    # one at a time: an idle engine, no batching
        t0 = time.perf_counter()
        recs += await run_schedule(sut.engine, [r], {"temperature": 0.0},
                                   sut.ec.model, t0, t0 + 120.0, "probe")
    faults = check_counts(recs, vocab)
    faults += [f"probe {r.index}: {r.error or 'did not finish'}"
               for r in recs if r.finish is None or r.error]
    worst_lp = worst_arg = 0.0
    d_lps: list[float] = []
    d_args: list[float] = []
    pad = -(-(max(lengths) + n_tokens) // 512) * 512
    for req, rec in zip(reqs, recs):
        if len(rec.tokens) != n_tokens or len(rec.logprobs) != n_tokens:
            faults.append(f"probe {req.index}: {len(rec.tokens)} tokens, "
                          f"{len(rec.logprobs)} logprobs")
            continue
        seq = list(req.prompt) + rec.tokens
        n = len(req.prompt)
        try:
            logits = await asyncio.get_running_loop().run_in_executor(
                None, lambda: reference.logits_at(
                    sut.params, model, seq[:-1],
                    list(range(n - 1, n - 1 + n_tokens)), pad_to=pad))
        except Exception as exc:  # noqa: BLE001 - the wrong reference for these parameters
            faults.append(f"probe {req.index}: reference {used['reference']} "
                          f"failed: {type(exc).__name__}: {exc}")
            traceback.print_exc()
            continue
        if logits.shape != (n_tokens, vocab):
            faults.append(f"probe {req.index}: reference {used['reference']} "
                          f"gave logits {logits.shape}, not {(n_tokens, vocab)}")
            continue
        ref_lp = logits - _logsumexp(logits)
        for j, tok in enumerate(rec.tokens):
            d_lp = abs(float(ref_lp[j, tok]) - rec.logprobs[j])
            d_arg = float(logits[j].max() - logits[j, tok])
            worst_lp, worst_arg = max(worst_lp, d_lp), max(worst_arg, d_arg)
            d_lps.append(d_lp)
            d_args.append(d_arg)
            # negated, so that a NaN from either side is a fault too
            if not (d_lp <= logprob_tol and d_arg <= argmax_tol):
                faults.append(
                    f"probe {req.index} (prompt {n}) token {j}: logprob off "
                    f"by {d_lp:.4f}, {d_arg:.4f} under the reference's best")
    return {"faults": faults, "worst_logprob_diff": worst_lp,
            "worst_argmax_gap": worst_arg,
            # Logged, not compared: over the positions these are steady from
            # seed to seed where a worst of 64 swings (PERF.md, PR 29).
            "rms_logprob_diff": float(np.sqrt(np.mean(np.square(d_lps)))) if d_lps else None,
            "mean_argmax_gap": float(np.mean(d_args)) if d_args else None,
            "positions": len(lengths) * n_tokens, **used}


def _logsumexp(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    return m + np.log(np.exp(x - m).sum(axis=-1, keepdims=True))
