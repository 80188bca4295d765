"""The comparison that decides ``correct``.

(a) Every request of the run that finished returned exactly its
``max_tokens`` tokens, each id inside the vocabulary, finish reason
``length``.

(b) Seeded prompts are served greedily on the idle engine and the
configuration's plain reference recomputes the logits at the generated
positions from the same parameters. Logits are compared, not tokens: with
random weights the largest logit changes on rounding.

The reference and the limits are found by the configuration, like the rest
of a cell: ``<config dir>/reference.py`` if the configuration brings one
(another architecture's layer equations), else ``reference.py`` here;
``about.json``'s ``probe`` block if it has one (another depth, mesh, routing
or cache precision; each limit set from two readings on the chip, which
``manifest.check`` holds it to), else the three constants below.

Limits (natural-log units; random weights give logits of unit scale, so
these are absolute errors of the logits themselves). The first two are held
at every position compared, the third over all of them:

- ``LOGPROB_TOL``: the engine's reported logprob of each chosen token
  against the reference's. The engine computes in bf16 (8 bits of mantissa)
  through every layer and the reference in float32; on the chip the largest
  difference seen over the 64 probe positions of either configuration was
  0.050 over 24 weight seeds (PERF.md, findings of PR 29), so the bound is
  twice what bf16 itself costs; int8 weights read 0.137 at the least.
- ``ARGMAX_TOL``: the chosen token's reference logit against the
  reference's maximum: greedy decoding must pick a token the reference
  ranks within rounding of its best (largest seen on the chip: 0.047; int8
  weights 0.067 at the least).
- ``RMS_TOL``: the root mean square of the logprob differences. A worst of
  64 swings with rounding (one sound seed reads 0.0467 of 0.05); the mean
  square is steady from seed to seed. On the chip, over 24 weight seeds of
  the two one-chip configurations, as stated it read 0.0137-0.0187 and with
  int8 weights 0.058-0.069 (PERF.md, PR 29): the limit is their geometric
  mean, 1.76x above the largest sound reading and 1.76x below the smallest
  with int8 weights. (An int8 KV cache at these short contexts reads
  0.023-0.030 and passes: PERF.md section 7.)

Int8 weights or an 8- or 4-bit KV cache carry several times bf16's
rounding (7 bits against a channel's or block's largest value, where bf16
keeps 8 bits of every value), so over 64 positions they pass twice bf16's
worst; a wrong mask, position or block table is off by whole units.

**Tied positions (a routed layer).** Where the last chosen and the first
unchosen expert's router scores lie closer than bf16 moves them, the engine
and the float32 reference choose different experts and that position's
logits move by tenths to whole units, with no fault in the program. The
reference itself says where: a ``reference.py`` may define
``routing_margin_at`` beside ``logits_at`` (same arguments; one float a
probed position: over the routed layers, the least distance in router score
between an expert held here and the boundary of being chosen), and the
``probe`` block then gives ``margin`` and ``max_tied_share``. A position
whose margin is under ``margin`` is *tied*: left out of all three
comparisons and counted. Every other position is held as above. More tied
positions than ``max_tied_share`` of the probed ones is a fault (a probe
that compares too little), as is a margin that is NaN, of the wrong shape,
or that raises. ``decide`` is the rule, on plain numbers.
"""

from __future__ import annotations

import asyncio
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .loadgen import Record, run_schedule
from .manifest import ROOT
from .measure import load_module
from .traffic import Request

LOGPROB_TOL = 0.1      # a configuration without a ``probe`` block: the
ARGMAX_TOL = 0.05      # 16- and 10-layer one-chip cuts these were set on
RMS_TOL = 0.033        # sqrt(0.0187 x 0.058), the readings above
DEFAULT_REFERENCE = Path(__file__).with_name("reference.py")
PROBE_LENGTHS = (48, 300, 700, 1500)   # the last two cross a 512 chunk
PROBE_TOKENS = 16
PROBE_SEED = 20240924


@dataclass(frozen=True)
class Limits:
    logprob_tol: float = LOGPROB_TOL
    argmax_tol: float = ARGMAX_TOL
    rms_tol: float = RMS_TOL
    margin: float | None = None           # a routed configuration's block:
    max_tied_share: float | None = None   # both or neither


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
    ``logits_at`` and may define ``routing_margin_at``."""
    mod = load_module(path, "reference_" + Path(path).parent.name)
    if not callable(getattr(mod, "logits_at", None)):
        raise AttributeError(f"{path} defines no logits_at(params, model, "
                             "tokens, positions, pad_to=0)")
    return mod


def limits(about: dict) -> Limits:
    """The configuration's own, else the defaults (``rms_tol`` alone may be
    left to its default inside a block)."""
    block = about.get("probe")
    if not block:
        return Limits()
    tie = {k: float(block[k]) for k in ("margin", "max_tied_share")
           if k in block}
    return Limits(float(block["logprob_tol"]), float(block["argmax_tol"]),
                  float(block.get("rms_tol", RMS_TOL)), **tie)


def decide(labels: list[str], d_lp, d_arg, margins, lim: Limits) -> dict:
    """The rule, on plain numbers: one label, logprob difference and argmax
    gap a probed position, and its routing margin (``margins`` None: the
    reference names no tied position). Returns the faults and every number
    compared."""
    d_lp, d_arg = np.asarray(d_lp, float), np.asarray(d_arg, float)
    faults: list[str] = []
    tied = np.zeros(len(labels), bool)
    if margins is not None:
        margins = np.asarray(margins, float)
        bad = np.isnan(margins)
        faults += [f"{labels[i]}: routing margin is NaN"
                   for i in np.flatnonzero(bad)]
        tied = ~bad & (margins < lim.margin)
    over = ~((d_lp <= lim.logprob_tol) & (d_arg <= lim.argmax_tol))   # NaN: over
    faults += [f"{labels[i]}: logprob off by {d_lp[i]:.4f}, {d_arg[i]:.4f} "
               "under the reference's best"
               + (f" (routing margin {margins[i]:.4f}, not tied)"
                  if margins is not None else "")
               for i in np.flatnonzero(over & ~tied)]
    kept = ~tied
    n, n_tied = len(labels), int(tied.sum())
    out = {"worst_logprob_diff": float(d_lp[kept].max(initial=0.0)),
           "worst_argmax_gap": float(d_arg[kept].max(initial=0.0)),
           # Steady from seed to seed where a worst of 64 swings.
           "rms_logprob_diff": (float(np.sqrt(np.mean(np.square(d_lp[kept]))))
                                if kept.any() else None),
           "mean_argmax_gap": float(np.mean(d_arg[kept])) if kept.any() else None,
           "positions": n, "compared": n - n_tied, "tied": n_tied,
           # what the tied positions were left out for
           "over_tolerance": int(over.sum()),
           "tied_over_tolerance": int((over & tied).sum()),
           "worst_tied_logprob_diff": float(d_lp[tied].max(initial=0.0)),
           "worst_tied_argmax_gap": float(d_arg[tied].max(initial=0.0)),
           "tied_share": n_tied / n if n else 0.0}
    rms = out["rms_logprob_diff"]
    if rms is not None and not rms <= lim.rms_tol:
        faults.append(f"rms_logprob_diff {rms:.4f} over {out['compared']} "
                      f"positions is above {lim.rms_tol}")
    if margins is not None and not out["tied_share"] <= lim.max_tied_share:
        faults.append(f"{n_tied} of {n} positions are tied (margin under "
                      f"{lim.margin}): more than max_tied_share "
                      f"{lim.max_tied_share}, the probe compares too little")
    return {"faults": faults, **out}


def _shown(path: Path) -> str:
    path = Path(path).resolve()
    return str(path.relative_to(ROOT)) if path.is_relative_to(ROOT) else str(path)


async def serve_probe(sut, cell, lengths=PROBE_LENGTHS,
                      n_tokens: int = PROBE_TOKENS):
    """The seeded prompts and what the engine returned for each, served
    greedily, one at a time: an idle engine, no batching."""
    rng = np.random.default_rng(PROBE_SEED)
    vocab = cell.model["vocab_size"]
    reqs = [Request(i, 0.0, tuple(rng.integers(0, vocab, size=n).tolist()), n_tokens, 0)
            for i, n in enumerate(lengths)]
    recs: list[Record] = []
    for r in reqs:
        t0 = time.perf_counter()
        recs += await run_schedule(sut.engine, [r], {"temperature": 0.0},
                                   sut.ec.model, t0, t0 + 120.0, "probe")
    return reqs, recs


async def compare_probe(params, cell, reqs, recs) -> dict:
    """What the engine returned against the configuration's reference over
    ``params``, by the configuration's limits."""
    model = cell.model
    ref_path = reference_path(cell.config_dir)
    lim = limits(cell.about)
    used = {"reference": _shown(ref_path), "logprob_tol": lim.logprob_tol,
            "argmax_tol": lim.argmax_tol, "rms_tol": lim.rms_tol,
            "margin": lim.margin, "max_tied_share": lim.max_tied_share}
    reference = load_reference(ref_path)
    margin_at = getattr(reference, "routing_margin_at", None)
    faults = []
    if (margin_at is None) != (lim.margin is None) or \
            (lim.margin is None) != (lim.max_tied_share is None):
        # manifest.check says the same without running anything
        faults.append(f"{used['reference']} "
                      f"{'defines' if margin_at else 'defines no'} "
                      f"routing_margin_at and the probe block gives margin "
                      f"{lim.margin}, max_tied_share {lim.max_tied_share}: "
                      "all three or none")
        margin_at = None
        lim = Limits(lim.logprob_tol, lim.argmax_tol, lim.rms_tol)
    vocab = model["vocab_size"]
    n_tokens = reqs[0].max_tokens
    faults += check_counts(recs, vocab)
    faults += [f"probe {r.index}: {r.error or 'did not finish'}"
               for r in recs if r.finish is None or r.error]
    labels: list[str] = []
    d_lps: list[float] = []
    d_args: list[float] = []
    margins: list[float] = []
    pad = -(-(max(len(r.prompt) for r in reqs) + n_tokens) // 512) * 512
    for req, rec in zip(reqs, recs):
        if len(rec.tokens) != n_tokens or len(rec.logprobs) != n_tokens:
            faults.append(f"probe {req.index}: {len(rec.tokens)} tokens, "
                          f"{len(rec.logprobs)} logprobs")
            continue
        seq = list(req.prompt) + rec.tokens
        n = len(req.prompt)
        at = list(range(n - 1, n - 1 + n_tokens))

        def both():
            logits = reference.logits_at(params, model, seq[:-1], at,
                                         pad_to=pad)
            if margin_at is None:
                return logits, None
            return logits, np.asarray(margin_at(params, model, seq[:-1], at,
                                                pad_to=pad))

        try:
            logits, margin = await asyncio.get_running_loop().run_in_executor(
                None, both)
        except Exception as exc:  # noqa: BLE001 - the wrong reference for these parameters
            faults.append(f"probe {req.index}: reference {used['reference']} "
                          f"failed: {type(exc).__name__}: {exc}")
            traceback.print_exc()
            continue
        if logits.shape != (n_tokens, vocab):
            faults.append(f"probe {req.index}: reference {used['reference']} "
                          f"gave logits {logits.shape}, not {(n_tokens, vocab)}")
            continue
        if margin is not None and margin.shape != (n_tokens,):
            faults.append(f"probe {req.index}: reference {used['reference']} "
                          f"gave routing margins {margin.shape}, not "
                          f"{(n_tokens,)}")
            continue
        ref_lp = logits - _logsumexp(logits)
        for j, tok in enumerate(rec.tokens):
            labels.append(f"probe {req.index} (prompt {n}) token {j}")
            d_lps.append(abs(float(ref_lp[j, tok]) - rec.logprobs[j]))
            d_args.append(float(logits[j].max() - logits[j, tok]))
        if margin is not None:
            margins += margin.tolist()
    verdict = decide(labels, d_lps, d_args,
                     margins if margin_at is not None else None, lim)
    verdict["faults"] = faults + verdict["faults"]
    verdict["positions"] = len(reqs) * n_tokens
    if margin_at is not None:
        # what a ``margin`` is set from: the margin of each position over a
        # tolerance, tied or not, and where the margins lie
        verdict["margins_over_tolerance"] = sorted(
            m for m, a, b in zip(margins, d_lps, d_args)
            if not (a <= lim.logprob_tol and b <= lim.argmax_tol))
        verdict["margin_quantiles"] = [
            float(q) for q in np.quantile(margins, [0, 0.1, 0.25, 0.5])] \
            if margins else []
    return {**verdict, **used}


async def run_probe(sut, cell) -> dict:
    reqs, recs = await serve_probe(sut, cell)
    return await compare_probe(sut.params, cell, reqs, recs)


def _logsumexp(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    return m + np.log(np.exp(x - m).sum(axis=-1, keepdims=True))
