"""Crash-consistent stream checkpoints (dynamo_stream_ckpt_*).

The crash-path twin of the drain protocol (runtime/drain.py): drain
evacuates *sessions* on a planned exit; this module's record format and
metrics family cover *in-flight streams* against an unplanned worker kill.
Every K committed decode blocks (and once at prefill completion) the
engine enqueues the stream's newly committed blocks plus a tiny
``StreamCheckpoint`` record through the OffloadManager's budgeted flush
into the shared G4 store (reference: lib/llm/src/migration.rs and
docs/architecture/request_migration.md treat request migration as a
first-class protocol; here the checkpoint makes it *warm* and
token-identical instead of cold and lossy). On ``StreamError`` the
frontend migration operator looks the record up and resumes the stream as
pull-to-warm, replaying only the post-checkpoint suffix — bitwise for
greedy streams, via the restored sampler PRNG state for sampled ones.

One module holds the three pieces every layer shares:

* the **record** schema (build/parse) — request_id, generated-token
  ledger, committed block-hash chain, sampler PRNG state (key data +
  draw counter so non-greedy resume is bit-identical), stop progress;
* the **annotation keys** the frontend stamps on a resume request so the
  engine/mocker can restore sampler state and continue the ledger;
* the **metrics family** (names cross-checked by tools/lint_metrics.py
  STREAM_CKPT_METRICS).
"""

from __future__ import annotations

import time
from typing import Any

from dynamo_tpu.utils.metrics import MetricsRegistry

# -- resume-request annotations (frontend → engine/mocker) -----------------
# Count of already-generated tokens appended to the resume prompt: the
# mocker continues its deterministic ledger at this offset, the engine
# knows how many trailing prompt tokens are *generated* (penalty rebuild +
# recompute accounting), and both count the request as a ckpt resume.
CKPT_GENERATED_KEY = "stream_ckpt.generated"
# Total sampler draws the stream had consumed before the crash (one draw
# per emitted token) — the fold/step counter the
# engine advances the restored key by.
CKPT_DRAWS_KEY = "stream_ckpt.draws"
# Captured device PRNG key data (list of uint32 words) at checkpoint time
# plus the draw count at capture — the resume path for *unseeded* streams,
# where the key cannot be re-derived from the request.
CKPT_KEY_DATA_KEY = "stream_ckpt.key"
CKPT_KEY_DRAWS_KEY = "stream_ckpt.key_draws"

# Records a crashed worker never deleted expire out of the shared store:
# lazy TTL, enforced client-side on get (kvbm/remote.py get_stream_ckpt).
DEFAULT_CKPT_TTL_S = 600.0

# Device blocks sitting in the checkpoint flush queue are pinned under the
# mem-ledger owner class "stream_ckpt" (obs/mem_ledger.py) — pin at
# OffloadManager.enqueue_stream_ckpt, unpin at flush or staleness drop.
MEM_OWNER_CLASS = "stream_ckpt"


def build_ckpt_record(request_id: str, generated: list[int],
                      seq_hashes: list[int], *,
                      key_data: list[int] | None = None,
                      draws: int = 0, seed: int | None = None,
                      prompt_tokens: int = 0) -> dict[str, Any]:
    """The msgpack-able StreamCheckpoint payload. ``generated`` is the full
    token ledger so far (stop-condition progress is reconstructed from it
    on resume), ``seq_hashes`` the committed chain covering prompt +
    ledger, ``key_data``/``draws`` the sampler PRNG state at capture."""
    return {
        "rid": request_id,
        "generated": [int(t) for t in generated],
        "hashes": [int(h) for h in seq_hashes],
        "key": [int(w) for w in key_data] if key_data is not None else None,
        "draws": int(draws),
        "seed": int(seed) if seed is not None else None,
        "prompt_tokens": int(prompt_tokens),
        "ts": time.time(),
    }


def parse_ckpt_record(rec: Any) -> dict[str, Any] | None:
    """Validate a decoded record; None for anything malformed (a corrupt
    record must degrade to the reprompt path, never raise mid-recovery)."""
    if not isinstance(rec, dict) or "generated" not in rec:
        return None
    try:
        return {
            "rid": str(rec.get("rid", "")),
            "generated": [int(t) for t in rec["generated"]],
            "hashes": [int(h) for h in rec.get("hashes") or []],
            "key": ([int(w) for w in rec["key"]]
                    if rec.get("key") is not None else None),
            "draws": int(rec.get("draws", 0)),
            "seed": (int(rec["seed"]) if rec.get("seed") is not None
                     else None),
            "prompt_tokens": int(rec.get("prompt_tokens", 0)),
            "ts": float(rec.get("ts", 0.0)),
        }
    except (TypeError, ValueError):
        return None


class StreamCkptMetrics:
    """The dynamo_stream_ckpt_* family (names cross-checked by
    tools/lint_metrics.py STREAM_CKPT_METRICS)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.bind(registry or MetricsRegistry())

    def bind(self, registry: MetricsRegistry) -> None:
        self.registry = registry
        self.writes = registry.counter(
            "stream_ckpt_writes",
            "StreamCheckpoint records written to the shared remote store")
        self.bytes = registry.counter(
            "stream_ckpt_bytes",
            "Bytes pushed for stream checkpoints (KV blocks + records)")
        self.resumes = registry.counter(
            "stream_ckpt_resumes",
            "Broken streams resumed warm from a stream checkpoint instead "
            "of the cold reprompt path")
        self.resume_recomputed_tokens = registry.counter(
            "stream_ckpt_resume_recomputed_tokens",
            "Tokens recomputed on checkpoint resume (the post-checkpoint "
            "suffix the crash cost — bounded by one checkpoint interval)")
        self.lag_blocks = registry.gauge(
            "stream_ckpt_lag_blocks",
            "Committed blocks of live streams not yet covered by a "
            "checkpoint (crash exposure, in blocks)")
        self.expired = registry.counter(
            "stream_ckpt_expired",
            "Checkpoint lookups that found only a TTL-expired record")


_metrics: StreamCkptMetrics | None = None


def get_stream_ckpt_metrics() -> StreamCkptMetrics:
    global _metrics
    if _metrics is None:
        _metrics = StreamCkptMetrics()
    return _metrics


def install_stream_ckpt_metrics(registry: MetricsRegistry) -> StreamCkptMetrics:
    """Re-home the singleton into ``registry`` (worker or frontend runtime)
    so the family is exposed on /metrics."""
    m = get_stream_ckpt_metrics()
    m.bind(registry)
    return m
