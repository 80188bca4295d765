"""The table of peaks and the counting of a step's operations and bytes.

Copied from ``dynamo_tpu/obs/costmodel.py`` (``HW_SPECS`` and the per-token
FLOP and byte arithmetic) so that a later PR cannot move the yardstick by
editing the program. Only counts live here: the cost model's predicted
times are never a metric.

Peaks: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s,
16 GB of HBM per chip. A device that is not in the table is an error.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Keyed by a lowercase substring of jax's ``device_kind``; a v5e reports
# "TPU v5 lite" (PERF.md, PR 21).
PEAKS: dict[str, Peaks] = {
    "tpu v5 lite": Peaks("tpu-v5e", 197e12, 819e9, 16e9,
                         "Google Cloud TPU documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    kind = device_kind.lower()
    for key, p in PEAKS.items():
        if key in kind:
            return p
    raise ValueError(f"no peaks for device_kind {device_kind!r}; add its "
                     "published peaks, with their source, to a new table entry")


def layer_params(m: dict) -> int:
    """Matmul parameters of one dense transformer layer (HF keys)."""
    h, i = m["hidden_size"], m["intermediate_size"]
    hd = m.get("head_dim", h // m["num_attention_heads"])
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return h * q + 2 * h * kv + q * h + 3 * h * i


def weight_bytes(m: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step must read: every layer and the
    output head (the embedding is a gather of a few rows)."""
    head = m["vocab_size"] * m["hidden_size"]
    return (layer_params(m) * m["num_hidden_layers"] + head) * bytes_per_param


def kv_bytes_per_token(m: dict, bytes_per_elem: int = 2) -> int:
    hd = m.get("head_dim", m["hidden_size"] // m["num_attention_heads"])
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * hd \
        * bytes_per_elem


def matmul_flops_per_token(m: dict) -> int:
    """Multiply-adds x2 of the layers' matmuls for one token (no logits)."""
    return 2 * layer_params(m) * m["num_hidden_layers"]


def logits_flops_per_row(m: dict) -> int:
    return 2 * m["vocab_size"] * m["hidden_size"]


def attention_flops(m: dict, q_len: int, kv_len: int) -> int:
    """QK^T and PV for ``q_len`` query tokens ending a context of
    ``kv_len`` (causal: a query sees the positions up to its own)."""
    hd = m.get("head_dim", m["hidden_size"] // m["num_attention_heads"])
    seen = q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * hd * seen
