"""What the routed layer's grouped matmuls have to move and compute: the
counting function of ``moe.expert_gemm_roofline_pct``, kept beside its reader
and not in the program. No metric of its own.

A routed layer-step (one routed layer in one step program) that computed
``rows`` (token, choice) rows for ``touched`` of the experts held here runs
three grouped matmuls (``models/moe.py held_rows``: gate and up ``[rows, H]
x [H, M]``, down ``[rows, M] x [M, H]``, each row against its own expert's
matrix):

- bytes: the matrices of the experts that had rows, ``touched x 3 x H x M``
  parameters, read once; the rows in (``H`` for gate, ``H`` for up, ``M``
  for down) and out (``M``, ``M``, ``H``), all at the weights' width. An
  expert nobody was routed to costs nothing; the sort, the gather of the
  rows and the weighted scatter back are not the matmuls' and are left out
  (so the share reads low rather than high);
- FLOP: ``rows x 3 x 2 x H x M``.

The ideal time of a layer-step is the larger of bytes over the memory
bandwidth and FLOP over the bf16 peak (``harness/peaks.py``).
"""


def layer_step(rows: float, touched: float, hidden: int, width: int,
               bytes_per_param: int = 2) -> tuple[float, float]:
    """(bytes, FLOP) of one routed layer-step's three grouped matmuls."""
    weights = touched * 3 * hidden * width
    moved = rows * (3 * hidden + 3 * width)
    return (weights + moved) * bytes_per_param, rows * 3 * 2 * hidden * width


def ideal_seconds(rows: float, touched: float, hidden: int, width: int,
                  bytes_per_param: int, peaks) -> float:
    nbytes, flop = layer_step(rows, touched, hidden, width, bytes_per_param)
    return max(nbytes / peaks.hbm_bytes_per_s, flop / peaks.flops_bf16)
