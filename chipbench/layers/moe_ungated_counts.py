"""What a routed layer's grouped matmuls have to move and compute where an
expert need not have a gate: the counting function of
``moe.ungated_experts_roofline_pct``, kept beside its reader and not in the
program. No metric of its own. ``moe_gemm_counts.py`` is the same count with
three matrices an expert written in; this one takes the number from
``stats()["moe"]["expert_matrices"]`` (3: gate, up and down; 2: up and down,
``down(act(up(x)))``).

A routed layer-step that computed ``rows`` (token, choice) rows for
``touched`` of the experts held here runs ``matrices`` grouped matmuls, all
but the last ``[rows, H] x [H, M]`` and the last ``[rows, M] x [M, H]``:

- bytes: ``touched x matrices x H x M`` parameters read once; the rows in
  (``H`` each but the last, ``M`` the last) and out (``M`` each but the
  last, ``H`` the last), at the weights' width;
- FLOP: ``rows x matrices x 2 x H x M``.
"""


def layer_step(rows: float, touched: float, hidden: int, width: int,
               matrices: int, bytes_per_param: int = 2) -> tuple[float, float]:
    first = matrices - 1
    weights = touched * matrices * hidden * width
    moved = rows * (first * hidden + width + first * width + hidden)
    return ((weights + moved) * bytes_per_param,
            rows * matrices * 2 * hidden * width)


def ideal_seconds(rows: float, touched: float, hidden: int, width: int,
                  matrices: int, bytes_per_param: int, peaks) -> float:
    nbytes, flop = layer_step(rows, touched, hidden, width, matrices,
                              bytes_per_param)
    return max(nbytes / peaks.hbm_bytes_per_s, flop / peaks.flops_bf16)
