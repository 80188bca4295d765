"""What the Mamba-1 recurrence has to move and compute: the counting function
of ``ssm.selective_scan_roofline_pct``, kept beside its reader and not in the
program. No metric of its own.

A step whose programs ran the selective-scan kernel (ops/selective_scan.py)
over ``rows`` (row, layer) pairs and ``positions`` (position, layer) pairs
(``ssm_scan_rows`` and ``ssm_scan_positions`` off the step's
``engine.record`` span), with the shapes of ``stats()["ssm"]``: ``heads x
head_dim`` is the inner width ``d``, ``state_size`` is ``N``:

- bytes: a scanned row's state ``[N, d]`` float32 is read and written once;
  a position's ``x`` and ``y`` (the model's type, 2 bytes), ``dt`` (float32)
  and ``B`` and ``C`` (``N`` each, 2 bytes) come in and go out once. The
  convolution and its tail are another phase's, as the projections are;
- operations: five an element of the state a position (the decay's product,
  ``dt x B``, the two of ``S <- a S + ..``, ``C S`` summed), and one
  exponential an element, counted as one operation.

The ideal time is the larger of bytes over the memory bandwidth and
operations over the bf16 peak (``harness/peaks.py``): the chip's vector unit
is far under that peak, so the bound that binds is the bytes' in a decode
step and the share errs low in a chunk step.
"""


def step(rows: float, positions: float, facts: dict) -> tuple[float, float]:
    """(bytes, operations) of one step's selective scans."""
    d = facts["heads"] * facts["head_dim"]
    n = facts["state_size"]
    nbytes = rows * 2 * n * d * 4 + positions * (d * (2 + 4 + 2) + 2 * n * 2)
    return nbytes, positions * 6 * n * d


def ideal_seconds(rows: float, positions: float, facts: dict, peaks) -> float:
    nbytes, ops = step(rows, positions, facts)
    return max(nbytes / peaks.hbm_bytes_per_s, ops / peaks.flops_bf16)
