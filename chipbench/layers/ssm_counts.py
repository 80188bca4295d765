"""What the recurrent layers' convolution and scan have to move and compute:
the counting function of ``ssm.scan_roofline_pct``, kept beside its reader
and not in the program. No metric of its own.

A step whose programs read and wrote the state of ``state_rows`` (row,
layer) pairs and carried ``live_tokens`` tokens through each of ``layers``
Mamba-2 layers (``models/mamba.py``; the shapes are ``stats()["ssm"]``):

- bytes: every such row's state is read and written once, ``2 x
  slot_layer_bytes`` (the float32 ``[H, P, N]`` state and the convolution's
  ``[K - 1, C]`` tail); a live token's ``z``, ``xBC`` and ``dt`` come in and
  its ``y`` goes out, ``token_bytes`` a layer. The in and out projections
  are another phase (``ssm_proj``) and are not counted here, nor timed;
- FLOP: the one-token recurrence, ``S <- a S + dt x (x) B`` and ``y = S C``,
  five operations an element of the state, and the convolution's ``2 K C``,
  a live token a layer. The blocked form a chunk program runs does more than
  that (the products within a block), so the share errs low in chunk steps.

The ideal time is the larger of bytes over the memory bandwidth and FLOP
over the bf16 peak (``harness/peaks.py``).
"""


def step(state_rows: float, live_tokens: float, facts: dict) -> tuple[float, float]:
    """(bytes, FLOP) of one step's convolutions and scans."""
    layers = facts["layers"]
    nbytes = (state_rows * 2 * facts["slot_layer_bytes"]
              + live_tokens * layers * facts["token_bytes"])
    per_token = (5 * facts["heads"] * facts["head_dim"] * facts["state_size"]
                 + 2 * facts["conv_kernel"] * facts["conv_dim"])
    return nbytes, live_tokens * layers * per_token


def ideal_seconds(state_rows: float, live_tokens: float, facts: dict,
                  peaks) -> float:
    nbytes, flop = step(state_rows, live_tokens, facts)
    return max(nbytes / peaks.hbm_bytes_per_s, flop / peaks.flops_bf16)
