"""What one engine step has to move and compute: the counting function of
``engine.step_mfu_pct``, ``engine.step_roofline_pct`` and
``attn.kernel_roofline_pct``, kept beside its readers and not in the program
(as ``moe_gemm_counts.py`` is for the routed layer's). No metric of its own.

``shapes`` is ``stats()["step_shapes"]``, the program's statement of its
matrices: per kind of layer the parameters a program reads whatever its rows
(``attn_params``: wq, wk, wv, wo; ``dense_ffn_params``;
``shared_expert_params``; ``router_params``), one routed expert's
(``expert_params``), the head's, ``bytes_per_param`` as the program holds
them, ``kv_block_bytes_per_layer`` (K and V of one block of one layer) and
the numbers of layers of each kind. ``counts`` is what the step's
``engine.record`` span carries: the one count of its rows (``programs``,
``live_tokens``, ``logit_rows``, ``attn_q_ctx``, ``kv_blocks_walked``) and
the routed layers' device counts (``moe_rows``, ``moe_experts_touched``).

- bytes: the parameters a program reads once whatever its rows, once a
  program of the step, and the head with them; one expert's times the
  experts touched; the KV blocks walked (each layer's window counted) times a
  block's bytes; the embedding's gathered rows and not its table.
  Activations between operations are left out, so a share reads low rather
  than high;
- FLOP: the matmuls of the live tokens (2 a parameter a token; a routed
  expert's a computed row), the head's of the rows that sample, attention's
  ``4 x heads x head_dim x`` the (query, key) pairs the rows' queries see.

The ideal time is the larger of bytes over the memory bandwidth and FLOP
over the bf16 peak (``harness/peaks.py``), a device: a model divided over
``devices`` chips divides both.
"""


def fixed_params(shapes: dict) -> float:
    """Matmul parameters a program reads whatever its rows, the head apart."""
    s = shapes
    return (s["layers"] * s["attn_params"]
            + s["dense_ffn_layers"] * s["dense_ffn_params"]
            + s["routed_layers"] * (s["shared_expert_params"]
                                    + s["router_params"]))


def step(shapes: dict, counts: dict) -> tuple[float, float]:
    """(bytes, FLOP) of one step, a device."""
    s = shapes
    n, rows = float(counts["live_tokens"]), float(counts["logit_rows"])
    fixed = fixed_params(s)
    touched = float(counts.get("moe_experts_touched", 0))
    moe_rows = float(counts.get("moe_rows", 0))
    params = (float(counts.get("programs", 1)) * (fixed + s["head_params"])
              + touched * s["expert_params"])
    nbytes = (params * s["bytes_per_param"]
              + float(counts["kv_blocks_walked"]) * s["kv_block_bytes_per_layer"]
              + n * s["hidden_size"] * 2)
    flop = (2.0 * n * fixed + 2.0 * moe_rows * s["expert_params"]
            + 2.0 * rows * s["head_params"]
            + 4.0 * s["num_heads"] * s["head_dim"] * float(counts["attn_q_ctx"]))
    d = float(s.get("devices", 1) or 1)
    return nbytes / d, flop / d


def kernel(shapes: dict, counts: dict) -> tuple[float, float]:
    """(bytes, FLOP) of the attention kernel's calls of one step, a device:
    the blocks walked, the queries in and the output back (``q_size`` a live
    token a layer, two bytes an element); QK^T and PV over the pairs seen."""
    s = shapes
    nbytes = (float(counts["kv_blocks_walked"]) * s["kv_block_bytes_per_layer"]
              + 2.0 * float(counts["live_tokens"]) * s["q_size"] * 2
              * s["layers"])
    flop = 4.0 * s["num_heads"] * s["head_dim"] * float(counts["attn_q_ctx"])
    d = float(s.get("devices", 1) or 1)
    return nbytes / d, flop / d


def ideal_seconds(work: tuple[float, float], peaks) -> float:
    nbytes, flop = work
    return max(nbytes / peaks.hbm_bytes_per_s, flop / peaks.flops_bf16)
