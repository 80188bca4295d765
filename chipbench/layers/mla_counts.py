"""What the latent (MLA) walk has to move and compute: the counting function
of ``attn.latent_roofline_pct``, kept beside its reader and not in the
program. No metric of its own.

``facts`` is ``stats()["attn"]`` of a model of latent attention: ``heads``
query heads, ``row_useful`` the values of a token's cached row (``c_kv |
k_r``, 576), ``value_width`` those of them that are its value (512);
``block_size`` is the cache's. The step's counts are its ``engine.record``
span's: ``kv_blocks_walked`` (the blocks the rows' contexts hold, over the
layers), ``attn_q_ctx`` (the (query, key) pairs the rows' queries see, over
the layers), ``live_tokens``, and ``layers`` of the facts for what a token
brings in and takes out of each layer's walk.

- bytes: a walked block's rows once, ``block_size x row_useful`` values of 2
  bytes (one row a token, read once for scores and for values: there is no
  second pool); a live token's queries in, ``heads x row_useful``, and its
  output back, ``heads x value_width``, 2 bytes a value, a layer. A chunk
  row's context is counted once, though the kernel walks it again for every
  tile of 16 tokens: the share errs low;
- FLOP: a (query, key) pair of a head is ``2 x row_useful`` for the score
  and ``2 x value_width`` for the value.

Useful widths only: the stored row's padding (640 for 576) is the kernel's
own cost and not work. The ideal time is the larger of bytes over the memory
bandwidth and FLOP over the bf16 peak (``harness/peaks.py``).
"""


def step(blocks: float, q_ctx: float, live: float, facts: dict,
         block_size: int) -> tuple[float, float]:
    """(bytes, FLOP) of one step's latent walks."""
    heads, row, val = facts["heads"], facts["row_useful"], facts["value_width"]
    nbytes = (blocks * block_size * row * 2
              + live * facts["layers"] * heads * (row + val) * 2)
    return nbytes, q_ctx * heads * 2.0 * (row + val)


def ideal_seconds(blocks: float, q_ctx: float, live: float, facts: dict,
                  block_size: int, peaks) -> float:
    nbytes, flop = step(blocks, q_ctx, live, facts, block_size)
    return max(nbytes / peaks.hbm_bytes_per_s, flop / peaks.flops_bf16)
