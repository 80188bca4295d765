"""TPU hot-op kernels (Pallas) with portable fallbacks.

The reference keeps its one hand-written kernel in CUDA
(lib/llm/src/kernels/block_copy.cu); here the hot ops are Pallas TPU
kernels with numerically-equivalent XLA fallbacks for CPU tests:

- paged_attention: flash-style attention over a block-table-paged KV cache.
- ring_attention: blockwise attention sharded over the "seq" mesh axis.
- moe_stream: a decode step's routed experts, each touched expert's three
  matrices streamed once (the fallback is models/moe.py's grouped form).
- kv_write: a packed step's K and V into the paged cache by runs of
  consecutive slots (the fallback is models/llama.py's scatter, an update a
  token).
"""

from dynamo_tpu.ops.paged_attention import (
    paged_attention_kernel,
    paged_attention_sharded,
    select_attn_impl,
)

__all__ = ["paged_attention_kernel", "paged_attention_sharded", "select_attn_impl"]
