"""Share of the device's busy time spent moving the KV pool: the self time
of the operations whose result has the per-device shape of the whole cache
(``stats()["kv_cache_shape"]``, ``[L, NB, BS, KH, D]``), of one layer of
it (``[1, NB, BS, KH, D]``) or of its flattening (``[NB*BS, KH, D]``). The
cache rides ``lax.scan`` through the layers, and XLA copies it."""
from harness import xevents

name, unit = "device.cache_copy_pct", "%"
layer, moves, source = "KV cache carry (models/llama.py scan)", "itl_p95_ms", "device_trace"


def read(ctx):
    shape = tuple(ctx.counters[1].get("kv_cache_shape") or ())
    if len(shape) != 5:
        return None       # a program that does not say
    layers, nb, bs, kh, d = shape
    moved = {shape, (1, nb, bs, kh, d), (nb * bs, kh, d)}
    return xevents.self_time_pct(
        xevents.current(), lambda hlo: xevents.result_shape(hlo) in moved)
