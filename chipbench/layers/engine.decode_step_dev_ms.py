"""Mean device time of one decode step: the executions of the
``jit_step_decode_*`` programs on the ``XLA Modules`` line of the traced
slice. None when the slice held none (or the programs are not named so)."""
from harness import xevents

name, unit = "engine.decode_step_dev_ms", "ms"
layer, moves, source = "model forward, decode (models/llama.py)", "itl_p95_ms", "device_trace"


def read(ctx):
    return xevents.module_mean_ms(xevents.current(), "jit_step_decode_")
