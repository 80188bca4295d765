"""Mean device time of one mixed step (decode rows and prefill chunks in
one program): the executions of the ``jit_step_mixed_*`` programs on the
``XLA Modules`` line of the traced slice. None when the slice held none."""
from harness import xevents

name, unit = "engine.mixed_step_dev_ms", "ms"
layer, moves, source = "model forward, prefill (models/llama.py)", "ttft_mean_ms", "device_trace"


def read(ctx):
    return xevents.module_mean_ms(xevents.current(), "jit_step_mixed_")
