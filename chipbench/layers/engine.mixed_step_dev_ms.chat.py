"""The chat cells' name for ``engine.mixed_step_dev_ms``.

There ``ttft_mean_ms`` is not end to end since PR 29 (the machine's freezes
move a mean of 41-49 times to first token past any bound), and a per-layer
metric names one end-to-end metric that its cells report: the same reading
moves ``itl_p95_ms`` there, since a prompt's mixed step is the gap its
neighbours see."""
from harness.measure import load_reader

_base = load_reader("engine.mixed_step_dev_ms")
name, unit = "engine.mixed_step_dev_ms.chat", _base.unit
layer, moves, source = _base.layer, "itl_p95_ms", _base.source
read = _base.read
