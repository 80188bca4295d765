"""Share of the device's busy time in latent (MLA) attention: the self time
of the operations whose phase scope is ``mla_down`` (the two
down-projections, their norms, the query's up-projection, the rope),
``mla_absorb`` (``q_nope W_uk^T``), ``mla_write`` (the row into the latent
pool), ``mla_walk`` (the paged walk in its latent form) or ``mla_unabsorb``
(``o_lat W_uv``) (``models/llama.py _latent_attention``), found by each
program's phase table and not by an instruction's name (``step_join.py``).
``wo`` is the ``proj`` phase's, as in every model. None without the tables
or on a program with no such phase."""
from pathlib import Path

from harness import measure, xevents

join = measure.load_module(Path(__file__).with_name("step_join.py"), "step_join")

name, unit = "device.mla_pct", "%"
layer, moves, source = "model forward, prefill (models/llama.py)", "itl_p95_ms", "device_trace"

PHASES = ("mla_down", "mla_absorb", "mla_write", "mla_walk", "mla_unabsorb")


def read(ctx):
    j = join.current()
    if j is None or not j.tables:
        return None
    busy = xevents.current().busy_ns()
    own = j.self_ns(lambda _i, phase: phase in PHASES)
    return 100.0 * own / busy if busy > 0 and own > 0 else None
