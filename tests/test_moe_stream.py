"""The streaming expert kernel (``dynamo_tpu/ops/moe_stream.py``) on the CPU,
interpreted: held to the grouped form it stands in for (``moe.held_rows``,
which on the CPU is always ``lax.ragged_dot``) and to the all-experts form
(``llama.moe_mlp``), in float32 with seeded random weights. Its counts are
the grouped form's, exactly. Whether it compiles for the chip, and which
programs take it, is ``tests/test_ops.py``'s.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import MODEL_PRESETS
from dynamo_tpu.ops import moe_stream

H, M, E, K = 128, 256, 8, 3
LAYERS = 3
# float32 sums of a token's expert parts in another order: 1e-6 of
# unit-scale values; a bf16 rounding anywhere reads 1e-2.
TOL = 2e-5


def _weights(rng, layers=None):
    lead = (E,) if layers is None else (layers, E)
    return tuple(jnp.asarray(rng.standard_normal(lead + shape) * 0.1,
                             jnp.float32)
                 for shape in ((H, M), (H, M), (M, H)))


def _routing(rng, n, width):
    """A token's K distinct choices over ``width`` experts, and weights."""
    topi = np.stack([rng.choice(width, K, replace=False) for _ in range(n)])
    return topi.astype(np.int32), rng.uniform(0.1, 1.0, (n, K)).astype(np.float32)


CASES = {
    # name: (N, act, router width, live tokens or None, layer or None, routing)
    "silu_whole_n8": (8, "silu", E, None, None, "random"),
    "relu_whole_n8": (8, "relu", E, None, None, "random"),
    "silu_share_n8": (8, "silu", 2 * E, None, None, "random"),
    "relu_share_n16": (16, "relu", 2 * E, None, None, "random"),
    "padding_rows_n8": (8, "silu", E, 5, None, "random"),
    "padding_rows_share_n16": (16, "relu", 2 * E, 11, None, "random"),
    "n1": (1, "silu", E, None, None, "random"),
    "n1_share": (1, "relu", 4 * E, None, None, "random"),
    "n16_whole": (16, "silu", E, None, None, "random"),
    "stack_first_layer": (8, "relu", E, 6, 0, "random"),
    "stack_middle_layer": (8, "silu", 2 * E, None, 1, "random"),
    "stack_last_layer": (16, "relu", E, 13, 2, "random"),
    "all_on_one_expert": (8, "silu", 2 * E, None, None, "one"),
    "all_on_one_expert_stack": (16, "relu", 2 * E, 9, 1, "one"),
    "none_held": (8, "silu", 2 * E, None, None, "none"),
    "none_held_stack": (8, "relu", 2 * E, 4, 2, "none"),
    "none_live": (8, "silu", E, 0, None, "random"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stream_rows_equal_the_grouped_and_the_all_experts_forms(case):
    n, act_name, width, n_live, layer, routing = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act_name]
    x = jnp.asarray(rng.standard_normal((n, H)), jnp.float32)
    topi, weights = _routing(rng, n, width)
    if routing == "one":
        # every token's first choice is expert 5, its others held elsewhere
        topi[:, 0], topi[:, 1:] = 5, E + 1 + np.arange(K - 1)
    elif routing == "none":
        topi = E + topi % (width - E)
    topi, weights = jnp.asarray(topi), jnp.asarray(weights)
    live = None if n_live is None else jnp.arange(n) < n_live
    w = _weights(rng, None if layer is None else LAYERS)
    mine = w
    if layer is not None:
        # Another layer's experts are never read: anything read of them
        # would spoil the result.
        mine = tuple(a[layer] for a in w)
        w = tuple(jnp.full_like(a, jnp.nan).at[layer].set(a[layer]) for a in w)

    y, counts = moe_stream.stream_rows(x, topi, weights, *w, live, layer, act,
                                       interpret=True)
    want, want_counts = moe.held_rows(x, topi, weights, *mine, live, act=act)
    assert y.shape == (n, H) and y.dtype == jnp.float32
    assert counts.dtype == jnp.int32
    assert counts.tolist() == want_counts.tolist()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=TOL)

    # ... and the plain form: every held expert for every token
    cfg = dataclasses.replace(
        MODEL_PRESETS["tiny-moe"], hidden_size=H, moe_intermediate_size=M,
        num_experts=E, num_experts_published=width, num_experts_per_tok=K,
        num_shared_experts=0, expert_act=act_name)
    lp = dict(zip(("w_gate", "w_up", "w_down"), mine))
    plain = np.asarray(llama.moe_mlp(x, lp, cfg, (topi, weights)))
    rows = slice(None) if n_live is None else slice(0, n_live)
    np.testing.assert_allclose(np.asarray(y)[rows], plain[rows], atol=TOL)
    if n_live is not None:
        assert not np.asarray(y)[n_live:].any()     # padding computes nothing

    here = (np.asarray(topi) < E) & (np.ones(n, bool) if live is None
                                     else np.asarray(live))[:, None]
    sizes = np.bincount(np.asarray(topi)[here], minlength=E)
    assert counts.tolist() == [int(here.sum()), int((sizes > 0).sum()),
                               int(sizes.max())]
    if routing == "one":
        assert counts.tolist()[1:] == [1, n if n_live is None else n_live]
    if routing == "none" or n_live == 0:
        assert counts.tolist() == [0, 0, 0] and not np.asarray(y).any()


def test_the_plan_names_each_touched_expert_once_then_the_last_again():
    """What the kernel walks: the touched experts in ascending order at the
    front of a list of the static length ``min(N x k, E_held)``, its tail
    the last of them (a block whose index does not change is not fetched
    again), and the combine matrix holds each live token's weight for each
    held expert it chose."""
    topi = jnp.asarray([[6, 1, 9], [1, 4, 12], [4, 6, 15], [0, 2, 3]], jnp.int32)
    weights = jnp.arange(1, 13, dtype=jnp.float32).reshape(4, 3)
    live = jnp.asarray([True, True, True, False])
    c, ids, n_touched, counts = moe_stream.stream_plan(topi, weights, live, E)
    assert ids.tolist() == [1, 4, 6] + [6] * 5 and n_touched.tolist() == [3]
    assert counts.tolist() == [6, 3, 2]
    want = np.zeros((E, 4), np.float32)
    want[6, 0], want[1, 0], want[1, 1] = 1, 2, 4
    want[4, 1], want[4, 2], want[6, 2] = 5, 7, 8
    np.testing.assert_array_equal(np.asarray(c), want)
    # two rows of three choices: the list is as long as the rows allow
    assert moe_stream.stream_plan(topi[:2], weights[:2], None, E)[1].shape == (6,)
