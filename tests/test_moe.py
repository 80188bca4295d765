"""Dropless expert-parallel MoE against the all-experts form, off and on a mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import resolve_model_config
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def moe_case():
    cfg = resolve_model_config("tiny-moe")
    params = llama.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])  # single layer slice
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.hidden_size)), jnp.float32)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    return cfg, lp, x


# ---------------------------------------------------------------------------
# Dropless dispatch (moe_mlp_dropless): exact under ANY routing skew,
# the property a capacity-bounded dispatch cannot give a serving engine.
# ---------------------------------------------------------------------------

def test_dropless_matches_dense(moe_case):
    from dynamo_tpu.models.moe import moe_mlp_dropless

    cfg, lp, x = moe_case
    ref = llama.moe_mlp(x, lp, cfg)
    out = moe_mlp_dropless(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_dropless_exact_under_total_skew(moe_case):
    """Router biased so EVERY token picks the same expert — the worst
    over-capacity regime. Dropless must still equal the dense reference."""
    from dynamo_tpu.models.moe import moe_mlp_dropless

    cfg, lp, x = moe_case
    lp_skew = dict(lp)
    bias = np.zeros((cfg.hidden_size, cfg.num_experts), np.float32)
    bias[:, 0] = 1.0  # expert 0 dominates every routing decision
    lp_skew["router"] = jnp.asarray(bias * 10.0)
    ref = llama.moe_mlp(x, lp_skew, cfg)
    out = moe_mlp_dropless(x, lp_skew, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_dropless_ep_sharded_matches_dense(moe_case):
    """shard_map over an 8-way expert axis: local ragged groups + psum must
    reproduce the dense reference bit-for-bit (within fp tolerance)."""
    from dynamo_tpu.models.moe import moe_mlp_dropless

    cfg, lp, x = moe_case
    mesh = make_mesh(MeshConfig(ep=8))
    ref = llama.moe_mlp(x, lp, cfg)
    out = jax.jit(lambda x, w: moe_mlp_dropless(x, w, cfg, mesh=mesh))(x, lp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_dropless_ep_sharded_under_skew(moe_case):
    from dynamo_tpu.models.moe import moe_mlp_dropless

    cfg, lp, x = moe_case
    lp_skew = dict(lp)
    bias = np.zeros((cfg.hidden_size, cfg.num_experts), np.float32)
    bias[:, 3] = 1.0
    lp_skew["router"] = jnp.asarray(bias * 10.0)
    mesh = make_mesh(MeshConfig(ep=8))
    ref = llama.moe_mlp(x, lp_skew, cfg)
    out = jax.jit(lambda x, w: moe_mlp_dropless(x, w, cfg, mesh=mesh))(x, lp_skew)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
