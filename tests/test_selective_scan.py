"""The Mamba-1 recurrence's kernel (ops/selective_scan.py) against the same
recurrence in ``jax.numpy``: interpreted on the CPU, at the published
``[5120, 16]`` state and at the tiny shape, rows of one and of several
positions mixed with padded rows; and compiled for the described v5e with
the scoped VMEM it asks for."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import selective_scan as ss


def _case(seed, d, ns, q_lens, t, *, layers=2, spare=2):
    """A step's tokens for rows of ``q_lens`` live positions each (0: a
    padded row, which names the trash row), packed row after row into a
    bucket with ``spare`` tokens of padding behind them, and a pool whose
    every element is its own number."""
    rng = np.random.default_rng(seed)
    b = len(q_lens)
    n = sum(q_lens) + spare
    slots = b + 1
    f32 = np.float32
    state = rng.standard_normal((layers, slots + 1, ns, d // 128, 128)).astype(f32)
    ends = np.cumsum(q_lens)
    # live rows hold the slots in another order than the rows'
    row_slot = np.where(np.asarray(q_lens) > 0,
                        (np.arange(b) * 3 + 1) % slots, slots)
    return dict(
        state=jnp.asarray(state), layer=jnp.int32(1),
        slots=jnp.asarray(row_slot, jnp.int32),
        starts=jnp.asarray(ends - q_lens, jnp.int32),
        q_len=jnp.asarray(q_lens, jnp.int32),
        fresh=jnp.asarray(np.arange(b) % 3 == 1),
        x=jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16),
        dt=jnp.asarray(np.abs(rng.standard_normal((n, d))) * 0.1, jnp.float32),
        bm=jnp.asarray(rng.standard_normal((n, ns)), jnp.bfloat16),
        cm=jnp.asarray(rng.standard_normal((n, ns)), jnp.bfloat16),
        a_log=jnp.asarray(np.log(np.tile(np.arange(1, ns + 1, dtype=f32)[:, None],
                                         (1, d)))),
        d_skip=jnp.asarray(1 + 0.1 * rng.standard_normal(d), jnp.float32),
    ), t


CASES = {
    # a decode program: every live row one position, two padded rows
    "decode_tiny": (128, 16, (1, 1, 0, 1, 0, 1), 1),
    # a mixed program: decode rows, a chunk longer than a block of the
    # kernel's copies, one shorter, one a whole bucket, padded rows between
    "mixed_tiny": (128, 16, (1, 13, 0, 5, 1, 16, 0), 16),
    "decode_published": (5120, 16, (1, 0, 1, 1), 1),
    "mixed_published": (5120, 16, (1, 11, 0, 3), 16),
}


@pytest.mark.parametrize("tiles", [1, 5])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_recurrence(case, tiles):
    """Interpreted, against ``jax.numpy`` (the form the CPU serves): the
    live rows' states and every live position's ``y``; a token of no row
    reads 0; every other slot of the pool, the other layer and the trash
    row that padded rows name are left bit for bit. ``tiles`` 5: the grid's
    second axis, a fifth of the published channels a step."""
    d, ns, q_lens, t = CASES[case]
    if tiles > 1 and d // 128 % tiles:
        pytest.skip("one group of lanes: one tile")
    kw, t = _case(len(case), d, ns, q_lens, t)
    want_state, want_y = ss.selective_scan(**kw, t=t, impl="jnp")
    got_state, got_y = ss.selective_scan(**kw, t=t, impl="pallas_interpret",
                                         tiles=tiles)
    live = sum(q_lens)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32),
        atol=0.05, rtol=0.02)                    # bf16 out, values to ~10
    assert (np.asarray(got_y, np.float32)[live:] == 0).all()
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               atol=1e-4, rtol=1e-4)
    before, after = np.asarray(kw["state"]), np.asarray(got_state)
    touched = {int(s) for s, n in zip(np.asarray(kw["slots"]), q_lens) if n}
    for layer in range(before.shape[0]):
        for slot in range(before.shape[1]):
            moved = layer == 1 and slot in touched
            same = (before[layer, slot] == after[layer, slot]).all()
            assert same != moved, (layer, slot)
    assert len(q_lens) + 1 not in touched        # the trash row


def test_a_program_without_live_rows_leaves_the_pool():
    kw, t = _case(3, 128, 16, (0, 0, 0), 1)
    state, y = ss.selective_scan(**kw, t=t, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(state), np.asarray(kw["state"]))
    assert (np.asarray(y, np.float32) == 0).all()


def test_the_state_keeps_the_channels_on_whole_lanes():
    assert ss.state_shape(16, 5120) == (16, 40, 128)
    with pytest.raises(ValueError, match="multiple of 128"):
        ss.state_shape(16, 96)


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


@pytest.mark.parametrize("b, t, n", [(32, 1, 32), (32, 512, 1024)],
                         ids=["decode", "mixed"])
def test_selective_scan_compiles_on_v5e(v5e_device, b, t, n):
    """The kernel at the published shape, compiled for the described v5e
    with no chip: Mosaic takes its copies (a token's ``[40, 128]`` tile cut
    at any token, bf16 ``y`` back), the pool is aliased to the output, and
    it asks for 16 MiB of scoped VMEM of which it holds under 3: the state
    in and out twice, ``A`` twice, a block of ``x``, ``dt``, ``y``, ``B``,
    ``C``. Memory and text only: no time is read here."""
    chip = jax.sharding.SingleDeviceSharding(v5e_device)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    d, ns, layers, slots = 5120, 16, 9, 65
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    pool = sds((layers, slots, ns, d // 128, 128), f32)
    compiled = jax.jit(
        lambda *a: ss.selective_scan(*a, t=t, impl="pallas"),
        donate_argnums=(0,)).lower(
            pool, sds((), i32), sds((b,), i32), sds((b,), i32), sds((b,), i32),
            sds((b,), jnp.bool_), sds((n, d), bf16), sds((n, d), f32),
            sds((n, ns), bf16), sds((n, ns), bf16), sds((ns, d), f32),
            sds((d,), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "selective_scan" in text
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(pool.shape) * 4
    assert mem.alias_size_in_bytes >= pool_bytes      # updated in place
    assert mem.temp_size_in_bytes < 0.2 * pool_bytes, mem
    assert ss.VMEM_LIMIT_BYTES == 16 * 2**20
    block = 1 if t == 1 else ss.BLOCK
    held = (4 * ns * d * 4 + 2 * ns * d * 4 + 2 * d * 4
            + block * (2 * d * 4 + d * 2 + 2 * ns * 128 * 4))
    assert held < 3 * 2**20 < ss.VMEM_LIMIT_BYTES
