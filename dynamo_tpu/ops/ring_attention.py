"""Ring attention: causal attention sharded over the "seq" mesh axis.

The reference has NO sequence/context parallelism anywhere (SURVEY.md §2.7 —
verified absent; it scales context only via engine-internal means + KV
offload), so this is greenfield TPU design: for long-context prefill the
sequence is sharded across devices on the "seq" axis; each device computes
blockwise attention of its local query chunk against k/v chunks that rotate
around the ring via ``lax.ppermute`` (one hop per step, so the transfer
rides ICI neighbor links and overlaps with the attention math of the
previous chunk — XLA schedules the ppermute DMA concurrently with compute).

State is the standard online-softmax triple (acc, row-max, row-sum), so the
result is exactly (up to fp assoc.) dense causal attention over the global
sequence. Causality is enforced by *global* positions: query chunk i attends
to kv chunk j fully if j < i, diagonally if j == i, not at all if j > i —
the j > i steps still rotate but contribute nothing (their mask is empty);
a production refinement is striped ordering to balance that wasted work.

Layout: [B, T_local, H, D] per device, global T = T_local * axis_size.
GQA via grouped einsum (no KV head repetition materialized).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attn(q, k, v, q_pos, k_pos, kv_len):
    """One blockwise attention piece: returns (unnorm_out, row_max, row_sum).

    q: [B, Tq, KH, rep, D] (pre-scaled); k/v: [B, Tk, KH, D];
    q_pos: [B, Tq]; k_pos: [B, Tk]; kv_len: [B] or None.
    """
    scores = jnp.einsum("btkrd,bskd->btkrs", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    visible = q_pos[:, :, None] >= k_pos[:, None, :]          # [B, Tq, Tk]
    if kv_len is not None:
        visible &= k_pos[:, None, :] < kv_len[:, None, None]
    visible = visible[:, :, None, None, :]
    scores = jnp.where(visible, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                               # [B,Tq,KH,rep]
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(visible, p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("btkrs,bskd->btkrd", p, v.astype(jnp.float32))
    return out, m, l


def ring_attention(
    q: jax.Array,      # [B, T_local, H, D] — this device's query chunk
    k: jax.Array,      # [B, T_local, KH, D]
    v: jax.Array,
    *,
    axis_name: str = "seq",
    kv_len: jax.Array | None = None,  # [B] global valid length (None = full)
) -> jax.Array:
    """Causal ring attention over ``axis_name``. Call inside shard_map/pjit
    with q/k/v sharded on the sequence dimension. Returns [B, T_local, H, D].
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    kh = k.shape[2]
    rep = h // kh
    qg = (q * (d ** -0.5)).reshape(b, t, kh, rep, d)
    my_pos = idx * t + jnp.arange(t)[None, :] + jnp.zeros((b, 1), jnp.int32)  # [B, T]

    acc0 = jnp.zeros((b, t, kh, rep, d), jnp.float32)
    m0 = jnp.full((b, t, kh, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, t, kh, rep), jnp.float32)

    def body(s, carry):
        acc, m, l, kc, vc = carry
        src = (idx - s) % n                     # whose chunk we hold this step
        k_pos = src * t + jnp.arange(t)[None, :] + jnp.zeros((b, 1), jnp.int32)
        out_c, m_c, l_c = _chunk_attn(qg, kc, vc, my_pos, k_pos, kv_len)
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        acc = acc * alpha[..., None] + out_c * beta[..., None]
        l = l * alpha + l_c * beta
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return acc, m_new, l, kc, vc

    acc, m, l, _, _ = lax.fori_loop(0, n, body, (acc0, m0, l0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows (padding)
    out = acc / l[..., None]
    return out.reshape(b, t, h, d).astype(q.dtype)


def ring_attention_prefill(
    mesh: Mesh,
    q: jax.Array,      # [B, T, H, D] — full fresh prompt chunk (q_start = 0)
    k: jax.Array,      # [B, T, KH, D]
    v: jax.Array,
    kv_len: jax.Array,  # [B] valid token count per row
) -> jax.Array:
    """Sequence-parallel prefill attention inside the serving step.

    For a *fresh* full-prompt chunk (q_start == 0) the attention context is
    exactly the chunk itself, so the paged cache never needs to be read:
    shard the T axis over "seq" and ring-rotate K/V chunks over ICI.
    Batch rides "data", heads ride "model" (both no-ops at size 1), so the
    same wrapper serves sp-only and sp×tp×dp meshes.

    Callers guard divisibility (T % sp, KH % tp, B % dp) and fall back to
    the dense path otherwise — see models/llama.forward.
    """
    spec = P("data", "seq", "model", None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, P("data")),
        out_specs=spec, check_vma=False,
    )
    def _fn(q, k, v, kv_len):
        return ring_attention(q, k, v, axis_name="seq", kv_len=kv_len)

    return _fn(q, k, v, kv_len)


def ring_attention_sharded(mesh: Mesh, *, axis_name: str = "seq") -> Callable:
    """Build a jitted global-view ring attention fn over ``mesh``.

    Returns fn(q, k, v, kv_len=None) taking GLOBAL arrays [B, T, H, D]
    sharded (or shardable) as P(None, axis_name, None, None); shard_map
    splits them into per-device chunks and runs ring_attention.
    """
    spec = P(None, axis_name, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, P(None)),
        out_specs=spec, check_vma=False,
    )
    def _fn(q, k, v, kv_len):
        return ring_attention(q, k, v, axis_name=axis_name, kv_len=kv_len)

    def call(q, k, v, kv_len=None):
        if kv_len is None:
            kv_len = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
        return _fn(q, k, v, kv_len)

    return jax.jit(call)
