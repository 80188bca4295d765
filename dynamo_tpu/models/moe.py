"""Expert-parallel MoE dispatch: dropless ragged groups + capacity variant.

The reference only passes wide-EP flags through to SGLang/vLLM
(SURVEY.md §2.7: TEP16/DEP16 recipes, e.g. recipes/deepseek-r1/sglang-wideep);
the expert math itself is ours.

Two formulations:

- :func:`moe_mlp_dropless` (the serving default, ``moe_impl="ep"``) — EXACT
  under any load: (token, choice) rows are sorted by expert id so each
  expert's tokens form one contiguous ragged group feeding one MXU matmul
  (``lax.ragged_dot`` — static shapes, no capacity, nothing dropped).
  EP sharding is an explicit ``shard_map`` over the "expert" axis with the
  batch staying on "data": each device computes the rows of ITS experts
  (non-local rows route through an appended all-zero "void" expert, so
  shapes stay static) and partial outputs ``psum`` over the axis. A
  serving engine cannot ship an output-changing dispatch — vLLM-class
  engines are dropless for the same reason.

- :func:`moe_mlp_ep` (``moe_impl="ep_capacity"``) — the classic
  Switch/GShard capacity-bounded dispatch/combine einsum formulation, kept
  for experimentation: with enough capacity it equals the dense reference;
  under pressure it drops over-capacity choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig

Params = dict


def _router_topk(xt: jax.Array, lp: Params, cfg: ModelConfig):
    """Top-k routing shared by both formulations: returns ([N,k] expert ids,
    [N,k] softmax weights) — identical math to the dense reference
    (models.llama.moe_mlp), so dispatch equivalence is purely about which
    chosen pairs get computed."""
    logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)   # [N, E]
    topv, topi = lax.top_k(logits, cfg.num_experts_per_tok)
    return topi, jax.nn.softmax(topv, axis=-1)


def _dropless_rows(xt, topi, weights, w_gate, w_up, w_down, e_lo, e_local):
    """Compute this device's expert rows. xt [N,H]; topi/weights [N,k];
    w_* [E_local(+0), H|M, M|H] local expert slabs. Returns [N, H] partial
    output (zero contribution for rows owned by other devices)."""
    n, h = xt.shape
    k = topi.shape[1]
    flat_e = topi.reshape(-1)                         # [Nk] token-major
    flat_t = jnp.repeat(jnp.arange(n), k)             # [Nk]
    local_e = flat_e - e_lo
    is_local = (local_e >= 0) & (local_e < e_local)
    # Sort rows by local expert; foreign rows collect in a trailing "void"
    # group whose weights are zero, keeping every shape static.
    key = jnp.where(is_local, local_e, e_local)
    perm = jnp.argsort(key, stable=True)
    xs = xt[flat_t[perm]]                             # [Nk, H]
    group_sizes = jnp.zeros((e_local + 1,), jnp.int32).at[key].add(1)

    void = jnp.zeros_like(w_gate[:1])
    wg = jnp.concatenate([w_gate, void], axis=0)
    wu = jnp.concatenate([w_up, void], axis=0)
    wd = jnp.concatenate([w_down, jnp.zeros_like(w_down[:1])], axis=0)

    gate = lax.ragged_dot(xs, wg, group_sizes)        # [Nk, M]
    up = lax.ragged_dot(xs, wu, group_sizes)
    act = jax.nn.silu(gate) * up
    out = lax.ragged_dot(act, wd, group_sizes)        # [Nk, H]

    contrib = out.astype(jnp.float32) * weights.reshape(-1)[perm][:, None]
    # Stays fp32: under EP sharding this is a PARTIAL sum — the caller must
    # psum across devices in fp32 and cast once, like the dense reference's
    # single fp32 accumulation (bf16 partials would compound per expert).
    return jnp.zeros((n, h), jnp.float32).at[flat_t[perm]].add(contrib)


def moe_mlp_dropless(x: jax.Array, lp: Params, cfg: ModelConfig,
                     mesh=None) -> jax.Array:
    """Dropless MoE FFN. x: [..., H] → [..., H] (token-major [N, H] in the
    step); exact vs the dense reference under ANY routing skew
    (tests/test_moe.py pressure tests)."""
    b, h = x.shape[0], x.shape[-1]
    e = cfg.num_experts
    ep = mesh.shape.get("expert", 1) if mesh is not None else 1

    shared = (
        (lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        if cfg.num_shared_experts else None
    )
    if ep <= 1 or e % ep != 0:
        xt = x.reshape(-1, h)
        topi, weights = _router_topk(xt, lp, cfg)
        y = _dropless_rows(xt, topi, weights, lp["w_gate"], lp["w_up"],
                           lp["w_down"], 0, e)
        if shared is not None:
            from dynamo_tpu.models.llama import swiglu

            y = y + swiglu(xt, *shared).astype(jnp.float32)
        return y.astype(x.dtype).reshape(x.shape)

    e_local = e // ep

    def shard_fn(x3, router, wg, wu, wd, *shared_w):
        # Each device owns (its expert slab) x (its slice of the expert
        # intermediate dim, on TEP meshes where "model" also shards M).
        # gate/up slice M locally (silu is columnwise-exact); w_down
        # contracts the local M slice, so y is a partial sum over BOTH
        # axes — one fp32 psum completes expert combine and TEP contraction.
        e_lo = lax.axis_index("expert") * e_local
        xt = x3.reshape(-1, h)
        topi, weights = _router_topk(xt, {"router": router}, cfg)
        y = _dropless_rows(xt, topi, weights, wg, wu, wd, e_lo, e_local)
        if shared_w:
            from dynamo_tpu.models.llama import swiglu

            # Shared-expert slabs are "model"-sharded the same way; their
            # partial rides the same psum, and the expert-axis replication
            # is cancelled by pre-dividing.
            sh = swiglu(xt, *shared_w).astype(jnp.float32)
            y = y + sh / ep
        y = lax.psum(y, ("expert", "model"))
        return y.astype(x3.dtype).reshape(x3.shape)

    # The leading axis (tokens, or a rectangle's batch) rides the "data"
    # axis when it divides; odd buckets (e.g. the B=1 prefill bucket on a
    # dp>1 mesh) fall back to replicated.
    batch_spec = P("data") if b % mesh.shape.get("data", 1) == 0 else P()
    args = [x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"]]
    # Weight specs mirror PARAM_RULES (parallel/mesh.py): experts on
    # "expert", the per-expert intermediate on "model" (TEP) — declaring
    # them this way means NO resharding of the slabs at the shard_map
    # boundary. The router needs full columns for top_k, so it alone
    # gathers (tiny: [H, E]).
    in_specs = [batch_spec, P(),
                P("expert", None, "model"), P("expert", None, "model"),
                P("expert", "model", None)]
    if shared is not None:
        args.extend(shared)
        in_specs.extend([P(None, "model"), P(None, "model"), P("model", None)])
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(in_specs), out_specs=batch_spec,
        check_vma=False,
    )
    return fn(*args)


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots, padded to a lane-friendly multiple of 8."""
    cap = int(num_tokens * top_k / num_experts * capacity_factor) + 1
    return max(-(-cap // 8) * 8, 8)


def moe_mlp_ep(x: jax.Array, lp: Params, cfg: ModelConfig,
               capacity_factor: float = 2.0) -> jax.Array:
    """Capacity-based EP MoE FFN. x: [..., H] → [..., H].

    The dispatch/combine tensors route each token's top-k expert choices to
    per-expert buffers of C slots; choice order is priority order (a token's
    1st choice wins slots over another token's 2nd choice at equal index by
    flattened position).
    """
    h = x.shape[-1]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(-1, h)
    n = xt.shape[0]
    topi, weights = _router_topk(xt, lp, cfg)                            # [N, k]

    cap = expert_capacity(n, e, k, capacity_factor)
    # Position of each (choice, token) within its expert's buffer. Flatten
    # choice-major so every token's 1st choice outranks all 2nd choices.
    oh = jax.nn.one_hot(topi.T.reshape(k * n), e, dtype=jnp.int32)       # [kN, E]
    pos = jnp.cumsum(oh, axis=0) * oh - 1                                # [kN, E]
    pos_in_e = jnp.max(pos, axis=1)                                      # [kN]
    keep = (pos_in_e >= 0) & (pos_in_e < cap)
    pos_in_e = jnp.where(keep, pos_in_e, 0)

    # Back to [N, k] layout.
    keep = keep.reshape(k, n).T
    pos_nk = pos_in_e.reshape(k, n).T                                    # [N, k]

    # dispatch[n, e, c] = 1 where token n's choice lands in slot c of expert e
    slot_oh = jax.nn.one_hot(pos_nk, cap, dtype=jnp.float32)             # [N, k, C]
    exp_oh = jax.nn.one_hot(topi, e, dtype=jnp.float32)                  # [N, k, E]
    keep_f = keep.astype(jnp.float32)[..., None]
    dispatch = jnp.einsum("nke,nkc->nec", exp_oh, slot_oh * keep_f)      # [N, E, C]
    combine = jnp.einsum("nke,nkc->nec", exp_oh * (weights * keep)[..., None],
                         slot_oh)                                        # [N, E, C]

    # Expert buffers [E, C, H]: sharded on "expert" with the weights; GSPMD
    # turns the N↔(E,C) einsums into token all-to-alls over ICI.
    expert_in = jnp.einsum("nec,nh->ech", dispatch, xt.astype(jnp.float32))
    expert_in = expert_in.astype(x.dtype)
    gate = jnp.einsum("ech,ehm->ecm", expert_in, lp["w_gate"])
    up = jnp.einsum("ech,ehm->ecm", expert_in, lp["w_up"])
    act = jax.nn.silu(gate) * up
    out_e = jnp.einsum("ecm,emh->ech", act, lp["w_down"])                # [E, C, H]
    y = jnp.einsum("nec,ech->nh", combine, out_e.astype(jnp.float32)).astype(x.dtype)

    if cfg.num_shared_experts:
        from dynamo_tpu.models.llama import swiglu

        y = y + swiglu(xt, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return y.reshape(x.shape)
