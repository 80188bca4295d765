"""Expert-parallel MoE dispatch: dropless ragged groups.

The reference only passes wide-EP flags through to SGLang/vLLM
(SURVEY.md §2.7: TEP16/DEP16 recipes, e.g. recipes/deepseek-r1/sglang-wideep);
the expert math itself is ours.

One router (:func:`route`) and one grouped core (:func:`held_rows`: the
(token, choice) rows of the experts held here, sorted by expert so that each
expert's tokens form one contiguous ragged group of one ``lax.ragged_dot``;
static shapes, no capacity, nothing dropped; rows of experts held elsewhere
belong to no group; in a decode-sized program on a TPU whose experts fit
VMEM, :func:`streams_experts`, the same result by one kernel that streams
each touched expert's matrices once, ops/moe_stream.py) under two layers
(the plain form both are held to is ``llama.moe_mlp``: every expert for
every token):

- :func:`moe_mlp_held` (``moe_impl="held"``): one chip told which experts
  it holds of a wider router, as one chip's share of an expert-parallel
  deployment: no exchange, and counts of what it computed.

- :func:`moe_mlp_dropless` (the serving default for ep > 1,
  ``moe_impl="ep"``) — EXACT under any load. EP sharding is an explicit
  ``shard_map`` over the "expert" axis with the batch staying on "data":
  each device computes the rows of ITS experts and partial outputs ``psum``
  over the axis. A serving engine cannot ship an output-changing dispatch —
  vLLM-class engines are dropless for the same reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig

Params = dict


def gate_act(cfg: ModelConfig):
    """The routed experts' activation, ``cfg.expert_act``: every
    formulation computes ``act(x W_gate) * (x W_up)`` with this one, or,
    for an expert without a gate (``cfg.expert_gated`` false: two matrices),
    ``act(x W_up)``."""
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[cfg.expert_act]


def ungated_ffn(x, w_up, w_down, act):
    """One dense expert without a gate (a shared one): ``down(act(up(x)))``;
    the gated one is ``llama.swiglu``."""
    from dynamo_tpu.models.llama import mm

    return mm(act(mm(x, w_up)), w_down)


def route(xt: jax.Array, lp: Params, cfg: ModelConfig):
    """The router, one function for every formulation and both scorings:
    ([N,k] expert ids over the router's whole width, [N,k] float32 weights).
    ``xt`` [N, H] is the state the router reads, which need not be the
    state the experts read: under ``cfg.router_input == "attn_norm"`` the
    layer calls this on the attention's input and hands the result past
    attention to the expert layer (models/llama.py ``_run_layers``).

    "softmax" (Mixtral): the k largest logits, weighted by the softmax over
    those k alone. "sigmoid" (DeepSeek-V3's, which K-EXAONE's keys name):
    scores ``sigmoid(logits)``; the k largest of ``score + bias`` are chosen
    (``router_bias``: it steers the choice and weighs nothing); the chosen
    scores, normalised to sum 1 under ``norm_topk_prob``, times
    ``routed_scaling_factor``. The router is as wide as the published model
    (``cfg.router_width``) also where this chip holds a share of the
    experts: which experts a token goes to does not depend on who holds
    them."""
    logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)   # [N, E]
    k = cfg.num_experts_per_tok
    if cfg.router_scoring != "sigmoid":
        topv, topi = lax.top_k(logits, k)
        return topi, jax.nn.softmax(topv, axis=-1)
    scores = jax.nn.sigmoid(logits)
    # (a biased model without the leaf is a KeyError, not a choice by score)
    biased = scores + lp["router_bias"].astype(jnp.float32) \
        if cfg.router_bias else scores
    _, topi = lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return topi, weights * cfg.routed_scaling_factor


# The most rows a program may hold for its experts to be streamed
# (ops/moe_stream.py computes every row against every touched expert):
# an expert's three products are N x 6HM FLOP against 6HM bytes of bf16
# weights, N FLOP a byte whatever H and M, and the chip's two peaks
# (obs/costmodel.py HW_SPECS, v5e: 197 TFLOP/s over 819 GB/s) meet at 240.
# The MXU takes a weight tile's 128 rows in the time of one, so up to 128
# rows an expert computes in about half the time it takes to read; 64
# leaves that room, covers the decode ladder, and leaves a chunk program's
# hundreds of rows, dozens to each of all the experts, to the grouped form.
STREAM_MAX_ROWS = 64


def streams_experts(n: int, h: int, m: int, itemsize: int,
                    mesh=None, matrices: int = 3) -> bool:
    """Whether a program of ``n`` tokens computes its held experts
    (``[h, m]``, ``[h, m]`` and ``[m, h]`` of ``itemsize`` bytes each, or,
    with ``matrices`` 2, an expert without a gate's ``[h, m]`` and
    ``[m, h]``) by
    the streaming kernel and not by groups: the program is decode-sized
    (``STREAM_MAX_ROWS``), one expert's matrices fit twice within the most
    VMEM the kernel asks for (``moe_stream.VMEM_MAX_BYTES``; the pipeline
    holds the next expert beside the current), and the
    backend is a TPU (the kernel is Mosaic's; on the CPU the tests run it
    interpreted and every engine keeps the grouped form) on which the
    program is one chip's: under a ``mesh`` of several the compiler
    partitions the grouped form over the matrices' shards and has no rule
    for a kernel (inside a ``shard_map`` the slab is local and there is no
    mesh to hand in). Shapes alone: no option, no model's name.
    :func:`held_rows` asks it while a program is traced, the engine for
    each program it records (``moe_streamed_layer_steps_total``)."""
    from dynamo_tpu.ops import moe_stream

    return (n <= STREAM_MAX_ROWS
            and moe_stream.vmem_ask(n, h, m, itemsize, matrices)
            <= moe_stream.VMEM_MAX_BYTES
            and jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1))


def held_rows(xt, topi, weights, w_gate, w_up, w_down, live=None,
              layer=None, act=jax.nn.silu, mesh=None):
    """The grouped formulation for the experts held here, the first
    ``E_held`` of the router's (a shard that holds others hands in ``topi``
    less its first expert's index: what falls outside ``0 .. E_held - 1`` is
    held elsewhere): the (token, choice) rows routed to them,
    sorted by expert so that each expert's rows are one ragged group of one
    grouped matmul (``lax.ragged_dot``: on a TPU a native grouped matmul
    that reads the weights of the groups that have rows). Rows routed to
    experts held elsewhere sort behind the groups and belong to none:
    nothing is computed for them and nothing stands in for the chips that
    hold them. ``live`` [N] bool drops the rows of a bucket's padding tokens
    the same way. ``act`` is the gate's activation (:func:`gate_act`).

    ``w_*`` are one layer's slabs ``[E_held, H|M, M|H]`` or, with ``layer``
    (an index, may be traced), the whole stack ``[L, E_held, ...]`` as the
    parameters hold it: the stack is then the grouped matmul's operand as
    it lies (``[L * E_held, ...]``, a free reshape) and the other layers'
    groups are empty. A grouped matmul is a custom call, and a slab cut out
    of the stack for it would be copied, every step.

    Where :func:`streams_experts` says so (``mesh``: the one that
    partitions the program, if any), the same results come from
    ops/moe_stream.py ``stream_rows``: no sort, gather or scatter, every
    row against each touched expert, whose matrices are read once.

    Returns ([N, H] float32: the held experts' part of the layer's result,
    int32 [3]: rows computed, experts touched, rows of the largest group)."""
    n, h = xt.shape
    k = topi.shape[1]
    if streams_experts(n, h, w_up.shape[-1], w_up.dtype.itemsize, mesh,
                       2 if w_gate is None else 3):
        from dynamo_tpu.ops.moe_stream import stream_rows

        return stream_rows(xt, topi, weights, w_gate, w_up, w_down, live,
                           layer, act)
    first = 0
    if layer is not None:
        n_layers, held = w_up.shape[:2]
        w_gate, w_up, w_down = (
            w if w is None else w.reshape(n_layers * held, *w.shape[2:])
            for w in (w_gate, w_up, w_down))
        first = layer * held
    else:
        held = w_up.shape[0]
    groups = w_up.shape[0]
    flat_e = topi.reshape(-1)                         # [Nk] token-major
    flat_t = jnp.repeat(jnp.arange(n), k)             # [Nk]
    here = (flat_e >= 0) & (flat_e < held)
    if live is not None:
        here = here & jnp.repeat(live, k)
    key = jnp.where(here, flat_e, held)
    perm = jnp.argsort(key, stable=True)
    rows = flat_t[perm]
    # (an index past the last group is dropped: a row of no group)
    group_sizes = jnp.zeros((groups,), jnp.int32).at[
        jnp.where(here, first + flat_e, groups)].add(1, mode="drop")
    xs = xt[rows]                                     # [Nk, H]
    if w_gate is None:
        hidden = act(lax.ragged_dot(xs, w_up, group_sizes))   # [Nk, M]
    else:
        gate = lax.ragged_dot(xs, w_gate, group_sizes)    # [Nk, M]
        up = lax.ragged_dot(xs, w_up, group_sizes)
        hidden = act(gate) * up
    out = lax.ragged_dot(hidden, w_down, group_sizes)
    # What a row behind the last group holds is not defined: select, do
    # not multiply.
    contrib = jnp.where(here[perm][:, None],
                        out.astype(jnp.float32)
                        * weights.reshape(-1)[perm][:, None], 0.0)
    y = jnp.zeros((n, h), jnp.float32).at[rows].add(contrib)
    counts = jnp.stack([jnp.sum(group_sizes),
                        jnp.sum((group_sizes > 0).astype(jnp.int32)),
                        jnp.max(group_sizes)])
    return y, counts


def moe_mlp_held(x: jax.Array, lp: Params, cfg: ModelConfig, live=None,
                 routing=None, mesh=None):
    """The routed FFN of one chip, which is told which experts it holds
    (``lp["w_gate"]`` is ``[E_held, H, M]``: the first ``E_held`` of the
    ``cfg.router_width`` the router scores; or, with ``lp["expert_layer"]``,
    the layers' whole stack and this layer's place in it): route over all
    of them, compute the held experts' rows (:func:`held_rows`) and add the
    shared expert once. With every expert held (``E_held`` is the router's
    width) it is the whole layer. ``routing``: the ``(topi, weights)`` of a
    router that read another state, earlier (:func:`route`); None routes
    from ``x``. ``mesh``: the step's, if it has one (:func:`held_rows`).
    x [N, H] -> ([N, H], int32 [3] counts). One chip: no exchange."""
    from dynamo_tpu.models.llama import swiglu
    from dynamo_tpu.obs.profiler import phase

    xt = x.reshape(-1, x.shape[-1])
    if routing is None:
        with phase("moe_route"):
            routing = route(xt, lp, cfg)
    topi, weights = routing
    with phase("moe_experts"):
        y, counts = held_rows(xt, topi, weights, lp.get("w_gate"), lp["w_up"],
                              lp["w_down"], live, lp.get("expert_layer"),
                              gate_act(cfg), mesh)
    if cfg.num_shared_experts:
        with phase("moe_shared"):
            if cfg.expert_gated:
                shared = swiglu(xt, lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"])
            else:
                shared = ungated_ffn(xt, lp["shared_up"], lp["shared_down"],
                                     gate_act(cfg))
            y = y + shared.astype(jnp.float32)
    return y.astype(x.dtype).reshape(x.shape), counts


def moe_mlp_dropless(x: jax.Array, lp: Params, cfg: ModelConfig,
                     mesh=None, routing=None) -> jax.Array:
    """Dropless MoE FFN. x: [..., H] → [..., H] (token-major [N, H] in the
    step); exact vs the dense reference under ANY routing skew
    (tests/test_moe.py pressure tests). ``routing``: as
    :func:`moe_mlp_held`'s, [N, k] each, sharded with the tokens."""
    b, h = x.shape[0], x.shape[-1]
    act = gate_act(cfg)
    e = cfg.num_experts
    ep = mesh.shape.get("expert", 1) if mesh is not None else 1

    shared = (
        (lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        if cfg.num_shared_experts else None
    )
    if ep <= 1 or e % ep != 0:
        xt = x.reshape(-1, h)
        topi, weights = routing or route(xt, lp, cfg)
        y, _ = held_rows(xt, topi, weights, lp["w_gate"], lp["w_up"],
                         lp["w_down"], act=act, mesh=mesh)
        if shared is not None:
            from dynamo_tpu.models.llama import swiglu

            y = y + swiglu(xt, *shared).astype(jnp.float32)
        return y.astype(x.dtype).reshape(x.shape)

    e_local = e // ep

    def shard_fn(x3, router, wg, wu, wd, *rest):
        rlp = {"router": router}
        if cfg.router_bias:
            rlp["router_bias"], *rest = rest
        routed = None
        if routing is not None:
            routed, rest = rest[:2], rest[2:]
        shared_w = tuple(rest)
        # Each device owns (its expert slab) x (its slice of the expert
        # intermediate dim, on TEP meshes where "model" also shards M).
        # gate/up slice M locally (the activation is columnwise); w_down
        # contracts the local M slice, so y is a partial sum over BOTH
        # axes — one fp32 psum completes expert combine and TEP contraction.
        e_lo = lax.axis_index("expert") * e_local
        xt = x3.reshape(-1, h)
        topi, weights = routed or route(xt, rlp, cfg)
        # Partial over the axis and float32: the psum below sums the
        # shards in float32 and the cast is made once.
        y, _ = held_rows(xt, topi - e_lo, weights, wg, wu, wd, act=act)
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
    if cfg.router_bias:
        # like the router it steers, whole on every device ([E])
        args.append(lp["router_bias"])
        in_specs.append(P())
    if routing is not None:
        args.extend(routing)
        in_specs.extend([batch_spec, batch_spec])
    if shared is not None:
        args.extend(shared)
        in_specs.extend([P(None, "model"), P(None, "model"), P("model", None)])
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(in_specs), out_specs=batch_spec,
        check_vma=False,
    )
    return fn(*args)
