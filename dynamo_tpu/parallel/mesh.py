"""Device mesh + sharding layout for the JAX engine.

The reference passes TP/PP/EP sizes through to vLLM/SGLang (SURVEY.md §2.7);
here parallelism is first-party: a ``jax.sharding.Mesh`` with axes

    ("data", "pipe", "seq", "model", "expert")

- **model**: tensor parallel — attention heads and MLP intermediate sharded;
  collectives (psum in the down-projections) ride ICI.
- **expert**: expert parallel for MoE layers (experts split across devices,
  tokens routed via ragged all-to-all).
- **seq**: sequence/context parallel for long-context prefill (ring
  attention over the sequence axis — absent in the reference, greenfield
  here per SURVEY.md §2.7).
- **data**: replica axis inside one engine (dp>1 engines also exist at the
  framework level as separate workers, like the reference's DP).

Shardings are expressed as PartitionSpec rules over logical param axes, GSPMD
inserts the collectives (scaling-book recipe: mesh + annotations + let XLA
do the rest).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "pipe", "seq", "model", "expert")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep


def make_mesh(cfg: MeshConfig | None = None, devices: list | None = None) -> Mesh:
    """Build the engine mesh. With no config, all local devices go on "model"."""
    devices = devices if devices is not None else jax.devices()
    if cfg is None:
        cfg = MeshConfig(tp=len(devices))
    if cfg.size > len(devices):
        raise ValueError(f"mesh needs {cfg.size} devices, have {len(devices)}")
    dev = np.asarray(devices[: cfg.size]).reshape(
        cfg.dp, cfg.pp, cfg.sp, cfg.tp, cfg.ep)
    return Mesh(dev, AXES)


# Logical→mesh axis rules for model parameters. Keys are logical axis names
# used by the model code; values are mesh axes (None = replicate).
PARAM_RULES: dict[str, str | None] = {
    "vocab": "model",          # embedding/lm_head vocab-sharded
    "hidden": None,            # activations' hidden axis replicated in params
    "heads": "model",          # attention heads sharded (TP)
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",            # MLP intermediate sharded (TP)
    "expert": "expert",        # MoE experts sharded (EP)
    # Stacked layer dim sharded over pipeline stages (PP); a size-1 "pipe"
    # axis makes this a no-op on non-PP meshes.
    "layers": "pipe",
    "moe_mlp": "model",        # per-expert intermediate (TEP)
}


def param_sharding_rules(mesh: Mesh, logical_axes: tuple[str | None, ...]) -> NamedSharding:
    spec = P(*(PARAM_RULES.get(ax) if ax else None for ax in logical_axes))
    return NamedSharding(mesh, spec)


def kv_cache_spec() -> P:
    """KV cache [layers, blocks, block_size, kv_heads, head_dim]:
    layers PP-sharded (each pipeline stage holds its own layers' cache),
    heads TP-sharded."""
    return P("pipe", None, None, "model", None)


def kv_scale_spec() -> P:
    """Per-(layer, block, kv_head) dequant scales [layers, blocks, kv_heads]
    for the int8 KV cache — sharded exactly like the payload's corresponding
    axes so scale lookups stay local to the shard that owns the heads."""
    return P("pipe", None, "model")


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def global_put(x, sharding: NamedSharding):
    """Build a (possibly cross-process) global array from host data.

    ``jax.device_put`` to a non-fully-addressable sharding internally runs a
    ``process_allgather`` to verify every rank passed an equivalent sharding
    — a hidden COLLECTIVE, so ranks that reach it at different times (e.g.
    the multi-host leader sharding params while followers still await the
    hello frame) deadlock. ``make_array_from_callback`` assembles the global
    array purely from local shards, no rendezvous; callers guarantee every
    rank holds the same host value (deterministic init / identical
    checkpoint), which is the same contract device_put documents.
    """
    import jax

    if isinstance(x, jax.Array) and x.sharding == sharding:
        return x  # already placed (e.g. loader-sharded checkpoint leaves)
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        raise ValueError(
            "global_put cannot re-shard a multi-host array to a different "
            f"layout (have {x.sharding}, want {sharding}); produce the host "
            "value on every rank instead")
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def _is_axes(x) -> bool:
    """A leaf of a logical-axes tree: a tuple of axis names / None."""
    return isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)


def param_shardings(logical_axes, mesh: Mesh):
    """The NamedSharding of every parameter leaf, as a tree shaped like the
    params: ``out_shardings`` for an init that builds each shard in place."""
    return jax.tree.map(lambda axes: param_sharding_rules(mesh, axes),
                        logical_axes, is_leaf=_is_axes)


def shard_params(params, logical_axes, mesh: Mesh):
    """Place a params pytree on the mesh per its logical-axis annotations.

    ``logical_axes`` mirrors the params tree with tuples of logical axis
    names (models.llama.param_logical_axes). GSPMD then propagates these
    shardings through the jitted step and inserts the TP/EP collectives.
    """
    def place(leaf, axes):
        return global_put(leaf, param_sharding_rules(mesh, axes))

    return jax.tree.map(place, params, logical_axes, is_leaf=_is_axes)


def single_device_mesh() -> Mesh:
    return make_mesh(MeshConfig(), devices=jax.devices()[:1])
