"""Logical-axis sharding rules (MaxText-style) for the model substrate.

Every parameter and activation is annotated with *logical* axis names
("embed", "heads", "mlp", "experts", "batch", ...).  An :class:`AxisRules`
table maps logical names to mesh axes ("pod", "data", "model").  This is the
single knob the perf hillclimb turns: changing a rule re-shards the whole
model with no model-code edits.

Parallelism styles expressed through rules:
  DP    batch -> ("pod", "data")
  TP    heads / kv_heads / mlp / vocab / experts_mlp -> "model"
  EP    experts -> "model"  (MoE all-to-all over the model axis)
  FSDP  embed -> "data"     (params additionally sharded over the data axis,
                             all-gathered at use; ZeRO-3 style)
  SP    kv_seq -> "data"    (long-context decode: KV/state sharded over seq)
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    rules: tuple[tuple[str, Any], ...]

    def lookup(self, name: str | None):
        if name is None:
            return None
        for key, val in self.rules:
            if key == name:
                return val
        return None

    def override(self, **kwargs) -> "AxisRules":
        new = dict(self.rules)
        new.update(kwargs)
        return AxisRules(tuple(new.items()))

    def mesh_axes(self, logical_axes: tuple[str | None, ...]) -> P:
        used: list = []
        parts = []
        for name in logical_axes:
            ax = self.lookup(name)
            # A mesh axis may appear at most once in a PartitionSpec; later
            # logical axes that map to an already-used mesh axis stay
            # replicated (standard MaxText behaviour).
            if ax is None:
                parts.append(None)
                continue
            ax_t = ax if isinstance(ax, tuple) else (ax,)
            ax_t = tuple(a for a in ax_t if a not in used)
            if not ax_t:
                parts.append(None)
            elif len(ax_t) == 1:
                parts.append(ax_t[0])
                used.append(ax_t[0])
            else:
                parts.append(ax_t)
                used.extend(ax_t)
        return P(*parts)


# Baseline rules: DP over (pod, data), TP/EP over model.  This is the
# paper-faithful production default; FSDP_RULES adds ZeRO-3 param sharding
# (used by the large MoE configs and by the hillclimb).
DEFAULT_RULES = AxisRules((
    ("batch", ("pod", "data")),
    ("seq", None),
    ("kv_seq", None),
    ("embed", None),
    ("embed_out", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("mlp", "model"),
    ("vocab", "model"),
    ("experts", "model"),
    ("expert_mlp", None),
    ("ssm_inner", "model"),
    ("ssm_state", None),
    ("ssm_heads", "model"),
    ("conv_width", None),
    ("layers", None),
    ("act_embed", None),
    ("act_heads", "model"),
    ("q_rows", None),
))

FSDP_RULES = DEFAULT_RULES.override(
    embed="data",          # shard the non-TP dim of weight matrices over data
    expert_mlp="data",
)

# Long-context decode: KV cache / attention over sequence sharded on data.
SP_DECODE_RULES = DEFAULT_RULES.override(kv_seq="data")

# Pure data-parallel + ZeRO-3 (no tensor parallelism): the batch is sharded
# over every mesh axis and parameters are fully sharded for storage
# (all-gathered at use).  No per-layer activation all-reduces at all —
# the right regime for small dense models like olmo-1b (see §Perf).
PUREDP_RULES = AxisRules((
    ("batch", ("pod", "data", "model")),
    ("seq", None), ("kv_seq", None),
    ("embed", "data"),
    ("embed_out", None),
    ("heads", "model"), ("kv_heads", "model"), ("head_dim", None),
    ("mlp", "model"),
    ("vocab", "model"),
    ("experts", "model"), ("expert_mlp", "data"),
    ("ssm_inner", "model"), ("ssm_state", None), ("ssm_heads", "model"),
    ("conv_width", None), ("layers", None),
    ("act_embed", None), ("act_heads", None), ("q_rows", None),
))

# Query-row sharded attention: for archs whose head counts don't divide the
# model axis (musicgen 24H), shard each attention chunk's query rows instead
# of heads.  Params stay TP-sharded where divisible.
QROWS_RULES = DEFAULT_RULES.override(q_rows="model", act_heads=None)

# Sharded GP-EI scoring plane (repro.shardgp, DESIGN.md §10): control-plane
# state is logically (tenants, models) / (obs, models); only the model axis
# shards — tenants ride along replicated (N ~ 10^2-10^3 is small next to
# |L| ~ 10^5-10^6) and the observation axis of the W readout buffer stays
# local so the streamed readout needs no cross-shard reduction.
SCORING_RULES = AxisRules((
    ("models", "shard"),
    ("tenants", None),
    ("obs", None),
))


@dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape + dtype + logical axes (+ init scale)."""

    shape: tuple[int, ...]
    logical_axes: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init: str = "normal"     # normal | zeros | ones | scaled
    init_scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (
            f"shape {self.shape} vs axes {self.logical_axes}")


def logical_to_pspec(spec: ParamSpec | tuple[str | None, ...], rules: AxisRules) -> P:
    axes = spec.logical_axes if isinstance(spec, ParamSpec) else spec
    return rules.mesh_axes(axes)


def _sanitize_pspec(spec: P, shape: tuple[int, ...], mesh) -> P:
    """Drop partitions whose dim isn't divisible by the mapped mesh extent
    (e.g. MQA's single KV head on a 16-way model axis -> replicate instead
    of GSPMD padding), and axes absent from this mesh (e.g. "pod" on the
    single-pod mesh)."""
    sizes = dict(mesh.shape)
    parts = []
    for i, part in enumerate(spec):
        if part is None or i >= len(shape):
            parts.append(None)
            continue
        ax_t = part if isinstance(part, tuple) else (part,)
        ax_t = tuple(a for a in ax_t if a in sizes)
        extent = 1
        for a in ax_t:
            extent *= sizes[a]
        if not ax_t or extent == 0 or shape[i] % extent != 0:
            parts.append(None)
        elif len(ax_t) == 1:
            parts.append(ax_t[0])
        else:
            parts.append(ax_t)
    return P(*parts)


def logical_sharding(
    spec: ParamSpec | tuple[str | None, ...], mesh: Mesh, rules: AxisRules
) -> NamedSharding:
    pspec = logical_to_pspec(spec, rules)
    if isinstance(spec, ParamSpec):
        pspec = _sanitize_pspec(pspec, spec.shape, mesh)
    return NamedSharding(mesh, pspec)


def shardings_for_tree(tree, mesh: Mesh, rules: AxisRules):
    """Map a pytree of ParamSpec -> pytree of NamedSharding."""
    return jax.tree.map(
        lambda s: logical_sharding(s, mesh, rules),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def shape_dtype_for_tree(tree):
    """Map a pytree of ParamSpec -> pytree of ShapeDtypeStruct (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def with_logical_constraint(x, logical_axes: tuple[str | None, ...], rules: AxisRules | None):
    """Annotate an activation with a logical sharding constraint.

    No-op outside a mesh context or when rules is None, so model code runs
    unchanged in single-device tests.
    """
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = rules.mesh_axes(logical_axes)
    spec = _sanitize_pspec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)
