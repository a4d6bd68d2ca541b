"""Logical-axis sharding: map named tensor axes onto mesh axes.

Every parameter / activation in the framework is annotated with a tuple of
*logical* axis names (one per dimension, ``None`` for replicated dims).  A
rule table maps logical names onto mesh axis names (or tuples of them).  This
is the MaxText/T5X pattern: the model definition never mentions the mesh, so
the same model lowers onto 1-device CPU, a 16x16 single pod, or a 2x16x16
multi-pod mesh purely by swapping the rule table.

Design notes for scale (1000+ nodes):
  * FSDP ("zero-3") is expressed by mapping the ``embed`` logical axis of
    weight matrices onto the ``data`` mesh axis; XLA SPMD then emits
    all-gather on use / reduce-scatter on grad, which the latency-hiding
    scheduler overlaps with layer compute when the layer stack is scanned.
  * Tensor parallelism maps ``mlp`` / ``heads`` / ``vocab`` / ``expert`` onto
    ``model``.
  * The slow cross-pod axis ``pod`` only ever carries batch (pure DP) by
    default, so the only cross-pod collective is the gradient all-reduce.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping from logical axis names to mesh axes.

    ``rules`` maps a logical name to a mesh axis name, a tuple of mesh axis
    names (the dim is sharded over their product), or None (replicated).
    Mesh axes that do not exist on the actual mesh are silently dropped so a
    single rule table serves single-pod and multi-pod meshes.
    """

    rules: Mapping[str, MeshAxes]

    def lookup(self, name: Optional[str], mesh_axis_names: Sequence[str]) -> MeshAxes:
        if name is None:
            return None
        axes = self.rules.get(name, None)
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if a in mesh_axis_names)
        if not present:
            return None
        if len(present) == 1:
            return present[0]
        return present

    def with_overrides(self, **overrides: MeshAxes) -> "AxisRules":
        merged = dict(self.rules)
        merged.update(overrides)
        return AxisRules(merged)


# The default production rule table.  ``batch`` spans the cross-pod axis and
# the data axis (pure DP over pods, DP+FSDP within a pod); weight ``embed``
# dims are FSDP-sharded over ``data``; model-parallel structures go to
# ``model``.
DEFAULT_RULES = AxisRules(
    {
        # activations
        "batch": ("pod", "data"),
        "seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_kv_heads": "model",
        "act_expert": "model",
        # weights
        "embed": "data",          # FSDP axis
        "embed_tp": "model",      # used when a weight's embed dim is the TP-reduced dim
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        "qkv_dim": None,
        "head_dim": None,
        "vocab": "model",
        "expert": "model",
        "expert_mlp": None,
        "state": None,
        "conv_in": None,
        "conv_out": "model",
        "caps_in": "data",
        "caps_out": "model",
        "caps_dim": None,
        "layers": None,           # scan-stacked layer axis: never sharded
        # SSM / xLSTM
        "mamba_inner": "model",
        "mamba_conv": "model",
        "mlstm_up": "model",
        "mlstm_inner": "model",
        "slstm_gates": None,
        "head_dim_v": None,       # xlstm TP axis (see rules_for_arch)
        # KV cache: batch claims data first; when batch can't shard (B=1
        # long-context) kv_seq claims data; when kv_heads can't shard
        # (GQA kv < model axis) kv_head_dim claims model.
        "kv_seq": "data",
        "kv_head_dim": "model",
    }
)

# CPU / single-device rules: everything replicated.
REPLICATED_RULES = AxisRules({})


# Small models must not be tensor-parallelised 256 ways: the per-layer
# activation all-reduce (B_loc x S x d) dwarfs the per-chip matmul work
# when d_model is small (§Perf H-A1: xlstm train collective 23.4s vs
# compute 0.93s at TP=16).  Policy: d_model <= 2048 -> pure DP + FSDP
# (batch additionally claims the model axis; weights FSDP over data);
# MoE keeps expert->model (EP without TP).
_NO_TP_OVERRIDES = dict(
    batch=("pod", "data", "model"),
    mlp=None, heads=None, kv_heads=None, vocab=None,
    act_heads=None, act_mlp=None, act_kv_heads=None,
    mamba_inner=None, mamba_conv=None,
    mlstm_up=None, mlstm_inner=None, head_dim_v=None,
    conv_out=None,
    kv_head_dim=None,
)

_NO_TP_ARCHS = ("xlstm-1.3b", "zamba2-1.2b", "qwen3-1.7b", "llama3.2-1b",
                "deepseek-moe-16b", "hubert-xlarge")


def rules_for_arch(arch_id: str, base: AxisRules = DEFAULT_RULES,
                   kind: str = "train") -> AxisRules:
    """Per-architecture, per-step-kind overrides of the default rule table.

    ``kind="decode"`` keeps the default TP/EP rules for every arch: decode
    wants weights STATIONARY (FSDP would all-gather the full model per
    generated token — §Perf iteration C2 refutation: deepseek decode
    memory term 0.38 s -> 6.1 s under no-TP/FSDP rules).
    """
    if kind != "decode" and arch_id in _NO_TP_ARCHS:
        rules = base.with_overrides(**_NO_TP_OVERRIDES)
        if arch_id == "deepseek-moe-16b":
            # EP stays: experts across model; dispatch/combine collectives
            # are the only model-axis traffic.
            rules = rules.with_overrides(expert="model",
                                         act_expert="model")
        return rules
    if arch_id.startswith("capsnet"):
        # CapsNet is small; shard input capsules over data, output capsules
        # over model (the routing contraction reduces over caps_in).
        return base
    return base


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: AxisRules,
    mesh_axis_names: Sequence[str],
) -> P:
    """Turn a tuple of logical axis names into a PartitionSpec.

    Guarantees each mesh axis is used at most once (first logical dim wins),
    which is a PartitionSpec validity requirement.
    """
    used: set = set()
    out = []
    for name in logical_axes:
        axes = rules.lookup(name, mesh_axis_names)
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        tup = tuple(a for a in tup if a not in used)
        if not tup:
            out.append(None)
            continue
        used.update(tup)
        out.append(tup[0] if len(tup) == 1 else tup)
    # strip trailing Nones (canonical form)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_tree_to_shardings(
    spec_tree: Any,
    mesh: Mesh,
    rules: AxisRules,
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    names = mesh.axis_names

    def _one(axes):
        if isinstance(axes, P):
            return NamedSharding(mesh, axes)
        return NamedSharding(mesh, logical_to_spec(axes, rules, names))

    return jax.tree.map(
        _one, spec_tree, is_leaf=lambda x: isinstance(x, (tuple, P)) or x is None
    )


def spec_tree_to_pspecs(spec_tree: Any, rules: AxisRules, mesh_axis_names) -> Any:
    """Same as above but returns raw PartitionSpecs (for in_shardings args)."""

    def _one(axes):
        if isinstance(axes, P):
            return axes
        return logical_to_spec(axes, rules, mesh_axis_names)

    return jax.tree.map(
        _one, spec_tree, is_leaf=lambda x: isinstance(x, (tuple, P)) or x is None
    )


def shape_aware_spec(
    logical_axes: Sequence[Optional[str]],
    shape: Sequence[int],
    rules: AxisRules,
    mesh_shape: Mapping[str, int],
) -> P:
    """Single-pass shape-aware spec builder (the production policy):

    For each dim, the rule-mapped mesh axes are kept only if (a) not already
    claimed by an earlier dim of this tensor and (b) the dim size is
    divisible by the axes' product.  An axis freed by (b) on one dim remains
    claimable by a later dim — e.g. a decode KV cache (L, B=1, T, K=8, D)
    on (data=16, model=16): batch(1) frees ``data`` which ``kv_seq`` then
    claims, kv_heads(8) frees ``model`` which ``kv_head_dim`` claims."""
    names = list(mesh_shape.keys())
    used: set = set()
    out = []
    for d, name in enumerate(tuple(logical_axes)):
        axes = rules.lookup(name, names)
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        tup = tuple(a for a in tup if a not in used)
        # longest prefix whose product divides the dim
        while tup:
            total = 1
            for a in tup:
                total *= mesh_shape[a]
            if total > 0 and shape[d] % total == 0:
                break
            tup = tup[:-1]
        if not tup:
            out.append(None)
            continue
        used.update(tup)
        out.append(tup[0] if len(tup) == 1 else tup)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_constraint(x, logical_axes, rules: AxisRules):
    """with_sharding_constraint by logical axes; no-op when no mesh is set.

    Uses the shape-aware single-pass policy (indivisible dims replicate)."""
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh is None or not env_mesh.axis_names:
        return x
    spec = shape_aware_spec(logical_axes, x.shape, rules,
                            dict(env_mesh.shape))
    return jax.lax.with_sharding_constraint(x, spec)


def shardings_for(structs: Any, axes_tree: Any, rules: AxisRules, mesh: Mesh
                  ) -> Any:
    """NamedShardings for a tree of ShapeDtypeStructs/arrays, with the
    shape-aware single-pass policy (the production entry point)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _one(struct, axes):
        if axes is None:
            return NamedSharding(mesh, P())
        if isinstance(axes, P):
            return NamedSharding(mesh, axes)
        spec = shape_aware_spec(axes, struct.shape, rules, mesh_shape)
        return NamedSharding(mesh, spec)

    s_leaves, treedef = jax.tree.flatten(structs)
    a_leaves = treedef.flatten_up_to(_mark_none(axes_tree))
    a_leaves = [None if isinstance(a, _NoneAxes) else a for a in a_leaves]
    return jax.tree.unflatten(
        treedef, [_one(s, a) for s, a in zip(s_leaves, a_leaves)])


class _NoneAxes:
    pass


_NONE_AXES = _NoneAxes()


def _mark_none(tree: Any) -> Any:
    """Replace None leaves with a sentinel so tree structures line up."""
    def walk(x):
        if x is None:
            return _NONE_AXES
        if isinstance(x, (tuple, P)):
            return x
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        if isinstance(x, list):
            return [walk(v) for v in x]
        return x
    return walk(tree)


def replicated_shardings(tree: Any, mesh: Mesh) -> Any:
    """Fully-replicated ``NamedSharding`` for every leaf of ``tree``.

    The placement for state that *crosses* engines instead of living on
    one — e.g. the per-request cache rows of a serving handoff: the rows
    are replicated onto the target mesh so the subsequent scatter into
    the (possibly slot-sharded) resident state reads device-locally on
    every shard, whatever slot the scheduler picked."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: sharding, tree)


def divisible_or_none(dim: int, axes: MeshAxes, mesh: Mesh) -> bool:
    """Check shardability of ``dim`` over ``axes`` of ``mesh``."""
    if axes is None:
        return True
    tup = (axes,) if isinstance(axes, str) else axes
    total = 1
    for a in tup:
        total *= mesh.shape[a]
    return dim % total == 0


def disjoint_submeshes(n: int, axis_name: str = "data",
                       devices: Optional[Sequence[Any]] = None
                       ) -> Tuple[Mesh, ...]:
    """``n`` single-axis meshes over disjoint device groups.

    The multi-host emulation primitive for disaggregated serving: give
    the prefill engine and each decode engine its *own* mesh so cache
    handoffs must genuinely cross device boundaries (and a
    device-to-device transport has real work to do).  With ``d`` devices
    each submesh gets ``d // n`` of them (any remainder stays unused so
    the groups stay equal-sized).  When the host has fewer devices than
    requested groups the meshes degrade to 1-device each and *reuse*
    devices round-robin — distinct Mesh objects, degenerate placement —
    so single-device CI still exercises every code path.
    """
    if n <= 0:
        raise ValueError(f"need a positive submesh count, got {n}")
    devs = list(devices) if devices is not None else list(jax.devices())
    if not devs:
        raise ValueError("no devices to build submeshes from")
    per = max(len(devs) // n, 1)
    groups = [[devs[(i * per + j) % len(devs)] for j in range(per)]
              for i in range(n)]
    return tuple(Mesh(np.array(g, dtype=object), (axis_name,))
                 for g in groups)
