"""Logical-axis sharding: rules, divisibility-aware resolution.

Every parameter / activation carries a tuple of *logical* axis names
(e.g. ("vocab", "embed")). A rule table maps logical names to ordered
candidate mesh-axis tuples; the resolver picks the first candidate whose
size divides the dimension and whose mesh axes are still unused in the
spec, else downgrades to replicated. All downgrades are recorded so the
dry-run can report exactly how each tensor ended up sharded.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


#: default rule table. Each logical axis maps to candidates in
#: preference order; () means replicated.
DEFAULT_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    "batch": (("pod", "data"), ("data",), ()),
    "seq": ((),),
    "embed": ((),),
    "qkv_features": (("model",), ()),
    "kv_features": (("model",), ()),
    "heads": (("model",), ()),
    "kv_heads": (("model",), ()),
    "head_dim": (("model",), ()),
    "mlp": (("model",), ()),
    "vocab": (("model",), ()),
    "experts": (("data",), ("model",), ()),
    "expert_mlp": (("model",), ()),
    "conv": ((),),
    "ssm_state": ((),),
    "ssm_heads": (("model",), ()),
    "layers": ((),),
    "kv_seq": ((),),
    # sequence-parallel candidates (enabled by perf configs)
    "seq_sp": (("data",), ()),
}


@dataclasses.dataclass
class ResolveReport:
    """Per-tensor record of the chosen spec and any downgrades."""
    chosen: dict[str, P] = dataclasses.field(default_factory=dict)
    downgrades: list[str] = dataclasses.field(default_factory=list)


def resolve_spec(shape: Sequence[int], axes: Sequence[str | None],
                 mesh: Mesh, rules: Mapping[str, tuple] | None = None,
                 name: str = "", report: ResolveReport | None = None) -> P:
    """Pick a PartitionSpec for ``shape`` with logical ``axes``."""
    rules = rules or DEFAULT_RULES
    mesh_axes = set(mesh.axis_names)
    used: set[str] = set()
    parts: list = []
    for dim, ax in zip(shape, axes):
        if ax is None:
            parts.append(None)
            continue
        candidates = rules.get(ax, ((),))
        placed = None
        for cand in candidates:
            cand = tuple(a for a in cand if a in mesh_axes)
            if not cand:
                placed = None
                break
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            if dim % size == 0 and not (set(cand) & used):
                placed = cand
                used |= set(cand)
                break
        else:
            placed = None
        if placed:
            parts.append(placed if len(placed) > 1 else placed[0])
        else:
            parts.append(None)
            if report is not None and rules.get(ax, ((),))[0]:
                first = tuple(a for a in rules.get(ax, ((),))[0]
                              if a in mesh_axes)
                if first:
                    report.downgrades.append(
                        f"{name}[{ax}]: {dim} not divisible/available for "
                        f"{first} -> replicated")
    spec = P(*parts)
    if report is not None:
        report.chosen[name] = spec
    return spec


def tree_shardings(spec_tree, mesh: Mesh, rules=None,
                   report: ResolveReport | None = None):
    """Map a tree of ParamSpec-likes (.shape/.axes) to NamedShardings."""
    flat, treedef = jax.tree.flatten_with_path(
        spec_tree, is_leaf=lambda x: hasattr(x, "axes"))
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        pspec = resolve_spec(leaf.shape, leaf.axes, mesh, rules, name, report)
        out.append(NamedSharding(mesh, pspec))
    return jax.tree.unflatten(treedef, out)
