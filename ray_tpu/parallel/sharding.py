"""Logical-axis sharding rules: the TP/FSDP engine.

The reference has no tensor/FSDP parallelism of its own (SURVEY.md §2.4 —
Train only wraps Torch-DDP, `train/torch/config.py:102-113`); here sharding is
a first-class framework service.  Model code annotates every parameter with
*logical* axis names (("embed", "mlp"), ("heads", "kv"), …) and a
`ShardingRules` table maps logical names → mesh axes.  Swapping DP for FSDP
for 2D FSDP×TP is a rules change, not a model change — the idiomatic
pjit/GSPMD recipe from the scaling playbook.

What the rules leave WHOLE: a parameter that is a vector (`pytree_shardings`:
a leaf whose logical axes, a leading "layers" apart, name ONE dimension: a
norm's scale and bias, ``("layers", "embed")`` / ``("embed",)``, a sink, a
router's bias) is replicated whatever its axis maps to.  Cutting 1600 floats
four ways saves 4.8 KB a chip, and the partitioner then has to gather the
vector at every use, forward and recomputed, in every layer of every
micro-batch, each a blocking collective inside the layer loop (two of them
stood 47 ms in gpt2-xl's 1115 ms step under fsdp=4 for under 1 MB of
payload; most of that was the wait for the slowest chip, which the next
collective inherited when they went: PERF.md, PR 58).  Matrices
(``("embed", None)``, ``("vocab", "embed")``, ...) are cut as the table says;
the stacking axis keeps what the rules give it (``layers="pp"``).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

MeshAxis = Union[str, Tuple[str, ...], None]


class ShardingRules(dict):
    """logical axis name → mesh axis (str), tuple of mesh axes, or None."""

    def spec_for(self, logical_axes: Optional[Sequence[str]]) -> P:
        if logical_axes is None:
            return P()
        return P(*(self.get(a) for a in logical_axes))

    def with_overrides(self, **overrides: MeshAxis) -> "ShardingRules":
        new = ShardingRules(self)
        new.update(overrides)
        return new


# Canonical rule tables for transformer-family models.  Logical names follow
# the T5X/flax convention: batch, seq, embed, mlp, heads, kv, vocab, expert,
# stage (pipeline), plus kv_seq for attention ring buffers.
DP_RULES = ShardingRules(
    batch=("dp", "fsdp"), seq=None, embed=None, mlp=None, heads=None,
    kv=None, vocab=None, expert=None, stage=None, kv_seq=None)

FSDP_RULES = ShardingRules(
    batch=("dp", "fsdp"), seq=None, embed="fsdp", mlp=None, heads=None,
    kv=None, vocab=None, expert=None, stage=None, kv_seq=None)

TP_RULES = ShardingRules(
    batch=("dp", "fsdp"), seq=None, embed=None, mlp="tp", heads="tp",
    kv=None, vocab="tp", expert=None, stage=None, kv_seq=None)

FSDP_TP_RULES = ShardingRules(
    batch=("dp", "fsdp"), seq="sp", embed="fsdp", mlp="tp", heads="tp",
    kv=None, vocab="tp", expert="ep", stage="pp", kv_seq=None)


def logical_to_mesh_axes(logical_axes: Optional[Sequence[str]],
                         rules: Mapping[str, MeshAxis]) -> P:
    if logical_axes is None:
        return P()
    return P(*(rules.get(a) for a in logical_axes))


def _drop_missing_axes(spec: P, mesh: Mesh) -> P:
    """Remove mesh axes the mesh doesn't have (lets the same rules run on a
    trivial single-axis test mesh)."""
    def fix(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in mesh.axis_names else None
        kept = tuple(a for a in entry if a in mesh.axis_names)
        return kept if kept else None
    return P(*(fix(e) for e in spec))


def named_sharding(mesh: Mesh, logical_axes: Optional[Sequence[str]],
                   rules: Mapping[str, MeshAxis]) -> NamedSharding:
    spec = logical_to_mesh_axes(logical_axes, rules)
    return NamedSharding(mesh, _drop_missing_axes(spec, mesh))


def tree_paths_to_logical(params: Any,
                          logical_axes_tree: Any) -> Dict[Tuple, Any]:
    """Zip a params pytree with a matching tree of logical-axis tuples."""
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_a = jax.tree_util.tree_leaves(
        logical_axes_tree, is_leaf=lambda x: x is None or isinstance(x, tuple))
    if len(flat_p) != len(flat_a):
        raise ValueError(
            f"params tree has {len(flat_p)} leaves but axes tree has "
            f"{len(flat_a)}")
    return {path: ax for (path, _), ax in zip(flat_p, flat_a)}


def _drop_nondividing_axes(spec: P, mesh: Mesh, shape) -> P:
    """Replicate any dimension whose assigned mesh-axis product does not
    divide it.  The canonical case is GQA under wide tensor parallelism:
    n_kv_heads=2 with tp=4 cannot shard the kv-head dim, so k/v projections
    fall back to replication across the excess tp ranks (the standard TPU
    recipe) while q/o stay head-sharded."""
    sizes = mesh.shape

    entries = tuple(spec)
    if len(entries) > len(shape):
        raise ValueError(
            f"sharding spec {spec} has {len(entries)} entries for a "
            f"rank-{len(shape)} array of shape {tuple(shape)} — bad "
            "logical-axes annotation")
    entries = entries + (None,) * (len(shape) - len(entries))

    def fix(entry, dim):
        if entry is None:
            return None
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        prod = 1
        for a in axes:
            prod *= sizes.get(a, 1)
        if prod and dim % prod == 0:
            return entry
        logger.warning(
            "sharding: axis %r (mesh extent %d) does not divide dim of "
            "size %d (shape %s) — replicating that dimension instead",
            entry, prod, dim, tuple(shape))
        return None

    return P(*(fix(e, d) for e, d in zip(entries, shape)))


def _whole_vectors(logical_axes: Optional[Sequence[str]]
                   ) -> Optional[Sequence[str]]:
    """A parameter's logical axes with a vector's one dimension unnamed (the
    module's note on what the rules leave whole); any other leaf's as they
    are."""
    if logical_axes is None \
            or sum(a != "layers" for a in logical_axes) != 1:
        return logical_axes
    return tuple(a if a == "layers" else None for a in logical_axes)


def pytree_shardings(params_axes: Any, mesh: Mesh,
                     rules: Mapping[str, MeshAxis],
                     params: Any = None) -> Any:
    """Map a tree of logical-axis tuples → tree of NamedShardings.  A
    vector leaf comes back replicated (`_whole_vectors`).

    With ``params`` given, each leaf's sharding is validated against its
    shape and non-dividing mesh axes degrade to replication (GQA kv heads
    under tp>n_kv_heads, odd vocab under wide tp, …)."""
    is_axes_leaf = lambda x: x is None or isinstance(x, tuple)
    params_axes = jax.tree_util.tree_map(_whole_vectors, params_axes,
                                         is_leaf=is_axes_leaf)
    if params is None:
        return jax.tree_util.tree_map(
            lambda ax: named_sharding(mesh, ax, rules),
            params_axes, is_leaf=is_axes_leaf)

    def fit(ax, p):
        s = named_sharding(mesh, ax, rules)
        shape = getattr(p, "shape", None)
        if shape is None:
            return s
        return NamedSharding(mesh, _drop_nondividing_axes(s.spec, mesh,
                                                          shape))

    return jax.tree_util.tree_map(fit, params_axes, params,
                                  is_leaf=is_axes_leaf)


def shard_pytree(params: Any, params_axes: Any, mesh: Mesh,
                 rules: Mapping[str, MeshAxis]) -> Any:
    """Place a host pytree onto the mesh under the given rules (shape-aware:
    non-dividing assignments replicate rather than error)."""
    shardings = pytree_shardings(params_axes, mesh, rules, params=params)
    return jax.device_put(params, shardings)


def constrain(x: jax.Array, logical_axes: Optional[Sequence[str]],
              rules: Mapping[str, MeshAxis],
              mesh: Optional[Mesh] = None) -> jax.Array:
    """`with_sharding_constraint` by logical names; no-op outside jit/mesh."""
    spec = logical_to_mesh_axes(logical_axes, rules)
    if mesh is not None:
        spec = _drop_missing_axes(spec, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def batch_sharding(mesh: Mesh, rules: Mapping[str, MeshAxis],
                   ndim: int = 2) -> NamedSharding:
    """Sharding for input batches: batch axis sharded, rest replicated."""
    axes = ["batch"] + [None] * (ndim - 1)
    spec = logical_to_mesh_axes(axes, {**rules, None: None})
    return NamedSharding(mesh, _drop_missing_axes(spec, mesh))
