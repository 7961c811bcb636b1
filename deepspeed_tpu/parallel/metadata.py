"""Bridging flax partitioning metadata into partition rules.

Models annotate params with ``nn.with_partitioning(init, (<logical axes>))``; at
``jax.eval_shape`` time those arrive as ``nn.Partitioned`` boxes.  The engine works
on *unboxed* param trees (plain arrays, maxtext/t5x convention) and uses this module
to extract an annotated abstract tree whose leaves carry ``.names`` so
``partition.infer_pspec`` can map logical axes → mesh axes.

This is the declarative analog of the reference's AutoTP graph parsing
(module_inject/auto_tp.py:273 tp_parser): instead of inferring row/col parallelism
from a torch graph, the model declares it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class AbstractLeaf:
    """ShapeDtypeStruct + logical axis names carrier."""

    shape: Tuple[int, ...]
    dtype: object
    names: Optional[Tuple[Optional[str], ...]] = None

    @property
    def ndim(self):
        return len(self.shape)


def _is_box(x) -> bool:
    try:
        from flax.linen import meta
        return isinstance(x, meta.AxisMetadata)
    except ImportError:  # pragma: no cover
        return False


def annotate_abstract(boxed_tree):
    """boxed/plain abstract pytree → tree of AbstractLeaf (boxes collapsed)."""

    def to_leaf(x):
        if _is_box(x):
            names = tuple(getattr(x, "names", ()) or ())
            inner = x.unbox() if hasattr(x, "unbox") else x.value
            return AbstractLeaf(tuple(inner.shape), inner.dtype, names or None)
        return AbstractLeaf(tuple(x.shape), x.dtype, None)

    return jax.tree_util.tree_map(to_leaf, boxed_tree, is_leaf=_is_box)


def unbox(tree):
    """Strip flax AxisMetadata boxes, returning plain arrays/structs.

    Constraints are NOT applied while unboxing: the names are LOGICAL, not
    mesh axes — the engine maps logical → mesh axes itself via
    ``partition.param_shardings`` and pins layouts through jit
    out_shardings."""
    try:
        from flax.linen import meta
    except ImportError:  # pragma: no cover
        return tree

    def _unbox(x):
        if isinstance(x, meta.AxisMetadata):
            try:
                return x.unbox(apply_constraint=False)
            except TypeError:  # AxisMetadata impls without the kwarg
                return x.unbox()
        return x

    return jax.tree_util.tree_map(
        _unbox, tree, is_leaf=lambda x: isinstance(x, meta.AxisMetadata))
