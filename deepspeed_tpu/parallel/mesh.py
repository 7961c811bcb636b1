"""Device mesh construction and topology.

TPU-native replacement for the reference's process-group machinery:

- ``deepspeed/utils/groups.py`` (``_get_{data,model,expert,sequence}_parallel_group``)
- ``deepspeed/runtime/pipe/topology.py`` (``ProcessTopology``, ``PipelineParallelGrid``)

Instead of creating torch.distributed process groups per parallelism flavor, we build a
single ``jax.sharding.Mesh`` with named axes ``("pp","dp","fsdp","ep","sp","tp")`` and
express every parallel strategy as a sharding over those axes.  XLA inserts the
collectives; ICI-adjacent axes are placed innermost so tp/sp collectives ride ICI.

MeshSpec sizes of ``-1`` mean "absorb all remaining devices" (at most one axis may be -1,
like a reshape).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.constants import MESH_AXES


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes of each parallel axis.  -1 on at most one axis means "all remaining".

    Replaces the reference's (pp, mp, dp) ``ProcessTopology`` axes plus the separately
    managed expert/sequence groups with one unified spec.
    """

    pp: int = 1
    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self) -> tuple:
        return (self.pp, self.dp, self.fsdp, self.ep, self.sp, self.tp)

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill in a -1 axis given the total device count; validate the product."""
        sizes = list(self.sizes())
        unknown = [i for i, s in enumerate(sizes) if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {self}")
        known = math.prod(s for s in sizes if s != -1)
        if unknown:
            if n_devices % known != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by fixed axes product {known}")
            sizes[unknown[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh spec product {known} != device count {n_devices}: {self}")
        return MeshSpec(*sizes)

    @property
    def data_parallel_size(self) -> int:
        """World size over which the batch is split (dp × fsdp)."""
        return self.dp * self.fsdp


def build_mesh(spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with canonical axis order.

    Axis order is (pp, dp, fsdp, ep, sp, tp) — outermost first.  On multi-slice
    systems the outer axes land on DCN and the inner axes on ICI, which is the layout
    the sharding strategies in this package assume (tp/sp collectives are
    latency-sensitive; dp/pp are bandwidth-tolerant).
    """
    if devices is None:
        devices = jax.devices()
    if -1 not in spec.sizes():
        # fully specified: allow using a leading subset of the devices
        need = math.prod(spec.sizes())
        if need <= len(devices):
            devices = devices[:need]
    spec = spec.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(spec.sizes())
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devices = [device] if device is not None else jax.devices()[:1]
    return Mesh(np.asarray(devices).reshape((1,) * len(MESH_AXES)), MESH_AXES)


def batch_pspec(extra_dims: int = 0) -> P:
    """PartitionSpec for a [batch, ...] input: batch split over (dp, fsdp) jointly.

    The reference splits the dataloader over the DP group
    (runtime/dataloader.py + engine.deepspeed_io); here the global batch is a single
    jax.Array sharded over dp×fsdp, and sp additionally splits the sequence dim when
    Ulysses sequence parallelism is active (sequence/ulysses.py).
    """
    return P(("dp", "fsdp"), *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(extra_dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def manual_axes_now() -> frozenset:
    """Mesh axes that are MANUAL in the current trace context (inside a
    (partial-)manual ``shard_map`` region), else empty.  The engine's qgZ
    gradient path runs the WHOLE model inside a manual-over-dp region
    (engine._qgz_grads); model code that builds sharding constraints or
    sizes shards from the mesh must treat those axes as already-applied."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return frozenset()
    from jax.sharding import AxisType
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == AxisType.Manual)


def auto_axes_spec(spec: P, manual=None) -> P:
    """Strip manual axes from a PartitionSpec —
    ``with_sharding_constraint`` inside a manual region may only name auto
    axes (a spec mixing a manual axis into a tuple, like the batch pin's
    ``('dp', 'fsdp')``, raises at trace time).  ``manual`` defaults to the
    current trace context's manual axes (identity outside any region);
    pass a set explicitly when building specs AHEAD of entering the
    region (engine._qgz_grads)."""
    if manual is None:
        manual = manual_axes_now()
    if not manual:
        return spec
    out = []
    for ax in spec:
        axes = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                     if a is not None and a not in manual)
        out.append(axes[0] if len(axes) == 1 else (axes or None))
    return P(*out)
