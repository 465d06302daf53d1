"""Production mesh topology — the single source of truth.

Single pod: 16×16 = 256 chips, (data, model).
Multi-pod:  2×16×16 = 512 chips, (pod, data, model) — the "pod" axis is a
second data-parallel dimension whose collectives cross the inter-pod DCI.

Every topology is described ONCE as a :class:`MeshDescriptor` — logical
shape, axis names, which axes are data-parallel, and which axes' edges
cross the inter-pod DCI (everything else is on-pod ICI).  The runtime
``use_mesh`` path consumes ``descriptor.build()`` (a real ``jax.Mesh``);
shardlint (``repro.analysis.comms_audit``) consumes the same descriptor
to lower under fake/abstract devices and to attribute collective bytes to
ICI vs DCI edges — so the auditor can never drift from the topology the
launcher actually runs.

Functions, not module constants: importing this module must not touch jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class MeshDescriptor:
    """Logical description of one mesh topology.

    ``dci_axes``: axes whose collectives cross the inter-pod data-center
    interconnect; collectives spanning only the remaining (``ici_axes``)
    stay on the pod's ICI.  ``build()`` materializes the jax Mesh (needs
    that many devices — real or ``--xla_force_host_platform_device_count``
    fakes); ``abstract()`` needs zero devices (host-side spec math).
    Both carry Auto axis types, so the ``with_sharding_constraint`` rules
    in ``repro.sharding`` apply as written."""
    name: str
    shape: tuple[int, ...]
    axis_names: tuple[str, ...]
    data_axes: tuple[str, ...]
    model_axis: str = "model"
    dci_axes: tuple[str, ...] = ()

    @property
    def device_count(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ici_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axis_names if a not in self.dci_axes)

    def axis_size(self, name: str) -> int:
        return self.shape[self.axis_names.index(name)]

    @property
    def data_axis_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.axis_size(a)
        return n

    @property
    def axis_types(self) -> tuple:
        return (jax.sharding.AxisType.Auto,) * len(self.shape)

    def build(self) -> jax.sharding.Mesh:
        return jax.make_mesh(self.shape, self.axis_names,
                             axis_types=self.axis_types)

    def abstract(self):
        from jax.sharding import AbstractMesh
        return AbstractMesh(self.shape, self.axis_names,
                            axis_types=self.axis_types)


def production_descriptor(multi_pod: bool = False,
                          shape: tuple[int, ...] | None = None
                          ) -> MeshDescriptor:
    """The deployment topologies.  ``shape``: optional (data, model)
    override for the 256 chips of one pod — the §Perf mesh-shape
    experiments (e.g. (64, 4) or (256, 1) for FSDP-dominant layouts on
    ≤8B dense models)."""
    if shape is not None:
        assert not multi_pod and len(shape) == 2
        return MeshDescriptor(name=f"pod{shape[0]}x{shape[1]}",
                              shape=tuple(shape),
                              axis_names=("data", "model"),
                              data_axes=("data",))
    if multi_pod:
        return MeshDescriptor(name="multi_pod", shape=(2, 16, 16),
                              axis_names=("pod", "data", "model"),
                              data_axes=("pod", "data"),
                              dci_axes=("pod",))
    return MeshDescriptor(name="single_pod", shape=(16, 16),
                          axis_names=("data", "model"),
                          data_axes=("data",))


def host_descriptor(n_devices: int | None = None) -> MeshDescriptor:
    """(local_devices, 1) topology for single-host launcher runs: the data
    axis spans every local device, so ``--mesh host`` on a multichip host
    data-parallelizes instead of pinning everything to device 0."""
    n = jax.local_device_count() if n_devices is None else n_devices
    return MeshDescriptor(name=f"host{n}", shape=(n, 1),
                          axis_names=("data", "model"),
                          data_axes=("data",))


def make_production_mesh(*, multi_pod: bool = False,
                         shape: tuple[int, ...] | None = None):
    return production_descriptor(multi_pod, shape).build()


def data_axes(multi_pod: bool = False) -> tuple[str, ...]:
    return production_descriptor(multi_pod).data_axes


def make_host_mesh():
    """See :func:`host_descriptor`.

    Row divisibility is no longer the user's problem: the plan-ahead
    scheduler (train/planner) sizes every batch's row count to a multiple
    of this mesh's data axis (``data_axis_size``); the launcher errors
    only when the user *forces* an indivisible ``--rows``."""
    return host_descriptor().build()


def data_axis_size(mesh, daxes: tuple[str, ...] = ("data",)) -> int:
    """Number of data-parallel replicas = product of the mesh's data axes
    — the row multiple the planner balances batches against."""
    n = 1
    for a in daxes:
        n *= mesh.shape[a]
    return n
