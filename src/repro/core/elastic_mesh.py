"""ElasticDeviceMesh (INTELLECT-1 §2.4, Fig. 1).

The paper's ElasticDeviceMesh gives every process a *local* rank (FSDP
process group, fast intra-node fabric) and a *global* rank (fault-
tolerant DiLoCo data-parallel group over the internet). The TPU-native
analogue:

  * the **mesh axes** play the roles of the process groups: the DiLoCo
    axis ('pod' across pods / 'data' inside one) is the global group,
    the remaining axes ('data'/'model') are the local FSDP/TP groups;
  * JAX cannot resize a mesh inside a compiled program, so elasticity is
    realized two ways, both at outer-step boundaries (the only points
    the paper changes membership either):
      - **mask-and-renormalize** inside a fixed-capacity mesh: every
        DiLoCo slot has a weight in {0, 1}; dead/empty/joining slots
        contribute weight 0 and the ring average divides by the live
        weight sum (exactly the paper's "join with zero pseudo-
        gradient" + "exclude failed nodes" semantics);
      - **remesh**: build a smaller/larger mesh over the healthy
        hardware and recompile (the paper pays an analogous cost:
        process-group reinit + NCCL/Gloo re-rendezvous).
  * node ids (stable across the run, paper's global ranks) are mapped to
    mesh slots by ``SlotAssignment``; a node that dies frees its slot.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class SlotAssignment:
    """Stable node-id -> DiLoCo-slot mapping with free-list reuse."""

    capacity: int
    slot_of: dict[int, int] = dataclasses.field(default_factory=dict)

    def assign(self, node_id: int) -> int:
        if node_id in self.slot_of:
            return self.slot_of[node_id]
        used = set(self.slot_of.values())
        for s in range(self.capacity):
            if s not in used:
                self.slot_of[node_id] = s
                return s
        raise RuntimeError("ElasticDeviceMesh at capacity; "
                           "remesh with a larger DiLoCo axis")

    def release(self, node_id: int) -> None:
        self.slot_of.pop(node_id, None)

    def live_mask(self, live_ids, zero_weight_ids=()) -> np.ndarray:
        mask = np.zeros((self.capacity,), np.float32)
        for nid in live_ids:
            if nid in self.slot_of and nid not in zero_weight_ids:
                mask[self.slot_of[nid]] = 1.0
        return mask


@dataclasses.dataclass(frozen=True)
class HierarchySpec:
    """The paper's ElasticDeviceMesh split of the outer sync: the WAN
    ring runs ONLY across the DiLoCo axis (one leader stream per site),
    while the remaining mesh axes form the fast intra-node group. The
    distributed sync (``train.step.DistSyncPrograms``) rings each
    device's 1/n_local slice over ``wan_axis`` and rebuilds the full
    vector with an intra-node all-gather — per-device WAN bytes drop by
    ``n_local``. ``local_rank`` ordering matches
    :meth:`ElasticDeviceMesh.local_rank` (row-major over the non-DiLoCo
    axes), which is also the order ``P(wan_axis, local_axes)`` shards
    and ``all_gather`` over ``local_axes`` re-concatenates."""

    wan_axis: str
    local_axes: tuple[str, ...]
    n_local: int

    @property
    def split(self) -> bool:
        """True when there is an intra-node group to split over."""
        return self.n_local > 1


def hierarchy(mesh: jax.sharding.Mesh,
              diloco_axis: str) -> HierarchySpec:
    """WAN/intra-node split of ``mesh`` around the DiLoCo axis."""
    local = tuple(a for a in mesh.axis_names if a != diloco_axis)
    n_local = int(np.prod([mesh.shape[a] for a in local],
                          dtype=np.int64)) if local else 1
    return HierarchySpec(diloco_axis, local, n_local)


class ElasticDeviceMesh:
    """Fixed-capacity mesh + slot assignment + weight computation."""

    def __init__(self, mesh: jax.sharding.Mesh, diloco_axis: str | None):
        self.mesh = mesh
        self.diloco_axis = diloco_axis
        cap = (mesh.shape[diloco_axis] if diloco_axis else 1)
        self.slots = SlotAssignment(cap)

    @property
    def capacity(self) -> int:
        return self.slots.capacity

    def admit(self, node_id: int) -> int:
        return self.slots.assign(node_id)

    def evict(self, node_id: int) -> None:
        self.slots.release(node_id)

    def weights(self, live_ids, joining_ids=(), straggler_ids=()):
        """Per-slot ring weights: 1 for contributing workers, 0 for
        joiners (zero pseudo-gradient), stragglers (excluded this
        round) and empty slots."""
        zero = set(joining_ids) | set(straggler_ids)
        return jnp.asarray(self.slots.live_mask(live_ids, zero))

    # -- rank bookkeeping (paper Fig. 1) -------------------------------------

    def global_rank(self, device_coords: dict[str, int]) -> int:
        """DiLoCo data-parallel rank of a device."""
        return device_coords.get(self.diloco_axis, 0)

    def local_rank(self, device_coords: dict[str, int]) -> int:
        """FSDP-group rank of a device (row-major over non-DiLoCo axes)."""
        rank, stride = 0, 1
        for name in reversed(list(self.mesh.shape.keys())):
            if name == self.diloco_axis:
                continue
            rank += device_coords.get(name, 0) * stride
            stride *= self.mesh.shape[name]
        return rank

    # -- remesh path ----------------------------------------------------------

    def remesh(self, new_diloco_size: int) -> "ElasticDeviceMesh":
        """Rebuild the mesh with a different DiLoCo-axis size over the
        currently healthy devices (recompile follows)."""
        shape = dict(self.mesh.shape)
        axes = list(shape.keys())
        assert self.diloco_axis is not None
        per_worker = np.prod(
            [s for a, s in shape.items() if a != self.diloco_axis],
            dtype=np.int64)
        need = int(per_worker) * new_diloco_size
        devices = np.asarray(self.mesh.devices).reshape(-1)[:need]
        new_shape = tuple(new_diloco_size if a == self.diloco_axis
                          else shape[a] for a in axes)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(new_shape, tuple(axes), devices=devices)
        out = ElasticDeviceMesh(mesh, self.diloco_axis)
        out.slots = SlotAssignment(new_diloco_size)
        for nid, slot in sorted(self.slots.slot_of.items(),
                                key=lambda kv: kv[1]):
            if slot < new_diloco_size:
                out.slots.slot_of[nid] = slot
        return out
