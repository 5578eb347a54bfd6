"""Hybrid device mesh (TPU-native answer to Fleet's HybridCommunicateGroup,
ref ``python/paddle/distributed/fleet/base/topology.py``).

The reference wires NCCL communicator groups per parallelism dim (dp/mp/pp/
sharding). Here ONE ``jax.sharding.Mesh`` with named axes carries the whole
topology; every parallel form is a PartitionSpec over these axes and XLA
emits the ICI collectives. Axis order is outermost→innermost with the
fastest-varying axes (tp, sp) innermost so their collectives ride the
shortest ICI hops on a real slice.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops import pallas

AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep", "cp")


class HybridMesh:
    """dp × fsdp × ep × pp × tp × sp × cp over the device grid.

    ``ep`` is a first-class expert-parallel axis: MoE expert weights carry
    ``P("ep", ...)`` and the MoE dispatcher's ``lax.all_to_all`` runs over
    it (ref: the MoE NCCL group's ``c_alltoall``). Tokens/batch are sharded
    over (dp, fsdp, ep) — experts ride chips that also carry data, the
    reference's "ep on dp" layout, but with an explicit named axis.

    ``cp`` is the serving-side context-parallel axis (ISSUE 18): the paged
    KV pool shards its physical blocks over cp while weights stay
    replicated; prefill partials merge via ring rotation or Ulysses
    all_to_all and decode merges via psum. Innermost so the per-tick
    O(heads·dim) merge rides the shortest ICI hops.
    """

    def __init__(self, dp: int = 1, fsdp: int = 1, pp: int = 1, tp: int = 1,
                 sp: int = 1, ep: int = 1, cp: int = 1,
                 devices: Optional[Sequence] = None):
        devices = list(devices if devices is not None else jax.devices())
        n = dp * fsdp * ep * pp * tp * sp * cp
        if n != len(devices):
            raise ValueError(
                f"mesh {dp}x{fsdp}x{ep}x{pp}x{tp}x{sp}x{cp}={n} != "
                f"{len(devices)} devices")
        grid = np.array(devices).reshape(dp, fsdp, ep, pp, tp, sp, cp)
        self.mesh = Mesh(grid, ("dp", "fsdp", "ep", "pp", "tp", "sp", "cp"))
        self.dp, self.fsdp, self.pp, self.tp, self.sp = dp, fsdp, pp, tp, sp
        self.ep = ep
        self.cp = cp

    # -- reference-style queries (HybridCommunicateGroup API) ---------------
    def get_data_parallel_world_size(self):
        return self.dp * self.fsdp

    def get_model_parallel_world_size(self):
        return self.tp

    def get_pipe_parallel_world_size(self):
        return self.pp

    def get_sharding_parallel_world_size(self):
        return self.fsdp

    # -- sharding helpers ----------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def batch_sharding(self) -> NamedSharding:
        """Global-batch sharding over all data axes."""
        return NamedSharding(self.mesh, P(("dp", "fsdp", "ep"),))

    def batch_spec(self) -> P:
        return P(("dp", "fsdp", "ep"),)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def __enter__(self):
        self.mesh.__enter__()
        _CURRENT.append(self)
        # tell the kernel layer what XLA partitions over while this is
        # active: Mosaic kernels apply only where it partitions nothing
        pallas.enter_mesh(self.mesh.shape)
        return self

    def __exit__(self, *exc):
        pallas.exit_mesh()
        _CURRENT.pop()
        return self.mesh.__exit__(*exc)

    @property
    def axis_names(self):
        return self.mesh.axis_names

    def size(self, axis: str) -> int:
        return self.mesh.shape[axis] if axis in self.mesh.shape else 1


_CURRENT: list[HybridMesh] = []


def current_mesh() -> Optional[HybridMesh]:
    return _CURRENT[-1] if _CURRENT else None


def single_device_mesh() -> HybridMesh:
    return HybridMesh(dp=1, fsdp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])


def make_mesh(shape: dict, devices=None) -> HybridMesh:
    """shape e.g. {"dp":2, "tp":4} — unspecified axes default 1."""
    kw = {a: int(shape.get(a, 1)) for a in AXES}
    return HybridMesh(**kw, devices=devices)
