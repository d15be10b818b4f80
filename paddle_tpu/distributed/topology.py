"""Hybrid-parallel topology -> jax device Mesh.

Analogue of ``python/paddle/distributed/fleet/base/topology.py``
(CommunicateTopology:60, HybridCommunicateGroup:173).  The reference builds
NCCL groups for every axis combination of the 5-axis order
``["data", "pipe", "sharding", "sep", "model"]``; here the same axes become
named axes of ONE ``jax.sharding.Mesh`` and "groups" become axis names used
in sharding annotations / shard_map collectives — GSPMD then materializes
the communicators (SURVEY §7 architecture mapping).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical axis order, matching topology.py:63
AXIS_ORDER = ["data", "pipe", "sharding", "sep", "model"]

_global_mesh: Optional[Mesh] = None


def build_mesh(dp: int = 1, pp: int = 1, sharding: int = 1, sep: int = 1,
               mp: int = 1, devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    need = dp * pp * sharding * sep * mp
    if need != len(devices):
        raise ValueError(
            f"topology {dp}x{pp}x{sharding}x{sep}x{mp}={need} does not match "
            f"{len(devices)} devices")
    arr = np.array(devices).reshape(dp, pp, sharding, sep, mp)
    return Mesh(arr, AXIS_ORDER)


def set_global_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_global_mesh() -> Optional[Mesh]:
    return _global_mesh


def pvary(x, axes):
    """Mark x as varying over manual mesh axes; idempotent —
    already-varying values pass through (``pcast`` itself refuses
    varying -> varying).  Shared by the shard_map-based engines
    (pipeline, ring attention)."""
    try:
        return jax.lax.pcast(x, axes, to="varying")
    except ValueError as e:
        if "from=varying" in str(e):
            return x
        raise


class CommunicateTopology:
    """Rank <-> coordinate arithmetic (reference CommunicateTopology:60)."""

    def __init__(self, hybrid_group_names=None, dims=None):
        self._parallel_names = list(hybrid_group_names or AXIS_ORDER)
        self._dims = list(dims or [1] * len(self._parallel_names))
        self.coordinate = list(itertools.product(
            *[range(d) for d in self._dims]))
        self._coord2rank = {c: i for i, c in enumerate(self.coordinate)}

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return int(np.prod(self._dims))

    def get_rank(self, **kwargs):
        coord = tuple(kwargs[name] for name in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank):
        return self.coordinate[rank]

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        return [r for r, c in enumerate(self.coordinate) if c[axis] == index]

    def get_comm_list(self, axis_name):
        """All rank groups along ``axis_name`` (reference get_comm_list)."""
        axis = self._parallel_names.index(axis_name)
        other_dims = [range(d) for i, d in enumerate(self._dims) if i != axis]
        groups = []
        for other in itertools.product(*other_dims):
            ranks = []
            for v in range(self._dims[axis]):
                coord = list(other)
                coord.insert(axis, v)
                ranks.append(self._coord2rank[tuple(coord)])
            groups.append(ranks)
        return groups

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = list(self.get_coord(global_rank))
        for k, v in kwargs.items():
            coord[self._parallel_names.index(k)] = v
        return self._coord2rank[tuple(coord)]


class _AxisGroup:
    """A logical communication group = a mesh axis (or fused axes)."""

    def __init__(self, axes, topo: CommunicateTopology, rank_in_group, ranks):
        self.axes = tuple(axes) if isinstance(axes, (list, tuple)) else (axes,)
        self.rank = rank_in_group
        self.ranks = ranks
        self.nranks = len(ranks)

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"AxisGroup(axes={self.axes}, nranks={self.nranks})"


class HybridCommunicateGroup:
    """Reference HybridCommunicateGroup:173 — axis bookkeeping + Mesh owner.

    On TPU the device-level axes live in one Mesh; each get_*_group returns
    an _AxisGroup whose ``axes`` name is usable in shard_map collectives and
    PartitionSpecs.
    """

    def __init__(self, topology: Optional[CommunicateTopology] = None,
                 dp=1, pp=1, sharding=1, sep=1, mp=1):
        if topology is not None:
            dims = [topology.get_dim(n) for n in AXIS_ORDER]
            dp, pp, sharding, sep, mp = dims
        self._topo = topology or CommunicateTopology(AXIS_ORDER,
                                                     [dp, pp, sharding, sep, mp])
        self.nranks = self._topo.world_size()
        self.global_rank = 0  # single-controller SPMD: logical rank 0
        self._dp_degree = dp
        self._pp_degree = pp
        self._sharding_degree = sharding
        self._sep_degree = sep
        self._mp_degree = mp
        n_local = len(jax.devices())
        if self.nranks == n_local:
            self.mesh = build_mesh(dp, pp, sharding, sep, mp)
            set_global_mesh(self.mesh)
        else:
            self.mesh = None  # multi-host meshes built by the launcher

    def _group(self, axis):
        coord = self._topo.get_coord(self.global_rank)
        idx = AXIS_ORDER.index(axis) if isinstance(axis, str) else None
        if isinstance(axis, str):
            ranks = [r for r in self._topo.get_comm_list(axis)
                     if self.global_rank in r][0]
            return _AxisGroup(axis, self._topo, ranks.index(self.global_rank),
                              ranks)
        # fused axes
        names = list(axis)
        all_ranks = list(range(self.nranks))

        def key(r):
            c = self._topo.get_coord(r)
            return tuple(v for i, v in enumerate(c)
                         if AXIS_ORDER[i] not in names)

        mykey = key(self.global_rank)
        ranks = [r for r in all_ranks if key(r) == mykey]
        return _AxisGroup(tuple(names), self._topo,
                          ranks.index(self.global_rank), ranks)

    # ---- parallel info (reference API surface) ----
    def get_parallel_mode(self):
        if self._pp_degree > 1:
            return "pipeline"
        if self._sharding_degree > 1 and self._dp_degree == 1 and \
                self._mp_degree == 1:
            return "sharding_parallel"
        if self._mp_degree > 1:
            return "model_parallel"
        return "data_parallel"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # data parallel
    def get_data_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[0]

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._group("data")

    def get_data_parallel_group_src_rank(self):
        return self.get_data_parallel_group().ranks[0]

    # model (tensor) parallel
    def get_model_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[4]

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._group("model")

    def get_model_parallel_group_src_rank(self):
        return self.get_model_parallel_group().ranks[0]

    # pipeline
    def get_stage_id(self):
        return self._topo.get_coord(self.global_rank)[1]

    def get_pipe_parallel_rank(self):
        return self.get_stage_id()

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._group("pipe")

    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._pp_degree - 1

    # sharding
    def get_sharding_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[2]

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._group("sharding")

    def get_sharding_parallel_group_src_rank(self):
        return self.get_sharding_parallel_group().ranks[0]

    # sep (Ulysses sequence axis; reference topology.py:216-237)
    def get_sep_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[3]

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def get_sep_parallel_group(self):
        return self._group("sep")

    def get_dp_sep_parallel_group(self):
        return self._group(("data", "sep"))

    def get_pp_mp_parallel_group(self):
        return self._group(("pipe", "model"))

    # check groups (sanity sets, reference get_check_parallel_group)
    def get_check_parallel_group(self, sharding_new_group=False):
        return self._group(("pipe", "sharding", "sep", "model"))

    def get_rank_from_stage(self, stage_id, **kwargs):
        return self._topo.get_rank_from_stage(self.global_rank,
                                              pipe=stage_id, **kwargs)


_hcg: Optional[HybridCommunicateGroup] = None


def set_hybrid_communicate_group(hcg):
    global _hcg
    _hcg = hcg


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _hcg
