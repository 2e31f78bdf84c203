"""CSBLinear: a three-mode linear layer — the CSB technique as a
first-class model feature (DESIGN.md §3).

Modes:
  dense   — plain matmul (training before pruning starts)
  masked  — dense matmul against the CSB-projected weight (ADMM training:
            the projection is the Z-update; the mask is free under jit)
  csb     — the PaddedCSB format through the Pallas kernel (serving).
            When a mesh with a non-trivial "model" axis is active (see
            ``models.layers.csb_dense``), the block grid is partitioned
            over that axis by cycle cost (``dist.csb_partition``) and
            executed via ``csb_matvec_sharded``; ``shard_for_mesh``
            builds and caches the per-mesh ``ShardedCSB``.

`csb_specs_for_params` builds the spec tree that repro.train's ADMM hooks
consume, selecting every >= min_dim 2-D/stacked-3-D projection of a model.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .csb_format import PaddedCSB, padded_csb_from_dense
from .pruning import CSBSpec, csb_masks, csb_project

PyTree = Any


@dataclasses.dataclass
class CSBLinear:
    """Stateful wrapper around one projection weight."""

    weight: jax.Array                    # (in, out) or (out, in) — caller's
    spec: CSBSpec
    mode: str = "dense"                  # dense | masked | csb
    transposed: bool = False             # True if weight is (in, out)
    _packed: PaddedCSB | None = None
    # (n_dev, axis) -> (PartitionPlan, ShardedCSB); host-side cache so the
    # greedy placement runs once per mesh width, not once per call
    _shards: dict = dataclasses.field(default_factory=dict)

    def _w_oi(self) -> jax.Array:
        return self.weight.T if self.transposed else self.weight

    def freeze(self, pad_to: int = 8) -> "CSBLinear":
        """Project + pack for serving (mode -> csb)."""
        w = np.asarray(csb_project(self._w_oi(), self.spec))
        rm, cm = csb_masks(jnp.asarray(w), self.spec)
        packed = padded_csb_from_dense(
            w, self.spec.bm, self.spec.bn, pad_to=pad_to,
            row_mask=np.asarray(rm), col_mask=np.asarray(cm))
        # fresh shard cache: replace() would alias the dict, and cached
        # shards of the previous packing must not survive a re-freeze
        return dataclasses.replace(self, mode="csb", _packed=packed,
                                   _shards={})

    def shard_for_mesh(self, mesh, axis: str = "model"):
        """(plan, ShardedCSB) for this weight on ``mesh[axis]``, cycle-
        balanced by the greedy planner and cached per mesh width."""
        assert self._packed is not None, "call freeze() first"
        from repro.dist.csb_partition import partition_padded
        n_dev = mesh.shape[axis]
        key = (n_dev, axis)
        if key not in self._shards:
            self._shards[key] = partition_padded(self._packed, n_dev)
        return self._shards[key]

    def __call__(self, x: jax.Array) -> jax.Array:
        from repro.kernels.ops import _active_model_mesh, matvec
        if self.mode == "dense":
            w = self._w_oi()
        elif self.mode == "masked":
            w = csb_project(self._w_oi(), self.spec)
        elif self.mode == "csb":
            assert self._packed is not None, "call freeze() first"
            mesh = _active_model_mesh()
            w = (self._packed if mesh is None
                 else self.shard_for_mesh(mesh)[1])
        else:  # pragma: no cover
            raise ValueError(self.mode)
        return matvec(w, x)

    def compression(self) -> float:
        if self._packed is None:
            return 1.0
        return (self._packed.shape[0] * self._packed.shape[1]
                / max(self._packed.true_flops_per_mvm() // 2, 1))


def csb_specs_for_params(params: PyTree, spec: CSBSpec,
                         min_dim: int = 64,
                         exclude: tuple[str, ...] = ("embed", "head",
                                                     "router")) -> PyTree:
    """Spec tree (CSBSpec | None per leaf) for ADMM pruning of a model's
    projections — 2-D weights and stacked (L, in, out) layer weights."""

    def assign(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        if any(e in keys[-1] for e in exclude):
            return None
        if leaf.ndim == 2 and min(leaf.shape) >= min_dim:
            return spec
        if leaf.ndim == 3 and min(leaf.shape[1:]) >= min_dim \
                and "layers" in keys:
            return spec
        return None

    return jax.tree_util.tree_map_with_path(assign, params)
