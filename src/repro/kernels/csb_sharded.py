"""Mesh-sharded CSB matvec: per-device Pallas kernels + output all-gather.

``csb_matvec_sharded(s, x, mesh=...)`` runs the CSB-MVM kernel on each
device's local block-row shard (a ``ShardedCSB`` built by
``repro.dist.csb_partition``) under ``shard_map``, all-gathers the
per-device output rows along the mesh "model" axis, and permutes the
gathered rows back to the original block-row order (the planner
assigns rows by cycle cost, not contiguously).

Collective-matmul pipeline: each device's block-rows are split into
``overlap`` chunks and the shard_map body interleaves one Pallas MVM +
one all-gather per chunk. The all-gather of a finished chunk is
independent of every later chunk's compute, so an async-collective
backend (TPU) starts gathering completed rows while the final chunk's
kernel is still running — compute hides the collective instead of
serializing behind it. Row chunks are disjoint (the kernel's grid is
independent per block-row), so per-row numerics are bit-identical for
any ``overlap``; only the gathered layout changes, and the row
unpermute (folded with the chunk reorder into one ``take``) restores
the original order exactly as before.

Device placement quality is the planner's job; this wrapper executes
whatever ``row_map`` it is handed, exactly as ``csb_mvm_pallas``
executes whatever block layout the engine scheduler chose. Pad rows
(devices with fewer block-rows than the max) carry ``m = n = 0`` and
the kernel masks them to zero, so they cost one grid step but never
corrupt the gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.csb_format import ShardedCSB, csb_output_permutation
from .csb_mvm import csb_mvm_pallas, default_interpret
from .ops import pad_to_grid


def _chunk_bounds(rpd: int, overlap: int) -> list[tuple[int, int]]:
    """Split ``rpd`` block-rows into ``overlap`` contiguous chunks,
    sizes as even as possible (first chunks take the remainder)."""
    overlap = max(1, min(overlap, rpd))
    base, rem = divmod(rpd, overlap)
    bounds, start = [], 0
    for i in range(overlap):
        size = base + (1 if i < rem else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


def _chunk_order(n_dev: int, rpd: int, bm: int,
                 bounds: list[tuple[int, int]]) -> np.ndarray:
    """Map device-order gather positions -> chunked-gather positions.

    The single-gather layout is ``[dev0 rows 0..rpd) | dev1 ...]``;
    chunked gathers concatenate ``[all devs' chunk0 | all devs' chunk1
    | ...]``. ``order[sp] = cp`` lets the wrapper fold the reorder into
    the existing row unpermute: ``take(chunked, order)[perm] ==
    take(chunked, order[perm])``."""
    order = np.empty(n_dev * rpd * bm, np.int64)
    base = 0
    for s_, e_ in bounds:
        size = e_ - s_
        for d in range(n_dev):
            for r in range(s_, e_):
                sp = (d * rpd + r) * bm
                cp = base + (d * size + (r - s_)) * bm
                order[sp:sp + bm] = np.arange(cp, cp + bm)
        base += n_dev * size * bm
    return order


@functools.lru_cache(maxsize=None)
def _sharded_fn(mesh, axis_name: str, grid: tuple[int, int],
                block: tuple[int, int], rpd: int,
                row_map: tuple[tuple[int, ...], ...],
                batch_tile: int, group: int, interpret: bool,
                overlap: int):
    """Jitted (shards..., xp) -> gathered-and-unpermuted output, cached
    per static configuration — the sharded twin of ops._run's jit cache,
    so eager serving loops don't re-trace the kernel every call."""
    br, bc = grid
    bm, bn = block
    spec1 = P(axis_name)
    # batch stays sharded over the non-model axes (data parallelism is
    # orthogonal to the block-row split); only the feature/row dims are
    # replicated along the model axis
    dp = tuple(ax for ax in mesh.axis_names if ax != axis_name)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    xspec = P(dp_entry, None)

    n_dev = mesh.shape[axis_name]
    bounds = _chunk_bounds(rpd, overlap)

    def body(vals, ridx, cidx, m, n, xl):
        # local shard: leading device axis is 1 here — squeeze it, then
        # pipeline chunk-MVM -> chunk-all-gather so each gather only
        # waits on its own rows (collective matmul: the last chunk's
        # kernel runs while earlier chunks are already in flight)
        v, r, c, mm, nn = vals[0], ridx[0], cidx[0], m[0], n[0]
        parts = []
        for s_, e_ in bounds:
            y = csb_mvm_pallas(
                v[s_ * bc:e_ * bc], r[s_ * bc:e_ * bc],
                c[s_ * bc:e_ * bc], mm[s_ * bc:e_ * bc],
                nn[s_ * bc:e_ * bc], xl,
                grid=(e_ - s_, bc), block=(bm, bn),
                batch_tile=batch_tile, group=group, interpret=interpret,
            )                                        # (Bp, (e-s)*bm)
            parts.append(
                jax.lax.all_gather(y, axis_name, axis=1, tiled=True))
        if len(parts) == 1:
            return parts[0]
        return jnp.concatenate(parts, axis=1)        # (Bp, D*rpd*bm)

    # pallas_call has no replication rule, so the check must be off
    shmapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec1, spec1, spec1, spec1, spec1, xspec),
        out_specs=xspec, check_vma=False,
    )

    # perm: original output row -> position in the device-order gather;
    # compose with the chunk reorder so one take() restores row order
    perm = np.asarray(csb_output_permutation(row_map, rpd, bm, br))
    final_perm = _chunk_order(n_dev, rpd, bm, bounds)[perm]

    def fn(vals, ridx, cidx, m, n, xp):
        y = shmapped(vals, ridx, cidx, m, n, xp)      # (Bp, D*rpd*bm)
        return jnp.take(y, jnp.asarray(final_perm), axis=1)
    return jax.jit(fn)


def csb_matvec_sharded(
    s: ShardedCSB,
    x: jax.Array,
    *,
    mesh,
    axis_name: str = "model",
    batch_tile: int = 8,
    group: int | None = None,
    interpret: bool | None = None,
    overlap: int | None = None,
) -> jax.Array:
    """y = x @ W^T with W's block-rows spread over ``mesh[axis_name]``.

    ``x``: (..., in_dim), replicated along the model axis (the paper's
    MVM input vector is broadcast to every PEGroup; same here, one
    level up) while the flattened batch dim stays sharded over the
    remaining (data) axes. Returns (..., out_dim) fp32, model-axis
    replicated, batch laid out as the input was.

    ``overlap`` = collective-matmul chunks per device (default 2,
    clamped to the rows available; 1 = the serial compute-then-gather
    pipeline). Results are identical for every value — rows are
    independent — only the compute/collective interleaving changes.
    """
    if axis_name not in tuple(mesh.axis_names):
        raise ValueError(f"mesh has no axis {axis_name!r}: "
                         f"{tuple(mesh.axis_names)}")
    if mesh.shape[axis_name] != s.n_dev:
        raise ValueError(
            f"ShardedCSB was split for {s.n_dev} devices but mesh axis "
            f"{axis_name!r} has {mesh.shape[axis_name]}")
    if interpret is None:
        interpret = default_interpret()
    if group is None:
        group = 1
    if overlap is None:
        overlap = 2
    if overlap < 1:
        raise ValueError(f"overlap must be >= 1, got {overlap}")
    overlap = min(overlap, s.rows_per_dev)

    bc = s.grid[1]
    bn = s.block[1]
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    b = x2.shape[0]
    # pad so every data-axis shard is a whole number of batch tiles
    dp_total = mesh.size // mesh.shape[axis_name]
    xp = pad_to_grid(x2, batch_tile * dp_total, bc * bn)

    fn = _sharded_fn(mesh, axis_name, s.grid, s.block, s.rows_per_dev,
                     s.row_map, batch_tile, group, interpret, overlap)
    y = fn(s.vals, s.row_idx, s.col_idx, s.m, s.n, xp)
    y = y[:b, : s.shape[0]]
    return y.reshape(*batch_shape, s.shape[0])


__all__ = ["csb_matvec_sharded"]
