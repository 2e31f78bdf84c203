"""Public jit'd wrappers around the Pallas CSB kernels.

``csb_matvec(p, x)`` accepts any leading batch shape (including none — a
single vector, the paper's MVM case), pads batch/feature dims to the
kernel's tile grid and strips the padding off the result.

The tiling comes from the shapes (``csb_tiling``). At the old fixed
tiling of 8 batch rows and one block a grid step, the kernel cost about
0.38 us a step on a TPU v5e in every served cell, at 8, 64 and 256
streams and at matrices 153 to 2,048 wide: a block's three dependent
dots cost about as much at 64 rows as at 8. So a step takes the whole
batch and a whole block-row, as far as the VMEM budget allows, and each
block is visited once a call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.csb_format import PaddedCSB
from repro.obs import metrics as obs_metrics
from .csb_mvm import csb_mvm_pallas, default_interpret

# Half of the 16 MiB of VMEM a kernel may use on a TPU v5e core unless it
# asks for more (Mosaic's default scoped limit): the other half is left
# to the body's temporaries (the gathered and scattered tiles, the bf16
# splits of the float32 dots), which Mosaic places itself.
_VMEM_BUDGET = 16 * 2**20 // 2


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM of a float32 (rows, cols) tile in Mosaic's (8, 128) layout."""
    return _round_up(rows, 8) * _round_up(cols, 128) * 4


def csb_vmem_bytes(batch_tile: int, group: int, block: tuple[int, int],
                   pm: int, pn: int) -> int:
    """VMEM one grid step of ``csb_mvm_pallas`` stages, counted at 4
    bytes an element (the widest dtype it takes): the x tile, the
    kernel values and the two index rows of ``group`` blocks and the
    output tile, each double-buffered, plus the accumulator."""
    bm, bn = block
    x = group * _tile_bytes(batch_tile, bn)
    w = group * (_tile_bytes(pm, pn) + _tile_bytes(1, pm)
                 + _tile_bytes(1, pn))
    out = _tile_bytes(batch_tile, bm)
    return 2 * (x + w + out) + out


def csb_tiling(b: int, grid: tuple[int, int], block: tuple[int, int],
               pm: int, pn: int) -> tuple[int, int]:
    """``(batch_tile, group)`` for a batch of ``b`` rows.

    The batch tile is the batch rounded up to 8, halved until one block
    a step fits ``_VMEM_BUDGET``; ``group`` is then the largest divisor
    of the block-columns ``Bc`` that fits. At every serving shape of the
    benchmark that is one batch tile and ``group == Bc``: one grid step
    per block-row."""
    bc = grid[1]

    def fits(tb: int, g: int) -> bool:
        return csb_vmem_bytes(tb, g, block, pm, pn) <= _VMEM_BUDGET

    tb = _round_up(max(b, 1), 8)
    while tb > 8 and not fits(tb, 1):
        tb = _round_up(tb // 2, 8)
    group = max(g for g in range(1, bc + 1) if bc % g == 0 and fits(tb, g))
    return tb, group


def pad_to_grid(x2: jax.Array, batch_tile: int, in_cols: int) -> jax.Array:
    """Pad a flattened (B, in_dim) batch to the kernel's tile grid:
    batch up to a batch_tile multiple, features up to the block grid's
    ``Bc * bn`` columns. Shared by the local and sharded entry points
    so their padding rules cannot diverge."""
    b = x2.shape[0]
    bp = _round_up(max(b, 1), batch_tile)
    return jnp.pad(x2, ((0, bp - b), (0, in_cols - x2.shape[-1])))


@functools.partial(jax.jit, static_argnames=("batch_tile", "group", "interpret"))
def _run(p: PaddedCSB, x2: jax.Array, batch_tile: int, group: int,
         interpret: bool) -> jax.Array:
    br, bc = p.grid
    bm, bn = p.block
    b = x2.shape[0]
    xp = pad_to_grid(x2, batch_tile, bc * bn)
    reg = obs_metrics.get()
    if reg is not None:       # at trace time: once per compiled product
        reg.histogram("kernel/csb/grid_steps").observe(
            xp.shape[0] // batch_tile * br * (bc // group))
    y = csb_mvm_pallas(
        p.vals, p.row_idx, p.col_idx, p.m, p.n, xp,
        grid=p.grid, block=p.block, batch_tile=batch_tile, group=group,
        interpret=interpret,
    )
    return y[:b, : p.shape[0]]


def csb_matvec(
    p: PaddedCSB,
    x: jax.Array,
    *,
    batch_tile: int | None = None,
    group: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """y = x @ W^T for CSB W;  x: (..., in_dim) -> (..., out_dim) fp32.

    ``batch_tile``/``group`` left None come from ``csb_tiling``: the
    whole batch and a whole block-row in one grid step where VMEM
    allows, since a (batch tile, block) visit costs ~0.38 us on a TPU
    v5e whether it holds 8 rows or 64. Given ones are honoured."""
    if interpret is None:
        interpret = default_interpret()
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if batch_tile is None or group is None:
        tb, g = csb_tiling(batch_tile or x2.shape[0], p.grid, p.block,
                           p.pm, p.pn)
        batch_tile, group = batch_tile or tb, group or g
    y = _run(p, x2, batch_tile, group, interpret)
    return y.reshape(*batch_shape, p.shape[0])
