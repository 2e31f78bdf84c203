"""Pallas TPU kernel for CSB matrix-vector/matrix multiplication.

Computes ``Y = X @ W^T`` where ``W`` is a CSB-pruned matrix held in the
padded device format (`PaddedCSB`): per block a dense kernel matrix
``(Pm, Pn)`` plus within-block survivor indices.

TPU adaptation of the paper's CSB-Engine (DESIGN.md §2):

* The FPGA engine gathers input neurons by ColIdx through a buffer port and
  scatter-accumulates by RowIdx. TPUs have no cheap random access out of
  VMEM, so both indirections become **one-hot matmuls** that run on the
  MXU: ``gather = X_blk @ C`` with ``C[l, k] = (col_idx[k] == l)`` and
  ``scatter = Yk @ R^T`` with ``R[j, k] = (row_idx[k] == j)``.
* inner-block parallelism  -> the (TB, Pn) x (Pn, Pm) kernel matmul;
* inter-block parallelism  -> the grid over block-rows x batch tiles, with
  the block-column dimension folded into a sequential accumulation axis
  (the standard TPU reduction-in-grid pattern);
* the WeightBuffer         -> BlockSpec-staged VMEM tiles.

Operand layout (Mosaic wants the last two dims of every block divisible
by (8, 128) or equal to the array's own):

* ``x`` goes in block-major as ``(Bc, B, bn)`` and the output comes out
  as ``(Br, B, bm)``, so a tile's trailing dims are ``(batch_tile, bn)``
  / ``(batch_tile, bm)`` and every block width (32, 64, 128, ...) is
  legal; the wrapper transposes the activations around the call;
* the survivor indices carry a unit axis, ``(Br, Bc, 1, P)``, so each
  block's index vector is a full-width ``(1, P)`` row. The true kernel
  dims ``m``/``n`` are folded into them before the call: pad lanes get
  index -1, which no one-hot lane matches, so no count operand is
  staged at all.

Workload balance across grid cells is the *scheduler's* job
(engine/schedule.py); this kernel executes whatever block layout it is
handed, masking pad lanes so padded FLOPs never corrupt results.

Grid: ``(batch_tiles, Br, Bc/G)`` — the last axis accumulates into a
VMEM scratch tile (minor-most, so the accumulator stays resident) and
stores the output block once, on the final column step. A step runs
its G blocks in a loop. All three dots run at ``Precision.HIGHEST`` so
the kernel is an fp32 matvec on the MXU.

The tiling is the caller's: ``ops.csb_tiling`` picks it from the shapes.
On a TPU v5e a block's three dependent dots cost about 0.4-0.5 us at 8
batch rows and hardly more at 64, far more than their MXU work: at 8
rows a tile the kernel's time was its count of (batch tile, block)
visits. So the chosen tiling takes the whole batch and a whole
block-row (G = Bc) a step where VMEM allows: one batch tile, grid
``(1, Br, 1)``, each block visited and its values read once a call.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract both operands' dim 1


def default_interpret() -> bool:
    """Interpret-mode default by backend: the TPU compiles the kernel
    with Mosaic; any other backend (the CPU of tests and CI) interprets.
    The CI golden lane sets REPRO_FORCE_TPU_INTERPRET=1: the TPU branch
    (interpret=False) is then taken on the CPU too, under
    ``pltpu.force_tpu_interpret_mode`` (tests/conftest.py enters it),
    which emulates the Mosaic lowering."""
    if os.environ.get("REPRO_FORCE_TPU_INTERPRET", "0") not in ("", "0"):
        return False
    return jax.default_backend() != "tpu"


def _kernel(x_ref, vals_ref, ridx_ref, cidx_ref, o_ref, acc_ref):
    """One grid step: TB batch rows x one block-row x G blocks.

    The G blocks run as a loop (``fori_loop``, so the body and its
    compile time do not grow with G), each adding its product into the
    accumulator in block-column order. The block-column reduction
    (grid axis 2) accumulates into the VMEM scratch ``acc_ref`` —
    persistent across grid steps that revisit the same output tile — and
    ``o_ref`` is stored exactly once, on the final column step. The
    output ref is never read, so the kernel does not rely on
    sequential-grid read-modify-write semantics."""
    jc = pl.program_id(2)

    @pl.when(jc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    group, _, bn = x_ref.shape
    pm, pn = vals_ref.shape[-2:]
    bm = o_ref.shape[-1]

    def block(g, acc):
        # ---- gather input neurons by ColIdx (one-hot matmul on MXU) ----
        xs = x_ref[g].astype(jnp.float32)                        # (TB, bn)
        coh = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (bn, pn), 0)
            == cidx_ref[0, g], 1.0, 0.0)                         # (bn, Pn)
        xg = jnp.dot(xs, coh, precision=_HI,
                     preferred_element_type=jnp.float32)         # (TB, Pn)

        # ---- dense kernel-matrix MVM (the paper's inner-block work) ----
        kmat = vals_ref[0, g].astype(jnp.float32)                # (Pm, Pn)
        yk = jax.lax.dot_general(
            xg, kmat, _NT, precision=_HI,
            preferred_element_type=jnp.float32)                  # (TB, Pm)

        # ---- scatter to output rows by RowIdx --------------------------
        roh = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (bm, pm), 0)
            == ridx_ref[0, g], 1.0, 0.0)                         # (bm, Pm)
        return acc + jax.lax.dot_general(
            yk, roh, _NT, precision=_HI,
            preferred_element_type=jnp.float32)                  # (TB, bm)

    acc_ref[...] = jax.lax.fori_loop(0, group, block, acc_ref[...])

    @pl.when(jc == pl.num_programs(2) - 1)
    def _store():
        o_ref[0] = acc_ref[...]


def _live_idx(idx: jax.Array, count: jax.Array) -> jax.Array:
    """(NB, P) survivor indices with lanes ``>= count`` set to -1."""
    lane = jnp.arange(idx.shape[-1], dtype=jnp.int32)
    return jnp.where(lane[None, :] < count[:, None], idx, -1)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "block", "batch_tile", "group", "interpret"),
)
def csb_mvm_pallas(
    vals: jax.Array,      # (NB, Pm, Pn)
    row_idx: jax.Array,   # (NB, Pm)
    col_idx: jax.Array,   # (NB, Pn)
    m: jax.Array,         # (NB,)
    n: jax.Array,         # (NB,)
    x: jax.Array,         # (B, Bc*bn) — already padded to the block grid
    *,
    grid: tuple[int, int],
    block: tuple[int, int],
    batch_tile: int = 128,
    group: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, Br*bm) fp32. ``group`` = blocks fused per grid step.

    ``interpret=None`` resolves from ``jax.default_backend()``: the TPU
    compiles the kernel, other backends keep interpret mode."""
    if interpret is None:
        interpret = default_interpret()
    br, bc = grid
    bm, bn = block
    nb, pm, pn = vals.shape
    assert nb == br * bc, (nb, grid)
    assert bc % group == 0, (bc, group)
    b = x.shape[0]
    assert b % batch_tile == 0, (b, batch_tile)
    if not interpret and batch_tile % 8 and batch_tile != b:
        raise ValueError(
            f"batch_tile={batch_tile} breaks the TPU tiling rule: a "
            f"block's second-minor dim must be divisible by 8 or equal "
            f"the array's ({b} rows here)")

    xb = x.reshape(b, bc, bn).transpose(1, 0, 2)             # (Bc, B, bn)
    vals4 = vals.reshape(br, bc, pm, pn)
    ridx4 = _live_idx(row_idx, m).reshape(br, bc, 1, pm)
    cidx4 = _live_idx(col_idx, n).reshape(br, bc, 1, pn)

    out = pl.pallas_call(
        _kernel,
        grid=(b // batch_tile, br, bc // group),
        in_specs=[
            pl.BlockSpec((group, batch_tile, bn),
                         lambda t, i, j: (j, t, 0)),
            pl.BlockSpec((1, group, pm, pn), lambda t, i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, group, 1, pm), lambda t, i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, group, 1, pn), lambda t, i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, batch_tile, bm),
                               lambda t, i, j: (i, t, 0)),
        out_shape=jax.ShapeDtypeStruct((br, b, bm), jnp.float32),
        scratch_shapes=[pltpu.VMEM((batch_tile, bm), jnp.float32)],
        interpret=interpret,
        # the op's name in HLO and in a profile, whatever wraps the call
        name="csb_mvm_pallas",
    )(xb, vals4, ridx4, cidx4)
    return out.transpose(1, 0, 2).reshape(b, br * bm)
