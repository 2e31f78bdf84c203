"""Pure-jnp oracles for the CSB-MVM kernel.

``densify`` reconstructs the dense matrix from the padded CSB arrays with
one-hot scatter einsums; the matvec oracle is then an ordinary matmul.
These are the ground truth every Pallas kernel run is asserted against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.csb_format import PaddedCSB


def densify(p: PaddedCSB) -> jax.Array:
    """(out, in) dense matrix equal to the CSB contents."""
    nb, pm, pn = p.vals.shape
    br, bc = p.grid
    bm, bn = p.block
    rmask = (jnp.arange(pm)[None, :] < p.m[:, None]).astype(p.vals.dtype)
    cmask = (jnp.arange(pn)[None, :] < p.n[:, None]).astype(p.vals.dtype)
    roh = jax.nn.one_hot(p.row_idx, bm, dtype=p.vals.dtype) * rmask[..., None]
    coh = jax.nn.one_hot(p.col_idx, bn, dtype=p.vals.dtype) * cmask[..., None]
    # scatter kernel (Pm,Pn) into the (bm,bn) block frame
    blocks = jnp.einsum("bkr,bkl,blc->brc", roh, p.vals, coh,
                        precision=jax.lax.Precision.HIGHEST)
    w = blocks.reshape(br, bc, bm, bn).transpose(0, 2, 1, 3)
    w = w.reshape(br * bm, bc * bn)
    return w[: p.shape[0], : p.shape[1]]


def csb_mvm_ref(p: PaddedCSB, x: jax.Array) -> jax.Array:
    """y = x @ W^T with W the CSB matrix; x: (..., in_dim) -> (..., out_dim).

    Accumulates in fp32 at highest precision, like the kernel does.
    """
    w = densify(p).astype(jnp.float32)
    y = jnp.einsum("...i,oi->...o", x.astype(jnp.float32), w,
                   precision=jax.lax.Precision.HIGHEST)
    return y.astype(x.dtype)
