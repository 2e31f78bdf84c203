"""CSB-MVM Pallas kernel accounting (replaces paper Fig. 11's FPGA
resource table with the TPU-relevant quantities): VMEM working set per
grid step at the tiling ``csb_matvec`` picks, padded-vs-true FLOPs
across block sizes / pruning rates, and interpret-mode allclose latency
vs the jnp oracle.

Also benches the Pallas paged-attention decode kernel
(``kernel/paged_attn/decode``, GATED — see benchmarks/diff.py) against
the XLA ``paged_gather`` fallback it replaces (informational oracle
row, allclose-checked).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CSBSpec, csb_masks, csb_project, padded_csb_from_dense
from repro.kernels import paged_attn_decode
from repro.kernels.ops import csb_matvec, csb_tiling, csb_vmem_bytes
from repro.kernels.ref import csb_mvm_ref
from repro.models.layers import paged_gather
from .common import emit, synthetic_rnn_weight, timed


def _paged_attn_rows() -> None:
    """Paged decode attention: the kernel walks the page table in-VMEM;
    the fallback materializes a (B, max_pages*P) HBM gather per step."""
    b, h, kv, d, psz, mp = 8, 8, 4, 64, 16, 8
    n_pages = b * mp
    scale = 1.0 / d ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    k_pool = jax.random.normal(ks[0], (n_pages + 1, psz, kv, d))
    v_pool = jax.random.normal(ks[1], (n_pages + 1, psz, kv, d))
    q = jax.random.normal(ks[2], (b, h, d))
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, mp)
    pos = jnp.full((b,), mp * psz - 2, jnp.int32)

    @jax.jit
    def gather_ref(q, kp, vp, tab, pos):
        kg = paged_gather(kp, tab)                  # (B, T, KV, D)
        vg = paged_gather(vp, tab)
        rep = h // kv
        qh = q.reshape(b, kv, rep, d)
        sc = jnp.einsum("bgrd,bkgd->bgrk", qh, kg,
                        preferred_element_type=jnp.float32)
        mask = jnp.arange(kg.shape[1])[None, :] <= pos[:, None]
        sc = jnp.where(mask[:, None, None, :], sc * scale, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bgrk,bkgd->bgrd", p, vg,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, h, d)

    ker = jax.jit(lambda *a: paged_attn_decode(*a, scale=scale))
    y_ref, t_ref = timed(lambda: gather_ref(q, k_pool, v_pool, table, pos),
                         iters=5, reduce="min")
    y_ker, t_ker = timed(lambda: ker(q, k_pool, v_pool, table, pos),
                         iters=5, reduce="min")
    err = float(jnp.max(jnp.abs(y_ker - y_ref)))
    # the /decode row joins the diff.py gate family (with the /mvm rows)
    emit("kernel/paged_attn/decode", t_ker,
         f"T={mp * psz};slots={b};allclose_err={err:.2e}")
    emit("kernel/paged_attn/gather_oracle", t_ref,
         f"gathered_mb={(2 * b * mp * psz * kv * d * 4) / 2**20:.2f}")
    assert err < 1e-3


def run() -> None:
    _paged_attn_rows()
    key = jax.random.PRNGKey(23)
    w = synthetic_rnn_weight(key, (1024, 1024))
    x = jax.random.normal(key, (8, 1024))
    for bm in (32, 64, 128):
        for rate in (0.75, 0.9):
            spec = CSBSpec(bm=bm, bn=bm, prune_rate=rate)
            z = csb_project(w, spec)
            rm, cm = csb_masks(w, spec)
            p = padded_csb_from_dense(
                np.asarray(z), bm, bm, pad_to=8,
                row_mask=np.asarray(rm), col_mask=np.asarray(cm))
            pad_ratio = p.padded_flops_per_mvm() / max(
                p.true_flops_per_mvm(), 1)
            tiling = csb_tiling(x.shape[0], p.grid, p.block, p.pm, p.pn)
            vb = csb_vmem_bytes(*tiling, p.block, p.pm, p.pn)
            y_ref, t_ref = timed(lambda: csb_mvm_ref(p, x))
            y_ker, t_ker = timed(lambda: csb_matvec(p, x), iters=5,
                                 reduce="min")
            err = float(jnp.max(jnp.abs(y_ker - y_ref)))
            tag = f"kernel/b{bm}/r{int(rate*100)}"
            # /mvm is the row benchmarks/diff.py gates on (kernel latency
            # proper); the oracle/static rows are informational
            emit(f"{tag}/mvm", t_ker, f"pad_flop_ratio={pad_ratio:.3f}")
            emit(f"{tag}/pad_flop_ratio", 0.0, f"{pad_ratio:.3f}")
            emit(f"{tag}/vmem_kb", 0.0, f"{vb/1024:.1f}")
            emit(f"{tag}/oracle", t_ref, f"allclose_err={err:.2e}")
            assert err < 1e-3


if __name__ == "__main__":
    run()
