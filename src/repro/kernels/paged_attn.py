"""Pallas paged-attention decode kernel (vLLM PagedAttention-style).

The paged serve path keeps every slot's KV in a shared page pool
``(N_pages, P, ...)`` indexed through a dense ``(slots, max_pages)``
int32 page table (``serve.paging.PagePool.device_table``). The XLA
fallback (``models.layers.paged_gather``) materializes each slot's
logical extent as a ``(B, max_pages*P, ...)`` gather in HBM before
every decode attention — exactly the kind of indirection CSB-RNN's
kernel co-design removes from the hot loop (PAPER.md §IV–V).

This kernel walks the page table *inside* the Pallas program instead:
grid ``(slots,)``, one program per decode slot, each step reading its
row of the table and dynamic-slicing pages straight out of the pool
ref into VMEM. No ``(B, max_pages*P)`` array ever exists in the traced
program — the test suite asserts the gather shape is absent from the
kernel path's jaxpr.

Numerics mirror the fallback exactly: scores are computed per KV group
in fp32 (``preferred_element_type``), masked to the slot's true length
with ``kpos <= pos`` (optional sliding ``window``), softmaxed over the
full logical extent, then contracted against the value pages. Garbage
rows (inactive slots mapped to the scratch page, pad pages past a
slot's extent) fall outside the mask and underflow to exactly 0, same
as the gather path.

MLA routes through the same kernel via the optional rope score term:
``q2``/``k2_pool`` add ``q2 . k2`` to the (compressed-latent) scores,
and the value pool is the ``c_kv`` pool itself — standard MHA with one
KV group and a value width different from the key width.

``interpret`` selection mirrors ``csb_mvm.default_interpret``: the TPU
compiles, the CPU interprets, and the CI golden lane
(REPRO_FORCE_TPU_INTERPRET=1) takes the compiled branch under
``pltpu.force_tpu_interpret_mode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .csb_mvm import default_interpret

F32 = jnp.float32


def _kernel(q_ref, tab_ref, pos_ref, *rest, rep: int, scale: float,
            window: int | None, has_rope: bool):
    """One grid step = one decode slot's attention over its pages."""
    if has_rope:
        q2_ref, k_ref, v_ref, k2_ref, o_ref = rest
    else:
        k_ref, v_ref, o_ref = rest
        q2_ref = k2_ref = None
    mp = tab_ref.shape[1]
    psz = k_ref.shape[1]
    kv = k_ref.shape[2]
    t = mp * psz
    pos = pos_ref[0, 0]

    # walk the page table: dynamic-slice each mapped page out of the
    # pool ref (VMEM-resident per slot, never a (B, T) HBM gather)
    k_pages, v_pages, k2_pages = [], [], []
    for j in range(mp):
        pg = tab_ref[0, j]
        k_pages.append(k_ref[pl.ds(pg, 1)][0])       # (P, KV, D)
        v_pages.append(v_ref[pl.ds(pg, 1)][0])       # (P, KV, Dv)
        if has_rope:
            k2_pages.append(k2_ref[pl.ds(pg, 1)][0])
    kcat = jnp.concatenate(k_pages, axis=0)          # (T, KV, D)
    vcat = jnp.concatenate(v_pages, axis=0)          # (T, KV, Dv)
    k2cat = jnp.concatenate(k2_pages, axis=0) if has_rope else None

    kpos = jax.lax.broadcasted_iota(jnp.int32, (rep, t), 1)
    mask = kpos <= pos
    if window is not None:
        mask &= kpos > pos - window

    outs = []
    for g in range(kv):
        qg = q_ref[0, g * rep:(g + 1) * rep, :].astype(kcat.dtype)
        kg = kcat[:, g, :]                           # (T, D)
        sc = jax.lax.dot_general(
            qg, kg, (((1,), (1,)), ((), ())),
            preferred_element_type=F32)              # (rep, T)
        if has_rope:
            q2g = q2_ref[0, g * rep:(g + 1) * rep, :].astype(k2cat.dtype)
            sc = sc + jax.lax.dot_general(
                q2g, k2cat[:, g, :], (((1,), (1,)), ((), ())),
                preferred_element_type=F32)
        sc = jnp.where(mask, sc * scale, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jax.lax.dot_general(
            p.astype(vcat.dtype), vcat[:, g, :], (((1,), (0,)), ((), ())),
            preferred_element_type=F32))             # (rep, Dv)
    o_ref[0] = jnp.concatenate(outs, axis=0)         # (H, Dv)


def paged_attn_decode(
    q: jax.Array,            # (B, H, D)
    k_pool: jax.Array,       # (N, P, KV, D)
    v_pool: jax.Array,       # (N, P, KV, Dv)
    page_table: jax.Array,   # (B, max_pages) int32
    pos,                     # scalar or (B,) decode positions
    *,
    scale: float,
    q2: jax.Array | None = None,       # (B, H, D2) rope query (MLA)
    k2_pool: jax.Array | None = None,  # (N, P, KV, D2) rope key pool
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-slot paged decode attention; returns (B, H, Dv) fp32.

    ``pos`` is the position being decoded this step, scalar (whole
    batch at one depth) or (B,) (continuous batching); key positions
    ``kpos <= pos`` attend, everything else — pad pages, scratch-page
    garbage of inactive slots — masks to exactly 0.
    """
    if interpret is None:
        interpret = default_interpret()
    b, h, _ = q.shape
    n, psz, kv = k_pool.shape[:3]
    mp = page_table.shape[1]
    dv = v_pool.shape[-1]
    assert h % kv == 0, (h, kv)
    rep = h // kv
    has_rope = q2 is not None
    assert has_rope == (k2_pool is not None)

    pos2 = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))
    table = jnp.asarray(page_table, jnp.int32)

    args = [q, table, pos2]
    in_specs = [
        pl.BlockSpec((1, h, q.shape[-1]), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, mp), lambda i: (i, 0)),
        pl.BlockSpec((1, 1), lambda i: (i, 0)),
    ]
    if has_rope:
        args.append(q2)
        in_specs.append(
            pl.BlockSpec((1, h, q2.shape[-1]), lambda i: (i, 0, 0)))
    # pools ride in whole (index map pinned to block 0) so the kernel
    # can dynamic-slice arbitrary pages out of them
    for pool in (k_pool, v_pool) + ((k2_pool,) if has_rope else ()):
        args.append(pool)
        in_specs.append(pl.BlockSpec(
            pool.shape, lambda *_, nd=pool.ndim: (0,) * nd))

    out = pl.pallas_call(
        functools.partial(_kernel, rep=rep, scale=scale, window=window,
                          has_rope=has_rope),
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, dv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dv), F32),
        interpret=interpret,
    )(*args)
    return out


__all__ = ["paged_attn_decode"]
