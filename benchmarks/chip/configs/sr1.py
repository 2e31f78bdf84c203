"""SR1 (paper Table 1): LSTMP 153->1024 (projection 512), then LSTMP
512->1024 (projection 512), every 2-D weight CSB-pruned, float32.

- ``program_params``: the served weights, built on the device from the
  seed in one jitted call, in the program's ``PaddedCSB`` format.
- ``dense_params`` + ``reference``: the same weights as dense float32
  matrices and a plain LSTMP written out by hand, at ``highest``
  precision. ``reference(..., mode="bf16x3")`` is the control: the same
  arithmetic with every product in three bf16 passes, the precision one
  step below the configuration's ``highest``.
- ``csb_work``: the operations and bytes each CSB product needs, from the
  configuration's shapes and survivor counts, never from padded tensors.

Every run serves the same work: the kernel sizes ``m x n`` of each block
come from ``structure_seed`` (the paper's Algorithm 1 on a Gaussian
matrix); which rows and columns survive in a block, and every value,
come from the run's seed.
"""
from __future__ import annotations

import functools
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
GATES = "ifog"
# one precision step below the configuration's float32 at highest
CONTROL_MODE = "bf16x3"
# the CSB pallas_call as the profiler names it (its kernel function)
CSB_KERNEL = re.compile(r"^csb_mvm_pallas$")


def _key(cfg: dict) -> str:
    return json.dumps({k: cfg[k] for k in
                       ("layers", "compression", "block", "pad_to",
                        "structure_seed")}, sort_keys=True)


def layer_shapes(layer: dict) -> dict[str, tuple[int, ...]]:
    """Weight names and shapes of one LSTMP layer, (out, in) for MVMs."""
    n_in, hid, proj = layer["n_input"], layer["n_hidden"], layer["proj"]
    shapes = {}
    for g in GATES:
        shapes[f"W_{g}"] = (hid, n_in)
        shapes[f"U_{g}"] = (hid, proj)
        shapes[f"b_{g}"] = (hid,)
    shapes["W_proj"] = (proj, hid)
    return shapes


def _prune(blk: np.ndarray, keep_r: int, keep_c: int):
    """The paper's Algorithm 1 on blocks ``blk`` (br, bc, bm, bn).
    RowPrune: per block-column, the ``keep_r`` strongest rows over all
    block-rows; ColumnPrune: per block-row, the ``keep_c`` strongest
    columns of what is left. Returns the row and column masks."""
    br, bc, bm, bn = blk.shape
    rn = (blk ** 2).sum(3).transpose(1, 0, 2).reshape(bc, -1)
    rmask = _top(rn, keep_r).reshape(bc, br, bm).transpose(1, 0, 2)
    cn = ((blk * rmask[..., None]) ** 2).sum(2).reshape(br, -1)
    return rmask, _top(cn, keep_c).reshape(br, bc, bn)


@functools.lru_cache(maxsize=None)
def _structure(key: str) -> list[dict]:
    """Per layer, per 2-D weight: block grid and survivor counts. Each
    matrix keeps as near as it can to 1/compression of its real weights:
    the share of rows and of columns kept (of the real ones, never of
    the padded block grid) is the largest whose survivors do not exceed
    that, found by bisection."""
    cfg = json.loads(key)
    bm, bn = cfg["block"]
    rng = np.random.default_rng(cfg["structure_seed"])
    out = []
    for layer in cfg["layers"]:
        st = {}
        for name, shape in sorted(layer_shapes(layer).items()):
            if len(shape) != 2:
                continue
            rows, cols = shape
            br, bc = -(-rows // bm), -(-cols // bn)
            w = np.zeros((br * bm, bc * bn))
            w[:rows, :cols] = rng.standard_normal(shape)
            blk = w.reshape(br, bm, bc, bn).transpose(0, 2, 1, 3)
            target = rows * cols / cfg["compression"]

            def masks(keep):
                return _prune(blk, max(round(keep * rows), 1),
                              max(round(keep * cols), 1))

            lo, hi = 0.0, 1.0
            for _ in range(24):
                mid = (lo + hi) / 2
                rm, cm = masks(mid)
                nnz = (rm.sum(-1) * cm.sum(-1)).sum()
                lo, hi = (mid, hi) if nnz <= target else (lo, mid)
            rmask, cmask = masks(lo)
            # zero padding has the least norm: survivors lie in the matrix
            assert not (rmask & (blk == 0).all(3)).any()
            m = rmask.sum(-1).reshape(-1).astype(np.int32)
            n = cmask.sum(-1).reshape(-1).astype(np.int32)
            pad = cfg["pad_to"]
            st[name] = dict(
                shape=shape, grid=(br, bc), block=(bm, bn), m=m, n=n,
                pm=max(-(-int(m.max()) // pad) * pad, pad),
                pn=max(-(-int(n.max()) // pad) * pad, pad))
        out.append(st)
    return out


def _top(scores: np.ndarray, k: int) -> np.ndarray:
    """Mask of the ``k`` largest entries of each row (ties by position)."""
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=-1)
    return mask


def structure(cfg: dict) -> list[dict]:
    return _structure(_key(cfg))


def _lanes(count: np.ndarray, width: int, valid: np.ndarray, pad: int,
           key) -> jax.Array:
    """Per block, ``count`` distinct sorted lanes out of the first
    ``valid``; lanes past ``count`` are 0, as in the program's format."""
    nb, bw = count.shape[0], width
    u = jax.random.uniform(key, (nb, bw))
    u = jnp.where(jnp.arange(bw)[None, :] < valid[:, None], u, 2.0)
    pick = jnp.argsort(u, axis=1)[:, :pad]
    live = jnp.arange(pad)[None, :] < count[:, None]
    pick = jnp.sort(jnp.where(live, pick, bw), axis=1)
    return jnp.where(pick == bw, 0, pick).astype(jnp.int32)


def _draw_matrix(key, st: dict, scale: float):
    (br, bc), (bm, bn) = st["grid"], st["block"]
    rows, cols = st["shape"]
    valid_r = np.repeat(np.minimum(bm, rows - np.arange(br) * bm), bc)
    valid_c = np.tile(np.minimum(bn, cols - np.arange(bc) * bn), br)
    kr, kc, kv = jax.random.split(key, 3)
    ridx = _lanes(st["m"], bm, valid_r, st["pm"], kr)
    cidx = _lanes(st["n"], bn, valid_c, st["pn"], kc)
    live = ((jnp.arange(st["pm"])[None, :, None] < st["m"][:, None, None])
            & (jnp.arange(st["pn"])[None, None, :]
               < st["n"][:, None, None]))
    vals = jax.random.normal(kv, (br * bc, st["pm"], st["pn"]), F32)
    return jnp.where(live, vals * scale, 0.0), ridx, cidx


def _draw(key, cfg_key: str) -> list[dict]:
    """Every layer's weights: (vals, row_idx, col_idx) per matrix."""
    cfg = json.loads(cfg_key)
    layers = []
    for li, (layer, st) in enumerate(zip(cfg["layers"],
                                         _structure(cfg_key))):
        k_layer = jax.random.fold_in(key, li)
        ws = {}
        for i, (name, shape) in enumerate(sorted(layer_shapes(layer).items())):
            k = jax.random.fold_in(k_layer, i)
            if len(shape) == 1:
                ws[name] = 0.1 * jax.random.normal(k, shape, F32)
            else:
                scale = math.sqrt(cfg["compression"] / shape[1])
                ws[name] = _draw_matrix(k, st[name], scale)
        layers.append(ws)
    return layers


@functools.lru_cache(maxsize=None)
def _program_fn(cfg_key: str):
    from repro.core import PaddedCSB

    sts = _structure(cfg_key)

    def build(key):
        out = []
        for ws, st in zip(_draw(key, cfg_key), sts):
            p = {}
            for name, w in ws.items():
                if name not in st:
                    p[name] = w
                    continue
                s = st[name]
                vals, ridx, cidx = w
                p[name] = PaddedCSB(
                    vals=vals, row_idx=ridx, col_idx=cidx,
                    m=jnp.asarray(s["m"]), n=jnp.asarray(s["n"]),
                    shape=tuple(s["shape"]), grid=tuple(s["grid"]),
                    block=tuple(s["block"]))
            out.append(p)
        return out

    return jax.jit(build)


def program_params(cfg: dict, key) -> list[dict]:
    """The served weights: per layer, name -> PaddedCSB or bias."""
    return _program_fn(_key(cfg))(key)


@functools.lru_cache(maxsize=None)
def _dense_fn(cfg_key: str):
    sts = _structure(cfg_key)

    def build(key):
        out = []
        for ws, st in zip(_draw(key, cfg_key), sts):
            p = {}
            for name, w in ws.items():
                if name not in st:
                    p[name] = w
                    continue
                s = st[name]
                vals, ridx, cidx = w
                (br, bc), (bm, bn) = s["grid"], s["block"]
                bi = np.repeat(np.arange(br), bc)[:, None]
                bj = np.tile(np.arange(bc), br)[:, None]
                rows = bi * bm + ridx                       # (NB, Pm)
                cols = bj * bn + cidx                       # (NB, Pn)
                dense = jnp.zeros((br * bm, bc * bn), F32).at[
                    rows[:, :, None], cols[:, None, :]].add(vals)
                p[name] = dense[:s["shape"][0], :s["shape"][1]]
            out.append(p)
        return out

    return jax.jit(build)


def dense_params(cfg: dict, key) -> list[dict]:
    """The same weights as dense float32 (out, in) matrices."""
    return _dense_fn(_key(cfg))(key)


def _mv(w, v, mode: str):
    """v @ w.T in the given arithmetic."""
    if mode == "highest":
        return jnp.dot(v, w.T, precision=HIGHEST)
    if mode == "bf16x3":
        def split(a):
            hi = a.astype(jnp.bfloat16).astype(F32)
            return hi, (a - hi).astype(jnp.bfloat16).astype(F32)
        (vh, vl), (wh, wl) = split(v), split(w)
        return (jnp.dot(vh, wh.T, precision=HIGHEST)
                + jnp.dot(vh, wl.T, precision=HIGHEST)
                + jnp.dot(vl, wh.T, precision=HIGHEST))
    if mode == "bf16":
        return jnp.dot(v.astype(jnp.bfloat16), w.T.astype(jnp.bfloat16),
                       preferred_element_type=F32)
    raise ValueError(mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def lstmp_layer(p: dict, xs: jax.Array, mode: str = "highest"):
    """Dense LSTMP over (T, B, in) from a zero state, written out by hand:
    i, f, o = sigmoid(W x + U h + b); c' = f c + i tanh(W_g x + U_g h +
    b_g); h' = W_proj (o tanh(c'))."""
    def step(carry, x):
        h, c = carry

        def gate(k):
            return (_mv(p[f"W_{k}"], x, mode) + _mv(p[f"U_{k}"], h, mode)
                    + p[f"b_{k}"])

        i, f, o = (jax.nn.sigmoid(gate(k)) for k in "ifo")
        c = f * c + i * jnp.tanh(gate("g"))
        h = _mv(p["W_proj"], o * jnp.tanh(c), mode)
        return (h, c), h

    b = xs.shape[1]
    init = (jnp.zeros((b, p["W_proj"].shape[0]), F32),
            jnp.zeros((b, p["W_i"].shape[0]), F32))
    return jax.lax.scan(step, init, xs)[1]


def reference(dense: list[dict], xs: jax.Array,
              mode: str = "highest") -> jax.Array:
    """Both layers over (T, B, n_input) frames: the last layer's output."""
    for p in dense:
        xs = lstmp_layer(p, xs, mode)
    return xs


def matrix_work(m, n, shape, streams: int) -> tuple[int, int]:
    """(operations, bytes) of one CSB product over ``streams`` streams,
    from the blocks' survivor counts ``m`` x ``n``: 2 nnz B operations;
    the survivor values, their row and column indices, x and y, four
    bytes each. Padding in the device format is not work."""
    m = np.asarray(m, np.int64)
    n = np.asarray(n, np.int64)
    nnz = int((m * n).sum())
    rows, cols = shape
    return (2 * nnz * streams,
            4 * (nnz + int(m.sum() + n.sum()) + streams * (rows + cols)))


def csb_work(cfg: dict, streams: int) -> list[tuple[int, int]]:
    """``matrix_work`` of every CSB product in one frame step."""
    return [matrix_work(s["m"], s["n"], s["shape"], streams)
            for st in structure(cfg) for s in st.values()]


def survivors(cfg: dict) -> int:
    return sum(int((s["m"].astype(np.int64) * s["n"]).sum())
               for st in structure(cfg) for s in st.values())
