"""The streaming RNN transducer of He et al. 2019 (arXiv:1811.06621) at
its published widths: an encoder of 8 LN-LSTMP layers (2,048 cells, 640
projection) with a time reduction by 2 after layer 2, a prediction
network of 2 such layers over a label embedding, and a joint network of
640 units over 4,096 wordpieces. Every LSTMP weight is CSB-pruned in the
form of ``sr1.py`` (the paper's Algorithm 1, a fixed structure seed);
the embedding, the layer norms and the joint are dense. Float32.

- ``program_params``: the served weights, drawn on the host from the
  seed (no program to compile) and put on the device in the program's
  format.
- ``dense_params``: the same weights as dense float32 matrices;
  ``encode``, ``greedy`` and ``forced_gap`` are the plain reference
  written out by hand at ``highest`` precision, each also in the
  control's arithmetic (``mode="bf16x3"``, every product in three bf16
  passes, one precision step below the configuration's).
- ``csb_work`` and ``run_work``: the operations and bytes of each CSB
  product, from the survivor counts; ``dense_ops``: the joint's.
"""
from __future__ import annotations

import functools
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import harness

SR1 = harness.load_module(Path(__file__).with_name("sr1.py"))

F32 = jnp.float32
GATES = "ifog"
CONTROL_MODE = "bf16x3"
CSB_KERNEL = re.compile(r"^csb_mvm_pallas$")
matrix_work = SR1.matrix_work


def layers(cfg: dict) -> list[dict]:
    """Every LN-LSTMP layer in order: the encoder's, then the prediction
    network's; ``rate`` says how often it runs (``frame``: every input
    frame, ``enc``: every encoder frame, ``label``: every label step)."""
    hid, proj, ra = cfg["n_hidden"], cfg["proj"], cfg["reduce_after"]
    out = []
    for li in range(cfg["encoder_layers"]):
        n_in = (cfg["input_dim"] if li == 0 else
                cfg["reduction"] * proj if li == ra else proj)
        out.append({"n_input": n_in, "n_hidden": hid, "proj": proj,
                    "rate": "frame" if li < ra else "enc"})
    for li in range(cfg["prediction_layers"]):
        out.append({"n_input": cfg["embed_dim"] if li == 0 else proj,
                    "n_hidden": hid, "proj": proj, "rate": "label"})
    return out


def layer_shapes(layer: dict) -> dict[str, tuple[int, ...]]:
    """Weight names and shapes of one LN-LSTMP layer, (out, in) for
    products: the LSTMP's matrices, and a gain and bias per layer norm
    (the gates' four, whose biases are the gates' biases, and c's)."""
    shapes = {k: v for k, v in SR1.layer_shapes(layer).items()
              if len(v) == 2}
    for k in (*GATES, "c"):
        shapes[f"ln_{k}_g"] = shapes[f"ln_{k}_b"] = (layer["n_hidden"],)
    return shapes


def _sr1_cfg(cfg: dict) -> dict:
    return {"layers": [{k: v for k, v in layer.items() if k != "rate"}
                       for layer in layers(cfg)],
            **{k: cfg[k] for k in ("compression", "block", "pad_to",
                                   "structure_seed")}}


@functools.lru_cache(maxsize=None)
def _structure(key: str) -> list[dict]:
    """``sr1.py``'s structure, mask for mask (a test holds them equal),
    in a fifth of the time at these widths: the blocks' squared weights
    and the order of their rows are taken once, not once a bisection
    step; the masks of a pair of kept counts are made once; the column
    norms are a batched product."""
    cfg = json.loads(key)
    bm, bn = cfg["block"]
    rng = np.random.default_rng(cfg["structure_seed"])
    out = []
    for layer in cfg["layers"]:
        st = {}
        for name, shape in sorted(SR1.layer_shapes(layer).items()):
            if len(shape) != 2:
                continue
            rows, cols = shape
            br, bc = -(-rows // bm), -(-cols // bn)
            w = np.zeros((br * bm, bc * bn))
            w[:rows, :cols] = rng.standard_normal(shape)
            sq = w.reshape(br, bm, bc, bn).transpose(0, 2, 1, 3) ** 2
            rn = sq.sum(3).transpose(1, 0, 2).reshape(bc, -1)
            row_order = np.argsort(-rn, axis=-1, kind="stable")
            target = rows * cols / cfg["compression"]

            @functools.cache
            def kept(keep_r: int, keep_c: int):
                rmask = np.zeros(rn.shape, bool)
                np.put_along_axis(rmask, row_order[:, :keep_r], True, -1)
                rmask = rmask.reshape(bc, br, bm).transpose(1, 0, 2)
                cn = np.matmul(rmask[:, :, None, :].astype(sq.dtype), sq)
                return rmask, SR1._top(cn.reshape(br, -1), keep_c).reshape(
                    br, bc, bn)

            def masks(keep):
                return kept(max(round(keep * rows), 1),
                            max(round(keep * cols), 1))

            lo, hi = 0.0, 1.0
            for _ in range(24):
                mid = (lo + hi) / 2
                rm, cm = masks(mid)
                nnz = (rm.sum(-1) * cm.sum(-1)).sum()
                lo, hi = (mid, hi) if nnz <= target else (lo, mid)
            rmask, cmask = masks(lo)
            m = rmask.sum(-1).reshape(-1).astype(np.int32)
            n = cmask.sum(-1).reshape(-1).astype(np.int32)
            pad = cfg["pad_to"]
            st[name] = dict(
                shape=shape, grid=(br, bc), block=(bm, bn), m=m, n=n,
                pm=max(-(-int(m.max()) // pad) * pad, pad),
                pn=max(-(-int(n.max()) // pad) * pad, pad))
        out.append(st)
    return out


def structure(cfg: dict) -> list[dict]:
    """Per layer, per matrix: block grid and survivor counts, as
    ``sr1.py`` gives them for these layers."""
    return _structure(SR1._key(_sr1_cfg(cfg)))


def _lanes(rng, count: np.ndarray, width: int, valid: np.ndarray,
           pad: int) -> np.ndarray:
    """Per block, ``count`` distinct sorted lanes out of the first
    ``valid``; lanes past ``count`` are 0, as in the program's format."""
    lane = np.arange(width)
    u = np.where(lane < valid[:, None], rng.random((len(count), width)), 2.0)
    pick = np.argsort(u, axis=1)[:, :pad]
    live = np.arange(pad) < count[:, None]
    pick = np.sort(np.where(live, pick, width), axis=1)
    return np.where(pick == width, 0, pick).astype(np.int32)


def _matrix(rng, st: dict, scale: float):
    """One matrix's survivors: values (NB, Pm, Pn), zero past each
    block's counts, and their row and column lanes."""
    (br, bc), (bm, bn) = st["grid"], st["block"]
    rows, cols = st["shape"]
    m, n = st["m"], st["n"]
    valid_r = np.repeat(np.minimum(bm, rows - np.arange(br) * bm), bc)
    valid_c = np.tile(np.minimum(bn, cols - np.arange(bc) * bn), br)
    ridx = _lanes(rng, m, bm, valid_r, st["pm"])
    cidx = _lanes(rng, n, bn, valid_c, st["pn"])
    live = ((np.arange(st["pm"])[None, :, None] < m[:, None, None])
            & (np.arange(st["pn"])[None, None, :] < n[:, None, None]))
    vals = rng.standard_normal(live.shape, np.float32) * np.float32(scale)
    return np.where(live, vals, np.float32(0)), ridx, cidx


def _densify(st: dict, vals, ridx, cidx) -> np.ndarray:
    (br, bc), (bm, bn) = st["grid"], st["block"]
    rows = (np.repeat(np.arange(br), bc)[:, None] * bm + ridx)[:, :, None]
    cols = (np.tile(np.arange(bc), br)[:, None] * bn + cidx)[:, None, :]
    live = vals != 0
    w = np.zeros((br * bm, bc * bn), np.float32)
    w[np.broadcast_to(rows, vals.shape)[live],
      np.broadcast_to(cols, vals.shape)[live]] = vals[live]
    return w[:st["shape"][0], :st["shape"][1]]


def _weights(cfg: dict, key, dense: bool) -> dict:
    """Every weight, drawn on the host from ``key`` (a raw JAX key): the
    LSTMP matrices as survivors (or dense), the rest dense. Each weight
    has a generator of its own, so the two forms agree."""
    words = [int(w) for w in np.asarray(key).ravel()]

    def gen(*path):
        return np.random.default_rng([*words, *path])

    def normal(shape, scale, *path):
        return gen(*path).standard_normal(shape, np.float32) \
            * np.float32(scale)

    out = []
    for li, (layer, st) in enumerate(zip(layers(cfg), structure(cfg))):
        ws = {}
        for i, (name, shape) in enumerate(sorted(layer_shapes(layer).items())):
            if len(shape) == 1:        # gain 1 + 0.1 N(0, 1), bias 0.1 N
                ws[name] = normal(shape, 0.1, li, i) \
                    + np.float32(name.endswith("_g"))
                continue
            w = _matrix(gen(li, i), st[name],
                        math.sqrt(cfg["compression"] / shape[1]))
            ws[name] = _densify(st[name], *w) if dense else w
        out.append(ws)
    j, v, proj, e = (cfg[k] for k in ("joint_dim", "vocab", "proj",
                                      "embed_dim"))
    top = len(out)
    b_out = normal((v,), 0.1, top, 5)
    b_out[cfg["blank"]] = cfg["blank_bias"]
    n_enc = cfg["encoder_layers"]
    tree = {"encoder": out[:n_enc], "prediction": out[n_enc:],
            "embed": normal((v, e), 1.0, top, 0),
            "joint": {"W_e": normal((j, proj), proj ** -0.5, top, 1),
                      "W_p": normal((j, proj), proj ** -0.5, top, 2),
                      "b": normal((j,), 0.1, top, 3),
                      "W_out": normal((v, j), j ** -0.5, top, 4),
                      "b_out": b_out}}
    return tree


def program_params(cfg: dict, key) -> dict:
    """The served weights: every LSTMP matrix a ``PaddedCSB``."""
    from repro.core import PaddedCSB

    p = _weights(cfg, key, dense=False)
    for st, ws in zip(structure(cfg), p["encoder"] + p["prediction"]):
        for name, s in st.items():
            vals, ridx, cidx = ws[name]
            ws[name] = PaddedCSB(
                vals=vals, row_idx=ridx, col_idx=cidx, m=s["m"], n=s["n"],
                shape=tuple(s["shape"]), grid=tuple(s["grid"]),
                block=tuple(s["block"]))
    return jax.device_put(p)


def dense_params(cfg: dict, key) -> dict:
    """The same weights, every matrix dense float32 (out, in)."""
    return jax.device_put(_weights(cfg, key, dense=True))


# -- the plain reference -------------------------------------------------

def _ln(x):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                               + 1e-5)


def lnlstmp_step(p: dict, x, h, c, mode: str):
    """One LN-LSTMP step (Ba et al. 2016's layer norm, as ``assumed``
    places it): each gate LN(W x + U h) * gain + bias, then its
    nonlinearity; c' = f c + i g; h' = W_proj (o tanh(LN(c')))."""
    def gate(k):
        s = SR1._mv(p[f"W_{k}"], x, mode) + SR1._mv(p[f"U_{k}"], h, mode)
        return _ln(s) * p[f"ln_{k}_g"] + p[f"ln_{k}_b"]

    i, f, o = (jax.nn.sigmoid(gate(k)) for k in "ifo")
    c = f * c + i * jnp.tanh(gate("g"))
    m = o * jnp.tanh(_ln(c) * p["ln_c_g"] + p["ln_c_b"])
    return SR1._mv(p["W_proj"], m, mode), c


def _zero_state(p: dict, batch: int):
    return (jnp.zeros((batch, p["W_proj"].shape[0]), F32),
            jnp.zeros((batch, p["W_i"].shape[0]), F32))


@functools.partial(jax.jit, static_argnames=("mode",))
def _layer(p: dict, xs, mode: str):
    def step(carry, x):
        h, c = lnlstmp_step(p, x, *carry, mode)
        return (h, c), h
    return jax.lax.scan(step, _zero_state(p, xs.shape[1]), xs)[1]


def encode(cfg: dict, dense: dict, xs, mode: str = "highest"):
    """The encoder over whole utterances (T, B, input_dim), T a multiple
    of the reduction, from a zero state: (T / reduction, B, proj)."""
    r = cfg["reduction"]
    for li, p in enumerate(dense["encoder"]):
        if li == cfg["reduce_after"]:
            xs = jnp.concatenate([xs[k::r] for k in range(r)], axis=-1)
        xs = _layer(p, xs, mode)
    return xs


def _predict(dense: dict, label, states, mode: str):
    y, new = dense["embed"][label], []
    for p, (h, c) in zip(dense["prediction"], states):
        h, c = lnlstmp_step(p, y, h, c, mode)
        y = h
        new.append((h, c))
    return y, new


def _logits(dense: dict, e, p_out, mode: str):
    j = dense["joint"]
    h = jnp.tanh(SR1._mv(j["W_e"], e, mode) + SR1._mv(j["W_p"], p_out, mode)
                 + j["b"])
    return SR1._mv(j["W_out"], h, mode) + j["b_out"]


@functools.partial(jax.jit, static_argnames=("blank", "steps", "mode"))
def _decode(dense: dict, enc, forced, live, blank: int, steps: int,
            mode: str):
    """Greedy decoding from a zero state, or, where ``forced`` (T, B,
    steps) is given, along those choices. Returns the choices taken and,
    per label step, how far the taken choice's logit lies below the best
    (0 where no step ran; inf where ``forced`` breaks the decoding's
    rules). Frames where ``live`` (T,) is false are skipped."""
    b = enc.shape[1]
    states = [_zero_state(p, b) for p in dense["prediction"]]
    init = (jnp.full((b,), blank, jnp.int32), states)

    def frame(carry, inp):
        e, f, ok = inp
        label, states = carry
        active = jnp.ones((b,), bool) & ok
        choices, gaps = [], []
        for k in range(steps):
            p_out, new = _predict(dense, label, states, mode)
            lg = _logits(dense, e, p_out, mode)
            y = jnp.argmax(lg, -1).astype(jnp.int32) if f is None else f[:, k]
            # a forced step where none runs, or none where one runs
            bad = False if f is None else ok & (active != (y >= 0))
            taken = jnp.take_along_axis(lg, jnp.maximum(y, 0)[:, None], -1)
            gap = jnp.where(active, lg.max(-1) - taken[:, 0], 0.0)
            gaps.append(jnp.where(bad, jnp.inf, gap))
            choices.append(jnp.where(active, y, -1))
            emit = active & (y >= 0) & (y != blank)
            states = jax.tree.map(
                lambda n, o: jnp.where(emit[:, None], n, o), new, states)
            label = jnp.where(emit, y, label)
            active = emit
        return (label, states), (jnp.stack(choices, -1),
                                 jnp.stack(gaps, -1))

    _, (choices, gaps) = jax.lax.scan(frame, init, (enc, forced, live))
    return choices, gaps


def greedy(cfg: dict, dense: dict, enc, mode: str = "highest"):
    """The reference's own greedy choices (T, B, max_symbols)."""
    live = jnp.ones((enc.shape[0],), bool)
    return _decode(dense, enc, None, live, cfg["blank"], cfg["max_symbols"],
                   mode)[0]


def forced_gap(cfg: dict, dense: dict, enc, choices, live=None) -> float:
    """The reference teacher-forced along ``choices`` (T, B, max_symbols)
    from a zero state: the widest gap of a choice below the reference's
    best logit at its step, over the frames where ``live`` is true."""
    if live is None:
        live = jnp.ones((enc.shape[0],), bool)
    gaps = _decode(dense, enc, jnp.asarray(choices, jnp.int32), live,
                   cfg["blank"], cfg["max_symbols"], "highest")[1]
    return float(gaps.max())


# -- counts ----------------------------------------------------------------

def csb_work(cfg: dict, streams: int) -> list[tuple[int, int]]:
    """``matrix_work`` of every CSB matrix, once each (encoder, then
    prediction network)."""
    return [matrix_work(s["m"], s["n"], s["shape"], streams)
            for st in structure(cfg) for s in st.values()]


def run_work(cfg: dict, streams: int, frame_steps: int, enc_steps: int,
             label_steps: int) -> list[tuple[int, int]]:
    """(operations, bytes) of the CSB products run over ``frame_steps``
    input frames, ``enc_steps`` encoder frames and ``label_steps`` label
    steps of ``streams`` streams."""
    runs = {"frame": frame_steps, "enc": enc_steps, "label": label_steps}
    return [(runs[layer["rate"]] * f, runs[layer["rate"]] * b)
            for layer, st in zip(layers(cfg), structure(cfg))
            for f, b in (matrix_work(s["m"], s["n"], s["shape"], streams)
                         for s in st.values())]


def dense_ops(cfg: dict, streams: int, enc_steps: int,
              label_steps: int) -> int:
    """The joint's products: W_e once an encoder frame; W_p and the
    output layer every label step."""
    j, proj = cfg["joint_dim"], cfg["proj"]
    return 2 * streams * (enc_steps * j * proj
                          + label_steps * (j * proj + cfg["vocab"] * j))


def survivors(cfg: dict) -> int:
    return sum(int((s["m"].astype(np.int64) * s["n"]).sum())
               for st in structure(cfg) for s in st.values())


def parameters(cfg: dict) -> dict[str, int]:
    """Dense parameter counts at these widths, by part."""
    def count(ls):
        return sum(int(np.prod(s)) for layer in ls
                   for s in layer_shapes(layer).values())
    ls = layers(cfg)
    j, v = cfg["joint_dim"], cfg["vocab"]
    out = {"encoder": count(ls[:cfg["encoder_layers"]]),
           "prediction": count(ls[cfg["encoder_layers"]:])
           + v * cfg["embed_dim"],
           "joint": 2 * j * cfg["proj"] + j + v * j + v}
    out["total"] = sum(out.values())
    return out


if __name__ == "__main__":
    cfg = json.loads(Path(__file__).with_suffix(".json").read_text())
    print(json.dumps({"parameters": parameters(cfg),
                      "survivors": survivors(cfg)}))
