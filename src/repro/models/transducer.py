"""Streaming RNN transducer (He et al. 2019, "Streaming End-to-end Speech
Recognition for Mobile Devices", arXiv:1811.06621).

- **Encoder:** a stack of recurrent cells over acoustic frames, with a
  time reduction after ``reduce_after`` layers: pairs of frames are
  concatenated, so the layers above run at half the frame rate. A frame
  left over at the end of a chunk waits in the stream's state for its
  partner in the next chunk.
- **Prediction network:** recurrent cells fed the embedding of the last
  non-blank label (blank at the start).
- **Joint network:** ``tanh(W_e e + W_p p + b)``, then an output layer
  over the vocabulary, blank among it.
- **Greedy decoding**, batched: per encoder frame up to ``max_symbols``
  label steps, each a prediction step, a joint and an argmax. A stream
  whose argmax is blank is done with the frame; its prediction state and
  last label stay as they were. All streams run every step (masked), so
  the step is one program over the batch.

Every cell goes through :func:`repro.cells.cell_apply`, so ``PaddedCSB``
weights run on the CSB kernel; the embedding and the joint are dense and
run at ``highest`` precision. The serving entry that carries the state
from chunk to chunk is :func:`repro.serve.rnnt_serve_frames`.

Parameters: ``{"encoder": [cell params], "prediction": [cell params],
"embed": (vocab, embed_dim), "joint": {"W_e": (joint, enc_dim), "W_p":
(joint, pred_dim), "b": (joint,), "W_out": (vocab, joint), "b_out":
(vocab,)}}``. Decode state: ``{"label": (B,) int32, "pred": [cell
states]}``, the prediction network's state *before* it has read
``label``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.cells import CellGraph, cell_apply, init_state, make_cell

HIGHEST = jax.lax.Precision.HIGHEST
PyTree = Any


@dataclasses.dataclass(frozen=True)
class Transducer:
    encoder: tuple[CellGraph, ...]
    prediction: tuple[CellGraph, ...]
    reduce_after: int        # encoder layers below the time reduction
    reduction: int           # frames concatenated into one
    vocab: int
    embed_dim: int
    joint_dim: int
    blank: int = 0
    max_symbols: int = 3     # label steps per encoder frame

    @property
    def key(self) -> tuple:
        """The structure, hashable (a graph is not)."""
        return (tuple(g.key for g in self.encoder),
                tuple(g.key for g in self.prediction), self.reduce_after,
                self.reduction, self.vocab, self.embed_dim, self.joint_dim,
                self.blank, self.max_symbols)


def make_transducer(input_dim: int, hidden: int, proj: int,
                    encoder_layers: int, reduce_after: int,
                    prediction_layers: int, vocab: int, embed_dim: int,
                    joint_dim: int, *, cell: str = "lnlstmp",
                    reduction: int = 2, blank: int = 0,
                    max_symbols: int = 3) -> Transducer:
    """Every layer a ``cell`` of ``hidden`` units projected to ``proj``;
    the layer above the reduction reads ``reduction * proj`` inputs."""
    if not 0 < reduce_after < encoder_layers:
        raise ValueError("the reduction lies between two encoder layers")
    enc = []
    for li in range(encoder_layers):
        n_in = (input_dim if li == 0 else
                reduction * proj if li == reduce_after else proj)
        enc.append(make_cell(cell, n_in, hidden, proj_dim=proj))
    pred = [make_cell(cell, embed_dim if li == 0 else proj, hidden,
                      proj_dim=proj) for li in range(prediction_layers)]
    return Transducer(tuple(enc), tuple(pred), reduce_after, reduction,
                      vocab, embed_dim, joint_dim, blank, max_symbols)


def time_reduce(frames: jax.Array, pending: jax.Array, factor: int):
    """Concatenate runs of ``factor`` frames: (T, B, D) after the
    ``pending`` (r, B, D) frames of the last chunk, r < factor, give
    ((T + r) // factor, B, factor * D) and the frames left over."""
    x = jnp.concatenate([pending, frames]) if pending.shape[0] else frames
    n = x.shape[0] // factor * factor
    t, b, d = x[:n].shape
    out = x[:n].reshape(t // factor, factor, b, d).transpose(0, 2, 1, 3)
    return out.reshape(t // factor, b, factor * d), x[n:]


def init_decode_state(model: Transducer, batch: int) -> PyTree:
    return {"label": jnp.full((batch,), model.blank, jnp.int32),
            "pred": [init_state(g, (batch,)) for g in model.prediction]}


def prediction_step(model: Transducer, params: PyTree, labels: jax.Array,
                    states: list) -> tuple[jax.Array, list]:
    """One step of the prediction network on ``labels`` (B,)."""
    y = params["embed"][labels]
    new = []
    for g, p, st in zip(model.prediction, params["prediction"], states):
        y, st = cell_apply(g, p, y, st)
        new.append(st)
    return y, new


def joint_logits(params: PyTree, enc_proj: jax.Array,
                 pred_out: jax.Array) -> jax.Array:
    """Logits over the vocabulary; ``enc_proj`` is ``W_e e + b``."""
    j = params["joint"]
    h = jnp.tanh(enc_proj + jnp.dot(pred_out, j["W_p"].T, precision=HIGHEST))
    return jnp.dot(h, j["W_out"].T, precision=HIGHEST) + j["b_out"]


def select_streams(mask: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """``new`` for the streams where ``mask`` (B,) holds, else ``old``,
    leaf by leaf (batch first)."""
    return jax.tree.map(lambda n, o: jnp.where(
        mask.reshape(-1, *[1] * (o.ndim - 1)), n, o), new, old)


def greedy_decode(model: Transducer, params: PyTree, enc: jax.Array,
                  state: PyTree) -> tuple[jax.Array, PyTree]:
    """Greedy decoding of encoder frames ``enc`` (T, B, enc_dim) from
    ``state``, in one program. Returns the choices (T, B, max_symbols)
    int32 (at each label step the argmax, blank included, or -1 where the
    stream had already taken blank in that frame) and the new state."""
    j = params["joint"]
    enc_proj = jnp.dot(enc, j["W_e"].T, precision=HIGHEST) + j["b"]

    def frame(carry, ep):
        def label_step(c, _):
            label, pred, active = c
            p, new = prediction_step(model, params, label, pred)
            y = jnp.argmax(joint_logits(params, ep, p), axis=-1)
            y = y.astype(jnp.int32)
            emit = active & (y != model.blank)
            pred = select_streams(emit, new, pred)
            return ((jnp.where(emit, y, label), pred, emit),
                    jnp.where(active, y, -1))

        label, pred = carry
        active = jnp.ones(label.shape, bool)
        (label, pred, _), choices = jax.lax.scan(
            label_step, (label, pred, active), None,
            length=model.max_symbols)
        return (label, pred), choices.T

    (label, pred), choices = jax.lax.scan(
        frame, (state["label"], state["pred"]), enc_proj)
    return choices, {"label": label, "pred": pred}
