"""Streaming RNN-T serving: chunks of acoustic frames in, labels out.

``rnnt_serve_frames`` serves one chunk of every stream through a
:class:`repro.models.transducer.Transducer`: each encoder layer through
the frame server (``rnn_serve_frames``, one jitted step a frame), the
time reduction between the layers below it and the layers above it, then
the greedy decode of the chunk's encoder frames in one jitted program.
Every state (the encoder's cells, a frame waiting for its partner in the
reduction, the prediction network and the last label) is carried from
chunk to chunk.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transducer as T
from repro.obs import metrics as obs_metrics, trace as obs_trace

from . import engine

PyTree = Any


def init_rnnt_state(model: T.Transducer, batch: int) -> PyTree:
    """The state of ``batch`` streams that have heard nothing yet."""
    below = model.encoder[model.reduce_after - 1]
    return {"encoder": [None] * len(model.encoder),
            "pending": jnp.zeros(
                (0, batch, below.op(below.output).shape[0]), jnp.float32),
            "decode": T.init_decode_state(model, batch)}


def _decode_program(model: T.Transducer):
    """The jitted greedy decode of ``model``, built once per structure
    and kernel mode (the frame server's step cache); its program is
    ``jit_rnnt_decode`` in a profile."""
    from repro.core.csb_linear import _active_model_mesh
    from repro.kernels import ops

    def rnnt_decode(params, enc, state):
        return T.greedy_decode(model, params, enc, state)

    key = ("rnnt/decode", model.key, T.greedy_decode, _active_model_mesh(),
           ops.default_interpret())
    (fn, _), _ = engine.cached_program(key, lambda: jax.jit(rnnt_decode))
    return fn


def rnnt_serve_frames(model: T.Transducer, params: PyTree, frames,
                      state: PyTree | None = None):
    """frames: (T, B, input_dim), one chunk of every stream. Returns the
    label choices (T_enc, B, max_symbols) int32 of the chunk's encoder
    frames (see :func:`repro.models.transducer.greedy_decode`: a label,
    blank, or -1 where no step ran), the encoder outputs (T_enc, B,
    enc_dim) and the state to pass with the next chunk (None: a fresh
    stream). T_enc is (T + frames pending from the last chunk) //
    reduction; a chunk that completes no encoder frame decodes nothing.

    Spans: ``serve/rnnt/call`` around it all, ``serve/rnnt/encoder``
    around the encoder layers below and above the reduction (each layer a
    frame-server call, ending when its outputs are ready),
    ``serve/rnnt/reduce`` and ``serve/rnnt/decode`` (ending when the
    labels are ready). Counters: ``serve/rnnt/labels``, labels other than
    blank emitted over every stream, and ``serve/rnnt/label_steps``,
    label steps run (each a prediction step, a joint and an argmax over
    the whole batch)."""
    with obs_trace.span("serve/rnnt/call"):
        frames = jnp.asarray(frames)
        if state is None:
            state = init_rnnt_state(model, frames.shape[1])
        enc = list(state["encoder"])
        n_enc = len(model.encoder)

        def encode(x, layers):
            with obs_trace.span("serve/rnnt/encoder"):
                for li in layers:
                    x, enc[li], _ = engine.rnn_serve_frames(
                        model.encoder[li], params["encoder"][li], x, enc[li])
            return x

        y = encode(frames, range(model.reduce_after))
        with obs_trace.span("serve/rnnt/reduce"):
            y, pending = T.time_reduce(y, state["pending"], model.reduction)
        dec = state["decode"]
        if y.shape[0]:
            y = encode(y, range(model.reduce_after, n_enc))
            with obs_trace.span("serve/rnnt/decode"):
                choices, dec = _decode_program(model)(params, y, dec)
                jax.block_until_ready(choices)
        else:
            top = model.encoder[-1]
            y = jnp.zeros((0, frames.shape[1],
                           top.op(top.output).shape[0]), jnp.float32)
            choices = jnp.zeros((0, frames.shape[1], model.max_symbols),
                                jnp.int32)
        reg = obs_metrics.get()
        if reg is not None:
            c = np.asarray(choices)
            reg.counter("serve/rnnt/labels").inc(
                int(((c >= 0) & (c != model.blank)).sum()))
            reg.counter("serve/rnnt/label_steps").inc(
                c.shape[0] * model.max_symbols)
    return choices, y, {"encoder": enc, "pending": pending, "decode": dec}
