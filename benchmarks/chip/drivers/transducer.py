"""Streaming recognition through ``repro.serve.rnnt_serve_frames``: each
chunk of every stream through the transducer's encoder and greedy
decode, every state carried from chunk to chunk.

Traffic (``traffic/<mix>.json``), as ``frames.py`` reads it: ``streams``
utterances are served side by side, each ``utterance_frames`` input
frames long, sent as chunks of ``chunk_frames`` frames; a chunk goes out
when the previous one has come back with its labels (a closed loop).
``distinct_utterances`` batches of utterances are drawn from the seed
and served in turn; at the start of each the state is fresh again.

Every encoder output and every label choice of the window is kept and,
once the window has closed, compared with the configuration's plain
reference: ``rnnt_enc_max_abs_err``, the encoder outputs against the
reference's; ``rnnt_max_logit_gap``, the reference teacher-forced along
each pass's served labels and blanks, the widest gap of a served choice
below the reference's best logit at its step.
"""
from __future__ import annotations

import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np


class State:
    pass


def model_of(cfg: dict):
    from repro.models.transducer import make_transducer

    return make_transducer(
        cfg["input_dim"], cfg["n_hidden"], cfg["proj"],
        cfg["encoder_layers"], cfg["reduce_after"],
        cfg["prediction_layers"], cfg["vocab"], cfg["embed_dim"],
        cfg["joint_dim"], reduction=cfg["reduction"], blank=cfg["blank"],
        max_symbols=cfg["max_symbols"])


def setup(cell, seed: int) -> State:
    from harness import seed_key
    from repro.serve import rnnt_serve_frames  # noqa: F401 (fails early)

    cfg, t, mod = cell.config, cell.traffic, cell.config_mod
    s = State()
    s.cell = cell
    s.model = model_of(cfg)
    for g, layer in zip(s.model.encoder + s.model.prediction,
                        mod.layers(cfg)):
        if dict(g.weight_shapes()) != mod.layer_shapes(layer):
            raise ValueError("the program's cell and the configuration "
                             "disagree on the weights")
    s.k_weights, k_frames = jax.random.split(seed_key(seed))
    s.params = mod.program_params(cfg, s.k_weights)
    u, c, r = t["utterance_frames"], t["chunk_frames"], cfg["reduction"]
    if u % c or c % r:
        raise ValueError("an utterance is a whole number of chunks, and a "
                         "chunk of encoder frames")
    shape = (t["distinct_utterances"], u, t["streams"], cfg["input_dim"])
    s.frames = jax.jit(
        lambda k: jax.random.normal(k, shape, jnp.float32))(k_frames)
    s.chunks = [[s.frames[i, j:j + c] for j in range(0, u, c)]
                for i in range(shape[0])]
    jax.block_until_ready((s.params, s.chunks))
    # warm-up: the first chunk of an utterance, then one with state
    st = None
    for x in s.chunks[0][:2]:
        _, _, st = _chunk(s, x, st)
    return s


def _chunk(s: State, x, st, span=None):
    from repro.serve import rnnt_serve_frames

    if span is None:
        out = rnnt_serve_frames(s.model, s.params, x, st)
    else:
        with span("rnnt_serve_frames"):
            out = rnnt_serve_frames(s.model, s.params, x, st)
    return jax.block_until_ready(out)


def window(s: State, seconds: float, span) -> dict:
    """Chunks back to back until ``seconds`` have passed; whole chunks."""
    t, cfg = s.cell.traffic, s.cell.config
    per_utt = len(s.chunks[0])
    s.outputs = []            # (utterance pass, chunk, choices, encoder)
    attempted = failed = k = 0
    st = None
    while True:
        u, c = divmod(k, per_utt)
        if c == 0:
            st = None
        attempted += 1
        try:
            choices, enc, st = _chunk(s, s.chunks[u % len(s.chunks)][c], st,
                                      span)
        except Exception:  # a call that fails ends the window
            traceback.print_exc()
            failed += 1
            break
        s.outputs.append((u, c, choices, enc))
        k += 1
        span.unit_done()
        if span.elapsed() >= seconds:
            break
    chunk = t["chunk_frames"]
    enc_steps = chunk // cfg["reduction"]
    unit = {"frame_steps": chunk, "enc_steps": enc_steps,
            "label_steps": enc_steps * cfg["max_symbols"]}
    return {"attempted": attempted, "failed": failed,
            "frame_steps": k * chunk, "streams": t["streams"],
            "enc_steps": k * enc_steps,
            "label_steps": k * unit["label_steps"],
            "units": [unit] * k}


def release(s: State) -> None:
    """Free the program's weights; the outputs stay for the check."""
    s.params = s.chunks = None


def check(s: State, out: dict, seed: int) -> list[dict]:
    enc_err, gap = errors(s)
    print(f"transducer: {label_rate(s)!r} labels per encoder frame",
          file=sys.stderr)
    lim = s.cell.config["limits"]
    return [{"name": "rnnt_enc_max_abs_err", "value": enc_err,
             "limit": lim["rnnt_enc_max_abs_err"]},
            {"name": "rnnt_max_logit_gap", "value": gap,
             "limit": lim["rnnt_max_logit_gap"]}]


def control(s: State, out: dict, seed: int) -> dict:
    """Each compared number of the program and of the control: the
    reference in the program's place, one precision step below the
    configuration's."""
    prog = errors(s)
    ctl = errors(s, s.cell.config_mod.CONTROL_MODE)
    return {"rnnt_enc_max_abs_err": (prog[0], ctl[0]),
            "rnnt_max_logit_gap": (prog[1], ctl[1])}


def label_rate(s: State) -> float:
    """Labels other than blank per encoder frame per stream."""
    blank = s.cell.config["blank"]
    n = sum(int(((np.asarray(ch) >= 0) & (np.asarray(ch) != blank)).sum())
            for _, _, ch, _ in s.outputs)
    frames = sum(ch.shape[0] * ch.shape[1] for _, _, ch, _ in s.outputs)
    return n / frames if frames else float("nan")


def errors(s: State, control_mode: str | None = None) -> tuple[float, float]:
    """(largest |encoder - reference|, widest logit gap) over the window:
    of the served outputs or, with ``control_mode``, of the reference in
    that arithmetic in the program's place."""
    if not s.outputs:
        return float("nan"), float("nan")
    mod, cfg = s.cell.config_mod, s.cell.config
    dense = mod.dense_params(cfg, s.k_weights)
    n = s.frames.shape[0]
    want = {u: mod.encode(cfg, dense, s.frames[u])
            for u in sorted({u % n for u, _, _, _ in s.outputs})}
    if control_mode:
        ctl = {u: mod.encode(cfg, dense, s.frames[u], control_mode)
               for u in want}
        ctl_choices = {u: mod.greedy(cfg, dense, e, control_mode)
                       for u, e in ctl.items()}
    per = s.cell.traffic["chunk_frames"] // cfg["reduction"]
    errs, passes = [], {}
    for u, c, choices, enc in s.outputs:
        sl = slice(c * per, (c + 1) * per)
        got = ctl[u % n][sl] if control_mode else enc
        errs.append(float(jnp.abs(got - want[u % n][sl]).max()))
        ch = ctl_choices[u % n][sl] if control_mode else choices
        passes.setdefault(u, []).append(np.asarray(ch))
    # each pass's choices, teacher-forced once per distinct sequence
    gaps, seen = [], set()
    for u, chunks in passes.items():
        ch = np.concatenate(chunks)
        key = (u % n, ch.tobytes())
        if key in seen:
            continue
        seen.add(key)
        enc_ref = want[u % n]
        full = np.full((enc_ref.shape[0], *ch.shape[1:]), -1, np.int32)
        full[:ch.shape[0]] = ch
        live = jnp.arange(enc_ref.shape[0]) < ch.shape[0]
        gaps.append(mod.forced_gap(cfg, dense, enc_ref, full, live))
    # a NaN anywhere is the reading (max() would drop it)
    return float(np.max(errs)), float(np.max(gaps))
