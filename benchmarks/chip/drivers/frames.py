"""Frame serving through ``repro.serve.rnn_serve_frames``, one call per
layer per chunk, each layer's state carried from chunk to chunk.

Traffic (``traffic/<mix>.json``): ``streams`` utterances are served side
by side, each ``utterance_frames`` long, sent as chunks of
``chunk_frames`` frames; a chunk goes out when the previous one has come
back from the last layer (a closed loop). ``distinct_utterances`` batches
of utterances are drawn from the seed and served in turn; at the start
of each a layer's state is zero again.

Every output of the window is kept and compared, once the window has
closed, with the configuration's plain reference over the same frames.
"""
from __future__ import annotations

import traceback

import jax
import jax.numpy as jnp


class State:
    pass


def setup(cell, seed: int) -> State:
    from harness import seed_key
    from repro.cells import make_cell

    cfg, t, mod = cell.config, cell.traffic, cell.config_mod
    s = State()
    s.cell = cell
    s.k_weights, k_frames = jax.random.split(seed_key(seed))
    s.graphs = []
    for layer in cfg["layers"]:
        g = make_cell(layer["cell"], layer["n_input"], layer["n_hidden"],
                      proj_dim=layer.get("proj"))
        if dict(g.weight_shapes()) != mod.layer_shapes(layer):
            raise ValueError("the program's cell and the configuration "
                             "disagree on the weights")
        s.graphs.append(g)
    s.params = mod.program_params(cfg, s.k_weights)
    u, c = t["utterance_frames"], t["chunk_frames"]
    if u % c:
        raise ValueError("an utterance is a whole number of chunks")
    shape = (t["distinct_utterances"], u, t["streams"],
             cfg["layers"][0]["n_input"])
    s.frames = jax.jit(
        lambda k: jax.random.normal(k, shape, jnp.float32))(k_frames)
    # the chunks the window sends, cut here so that the window only calls
    s.chunks = [[s.frames[i, j:j + c] for j in range(0, u, c)]
                for i in range(shape[0])]
    jax.block_until_ready((s.params, s.chunks))
    # warm-up: the first chunk of an utterance, then one with state
    st = [None] * len(s.graphs)
    for x in s.chunks[0][:2]:
        _chunk(s, x, st)
    return s


def _chunk(s: State, x, st: list, span=None):
    """One chunk through every layer; ``st`` is updated in place."""
    from repro.serve import rnn_serve_frames

    y = x
    for li, (g, p) in enumerate(zip(s.graphs, s.params)):
        if span is None:
            y, st[li], _ = rnn_serve_frames(g, p, y, st[li])
        else:
            with span(f"rnn_serve_frames.layer{li + 1}"):
                y, st[li], _ = rnn_serve_frames(g, p, y, st[li])
    return jax.block_until_ready(y)


def window(s: State, seconds: float, span) -> dict:
    """Chunks back to back until ``seconds`` have passed; whole chunks.
    ``span`` is the harness's ``Spans``: it times the window."""
    per_utt = len(s.chunks[0])
    s.outputs = []            # (utterance pass, chunk index, output)
    attempted = failed = 0
    k = 0
    st = [None] * len(s.graphs)
    while True:
        u, c = divmod(k, per_utt)
        if c == 0:
            st = [None] * len(s.graphs)
        attempted += len(s.graphs)
        try:
            y = _chunk(s, s.chunks[u % len(s.chunks)][c], st, span)
        except Exception:  # a call that fails ends the window
            traceback.print_exc()
            failed += 1
            break
        s.outputs.append((u, c, y))
        k += 1
        span.unit_done()
        if span.elapsed() >= seconds:
            break
    chunk = s.cell.traffic["chunk_frames"]
    return {"attempted": attempted, "failed": failed,
            "frame_steps": k * chunk, "streams": s.cell.traffic["streams"],
            "units": [{"frame_steps": chunk}] * k}


def release(s: State) -> None:
    """Free the program's weights; the outputs stay for the check."""
    s.params = s.chunks = None


def check(s: State, out: dict, seed: int) -> list[dict]:
    """Largest |served - reference| over every output of the window."""
    return [{"name": "frames_max_abs_err", "value": max_err(s),
             "limit": s.cell.config["limits"]["frames_max_abs_err"]}]


def control(s: State, out: dict, seed: int) -> dict:
    """The compared number of the program and of the control: the
    reference in the program's place, one precision step below the
    configuration's."""
    return {"frames_max_abs_err": (
        max_err(s), max_err(s, s.cell.config_mod.CONTROL_MODE))}


def max_err(s: State, control_mode: str | None = None) -> float:
    """Largest gap between the served outputs (or, with
    ``control_mode``, the reference in that arithmetic) and the
    reference at the configuration's precision."""
    if not s.outputs:
        return float("nan")
    mod, cfg = s.cell.config_mod, s.cell.config
    dense = mod.dense_params(cfg, s.k_weights)
    n = s.frames.shape[0]
    used = sorted({u % n for u, _, _ in s.outputs})
    want = {u: mod.reference(dense, s.frames[u]) for u in used}
    ctl = ({u: mod.reference(dense, s.frames[u], control_mode)
            for u in used} if control_mode else None)
    chunk = s.cell.traffic["chunk_frames"]
    err = 0.0
    for u, c, y in s.outputs:
        sl = slice(c * chunk, (c + 1) * chunk)
        got = ctl[u % n][sl] if ctl else y
        e = float(jnp.abs(got - want[u % n][sl]).max())
        if e != e:                       # NaN: nothing to compare
            return e
        err = max(err, e)
    return err
