"""Serving paths: batched generate + frame-by-frame RNN serving."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.cells import init_params, init_state, make_cell
from repro.core import CSBSpec, csb_masks, csb_project, padded_csb_from_dense
from repro.models import ModelConfig, init_params as lm_init
from repro.dist import Rules, use_rules
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.serve import EngineConfig, engine, generate, rnn_serve_frames
from repro.serve.engine import make_frame_step

CFG = ModelConfig(name="tiny", mixer="attn", ffn="swiglu", n_layers=2,
                  d_model=32, n_heads=2, n_kv=2, head_dim=16, d_ff=64,
                  vocab=50, dtype="float32", logit_chunk=16, remat=False)


def test_generate_greedy_deterministic():
    params = lm_init(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 50)
    out1 = generate(params, CFG, prompt, EngineConfig(max_new_tokens=6))
    out2 = generate(params, CFG, prompt, EngineConfig(max_new_tokens=6))
    assert out1.shape == (2, 14)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.max()) < 50


def test_generate_matches_teacher_forcing():
    """Greedy generation must agree with running prefill on the grown
    sequence at every step (cache correctness through the serve loop)."""
    from repro.models import prefill
    params = lm_init(jax.random.PRNGKey(3), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 6), 0, 50)
    out = np.asarray(generate(params, CFG, prompt,
                              EngineConfig(max_new_tokens=4)))
    seq = prompt
    for i in range(4):
        logits, _ = prefill(params, {"tokens": jnp.asarray(seq)}, CFG)
        nxt = int(jnp.argmax(logits[0]))
        assert nxt == out[0, 6 + i], (i, nxt, out)
        seq = np.concatenate([np.asarray(seq), [[nxt]]], axis=1)


def test_rnn_serve_frames_csb():
    cell = make_cell("lstm", 16, 32)
    params = init_params(cell, jax.random.PRNGKey(5))
    spec = CSBSpec(bm=8, bn=8, prune_rate=0.5)
    csb_params = {}
    for k, w in params.items():
        if w.ndim == 2:
            z = csb_project(w, spec)
            rm, cm = csb_masks(w, spec)
            csb_params[k] = padded_csb_from_dense(
                np.asarray(z), 8, 8, row_mask=np.asarray(rm),
                col_mask=np.asarray(cm))
        else:
            csb_params[k] = w
    frames = jax.random.normal(jax.random.PRNGKey(6), (5, 2, 16))
    outs, st, us = rnn_serve_frames(cell, csb_params, frames)
    assert outs.shape == (5, 2, 32)
    assert np.isfinite(np.asarray(outs)).all()
    assert us > 0


# ---------------------------------------------------------------------------
# the frame server's step cache
# ---------------------------------------------------------------------------

@pytest.fixture
def step_cache(monkeypatch):
    """An empty step cache and a live metrics registry for one test."""
    monkeypatch.setattr(engine, "_step_cache", collections.OrderedDict())
    reg = obs_metrics.enable()
    yield reg
    obs_metrics.disable()


def _lookups(reg) -> tuple[int, int]:
    """(misses, hits) of the step cache so far."""
    return tuple(int(reg.counter(f"serve/frames/step_cache/{k}").value)
                 for k in ("miss", "hit"))


def _dense_lstm(hidden: int = 16, seed: int = 2):
    cell = make_cell("lstm", 8, hidden)
    return cell, init_params(cell, jax.random.PRNGKey(seed))


def test_frame_step_cache_hit_matches_fresh_step(step_cache):
    cell, params = _dense_lstm()
    frames = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 8))
    state = init_state(cell, (2,))
    runs = [rnn_serve_frames(cell, params, frames, state)
            for _ in range(2)]
    assert _lookups(step_cache) == (1, 1)
    # the same program as a step built fresh, bit for bit
    step, st = make_frame_step(cell), state
    want = []
    for t in range(frames.shape[0]):
        y, st = step(params, st, frames[t])
        want.append(y)
    for outs, st_out, _ in runs:
        np.testing.assert_array_equal(np.asarray(outs),
                                      np.asarray(jnp.stack(want)))
        for k in st:
            np.testing.assert_array_equal(np.asarray(st_out[k]),
                                          np.asarray(st[k]))


@pytest.mark.parametrize("variant, want", [
    ("same_structure", (1, 1)),
    ("other_structure", (2, 0)),
    ("interpret_patched", (2, 0)),
    ("cell_apply_swapped", (2, 0)),
])
def test_frame_step_cache_key(step_cache, monkeypatch, variant, want):
    cell, params = _dense_lstm()
    frames = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 8))
    rnn_serve_frames(cell, params, frames)
    if variant == "same_structure":       # another object, equal graph
        cell = make_cell("lstm", 8, 16)
    elif variant == "other_structure":
        cell, params = _dense_lstm(hidden=24)
    elif variant == "interpret_patched":  # dense weights: either mode runs
        interpret = ops.default_interpret()
        monkeypatch.setattr(ops, "default_interpret", lambda: not interpret)
    else:
        real = engine.cell_apply
        monkeypatch.setattr(engine, "cell_apply", lambda *a: real(*a))
    rnn_serve_frames(cell, params, frames)
    assert _lookups(step_cache) == want


def test_frame_step_cache_keys_on_model_mesh(step_cache):
    cell = make_cell("lstm", 8, 16)
    mesh = AbstractMesh((1, 2), ("data", "model"))
    first = engine._frame_step_for(cell)
    with use_rules(Rules({}, mesh=mesh)):
        sharded = engine._frame_step_for(cell)
        assert engine._frame_step_for(cell) is sharded
    assert sharded is not first
    assert _lookups(step_cache) == (2, 1)


def test_frame_step_and_rnnt_decode_share_the_trace_context(step_cache):
    """Both program caches take their key from ``ops.trace_context()``:
    entering a model mesh misses both, and inside it both hit."""
    from repro.models import transducer as T
    from repro.serve import transducer
    model = T.make_transducer(6, 16, 8, encoder_layers=2, reduce_after=1,
                              prediction_layers=1, vocab=5, embed_dim=4,
                              joint_dim=8)
    cell = model.encoder[0]
    step, decode = (engine._frame_step_for(cell)[0],
                    transducer._decode_program(model))
    with use_rules(Rules({}, mesh=AbstractMesh((1, 2), ("data", "model")))):
        step_in, decode_in = (engine._frame_step_for(cell)[0],
                              transducer._decode_program(model))
        assert step_in is not step and decode_in is not decode
        assert engine._frame_step_for(cell)[0] is step_in
        assert transducer._decode_program(model) is decode_in
    assert _lookups(step_cache) == (2, 1)


def test_frame_warmup_on_fresh_arguments_only(step_cache, monkeypatch):
    calls = []

    def counting_step(graph):
        step = make_frame_step(graph)

        def run(p, st, x):
            calls.append(x.shape[0])
            return step(p, st, x)
        return run

    monkeypatch.setattr(engine, "make_frame_step", counting_step)
    cell, params = _dense_lstm()
    key = jax.random.PRNGKey(3)
    for batch in (2, 2, 3, 3, 2):
        rnn_serve_frames(cell, params,
                         jax.random.normal(key, (4, batch, 8)))
    # one warm-up step the first time each batch size comes, then 4 frames
    assert calls == [2] * 5 + [2] * 4 + [3] * 5 + [3] * 4 + [2] * 4
    assert _lookups(step_cache) == (1, 4)


def test_frame_step_cache_stays_bounded(step_cache):
    cells = [make_cell("lstm", 8, 8 + h)
             for h in range(engine._STEP_CACHE_SIZE + 5)]
    steps = [engine._frame_step_for(c)[0] for c in cells]
    assert len(engine._step_cache) == engine._STEP_CACHE_SIZE
    assert engine._frame_step_for(cells[-1])[0] is steps[-1]   # kept
    assert engine._frame_step_for(cells[0])[0] is not steps[0]  # evicted
    assert len(engine._step_cache) == engine._STEP_CACHE_SIZE
