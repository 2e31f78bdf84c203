"""The streaming RNN transducer (models/transducer.py, serve/transducer.py)
at tiny sizes on the CPU: the layernorm op, the time reduction's carry,
the greedy decode's rules, and the server's spans and counters. The
comparison with the plain reference is in
``benchmarks/chip/tests/test_chipbench_rnnt.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cells import GraphBuilder, cell_apply, init_params, make_cell
from repro.cells.dataflow import LN_EPS, layernorm
from repro.models import transducer as T
from repro.obs import metrics as obs_metrics, trace as obs_trace
from repro.serve import init_rnnt_state, rnnt_serve_frames


def test_layernorm_op_against_plain_jnp():
    g = GraphBuilder("ln", 24, 24)
    x = g.input("x")
    graph = g.build((), {}, g.layernorm("ln", x, 24))
    assert graph.weight_shapes() == {"ln_g": (24,), "ln_b": (24,)}
    gain = init_params(graph, jax.random.PRNGKey(0))["ln_g"]
    assert set(np.asarray(gain).tolist()) == {1.0}
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    xs = 3.0 * jax.random.normal(k[0], (5, 24)) + 2.0
    p = {"ln_g": jax.random.normal(k[1], (24,)),
         "ln_b": jax.random.normal(k[2], (24,))}
    got, _ = cell_apply(graph, p, xs, {})
    mu = xs.mean(-1, keepdims=True)
    var = ((xs - mu) ** 2).mean(-1, keepdims=True)
    want = (xs - mu) / jnp.sqrt(var + LN_EPS) * p["ln_g"] + p["ln_b"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got, layernorm(xs, p["ln_g"], p["ln_b"]))


def test_lnlstmp_weights():
    g = make_cell("lnlstmp", 12, 32, proj_dim=16)
    shapes = g.weight_shapes()
    assert shapes["W_i"] == (32, 12) and shapes["U_i"] == (32, 16)
    assert shapes["W_proj"] == (16, 32)
    for k in "ifogc":
        assert shapes[f"ln_{k}_g"] == shapes[f"ln_{k}_b"] == (32,)
    assert "b_i" not in shapes      # the norm's bias is the gate's bias
    assert len(g.mvm_ops) == 9


@pytest.mark.parametrize("cuts", [[8], [3, 5], [1, 1, 5, 1], [2, 2, 2, 2]])
def test_time_reduce_carries_the_odd_frame(cuts):
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 3))
    whole, rest = T.time_reduce(x, jnp.zeros((0, 2, 3)), 2)
    assert whole.shape == (4, 2, 6) and rest.shape == (0, 2, 3)
    np.testing.assert_array_equal(whole[1], jnp.concatenate([x[2], x[3]], -1))
    pending, outs, t = jnp.zeros((0, 2, 3)), [], 0
    for n in cuts:
        y, pending = T.time_reduce(x[t:t + n], pending, 2)
        outs.append(y)
        t += n
    np.testing.assert_array_equal(jnp.concatenate(outs), whole)


def tiny_model(max_symbols=3):
    return T.make_transducer(6, 16, 8, encoder_layers=3, reduce_after=2,
                             prediction_layers=2, vocab=7, embed_dim=4,
                             joint_dim=8, max_symbols=max_symbols)


def tiny_params(model, key=0, blank_bias=0.0):
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 16))
    cells = {part: [init_params(g, next(keys), scale=0.5)
                    for g in getattr(model, part)]
             for part in ("encoder", "prediction")}
    n = model.encoder[-1].op("W_proj").shape[0]
    j, v = model.joint_dim, model.vocab

    def normal(*shape):
        return jax.random.normal(next(keys), shape)

    return {**cells, "embed": normal(v, model.embed_dim),
            "joint": {"W_e": normal(j, n), "W_p": normal(j, n),
                      "b": jnp.zeros((j,)), "W_out": normal(v, j),
                      "b_out": jnp.zeros((v,)).at[model.blank].set(
                          blank_bias)}}


@pytest.mark.parametrize("blank_bias, labels", [(-1e3, "all"),
                                                (1e3, "none"),
                                                (1.0, "some")])
def test_decode_rules(blank_bias, labels):
    """At most max_symbols labels a frame; a stream's step runs only
    while it has not taken blank in the frame; a stream that takes blank
    keeps its prediction state and last label."""
    model = tiny_model()
    params = tiny_params(model, blank_bias=blank_bias)
    enc = jax.random.normal(jax.random.PRNGKey(3), (6, 5, 8))
    state = T.init_decode_state(model, 5)
    ch, _ = jax.jit(lambda p, e, s: T.greedy_decode(model, p, e, s))(
        params, enc, state)
    ch = np.asarray(ch)
    assert ch.shape == (6, 5, 3)
    for t in range(6):
        for b in range(5):
            steps = list(ch[t, b])
            assert steps[0] >= 0
            for k in range(1, 3):
                assert (steps[k] >= 0) == (steps[k - 1] > 0)
    emitted = (ch > 0).sum(-1)
    if labels == "all":
        assert (emitted == 3).all()
    elif labels == "none":
        assert (emitted == 0).all()
    else:
        assert 0 < (emitted > 0).mean() < 1
    # frame by frame: a frame that emits nothing leaves the state alone
    st = state
    for t in range(6):
        _, new = T.greedy_decode(model, params, enc[t:t + 1], st)
        quiet = ch[t, :, 0] == model.blank
        np.testing.assert_array_equal(np.asarray(new["label"])[quiet],
                                      np.asarray(st["label"])[quiet])
        for a, b in zip(jax.tree.leaves(new["pred"]),
                        jax.tree.leaves(st["pred"])):
            np.testing.assert_array_equal(np.asarray(a)[quiet],
                                          np.asarray(b)[quiet])
        last = np.array([next((y for y in ch[t, b][::-1] if y > 0), -1)
                         for b in range(5)])
        np.testing.assert_array_equal(
            np.asarray(new["label"])[~quiet], last[~quiet])
        st = new


def test_server_spans_and_counters():
    model = tiny_model()
    params = tiny_params(model, blank_bias=1.0)
    frames = jax.random.normal(jax.random.PRNGKey(4), (5, 3, 6))
    tr = obs_trace.enable()
    reg = obs_metrics.enable()
    try:
        ch, enc, st = rnnt_serve_frames(model, params, frames[:3])
        ch2, enc2, st = rnnt_serve_frames(model, params, frames[3:4], st)
        ch3, enc3, st = rnnt_serve_frames(model, params, frames[4:], st)
        names = [e[1] for e in tr.events() if e[0] == "X"]
        labels = reg.counter("serve/rnnt/labels").value
        steps = reg.counter("serve/rnnt/label_steps").value
    finally:
        obs_trace.disable()
        obs_metrics.disable()
    # 3 frames give 1 encoder frame and one pending; 1 more completes it
    assert enc.shape[0] == 1 and enc2.shape[0] == 1 and enc3.shape[0] == 0
    assert ch3.shape == (0, 3, 3) and st["pending"].shape == (1, 3, 8)
    assert names.count("serve/rnnt/call") == 3
    assert names.count("serve/rnnt/reduce") == 3
    assert names.count("serve/rnnt/decode") == 2
    assert names.count("serve/rnnt/encoder") == 5
    assert steps == 2 * model.max_symbols
    c = np.concatenate([ch, ch2])
    assert labels == int((c > 0).sum())


def test_init_state_shapes():
    model = tiny_model()
    st = init_rnnt_state(model, 4)
    assert st["encoder"] == [None] * 3
    assert st["pending"].shape == (0, 4, 8)
    assert st["decode"]["label"].tolist() == [0] * 4
    assert [s["h"].shape for s in st["decode"]["pred"]] == [(4, 8)] * 2
