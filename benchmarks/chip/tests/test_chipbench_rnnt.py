"""The RNN-T configuration (``configs/rnnt-he2019.*``) and its driver at
tiny sizes on the CPU: the served transducer against the plain reference,
whole and in chunks that split the time reduction's pairs; a run through
the harness comes out correct and its traced run reads the cell's
metrics; the control reads above a limit; each fault planted in the
served path turns ``correct`` false; and the counts."""
import contextlib
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

RNNT = harness.load_module(HERE / "configs" / "rnnt-he2019.py")
SR1 = harness.load_module(HERE / "configs" / "sr1.py")
WORKLOAD = "rnnt-he2019.stream_b64"
SEED = 2**33 + 5
# limits of the tiny float32 copy: the served path matches the reference
# to float32 rounding on the CPU (encoder outputs of magnitude ~1 through
# 3 layers: a few 1e-6 at most)
TINY_LIMITS = {"rnnt_enc_max_abs_err": 1e-5, "rnnt_max_logit_gap": 1e-5}
TRAFFIC = {"streams": 8, "utterance_frames": 12, "chunk_frames": 4,
           "distinct_utterances": 2}


def tiny_rnnt(**kw) -> dict:
    """A tiny float32 copy: 3 encoder layers (the reduction after 2), 2
    prediction layers, 11 outputs; 2x in 8x8 blocks, so that tiny
    matrices keep some survivors in every block-row; a blank bias at
    which the reference emits labels on some frames and not on others."""
    cfg = json.loads((HERE / "configs" / "rnnt-he2019.json").read_text())
    return dict(cfg, input_dim=12, n_hidden=32, proj=16, encoder_layers=3,
                reduce_after=2, prediction_layers=2, embed_dim=8,
                joint_dim=16, vocab=11, block=[8, 8], compression=2.0,
                blank_bias=1.2, limits=TINY_LIMITS, **kw)


def weights_key():
    """The key the driver draws the weights of ``SEED`` from."""
    return jax.random.split(harness.seed_key(SEED))[0]


def rnnt_cell():
    return harness.load_cell(WORKLOAD, config=tiny_rnnt(), traffic=TRAFFIC)


class Units:
    """Stands in for the harness's ``Spans``: each chunk counts as one
    second, so a window of ``n`` seconds serves ``n`` chunks."""

    def __init__(self):
        self.units = 0

    @contextlib.contextmanager
    def __call__(self, name):
        yield

    def unit_done(self):
        self.units += 1

    def elapsed(self):
        return float(self.units)


def run_chunks(cell, chunks: int = 4) -> dict:
    """The driver's set-up, a window of ``chunks`` chunks (the second
    utterance pass starts at the fourth) and its checks."""
    s = cell.driver.setup(cell, SEED)
    out = cell.driver.window(s, chunks, Units())
    cell.driver.release(s)
    assert out["failed"] == 0 and out["frame_steps"] == 4 * chunks
    return {c["name"]: c["value"] for c in cell.driver.check(s, out, SEED)}


def correct(checks: dict) -> bool:
    return all(v <= TINY_LIMITS[k] for k, v in checks.items())


@pytest.fixture
def fresh_programs(monkeypatch):
    """An empty step cache, so that a planted fault is traced."""
    import collections

    from repro.serve import engine
    monkeypatch.setattr(engine, "_step_cache", collections.OrderedDict())
    return engine


# -- the served transducer against the reference ------------------------

@pytest.mark.parametrize("cuts", [[12], [4, 4, 4], [3, 5, 1, 3]])
def test_served_matches_reference(cuts):
    from repro.serve import rnnt_serve_frames

    cfg = tiny_rnnt()
    drv = harness.load_module(HERE / "drivers" / "transducer.py")
    model = drv.model_of(cfg)
    key = weights_key()
    params = RNNT.program_params(cfg, key)
    dense = RNNT.dense_params(cfg, key)
    xs = jax.random.normal(jax.random.PRNGKey(2), (12, 8, 12))
    st, chs, encs, t = None, [], [], 0
    for n in cuts:
        ch, enc, st = rnnt_serve_frames(model, params, xs[t:t + n], st)
        chs.append(ch)
        encs.append(enc)
        t += n
    ch, enc = jnp.concatenate(chs), jnp.concatenate(encs)
    want = RNNT.encode(cfg, dense, xs)
    assert enc.shape == want.shape == (6, 8, 16)
    lim = TINY_LIMITS
    assert float(jnp.abs(enc - want).max()) < lim["rnnt_enc_max_abs_err"]
    assert RNNT.forced_gap(cfg, dense, want, ch) < lim["rnnt_max_logit_gap"]
    # the decode made choices of every kind
    emitted = (np.asarray(ch) > 0).sum(-1)
    assert 0 < (emitted > 0).mean() < 1 and emitted.max() >= 2
    # and the reference's own greedy decoding makes the same ones
    np.testing.assert_array_equal(RNNT.greedy(cfg, dense, want), ch)


def test_forced_gap_reads_a_wrong_choice():
    cfg = tiny_rnnt()
    dense = RNNT.dense_params(cfg, weights_key())
    enc = RNNT.encode(cfg, dense,
                      jax.random.normal(jax.random.PRNGKey(2), (12, 8, 12)))
    ch = np.asarray(RNNT.greedy(cfg, dense, enc))
    assert RNNT.forced_gap(cfg, dense, enc, ch) == 0.0
    other = ch.copy()
    t, b = np.argwhere(ch[:, :, 0] > 0)[0]
    other[t, b, 0] = 1 + ch[t, b, 0] % (cfg["vocab"] - 1)   # another label
    assert RNNT.forced_gap(cfg, dense, enc, other) > 1e-3
    broken = ch.copy()
    broken[t, b, 1] = -1        # no step after a label: the rules broken
    assert RNNT.forced_gap(cfg, dense, enc, broken) == np.inf


def test_structure_is_sr1s():
    """The configuration's faster structure is sr1.py's, block for block
    (checked here at small widths; at the published widths both give
    8,932,333 survivors)."""
    cfg = dict(tiny_rnnt(), n_hidden=256, proj=80, input_dim=40,
               block=[32, 32], compression=13.0)
    fast = RNNT.structure(cfg)
    slow = SR1.structure(RNNT._sr1_cfg(cfg))
    for a, b in zip(fast, slow):
        assert a.keys() == b.keys()
        for k in a:
            for f in ("m", "n"):
                np.testing.assert_array_equal(a[k][f], b[k][f])
            assert (a[k]["pm"], a[k]["pn"]) == (b[k]["pm"], b[k]["pn"])


# -- runs through the harness ------------------------------------------

def test_rnnt_run_is_correct():
    res = harness.run_cell(rnnt_cell(), SEED, 0.0, False,
                           t_start=time.perf_counter(), compile_cache=False,
                           peaks=harness.peaks_for("TPU v5 lite"))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "stream_frame_ms"}
    assert set(res["checks"]) == set(TINY_LIMITS)


def test_rnnt_traced_run_reads_the_cell_metrics():
    res = harness.run_cell(rnnt_cell(), SEED, 0.0, True,
                           t_start=time.perf_counter(), compile_cache=False,
                           peaks=harness.peaks_for("TPU v5 lite"))
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in ("rnnt_stream.decode_us_per_frame",
                 "rnnt_stream.encoder_us_per_frame", "rnnt_stream.mfu"):
        assert m[name]["value"] > 0, name
    # the CPU's interpreted kernel leaves no device op to time
    assert "rnnt_stream.csb_roofline" not in m


def test_rnnt_chunks_are_correct():
    assert correct(run_chunks(rnnt_cell()))


def test_rnnt_control_fails():
    got = harness.readings(rnnt_cell(), SEED, 0.0)
    assert set(got) == set(TINY_LIMITS)
    for name, (prog, _) in got.items():
        assert prog <= TINY_LIMITS[name], name
    assert any(ctl > TINY_LIMITS[name] for name, (_, ctl) in got.items())


@pytest.mark.parametrize("fault", ["encoder_state_not_carried",
                                   "reduction_shifted", "label_replaced",
                                   "pred_state_advanced_on_blank"])
def test_rnnt_fault_fails(monkeypatch, fresh_programs, fault):
    from repro.models import transducer as T

    engine = fresh_programs
    if fault == "encoder_state_not_carried":
        real = engine.rnn_serve_frames

        def frames(graph, params, x, state=None, *a, **k):
            y, _, us = real(graph, params, x, state, *a, **k)
            return y, state, us

        monkeypatch.setattr(engine, "rnn_serve_frames", frames)
    elif fault == "reduction_shifted":
        real = T.time_reduce

        def reduce(frames, pending, factor):
            if not pending.shape[0]:      # a zero frame ahead of the first
                pending = jnp.zeros_like(frames[:1])
            return real(frames, pending, factor)

        monkeypatch.setattr(T, "time_reduce", reduce)
    elif fault == "label_replaced":
        real = T.greedy_decode

        def decode(model, params, enc, state):
            ch, st = real(model, params, enc, state)
            return jnp.where(ch > 0, 1 + ch % (model.vocab - 1), ch), st

        monkeypatch.setattr(T, "greedy_decode", decode)
    else:
        monkeypatch.setattr(T, "select_streams", lambda mask, new, old: new)
    checks = run_chunks(rnnt_cell())
    assert not correct(checks), checks


# -- counts ----------------------------------------------------------------

def test_rnnt_csb_work_sums_to_survivors():
    cfg = tiny_rnnt()
    ops = sum(f for f, _ in RNNT.csb_work(cfg, 1))
    assert ops == 2 * RNNT.survivors(cfg)
    dense = RNNT.dense_params(cfg, harness.seed_key(3))
    nnz = sum(int((np.asarray(p[k]) != 0).sum())
              for p in dense["encoder"] + dense["prediction"]
              for k in p if k.startswith(("W_", "U_")))
    assert nnz == RNNT.survivors(cfg)
    # products run: layers below the reduction every input frame, above
    # it every encoder frame, the prediction network every label step
    per = [f for f, _ in RNNT.csb_work(cfg, 4)]
    run = RNNT.run_work(cfg, 4, frame_steps=8, enc_steps=4, label_steps=12)
    rates = [8] * 18 + [4] * 9 + [12] * 18
    assert [f for f, _ in run] == [r * f for r, f in zip(rates, per)]
    assert RNNT.dense_ops(cfg, 2, 1, 1) == 2 * 2 * (16 * 16 * 2 + 11 * 16)


def test_rnnt_served_weights_match_their_dense_twin():
    from repro.kernels.ref import csb_mvm_ref

    cfg = tiny_rnnt()
    key = harness.seed_key(2**31 + 99)
    prog, dense = RNNT.program_params(cfg, key), RNNT.dense_params(cfg, key)
    for layer, ref in zip(prog["encoder"] + prog["prediction"],
                          dense["encoder"] + dense["prediction"]):
        for name, p in layer.items():
            if name.startswith("ln_"):
                np.testing.assert_array_equal(p, ref[name])
                continue
            x = jax.random.normal(jax.random.PRNGKey(0), (8, p.shape[1]))
            np.testing.assert_allclose(
                csb_mvm_ref(p, x),
                jnp.dot(x, ref[name].T, precision="highest"), atol=1e-5)
    for a, b in zip(jax.tree.leaves((prog["embed"], prog["joint"])),
                    jax.tree.leaves((dense["embed"], dense["joint"]))):
        np.testing.assert_array_equal(a, b)
    assert prog["joint"]["b_out"][0] == np.float32(cfg["blank_bias"])


def test_rnnt_at_published_widths():
    cfg = json.loads((HERE / "configs" / "rnnt-he2019.json").read_text())
    assert cfg["reduced"] == []
    n = RNNT.parameters(cfg)
    assert n["total"] == cfg["parameters"]["these_widths"] == 120_566_400
    for part in ("encoder", "prediction", "joint"):
        assert n[part] == cfg["parameters"][part]
    assert abs(n["total"] - cfg["parameters"]["paper"]) < 0.04 * 117e6
    layers = RNNT.layers(cfg)
    assert [x["n_input"] for x in layers] == [320, 640, 1280] + [640] * 5 \
        + [128, 640]
    assert RNNT.survivors(cfg) == cfg["parameters"]["csb_survivors"] \
        == 8_932_333
    for st in RNNT.structure(cfg):
        for s in st.values():
            rows, cols = s["shape"]
            nnz = int((s["m"].astype(np.int64) * s["n"]).sum())
            assert rows * cols / 13.5 < nnz <= rows * cols / 13
