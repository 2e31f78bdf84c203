"""Continuous-batching serve: slot lifecycle, admission, sharded parity.

Device-parity tests for the sharded paths need 8 host devices (CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); without them
they skip. Scheduler and diff-gate tests are host-only and always run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import ModelConfig, decode_step, init_cache
from repro.models import init_params as lm_init
from repro.serve import (
    EngineConfig, Request, SlotScheduler, cache_len_of, generate,
    grow_cache, serve_continuous, simulate_admission,
)

CFG = ModelConfig(name="tiny", mixer="attn", ffn="swiglu", n_layers=2,
                  d_model=32, n_heads=2, n_kv=2, head_dim=16, d_ff=64,
                  vocab=50, dtype="float32", logit_chunk=16, remat=False)

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def params():
    return lm_init(jax.random.PRNGKey(0), CFG)


def _requests(prompts, max_new, arrivals=None):
    arrivals = arrivals or [0] * len(prompts)
    return [Request(rid=i, tokens=np.asarray(p), max_new_tokens=m,
                    arrival=a)
            for i, (p, m, a) in enumerate(zip(prompts, max_new, arrivals))]


def _ref_tokens(params, prompt, n_new):
    """Generated tail of a solo fixed-batch greedy run."""
    out = generate(params, CFG, jnp.asarray(prompt)[None],
                   EngineConfig(max_new_tokens=n_new))
    return np.asarray(out)[0, len(prompt):]


# ---------------------------------------------------------------------------
# cache time-dim helpers (host + tiny device work)
# ---------------------------------------------------------------------------

def test_grow_cache_empty_and_ragged():
    assert grow_cache({}, 4) == {}
    # pure-state cache (SSD): no time-keyed leaves -> untouched
    ssd = {"conv": jnp.zeros((2, 1, 3, 8)), "ssm": jnp.zeros((2, 1, 4, 4))}
    grown = grow_cache(ssd, 5)
    assert jax.tree.map(lambda a: a.shape, grown) == \
        jax.tree.map(lambda a: a.shape, ssd)
    assert cache_len_of(ssd) == 0
    # ragged hybrid cache: attn leaves grow, ssd leaves don't
    hyb = {"attn": {"k": jnp.ones((2, 1, 3, 2, 4)),
                    "v": jnp.ones((2, 1, 3, 2, 4))},
           "ssd": ssd}
    grown = grow_cache(hyb, 2)
    assert grown["attn"]["k"].shape == (2, 1, 5, 2, 4)
    assert grown["ssd"]["conv"].shape == ssd["conv"].shape
    # grown region is zero-padded, original values intact
    np.testing.assert_array_equal(np.asarray(grown["attn"]["k"][:, :, :3]),
                                  1.0)
    np.testing.assert_array_equal(np.asarray(grown["attn"]["k"][:, :, 3:]),
                                  0.0)
    # zero-length time dim grows from empty
    empty_t = {"k": jnp.zeros((1, 1, 0, 2, 4))}
    assert grow_cache(empty_t, 3)["k"].shape == (1, 1, 3, 2, 4)
    # non-positive growth is the identity
    assert grow_cache(hyb, 0) is hyb
    assert grow_cache(hyb, -2) is hyb


# ---------------------------------------------------------------------------
# scheduler (host only)
# ---------------------------------------------------------------------------

def test_scheduler_admission_queues_beyond_slots():
    reqs = [Request(rid=i, tokens=np.zeros(2, np.int32), max_new_tokens=3)
            for i in range(5)]
    sched = SlotScheduler(2)
    for r in reqs:
        sched.submit(r)
    first = sched.admit()
    assert [r.rid for _, r in first] == [0, 1]
    assert sched.admit() == []          # both slots busy now
    for slot, _ in first:
        assert sched.started(slot, 7)
    # run both to completion; freed slots must readmit FIFO
    freed = []
    while not freed:
        freed = sched.advance(np.zeros(2, np.int64))
    nxt = sched.admit()
    assert [r.rid for _, r in nxt] == [2, 3]


def test_scheduler_occupancy_and_idle():
    # uniform trace fills every slot-step
    uni = [Request(rid=i, tokens=np.zeros(1, np.int32), max_new_tokens=4)
           for i in range(4)]
    sim = simulate_admission(2, uni)
    assert sim["occupancy"] == 1.0
    assert sim["decode_steps"] == 6     # 2 waves x 3 decode steps
    assert sim["generated_tokens"] == 16
    # a gap in arrivals idles the clock, not the decode accounting
    gap = [Request(rid=0, tokens=np.zeros(1, np.int32), max_new_tokens=2),
           Request(rid=1, tokens=np.zeros(1, np.int32), max_new_tokens=2,
                   arrival=50)]
    sim = simulate_admission(2, gap)
    assert sim["idle_steps"] > 0
    assert sim["occupancy"] == 0.5      # one slot of two ever busy
    # single-token requests finish off the prefill, no decode at all
    one = [Request(rid=0, tokens=np.zeros(1, np.int32), max_new_tokens=1)]
    sim = simulate_admission(1, one)
    assert sim["decode_steps"] == 0 and sim["generated_tokens"] == 1


def test_scheduler_errors():
    with pytest.raises(ValueError):
        SlotScheduler(0)
    with pytest.raises(ValueError):
        SlotScheduler(1).submit(
            Request(rid=0, tokens=np.zeros(1, np.int32), max_new_tokens=0))


# ---------------------------------------------------------------------------
# decode-step per-slot positions
# ---------------------------------------------------------------------------

def test_vector_pos_matches_scalar(params):
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 1), 0, 50)
    cache = init_cache(CFG, 3, 8, jnp.float32)
    lg_s, c_s = decode_step(params, cache, toks, 4, CFG)
    lg_v, c_v = decode_step(params, cache, toks,
                            jnp.full((3,), 4, jnp.int32), CFG)
    np.testing.assert_allclose(np.asarray(lg_v), np.asarray(lg_s),
                               rtol=1e-6, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6), c_v, c_s)


# ---------------------------------------------------------------------------
# slot lifecycle through the real engine
# ---------------------------------------------------------------------------

def test_evict_refill_single_slot_no_leak(params):
    """Two very different requests forced through the SAME slot one
    after the other: each must decode exactly as it does alone (no KV
    or state of request 0 survives into request 1)."""
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, 50, size=9)
    p1 = rng.integers(0, 50, size=4)
    res = serve_continuous(params, CFG, _requests([p0, p1], [5, 6]),
                           EngineConfig(n_slots=1))
    assert res.stats["requests"] == 2
    np.testing.assert_array_equal(res.tokens[0], _ref_tokens(params, p0, 5))
    np.testing.assert_array_equal(res.tokens[1], _ref_tokens(params, p1, 6))


def test_continuous_matches_generate_batch(params):
    """Same-length prompts admitted together == fixed-batch generate,
    token for token."""
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (3, 6), 0, 50))
    ref = np.asarray(generate(params, CFG, jnp.asarray(prompts),
                              EngineConfig(max_new_tokens=5)))[:, 6:]
    res = serve_continuous(
        params, CFG, _requests(list(prompts), [5, 5, 5]),
        EngineConfig(n_slots=3))
    for i in range(3):
        np.testing.assert_array_equal(res.tokens[i], ref[i])
    assert res.stats["occupancy"] == 1.0


def test_continuous_mixed_lengths_and_arrivals(params):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 50, size=n) for n in (4, 8, 5, 7, 6)]
    max_new = [4, 6, 5, 4, 6]
    reqs = _requests(prompts, max_new, arrivals=[0, 0, 3, 6, 6])
    res = serve_continuous(params, CFG, reqs, EngineConfig(n_slots=2))
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            res.tokens[i], _ref_tokens(params, p, max_new[i]),
            err_msg=f"request {i}")
    st = res.stats
    assert st["prefills"] == 5 and 0.0 < st["occupancy"] <= 1.0


def test_continuous_rejects_undersized_cache(params):
    reqs = _requests([np.zeros(6, np.int64)], [8])
    with pytest.raises(ValueError):
        serve_continuous(params, CFG, reqs,
                         EngineConfig(n_slots=1, cache_len=10))


# ---------------------------------------------------------------------------
# sharded parity (8 host devices)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("shape", [(1, 8), (2, 4)],
                         ids=["mesh1x8", "mesh2x4"])
def test_continuous_sharded_matches_unsharded(params, shape):
    """Acceptance: sharded continuous-batching generate == unsharded
    greedy output token-for-token on 1x8 and 2x4 host meshes."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(shape),
                ("data", "model"))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 50, size=n) for n in (5, 9, 6, 7)]
    max_new = [5, 4, 6, 5]
    reqs = _requests(prompts, max_new, arrivals=[0, 0, 2, 4])
    res = serve_continuous(params, CFG, reqs, EngineConfig(n_slots=2),
                           mesh=mesh)
    assert res.stats["sharded"]
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            res.tokens[i], _ref_tokens(params, p, max_new[i]),
            err_msg=f"mesh {shape} request {i}")


@needs8
def test_rnn_frames_sharded_matches_local(rng):
    """Frame serving with CSB weights: partitioned over the model axis
    + data-sharded batch == the local Pallas kernel."""
    from repro.cells import init_params as cell_init, make_cell
    from repro.core import (
        CSBSpec, csb_masks, csb_project, padded_csb_from_dense,
    )
    from repro.dist import Rules, use_rules
    from repro.serve import rnn_serve_frames, shard_cell_params

    cell = make_cell("gru", 16, 32)
    wparams = cell_init(cell, jax.random.PRNGKey(8))
    spec = CSBSpec(bm=8, bn=8, prune_rate=0.5)
    csb = {}
    for k, w in wparams.items():
        if w.ndim == 2:
            z = csb_project(w, spec)
            rm, cm = csb_masks(w, spec)
            csb[k] = padded_csb_from_dense(
                np.asarray(z), 8, 8, row_mask=np.asarray(rm),
                col_mask=np.asarray(cm))
        else:
            csb[k] = w
    frames = jnp.asarray(rng.normal(size=(4, 2, 16)).astype(np.float32))
    outs, _, _ = rnn_serve_frames(cell, csb, frames)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    sharded = shard_cell_params(csb, mesh)        # once per model
    frames = jax.device_put(frames, NamedSharding(mesh, P(None, "data")))
    with use_rules(Rules({}, mesh=mesh)):
        outs_sh, _, us = rnn_serve_frames(cell, sharded, frames)
    np.testing.assert_allclose(np.asarray(outs_sh), np.asarray(outs),
                               rtol=2e-5, atol=2e-5)
    assert us > 0


@needs8
def test_generate_sharded_matches_unsharded(params):
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    prompt = jax.random.randint(jax.random.PRNGKey(7), (4, 6), 0, 50)
    scfg = EngineConfig(max_new_tokens=5)
    ref = generate(params, CFG, prompt, scfg)
    out = generate(params, CFG, prompt, scfg, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# serve rows gate in benchmarks/diff.py
# ---------------------------------------------------------------------------

def _rec(name, rows, calib=100.0):
    return {name: {"bench": name, "calib_us": calib,
                   "rows": [{"name": n, "us_per_call": us, "derived": d}
                            for n, us, d in rows]}}


def test_diff_gates_serve_rows():
    from benchmarks.diff import diff_records, parse_gate_rows

    assert parse_gate_rows("kernel:/mvm,serve:/us_per") == \
        {"kernel": ("/mvm",), "serve": ("/us_per",)}
    assert parse_gate_rows("/mvm") == {"*": ("/mvm",)}
    assert parse_gate_rows("kernel:/mvm|paged_attn/decode") == \
        {"kernel": ("/mvm", "paged_attn/decode")}

    base = _rec("serve", [
        ("serve/continuous/us_per_token", 1000.0, 100.0),
        ("serve/frames/us_per_frame", 2000.0, "x"),
        ("serve/continuous/occupancy", 0.0, 0.9),
    ])
    fresh = _rec("serve", [
        ("serve/continuous/us_per_token", 1500.0, 66.0),   # 1.5x: fails
        ("serve/frames/us_per_frame", 2100.0, "x"),        # 1.05x: ok
        ("serve/continuous/occupancy", 0.0, 0.4),          # never gates
    ])
    _, failures = diff_records(fresh, base, 0.25, {"serve"}, 50.0)
    assert len(failures) == 1 and "us_per_token" in failures[0]

    # same 1.5x regression passes when the serve table is not gated
    _, failures = diff_records(fresh, base, 0.25, {"kernel"}, 50.0)
    assert failures == []

    # tokens/sec collapse == us/token rise: the one rule covers both
    ok = _rec("serve", [("serve/continuous/us_per_token", 1100.0, 91.0)])
    _, failures = diff_records(ok, base, 0.25, {"serve"}, 50.0)
    assert failures == []
