"""``correct`` at tiny sizes on the CPU: a whole run of each driver
(without the harness's look for a chip) comes out correct, the control
reads above the limit, and each fault planted in the timed path turns
``correct`` false."""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
from test_chipbench_counts import tiny_mamba2, tiny_sr1  # noqa: E402

SEED = 2**33 + 5
# limits of the tiny float32 copies: both programs match their
# references to float32 rounding on the CPU
TINY_FRAMES_LIMIT = 3e-7
TINY_LM_LIMIT = 1e-3


def sr1_cell(workload="sr1.stream_b8"):
    cfg = dict(tiny_sr1(), limits={"frames_max_abs_err": TINY_FRAMES_LIMIT})
    traffic = {"streams": 8, "utterance_frames": 8, "chunk_frames": 4,
               "distinct_utterances": 2}
    return harness.load_cell(workload, config=cfg, traffic=traffic)


# the LM driver's metrics; no cell of BENCHMARK.json runs it while the
# program departs from mamba2-370m's published block (PERF.md)
LM_END_TO_END = [{"name": "setup_s", "unit": "s"},
                 {"name": "lm_tokens_per_s", "unit": "tokens/s"}]
LM_PER_LAYER = [{"name": n, "unit": u} for n, u in (
    ("lm.device_idle", "%"), ("lm.occupancy", "%"),
    ("lm.decode_step_ms", "ms"), ("lm.prefill_ms_per_ktok", "ms/ktok"),
    ("lm.mfu", "%"))]


def lm_cell():
    cfg = dict(tiny_mamba2(), limits={"lm_max_logit_gap": TINY_LM_LIMIT})
    traffic = {"requests_per_call": 6, "prompt_lens": [5, 9],
               "new_tokens": 4, "n_slots": 4, "check_requests": 4}
    return harness.Cell(
        name="mamba2-370m.tiny", dir=HERE, chips=1, config=cfg,
        config_mod=harness.load_module(HERE / "configs" / "mamba2-370m.py"),
        traffic=traffic, driver=harness.load_module(HERE / "drivers" / "lm.py"),
        end_to_end=LM_END_TO_END, per_layer=LM_PER_LAYER)


def run(cell, trace=False) -> dict:
    res = harness.run_cell(cell, SEED, 0.0, trace,
                           t_start=time.perf_counter(), compile_cache=False,
                           peaks=harness.peaks_for("TPU v5 lite"))
    res.pop("_log")
    json.dumps(res)
    return res


def test_frames_run_is_correct():
    res = run(sr1_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "stream_frame_ms"}
    assert list(res)[-1] == "checks"


def test_frames_control_fails():
    cell = sr1_cell()
    prog, ctl = harness.readings(cell, SEED, 0.0)["frames_max_abs_err"]
    assert prog <= TINY_FRAMES_LIMIT < ctl


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_batch_left_out"])
def test_frames_fault_fails(monkeypatch, fault):
    from repro.serve import engine

    real = engine.cell_apply

    def faulty(graph, params, x, state):
        if fault == "half_batch_left_out":
            # the first half of the streams computed, copied to the rest
            half = x.shape[0] // 2
            y, new = real(graph, params, x[:half],
                          jax.tree.map(lambda a: a[:half], state))
            return jax.tree.map(lambda a: jnp.concatenate([a, a]), (y, new))
        y, new = real(graph, params, x, state)
        if fault == "state_unchanged":
            return y, state
        return y.at[..., 0].add(1e-3), new

    monkeypatch.setattr(engine, "cell_apply", faulty)
    res = run(sr1_cell())
    assert not res["correct"] and res["failed"] == 0
    assert res["checks"]["frames_max_abs_err"]["value"] > TINY_FRAMES_LIMIT


def test_lm_run_is_correct():
    res = run(lm_cell(), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 6 and res["failed"] == 0
    assert "lm.occupancy" in res["metrics"]
    assert "busy_s" in res["device"] and "breakdown" in res


def test_lm_control_fails():
    prog, ctl = harness.readings(lm_cell(), SEED, 0.0)["lm_max_logit_gap"]
    assert prog <= TINY_LM_LIMIT < ctl


@pytest.fixture
def fresh_programs():
    """The engine caches its jitted programs per model config; clear them
    so that a planted fault is traced, and again after it."""
    from repro.serve import engine
    engine._jitted.cache_clear()
    yield engine
    engine._jitted.cache_clear()


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_lm_fault_fails(monkeypatch, fresh_programs, fault):
    engine = fresh_programs
    if fault == "state_unchanged":
        real = engine.LM.decode_step_paged

        def step(params, cache, tokens, pos, page_table, cfg,
                 use_kernel=False):
            lg, _ = real(params, cache, tokens, pos, page_table, cfg=cfg,
                         use_kernel=use_kernel)
            return lg, cache

        monkeypatch.setattr(engine.LM, "decode_step_paged", step)
    else:
        real = engine._sampler

        def sampler(cfg, temperature):
            sample = real(cfg, temperature)
            return lambda lg, key: (sample(lg, key) + 1) % cfg.vocab

        monkeypatch.setattr(engine, "_sampler", sampler)
    res = run(lm_cell())
    assert not res["correct"] and res["failed"] == 0
    assert res["checks"]["lm_max_logit_gap"]["value"] > TINY_LM_LIMIT
