"""The reduction from a profiler trace to busy time, kernel time and the
breakdown, on small traces: one written by hand, and one recorded on a
TPU v5e from the ``sr1.stream_b8`` cell and cut to a few calls."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "sr1_stream_trace.json"


def hand_trace() -> trace_reduce.Trace:
    ops = {"/device:TPU:0": [("fusion.1", 100, 50),
                             ("csb_kernel", 140, 30),
                             ("fusion.2", 300, 100),
                             ("fusion.3", 500, 10)]}     # after the window
    mods = {"/device:TPU:0": [("jit_step(1)", 100, 70),
                              ("jit_step(2)", 300, 100)]}
    host = {"/host:CPU/python": [("bench/call", 90, 350),
                                 ("PjitFunction(step)", 200, 80),
                                 ("jit compile", 420, 10)]}
    return trace_reduce.Trace(ops, mods, host)


def test_window_busy_and_idle():
    tr = hand_trace()
    assert tr.window == (90, 440)
    assert tr.window_ns == 350
    # union of [100, 170] and [300, 400]; fusion.3 lies past the window
    assert tr.busy_intervals("/device:TPU:0") == [(100, 170), (300, 400)]
    assert tr.busy_ns() == 170
    assert tr.idle_share() == pytest.approx(1 - 170 / 350)


def test_kernel_and_module_time():
    tr = hand_trace()
    assert tr.op_ns(lambda n: "csb" in n) == 30
    assert tr.op_ns(lambda n: "nothing" in n) == 0
    assert tr.module_runs() == {"jit_step(1)": (1, 70),
                                "jit_step(2)": (1, 100)}


def test_breakdown():
    tr = hand_trace()
    assert tr.top_ops(2) == [["fusion.2", 100e-9], ["fusion.1", 50e-9]]
    gaps = dict(tr.idle_gaps())
    # [90, 100] under the call's span, [170, 300] inside the dispatch,
    # [400, 440] with the compile at its midpoint
    assert gaps == pytest.approx({"bench/call": 10e-9,
                                  "PjitFunction(step)": 130e-9,
                                  "jit compile": 40e-9})


def test_nothing_to_read():
    tr = trace_reduce.Trace({}, {}, {"/host:CPU/python": []})
    assert tr.window is None and tr.idle_share() is None
    assert tr.op_ns(lambda n: True) == 0 and tr.idle_gaps() == []


def test_json_round_trip():
    tr = hand_trace()
    back = trace_reduce.from_json(trace_reduce.to_json(tr))
    assert back.window == tr.window and back.busy_ns() == tr.busy_ns()


def test_recorded_trace():
    tr = trace_reduce.from_json(RECORDED.read_text())
    assert tr.window is not None and tr.window_ns > 0
    share = tr.idle_share()
    assert 0.0 < share < 1.0
    import harness
    sr1 = harness.load_module(HERE / "configs" / "sr1.py")
    kernel = tr.op_ns(sr1.CSB_KERNEL.search)
    assert 0 < kernel < tr.busy_ns()
    # one chunk of 8 streams through both layers: 18 CSB products for
    # each of 16 frames and 2 warm-up steps per call
    runs = sum(1 for evs in tr.device_ops.values() for e in evs
               if sr1.CSB_KERNEL.search(e[0]))
    assert runs == 9 * (16 + 2) * 2
    assert tr.top_ops()[0][0] and tr.idle_gaps()


def test_lm_programs_found_by_run_counts():
    """The engine's programs trace as jit__unknown(<id>); the decode step
    and the prefills are told apart by how often they ran (counts from a
    traced mamba2-370m.decode call)."""
    import harness
    lm = harness.load_module(HERE / "drivers" / "lm.py")
    runs = {"jit__unknown(1)": (1020, 11384e6),   # decode step
            "jit__unknown(2)": (64, 352e6),       # prefill, 512 tokens
            "jit__unknown(3)": (64, 167e6),       # prefill, 128 tokens
            "jit_dynamic_slice(4)": (1020, 20e6),
            "jit__argmax(5)": (1020, 14e6),
            "jit__insert_paged(6)": (128, 19e6)}
    decode, prefill = lm.find_programs(runs, 1020, {128: 64, 512: 64})
    assert decode == [(1020, 11384e6)]
    assert sorted(prefill) == [(64, 167e6), (64, 352e6)]
    # a count that no program has finds nothing
    assert lm.find_programs(runs, 999, {128: 7}) == ([], [])
