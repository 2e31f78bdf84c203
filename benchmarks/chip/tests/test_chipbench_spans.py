"""The frame server's phase spans as the benchmark reads them: the span
arithmetic of ``spans.py`` and the three readers on a trace written by
hand and on one recorded on a TPU v5e, nothing read from a trace without
the spans, and a traced run of each tiny cell on the CPU."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
from test_chipbench_faults import run, sr1_cell  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "sr1_stream_trace.json"
# one 20-frame chunk of sr1.stream_b8 (both layers' calls) with the spans,
# cut to the harness's and the program's spans, device ops and programs
SPANNED = Path(__file__).parent / "data" / "sr1_stream_spans_trace.json"
READERS = ("sr_stream.warmup_ms_per_call", "sr_stream.dispatch_us_per_frame",
           "sr_batch.warmup_share")


def _call(t0, warmup, dispatch, sync, stack, prepare=40, tail=0):
    """One call's spans from ``t0``: the phases back to back, then
    ``tail`` ns of the call under no phase."""
    evs, t = [], t0
    for name, d in zip(spans.PHASES, (prepare, warmup, dispatch, sync,
                                      stack)):
        evs.append((name, t, d))
        t += d
    return [(spans.CALL, t0, t - t0 + tail)] + evs


def hand_trace() -> trace_reduce.Trace:
    host = [("bench/rnn_serve_frames.layer1", 100, 1000),
            *_call(110, warmup=400, dispatch=300, sync=200, stack=40),
            ("bench/rnn_serve_frames.layer2", 1200, 1000),
            *_call(1210, warmup=200, dispatch=500, sync=100, stack=100,
                   tail=40),
            # a call after the window: not read
            *_call(2900, warmup=100, dispatch=100, sync=100, stack=100)]
    ops = [("csb_mvm_pallas", 200, 100),      # in the first warm-up
           ("fusion", 540, 20),               # across warm-up | dispatch
           ("csb_mvm_pallas", 900, 100),      # in the first sync
           ("csb_mvm_pallas", 1300, 50),      # in the second warm-up
           ("csb_mvm_pallas", 2000, 50)]      # in the second sync
    return trace_reduce.Trace({"/device:TPU:0": ops}, {},
                              {"/host:CPU/python": host})


def context(tr, frame_steps=20):
    return harness.Context(
        cell=None, setup_s=0.0, window_s=0.0,
        out={"units": [{"frame_steps": frame_steps}]}, compiles={},
        calls=2, peaks=None, trace=tr, traced_units=1)


def read(name, ctx):
    return harness.load_module(HERE / "metrics" / f"{name}.py").read(ctx)


def test_spans_inside_the_window():
    tr = hand_trace()
    assert tr.window == (100, 2200)
    assert spans.calls(tr) == 2
    assert spans.spans(tr, spans.WARMUP) == [(150, 550), (1250, 1450)]
    assert spans.total_ns(tr, spans.DISPATCH) == 800
    assert spans.calls(None) == 0


def test_readers_on_hand_trace():
    ctx = context(hand_trace())
    # 600 ns of warm-up over two calls; 800 ns of dispatch over 20 steps
    assert read("sr_stream.warmup_ms_per_call", ctx) == pytest.approx(3e-4)
    assert read("sr_stream.dispatch_us_per_frame", ctx) == pytest.approx(
        0.04)
    assert read("sr_batch.warmup_share", ctx) == pytest.approx(
        100 * 600 / 2100)


def test_idle_split_by_phase():
    tr = hand_trace()
    # each span's length less the device's busy time inside it
    assert spans.idle_ns(tr, spans.WARMUP) == pytest.approx(290 + 150)
    assert spans.idle_ns(tr, spans.DISPATCH) == pytest.approx(290 + 500)
    assert spans.idle_ns(tr, spans.CALL) == pytest.approx(760 + 880)
    split = spans.phase_split(tr)
    assert split["window"] == pytest.approx(
        {"window_s": 2100e-9, "idle_s": 1780e-9})
    assert split["(no program span)"] == pytest.approx(
        {"window_s": 140e-9, "idle_s": 140e-9})
    assert split[spans.CALL + " (no phase)"] == pytest.approx(
        {"window_s": 40e-9, "idle_s": 40e-9})
    parts = [v for k, v in split.items() if k != "window"]
    assert sum(v["window_s"] for v in parts) == pytest.approx(2100e-9)
    assert sum(v["idle_s"] for v in parts) == pytest.approx(1780e-9)


def test_recorded_trace_with_spans():
    tr = trace_reduce.from_json(SPANNED.read_text())
    assert spans.calls(tr) == 2
    ctx = context(tr, frame_steps=20)
    assert read("sr_stream.warmup_ms_per_call", ctx) == pytest.approx(
        350.777709 / 2)
    assert read("sr_stream.dispatch_us_per_frame", ctx) == pytest.approx(
        75181.208 / 20)
    assert 0 < read("sr_batch.warmup_share", ctx) < 100
    # the device idles 98.9% of the chunk, nearly all of it under a call
    idle = tr.window_ns - tr.busy_ns()
    assert spans.idle_ns(tr, spans.CALL) > 0.95 * idle
    assert spans.idle_ns(tr, spans.WARMUP) > 0.75 * idle
    # the stable names: the step's program and the kernel, 9 CSB products
    # a step, 20 frames and 2 warm-up steps a call
    progs = {trace_reduce.short_name(k) for k in tr.module_runs()}
    assert "jit_frame_step" in progs
    sr1 = harness.load_module(HERE / "configs" / "sr1.py")
    runs = sum(1 for evs in tr.device_ops.values() for e in evs
               if sr1.CSB_KERNEL.search(e[0]))
    assert runs == 9 * (20 + 2) * 2


@pytest.mark.parametrize("name", READERS)
def test_no_program_spans_reads_nothing(name):
    recorded = trace_reduce.from_json(RECORDED.read_text())
    assert recorded.window is not None and spans.calls(recorded) == 0
    assert read(name, context(recorded)) is None
    assert read(name, context(None)) is None
    assert spans.idle_ns(recorded, spans.CALL) == 0.0


@pytest.mark.parametrize("workload,names", [
    ("sr1.stream_b8", READERS[:2]), ("sr1.batch_b256", READERS[2:])])
def test_traced_tiny_run_reads_the_spans(workload, names):
    res = run(sr1_cell(workload), trace=True)
    assert res["correct"], res["checks"]
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
