"""The observability layer (``repro.obs``): tracer semantics, metric
math, Chrome-trace export shape, and the wiring through the serve /
train / dist stacks.

The pins that matter:

* the DISABLED path is allocation-free (tracing must not move the
  gated ``serve/*/us_per*`` perf numbers when off),
* the ring buffer wraps without growing and counts what it dropped,
* histogram percentiles are exact nearest-rank at tiny sample counts,
* every exported event carries the Chrome ``trace_event`` required
  fields, so the file loads in Perfetto unmodified.
"""
import collections
import importlib.util
import json
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import metrics, trace
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.summary import (
    format_table, load_trace, request_table, summarize,
)
from repro.obs.trace import Tracer

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

@pytest.fixture(autouse=True)
def _obs_off():
    """Global tracer/registry must never leak between tests."""
    obs.disable_all()
    yield
    obs.disable_all()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_contained():
    tr = Tracer(capacity=16)
    tr.begin("outer", track="t")
    tr.begin("inner", track="t")
    tr.end()
    tr.end(args={"k": 1})
    evs = tr.events()
    assert [e[1] for e in evs] == ["inner", "outer"]  # inner closes first
    (_, _, i_ts, i_dur, _, _), (_, _, o_ts, o_dur, _, o_args) = evs
    assert o_ts <= i_ts and i_ts + i_dur <= o_ts + o_dur
    assert o_args == {"k": 1}


def test_span_context_manager_records_x_event():
    tr = Tracer(capacity=8)
    with tr.span("work", track="main", args={"n": 3}):
        pass
    (ph, name, ts, dur, tid, args), = tr.events()
    assert (ph, name, tid, args) == ("X", "work", "main", {"n": 3})
    assert dur >= 0


def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert tr.dropped == 6
    evs = tr.events()
    assert len(evs) == 4
    assert [e[1] for e in evs] == ["e6", "e7", "e8", "e9"]  # oldest first
    ts = [e[2] for e in evs]
    assert ts == sorted(ts)


def test_disabled_span_is_shared_singleton():
    assert trace.get() is None
    s = trace.span("a")
    assert s is trace.span("b")
    with s:
        pass                      # usable as a context manager
    trace.instant("nothing")      # no-op, no error
    assert trace.export_chrome("/tmp/should_not_exist.json") is None


def test_disabled_hot_path_is_allocation_free():
    """With tracing off, the instrumentation gate must not allocate:
    no dict, no tuple, no span object — one global read and a branch.
    tracemalloc attributes allocations to trace.py if any happen."""
    assert trace.get() is None
    trace_file = trace.__file__

    n = 10_000

    def hot_loop():
        for _ in range(n):
            trace.span("serve/decode_step")
            trace.instant("serve/sched/admit")
            trace.get()

    hot_loop()                                      # warm any caches
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot_loop()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = (tracemalloc.Filter(True, trace_file),)
    grew = sum(st.size_diff for st in after.filter_traces(flt)
               .compare_to(before.filter_traces(flt), "lineno"))
    # snapshots see LIVE blocks: anything retained per call would grow
    # linearly (>= n bytes over 10k calls). The few hundred bytes of
    # slack is the last iteration's frame objects, which tracemalloc
    # itself keeps alive at snapshot time.
    assert grew < n // 10, f"disabled tracer retained {grew} bytes/{n} calls"


def test_enable_disable_roundtrip():
    tr = trace.enable(capacity=8)
    assert trace.get() is tr and trace.enabled()
    with trace.span("x"):
        pass
    got = trace.disable()
    assert got is tr and trace.get() is None
    assert len(got.events()) == 1       # export still works post-disable


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_required_fields(tmp_path):
    tr = trace.enable(capacity=32)
    with trace.span("outer", track="engine", args={"rid": 1}):
        trace.instant("mark", track="engine")
    tr.complete("timed", tr.now_ns() - 1000, 1000, track="req 0")
    path = trace.export_chrome(str(tmp_path / "t.json"))
    obj = json.load(open(path))
    evs = obj["traceEvents"]
    assert evs, "no events exported"
    for ev in evs:
        for field in ("ph", "ts", "pid", "tid", "name"):
            assert field in ev, (field, ev)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all("dur" in e for e in xs)
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" for e in inst)
    # one thread_name metadata row per distinct track, Perfetto labels
    meta = {e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine", "req 0"} <= meta
    assert obj["otherData"]["dropped_events"] == 0


def test_summary_tables_from_export(tmp_path):
    tr = trace.enable()
    for name, dur in (("a", 100), ("a", 300), ("b", 50)):
        tr.complete(name, tr.now_ns(), dur * 1000)
    path = trace.export_chrome(str(tmp_path / "t.json"))
    rows = summarize(load_trace(path))
    assert [r["name"] for r in rows] == ["a", "b"]   # by total desc
    a = rows[0]
    assert a["count"] == 2 and a["p50_us"] == 100 and a["max_us"] == 300
    assert request_table(load_trace(path)) == []     # no serve spans
    txt = format_table(rows)
    assert "a" in txt and "p99_us" in txt


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_percentiles_tiny_counts():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.summary()["p99"] is None and h.summary()["count"] == 0
    h.observe(5.0)
    assert (h.percentile(50), h.percentile(99)) == (5.0, 5.0)
    h2 = Histogram()
    h2.observe(2.0)
    h2.observe(1.0)
    # nearest-rank: p50 of [1, 2] is the 1st sample, not 1.5
    assert h2.percentile(50) == 1.0
    assert h2.percentile(95) == 2.0 and h2.percentile(99) == 2.0
    s = h2.summary()
    assert s["count"] == 2 and s["mean"] == 1.5 and s["min"] == 1.0


def test_histogram_sample_cap_counts_dropped():
    h = Histogram(max_samples=3)
    for v in (1, 2, 3, 4, 5):
        h.observe(v)
    assert h.count == 5 and h.dropped == 2
    assert h.summary()["mean"] == 3.0      # sum tracks all observations


def test_registry_kinds_and_export():
    reg = MetricsRegistry()
    reg.counter("serve/sched/admitted").inc()
    reg.counter("serve/sched/admitted").inc(2)
    g = reg.gauge("serve/pool/pages")
    g.set(3)
    g.set(5)
    reg.histogram("serve/req/ttft_us").observe(10.0)
    with pytest.raises(ValueError):
        reg.gauge("serve/sched/admitted")   # name bound to counter
    d = json.loads(reg.to_json())
    assert d["counters"]["serve/sched/admitted"] == 3
    assert d["gauges"]["serve/pool/pages"]["last"] == 5
    assert d["gauges"]["serve/pool/pages"]["series"] == [3.0, 5.0]
    assert d["histograms"]["serve/req/ttft_us"]["count"] == 1
    assert "series" not in reg.to_dict(series=False)["gauges"][
        "serve/pool/pages"]


def test_metrics_module_gate():
    assert metrics.get() is None
    reg = metrics.enable()
    assert metrics.get() is reg
    assert metrics.disable() is reg and metrics.get() is None
    # registry() auto-enables (docs/interactive convenience)
    r2 = metrics.registry()
    assert metrics.get() is r2


# ---------------------------------------------------------------------------
# wiring: serve engine / paging / frames / train / csb partition
# ---------------------------------------------------------------------------

from repro.models import ModelConfig, init_params as lm_init  # noqa: E402
from repro.serve import EngineConfig, Request, \
    serve_continuous                                          # noqa: E402

TINY = ModelConfig(name="tiny-obs", mixer="attn", ffn="swiglu", n_layers=2,
                   d_model=32, n_heads=4, n_kv=2, head_dim=16, d_ff=64,
                   vocab=50, dtype="float32", logit_chunk=16, remat=False)


def _reqs(n=4, seed=0):
    r = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=list(r.integers(1, 50, size=int(r.integers(3, 9)))),
                    max_new_tokens=3, arrival=i // 2)
            for i in range(n)]


def test_serve_continuous_request_lifecycle(tmp_path):
    tr, reg = obs.enable_all()
    params = lm_init(jax.random.PRNGKey(0), TINY)
    res = serve_continuous(params, TINY, _reqs(4),
                           EngineConfig(n_slots=2, cache_len=32))
    # satellite 1: compile vs steady-state throughput, both always on
    assert res.stats["compile_time_s"] >= 0.0
    assert "steady_tokens_per_sec" in res.stats
    assert "tokens_per_sec" in res.stats
    # one lifecycle histogram sample per request
    for name in ("serve/req/ttft_us", "serve/req/queue_wait_us",
                 "serve/req/prefill_us", "serve/req/decode_per_token_us"):
        assert reg.histogram(name).count == 4, name
    assert reg.counter("serve/sched/admitted").value == 4
    path = trace.export_chrome(str(tmp_path / "serve.json"))
    names = {e["name"] for e in load_trace(path)}
    for want in ("serve/req/queue_wait", "serve/req/prefill",
                 "serve/req/ttft", "serve/req/decode", "serve/req/finish",
                 "serve/decode_step", "serve/sched/admit"):
        assert want in names, want
    # ...and the lifecycle table renders from the file
    rows = request_table(load_trace(path))
    assert [r["name"] for r in rows] == [
        "serve/req/queue_wait", "serve/req/prefill",
        "serve/req/ttft", "serve/req/decode"]
    assert all(r["count"] == 4 for r in rows)


def test_serve_stats_keys_present_when_disabled():
    """The throughput-accounting split is real engine state, not an
    obs side effect — present with tracing off."""
    assert trace.get() is None and metrics.get() is None
    params = lm_init(jax.random.PRNGKey(0), TINY)
    res = serve_continuous(params, TINY, _reqs(2),
                           EngineConfig(n_slots=2, cache_len=32))
    assert "compile_time_s" in res.stats
    assert "steady_tokens_per_sec" in res.stats
    res0 = serve_continuous(params, TINY, [], EngineConfig(n_slots=2))
    assert res0.stats["compile_time_s"] == 0.0


def test_paged_serve_pool_gauges():
    _, reg = obs.enable_all()
    params = lm_init(jax.random.PRNGKey(0), TINY)
    res = serve_continuous(params, TINY, _reqs(4, seed=1),
                           EngineConfig(n_slots=2, cache_len=32,
                                        paged=True, page_size=8))
    g = reg.gauge("serve/pool/pages")
    assert g.last is not None and g.last >= 0
    # one pool sample per decode step: the timeline the stats can't give
    assert len(g.series) == res.stats["decode_steps"]
    assert len(reg.gauge("serve/pool/fragmentation").series) == \
        res.stats["decode_steps"]


FRAME_PHASES = ("prepare", "warmup", "dispatch", "sync", "stack")


def _serve_tiny_frames():
    from repro.cells import init_params as cell_init, make_cell
    from repro.serve import rnn_serve_frames
    cell = make_cell("lstm", 8, 16)
    params = cell_init(cell, jax.random.PRNGKey(2))
    frames = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 8))
    for _ in range(2):
        rnn_serve_frames(cell, params, frames)


def _profiled_frames(tmp_path) -> dict:
    """Two frame-server calls under a profiler session; the host spans
    of the ``.xplane.pb`` as {name: [(start, end)]}, read back by the
    chip benchmark's own trace reduction."""
    spec = importlib.util.spec_from_file_location(
        "trace_reduce", os.path.join(os.path.dirname(__file__), "..",
                                     "benchmarks", "chip", "trace_reduce.py"))
    trace_reduce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_reduce)
    with jax.profiler.trace(str(tmp_path)):
        _serve_tiny_frames()
    out: dict = {}
    for evs in trace_reduce.load(tmp_path).host.values():
        for name, start, dur in evs:
            out.setdefault(name, []).append((start, start + dur))
    return out


def test_frame_phase_spans_in_profiler_trace(tmp_path, monkeypatch):
    from repro.serve import engine
    monkeypatch.setattr(engine, "_step_cache", collections.OrderedDict())
    reg = metrics.enable()
    assert trace.get() is None                 # the ring stays off
    host = _profiled_frames(tmp_path)
    # the second call reuses the first's step: nothing to warm up, yet
    # every phase is there, so the span readers read numbers, not None
    assert reg.counter("serve/frames/step_cache/miss").value == 1
    assert reg.counter("serve/frames/step_cache/hit").value == 1
    calls = sorted(host["serve/frames/call"])
    assert len(calls) == 2
    for phase in FRAME_PHASES:
        spans = sorted(host[f"serve/frames/{phase}"])
        assert len(spans) == 2, phase
        for (a, b), (lo, hi) in zip(spans, calls):
            assert lo <= a <= b <= hi, phase    # inside its own call
    # the phases follow one another in each call
    for i in range(2):
        ends = [sorted(host[f"serve/frames/{p}"])[i] for p in FRAME_PHASES]
        assert all(e[1] <= f[0] for e, f in zip(ends, ends[1:]))


def test_frame_phase_spans_reach_both_sinks(tmp_path):
    tr = trace.enable()
    host = _profiled_frames(tmp_path)
    names = ["serve/frames/call"] + [f"serve/frames/{p}"
                                     for p in FRAME_PHASES]
    ring = [e[1] for e in tr.events() if e[0] == "X"]
    for name in names:
        assert ring.count(name) == 2 and len(host[name]) == 2, name


def test_frame_phase_spans_off_records_nothing(monkeypatch):
    asked = []
    real = trace.span

    def spy(name, track=None, args=None):
        s = real(name, track, args)
        asked.append((name, s))
        return s

    monkeypatch.setattr(trace, "span", spy)
    assert trace.get() is None
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _serve_tiny_frames()
    assert [n for n, _ in asked].count("serve/frames/call") == 2
    assert len(asked) == 12
    assert all(s is trace._NULL_SPAN for _, s in asked)


def test_train_loop_step_spans():
    from repro.train import TrainConfig, train
    tr, reg = obs.enable_all()

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    params = {"w": jnp.ones((4, 2), jnp.float32)}
    batches = ((s, {"x": jnp.ones((2, 4)), "y": jnp.zeros((2, 2))})
               for s in range(3))
    tcfg = TrainConfig(steps=3, log_every=100)
    _, history = train(loss_fn, params, batches, tcfg,
                       log=lambda *_: None)
    assert len(history) == 3
    assert reg.histogram("train/step/wall_us").count == 3
    assert reg.gauge("train/step/loss").last is not None
    steps = [e for e in tr.events() if e[1] == "train/step"]
    assert len(steps) == 3 and steps[0][4] == "train"


def test_csb_partition_balance_gauge(rng):
    from repro.core import padded_csb_from_dense
    from repro.dist.csb_partition import partition_padded
    tr, reg = obs.enable_all()
    z = np.zeros((128, 64), np.float32)
    z[:32] = rng.normal(size=(32, 64))
    p = padded_csb_from_dense(z, 16, 16)
    plan, _ = partition_padded(p, 4)
    g = reg.gauge("dist/csb_partition/imbalance")
    assert g.last == pytest.approx(plan.imbalance)
    assert reg.gauge("dist/csb_partition/max_device_cycles").last == \
        max(plan.device_cycles)
    inst = [e for e in tr.events() if e[1] == "dist/csb_partition"]
    assert inst and inst[-1][5]["policy"] == "greedy"


# ---------------------------------------------------------------------------
# tools/hlo_diff.py (satellite: sharded-vs-unsharded decode probe)
# ---------------------------------------------------------------------------

def _load_hlo_diff():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hlo_diff.py")
    spec = importlib.util.spec_from_file_location("hlo_diff_tool", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@needs8
def test_hlo_diff_smoke(tmp_path):
    """The probe lowers + structurally diffs both programs and the
    sharded one actually differs (collectives appear)."""
    hd = _load_hlo_diff()
    res = hd.hlo_diff("attn", (2, 4), stage="stablehlo",
                      out_dir=str(tmp_path))
    assert res["ops_unsharded"] > 0 and res["ops_sharded"] > 0
    assert res["n_changed_lines"] > 0          # shardings change the text
    assert len(res["files"]) == 2
    for f in res["files"]:
        assert os.path.getsize(f) > 0
