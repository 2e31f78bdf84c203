"""The chip benchmark's harness. It is driven by data: a cell, a
configuration, a traffic mix or a metric is added by adding files and
``BENCHMARK.json`` entries, never by editing this file.

Lookups, all by name:

- ``BENCHMARK.json`` (root of the checkout): the cell gives its
  configuration, its traffic mix and its chips; the metric entries say
  which metrics the cell reports (an entry without ``workloads`` is
  reported by every cell that reports the end-to-end metric it moves).
- ``configs/<config>.json``: the configuration as it is run; its key
  ``driver`` names ``drivers/<driver>.py``. ``configs/<config>.py``
  beside it builds the served weights on the device from the seed and
  holds the plain reference, the control and the op/byte counts.
- ``traffic/<mix>.json``: the mix's parameters, read by the driver.
- ``metrics/<metric>.py``: ``read(ctx)`` returns the metric's value, or
  None where the run gave it nothing to read.
- ``peaks.json``: the chip's peaks by ``device_kind``.

A driver module has ``setup(cell, seed)``, ``window(state, seconds,
span)``, ``release(state)``, ``check(state, out, seed)`` and
``control(state, out, seed)``; see ``drivers/frames.py``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import re
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = HERE / ".traces"
# a traced run traces whole units of work up to this far into the window
TRACE_SECONDS = 10.0

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_modules: dict[Path, ModuleType] = {}


def read_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path) -> ModuleType:
    """Import a file by path (names may hold '.' and '-'), once."""
    path = Path(path).resolve()
    if path not in _modules:
        if not path.is_file():
            raise FileNotFoundError(f"no such file: {path}")
        name = "chipbench_" + re.sub(r"\W", "_", path.stem)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def seed_key(seed: int):
    """A JAX key from any whole number (64 bits are kept)."""
    import jax
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


@dataclasses.dataclass
class Cell:
    name: str
    dir: Path               # the benchmark's directory
    chips: int
    config: dict            # configs/<config>.json
    config_mod: ModuleType  # configs/<config>.py
    traffic: dict           # traffic/<mix>.json
    driver: ModuleType      # drivers/<driver>.py
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, root: Path = ROOT, here: Path = HERE,
              config: dict | None = None,
              traffic: dict | None = None) -> Cell:
    """The cell named ``workload`` of ``root``'s ``BENCHMARK.json``, with
    the benchmark's files under ``here``; ``config``/``traffic`` replace
    the files' contents (tests run tiny copies through the same path)."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = root / entry["file"]
    cfg = config if config is not None else read_json(cfg_path)
    traffic = traffic if traffic is not None else read_json(
        here / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, workload)]
    return Cell(
        name=workload, dir=here, chips=int(w["chips"]), config=cfg,
        config_mod=load_module(cfg_path.with_suffix(".py")),
        traffic=traffic,
        driver=load_module(here / "drivers" / f"{cfg['driver']}.py"),
        end_to_end=e2e, per_layer=per_layer)


def load_metric(cell: Cell, name: str) -> ModuleType:
    return load_module(cell.dir / "metrics" / f"{name}.py")


def peaks_for(kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = read_json(path)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {path.name} "
                       f"(known: {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Counts JAX's trace and compile events while ``on``: a compile
    request whose program the persistent cache holds is a cache hit,
    the others compile."""

    _installed: "CompileCounter | None" = None

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()
        self.on = False

    @classmethod
    def get(cls) -> "CompileCounter":
        """The process's counter, its listeners registered once."""
        if cls._installed is None:
            import jax
            c = cls._installed = cls()
            jax.monitoring.register_event_listener(c._event)
            jax.monitoring.register_event_duration_secs_listener(c._duration)
        c = cls._installed
        c.counts.clear()
        return c

    def _event(self, event, *args, **kwargs):
        if self.on:
            self.counts[event] += 1

    def _duration(self, event, secs, *args, **kwargs):
        if self.on:
            self.counts[event] += 1

    def summary(self) -> dict:
        req = self.counts[COMPILE_EVENT]
        hits = self.counts[CACHE_HIT_EVENT]
        return {"traces": self.counts[TRACE_EVENT], "compile_requests": req,
                "cache_hits": hits, "compiles": req - hits}


class Spans:
    """Host spans around the entry calls, in the profiler's own trace.

    A driver calls ``unit_done()`` after each whole unit of work (a chunk,
    a batch of requests) and ends its window once ``elapsed()`` reaches
    its seconds. While tracing, the first unit to end ``trace_seconds``
    or more into the window stops the profiler: the traced window is the
    first ``traced_units`` units. The time the profiler takes to stop is
    not part of the window."""

    def __init__(self, trace_seconds: float | None = None):
        self.calls = 0
        self.units = 0
        self.trace_seconds = trace_seconds
        self.traced_units = None
        self.stop_s = 0.0
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0 - self.stop_s

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        self.calls += 1
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            yield

    def unit_done(self) -> None:
        self.units += 1
        if self.tracing and self.elapsed() >= self.trace_seconds:
            self.stop()

    @property
    def tracing(self) -> bool:
        return self.trace_seconds is not None and self.traced_units is None

    def stop(self) -> None:
        import jax
        if self.tracing:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s += time.perf_counter() - t
            self.traced_units = self.units


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    setup_s: float
    window_s: float
    out: dict               # the driver's counts over the window
    compiles: dict          # CompileCounter.summary() over the window
    calls: int              # entry calls in the window
    peaks: dict | None
    trace: object | None    # trace_reduce.Trace of the window, if traced
    traced_units: int | None = None

    def traced(self, key: str):
        """Sum of a per-unit count over the traced units; counts kept per
        key ({length: count}) are summed key by key."""
        units = self.out.get("units", [])[:self.traced_units]
        if units and isinstance(units[0][key], dict):
            acc: dict = {}
            for u in units:
                for k, v in u[key].items():
                    acc[k] = acc.get(k, 0) + v
            return acc
        return sum(u[key] for u in units)


def peak_memory(n: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: dict | None = None,
             compile_cache: bool = True) -> dict:
    """Set up, measure one window, check, and reduce: the result line.
    ``compile_cache=False`` leaves JAX's configuration alone (tests)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    if compile_cache:
        enable_compile_cache()
        # the frame server builds a fresh jit per call: with the default
        # 1 s floor its sub-second programs never reach the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter.get()
    state = cell.driver.setup(cell, seed)
    trace_dir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.on = True
    spans = Spans(TRACE_SECONDS if trace else None)
    out = cell.driver.window(state, seconds, spans)
    out["window_s"] = spans.elapsed()
    counter.on = False
    spans.stop()
    setup_s = spans.t0 - t_start
    memory = peak_memory(cell.chips)
    cell.driver.release(state)
    t1 = time.perf_counter()
    checks = cell.driver.check(state, out, seed)
    t2 = time.perf_counter()
    tr = None
    if trace:
        import trace_reduce
        tr = trace_reduce.load(trace_dir)
    ctx = Context(cell=cell, setup_s=setup_s,
                  window_s=out["window_s"], out=out,
                  compiles=counter.summary(), calls=spans.calls,
                  peaks=peaks, trace=tr, traced_units=spans.traced_units)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_metric(cell, m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {
        "correct": bool(checks) and out["failed"] == 0
        and all(c["value"] <= c["limit"] for c in checks),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        device["busy_s"] = tr.busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    result["_log"] = {"setup_s": setup_s, "window_s": out["window_s"],
                      "check_s": t2 - t1, "trace_stop_s": spans.stop_s,
                      "trace_read_s": time.perf_counter() - t2,
                      "calls": spans.calls, **counter.summary()}
    return result


def readings(cell: Cell, seed: int, seconds: float) -> dict:
    """One short window, then each compared number as the program and as
    the control read it: {name: (program, control)}. Not part of a run;
    the limits are set from these readings (calibrate.py)."""
    state = cell.driver.setup(cell, seed)
    out = cell.driver.window(state, seconds, Spans())
    cell.driver.release(state)
    return cell.driver.control(state, out, seed)


def emit(result: dict) -> None:
    """The log line, then the checks as stderr's last lines, then the
    result as stdout's last line."""
    log = result.pop("_log")
    print("window: " + " ".join(f"{k}={v}" for k, v in log.items()),
          file=sys.stderr)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(workload: str, seed: int, seconds: float, trace: bool, *,
         t_start: float) -> int:
    try:
        cell = load_cell(workload)
    except (FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"run.py: the program is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    peaks = peaks_for(devices[0].device_kind)
    emit(run_cell(cell, seed, seconds, trace, t_start=t_start,
                  peaks=peaks))
    return 0
