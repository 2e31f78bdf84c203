# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Each benchmark runs in its OWN subprocess: XLA:CPU's JIT accumulates
# dylib/symbol state over hundreds of compilations and eventually fails
# with "Failed to materialize symbols" in a long-lived process; process
# isolation keeps every table reproducible.
#
# EVERY table additionally lands a machine-readable perf record at
# benchmarks/results/BENCH_<name>.json so the perf trajectory is tracked
# across PRs, not just printed. Records carry a machine-calibration
# measurement (a fixed numpy matmul, timed in the same worker) so
# benchmarks/diff.py can separate "this runner is slower" from "this
# kernel regressed" when diffing against the committed baseline.
import json
import os
import subprocess
import sys
import time

BENCHES = [
    ("table1", "bench_pruning_rate"),
    ("fig10", "bench_block_size"),
    ("table2", "bench_compare_schemes"),
    ("fig12", "bench_utilization"),
    ("table3", "bench_latency"),
    ("kernel", "bench_kernel"),
    ("roofline", "bench_roofline"),
    ("serve", "bench_serve"),
]


def _calibration_us(iters: int = 9) -> float:
    """Fixed-size numpy matmul latency — a jax-free proxy for this
    machine's speed, stored in every record for cross-machine diffs.
    Median of several runs after warmup: single-shot timings on shared
    runners spread several-x (thread ramp-up, throttling windows), and
    diff.py's normalization is only as good as this number."""
    import numpy as np

    a = np.ones((768, 768), np.float32)
    b = np.ones((768, 768), np.float32)
    a @ b
    a @ b  # warm the BLAS path / thread pool
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        a @ b
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _run_inprocess(mod_name: str) -> None:
    import importlib

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # metadata rows for the coordinator's perf record — they describe
    # THIS worker (the coordinator stays jax-free by design, see header)
    print(f"_meta/backend,0,{jax.default_backend()}"
          f"/{jax.devices()[0].device_kind}", flush=True)
    print(f"_meta/calib,{_calibration_us():.3f},np_matmul768", flush=True)
    mod = importlib.import_module(f"benchmarks.{mod_name}")
    mod.run()


def _parse_row(line: str) -> dict | None:
    """name,us,derived -> record row. ``derived`` round-trips as float
    when numeric (pruning rate, utilization, ...) and as string
    otherwise — no table-specific schema."""
    rname, us, derived = line.split(",", 2)
    try:
        us_f = float(us)
    except ValueError:
        return None
    try:
        dval: float | str = float(derived)
    except ValueError:
        dval = derived
    return {"name": rname, "us_per_call": us_f, "derived": dval}


def _perf_record(name: str, rows: list[dict], meta: str, calib_us: float,
                 total_us: float, root: str) -> None:
    """Land benchmarks/results/BENCH_<name>.json so the perf trajectory
    is tracked across PRs, not just printed."""
    out_dir = os.path.join(root, "benchmarks", "results")
    os.makedirs(out_dir, exist_ok=True)
    backend, _, device = meta.partition("/")
    rec = {
        "bench": name,
        "backend": backend or "unknown",
        "device": device or "unknown",
        "calib_us": round(calib_us, 3),
        "total_us": round(total_us, 1),
        "rows": rows,
    }
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"{name}/record,0,{os.path.relpath(path, root)}", flush=True)


def main() -> None:
    args = sys.argv[1:]
    if len(args) == 2 and args[0] == "--worker":
        _run_inprocess(args[1])
        return

    only = args[0] if args else None
    print("name,us_per_call,derived")
    failures = 0
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    for name, mod in BENCHES:
        if only and only != name:
            continue
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--worker", mod],
            env=env, cwd=root, capture_output=True, text=True)
        rows, meta, calib_us = [], "", 0.0
        for line in proc.stdout.splitlines():
            if line.count(",") < 2 or line.startswith("name,"):
                continue
            if line.startswith("_meta/backend,"):
                meta = line.split(",", 2)[2]
                continue
            if line.startswith("_meta/calib,"):
                try:
                    calib_us = float(line.split(",", 2)[1])
                except ValueError:
                    pass
                continue
            print(line, flush=True)
            row = _parse_row(line)
            if row is not None:
                rows.append(row)
        if proc.returncode != 0:
            failures += 1
            err = proc.stderr.strip().splitlines()
            print(f"{name}/ERROR,0,{err[-1][:160] if err else 'unknown'}",
                  flush=True)
        total_us = (time.perf_counter() - t0) * 1e6
        if proc.returncode == 0:
            _perf_record(name, rows, meta, calib_us, total_us, root)
        print(f"{name}/total,{total_us:.0f},done", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
