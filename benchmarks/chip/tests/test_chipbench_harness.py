"""The chip benchmark's harness: lookups by name, the peak table, the
contract of BENCHMARK.json, and a run without a chip. Nothing here
loads the TPU's library."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell.chips == 1
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(cell.driver, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_metric(cell, m["name"]).read)
    assert cell.config["limits"], "every compared number has a limit"


def test_new_cell_config_and_metric_are_found_by_adding_files(tmp_path):
    """A later cell, configuration and metric are files and entries."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        ".traces", ".scratch", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs" / "sr1.json").read_text())
    (here / "configs" / "sr1-copy.json").write_text(json.dumps(cfg))
    shutil.copy(HERE / "configs" / "sr1.py", here / "configs" / "sr1-copy.py")
    (here / "traffic" / "new_mix.json").write_text(
        json.dumps({"streams": 8, "utterance_frames": 32,
                    "chunk_frames": 32, "distinct_utterances": 1}))
    (here / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="sr1-copy",
                                 file="benchmarks/chip/configs/sr1-copy.json"))
    bench["workloads"].append({"name": "sr1-copy.new_mix",
                               "config": "sr1-copy", "traffic": "new_mix",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "new_e2e", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["sr1-copy.new_mix"]})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "new_e2e"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (here / "metrics" / "new_e2e.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    cell = harness.load_cell("sr1-copy.new_mix", root=tmp_path, here=here)
    assert cell.traffic["utterance_frames"] == 32
    assert Path(cell.config_mod.__file__) == (
        here / "configs" / "sr1-copy.py").resolve()
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert harness.load_metric(cell, "new.metric").read(None) == 42.0
    # a per-layer metric without "workloads" follows the metric it moves:
    # the existing cells do not report new_e2e, so not new.metric either
    old = harness.load_cell(WORKLOADS[0], root=tmp_path, here=here)
    assert "new.metric" not in [m["name"] for m in old.per_layer]


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")


def test_peaks_known_device():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads((HERE / "peaks.json").read_text())["source"]


def test_peaks_unknown_device_raises():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99 imaginary")


def test_seed_key_takes_large_and_negative_seeds():
    a = harness.seed_key(2**31 + 12345)
    b = harness.seed_key(2**40 + 3)
    c = harness.seed_key(-5)
    assert len({str(k) for k in (a, b, c)}) == 3
    assert str(harness.seed_key(2**40 + 3)) == str(b)


def _run(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(cwd / "benchmarks" / "chip" / "run.py"),
         "--workload", WORKLOADS[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".traces", ".scratch",
                                                  "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)


# -- BENCHMARK.json against the benchmark's contract ---------------------

def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in names
            names.add(e["name"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    pairs = set()
    for w in BENCH["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = [m for m in BENCH["per_layer"] if m["moves"] in e2e and (
            "workloads" not in m or w["name"] in m["workloads"])]
        assert layers
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200, total


def test_roofline_and_mfu_metrics_are_shares():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    moved = {m["moves"] for m in BENCH["per_layer"]
             if m["name"].endswith("_roofline")}
    for e2e in moved:
        assert any("mfu" in m["name"] and m["moves"] == e2e
                   for m in BENCH["per_layer"])
