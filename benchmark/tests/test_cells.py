"""Every cell runs end to end at a tiny size on the CPU, with its checks
passing; the load generator never starts a JAX backend; a cell made of
new files and a new BENCHMARK.json entry runs with no file edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from conftest import run_cell

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    line = run_cell(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if harness.applies(m, cell)}
    assert set(line["metrics"]) == e2e
    assert line["info"]["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"
    if "loadgen_backend_started" in line["checks"]:
        assert line["checks"]["loadgen_backend_started"]["value"] == 0


def test_off_the_chip_it_exits_without_a_result():
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT,
                       capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_without_the_program_it_exits_without_a_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "benchmark")
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_a_cell_from_new_files_only(tmp_path):
    """A later PR adds a traffic file and a BENCHMARK.json entry; nothing
    that exists is edited."""
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "go_crdt_playground_tpu"),
               tmp_path / "go_crdt_playground_tpu")
    bench = json.loads(json.dumps(BENCH))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "churn.k80.json"))
    mix.update(connections=2, warmup_ops=8,
               store={"preload": "full", "history_ops": 500})
    with open(tmp_path / "benchmark" / "traffic" / "churn.two.json",
              "w") as f:
        json.dump(mix, f)
    bench["workloads"].append(
        {"name": "serve-1m.churn.two", "config": "awset-serve-1m",
         "traffic": "churn.two", "chips": 1, "why": "two connections"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "serve-1m.churn.k80" in m["workloads"]:
            m["workloads"].append("serve-1m.churn.two")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    code = ("import sys, time; sys.path.insert(0, '.');"
            "from benchmark import harness;"
            "harness.place_cache = lambda: None;"
            "harness.run(['--workload', 'serve-1m.churn.two', '--seed', '5',"
            " '--seconds', '1', '--trace', '0'], time.monotonic(),"
            " allow_cpu=True, sizes={'elements': 2048})")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] and "ack_p50_ms" in line["metrics"]
