"""Every cell runs end to end at its rehearsal size on the CPU, on as
many devices as it has chips, with its checks passing; a cell asking
for more chips than there are devices is refused on the CPU as on the
chip; a one-chip cell's state sits on one device; the load generator
never starts a JAX backend; a cell made of new files and new
BENCHMARK.json entries runs, and fails its faults and control, with no
file edited."""

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
CHIPS = {c["name"]: c["chips"] for c in BENCH["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    line = run_cell(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == CHIPS[cell]
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


def test_too_few_devices_are_refused_on_the_cpu_too():
    import jax

    n = len(jax.devices())
    assert n == 4
    assert harness.device_info(n, allow_cpu=True)["count"] == n
    assert harness.device_info(1, allow_cpu=True)["count"] == 1
    with pytest.raises(SystemExit):
        harness.device_info(n + 1, allow_cpu=True)
    with pytest.raises(SystemExit):        # the platform: not a TPU
        harness.device_info(1, allow_cpu=False)


def _one_device(arrays):
    import jax

    sets = {frozenset(a.sharding.device_set)
            for a in jax.tree.leaves(arrays)}
    return sets == {frozenset(jax.devices()[:1])}


def test_one_chip_fleet_sits_on_one_device(monkeypatch):
    """Four devices are visible; the one-chip fleet cell's every fleet
    stays on the first."""
    from go_crdt_playground_tpu.parallel import gossip

    seen = []
    orig = gossip.rounds_to_convergence

    def spy(state, *a, **kw):
        seen.append(_one_device(state))
        rounds, final = orig(state, *a, **kw)
        seen.append(_one_device(final))
        return rounds, final

    monkeypatch.setattr(gossip, "rounds_to_convergence", spy)
    line = run_cell("dawset-100k.converge", seconds=0.5)
    assert line["correct"] and line["device"]["count"] == 1
    assert seen and all(seen)


def test_one_chip_served_store_sits_on_one_device(monkeypatch):
    """The served driver keeps its frontend to itself; the frontend the
    CLI builds holds its store on the first of four devices."""
    import go_crdt_playground_tpu.__main__ as cli

    built = []
    orig = cli._build_frontend

    def spy(args):
        fe = orig(args)
        built.append(fe)
        return fe

    monkeypatch.setattr(cli, "_build_frontend", spy)
    line = run_cell("serve-1m.ycsb-load", seconds=1.0)
    assert line["correct"] and line["device"]["count"] == 1
    assert len(built) == 1 and _one_device(built[0].node._state)


# A later PR's cell, made only of new files.  "traffic": a new mix for
# an existing configuration.  "fleet_driver": a four-chip fleet cell
# with a configuration, a driver that builds each fleet row-sharded
# over ``ctx["devices"]`` and converges it through the program's
# ``rounds_to_convergence``, a traffic file and a per-layer reader.
_ROWS_CELL = "dawset-fleet-1m.rows4"

_ROWS_DRIVER = '''"""Row-sharded fleet cells: the fleet driver's convergences, each
fleet built row-sharded over the cell's chips."""

FAMILY = "fleet"


def run(ctx):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark.drivers import fleet

    mesh = Mesh(np.array(ctx["devices"]), ("rows",))
    fleet.run(ctx, NamedSharding(mesh, PartitionSpec("rows")))
'''

_ROWS_READER = '''"""collective_ms.rows4: device ms of collectives per convergence."""

from benchmark import readers


def value(ctx):
    s = readers.collective_s(ctx)
    return None if s is None else s * 1e3 / len(ctx["fleet"]["rounds"])
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def _new_traffic(root, bench):
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "churn.k80.json"))
    mix.update(connections=2, warmup_ops=8,
               store={"preload": "full", "history_ops": 500})
    _write(os.path.join(root, "benchmark", "traffic", "churn.two.json"),
           json.dumps(mix))
    bench["workloads"].append(
        {"name": "serve-1m.churn.two", "config": "awset-serve-1m",
         "traffic": "churn.two", "chips": 1, "why": "two connections"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "serve-1m.churn.k80" in m["workloads"]:
            m["workloads"].append("serve-1m.churn.two")
    return "serve-1m.churn.two", "ack_p50_ms"


def _new_fleet_driver(root, bench):
    cfg = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "configs", "dawset-fleet-100k.json"))
    cfg.update(name="dawset-fleet-1m", driver="fleet_rows",
               replicas=1000192, chips=4, rehearsal={"replicas": 1024},
               reduced={})
    b = os.path.join(root, "benchmark")
    _write(os.path.join(b, "configs", "dawset-fleet-1m.json"),
           json.dumps(cfg))
    _write(os.path.join(b, "drivers", "fleet_rows.py"), _ROWS_DRIVER)
    _write(os.path.join(b, "traffic", "converge.rows4.json"),
           json.dumps(harness.load_json(os.path.join(
               harness.BENCH, "traffic", "converge.json"))))
    _write(os.path.join(b, "layers", "collective_ms.rows4.py"),
           _ROWS_READER)
    bench["configs"].append(
        {"name": "dawset-fleet-1m", "source": "BASELINE.json config 4",
         "file": "benchmark/configs/dawset-fleet-1m.json", "reduced": [],
         "why": "a row-sharded fleet"})
    bench["workloads"].append(
        {"name": _ROWS_CELL, "config": "dawset-fleet-1m",
         "traffic": "converge.rows4", "chips": 4, "why": "four chips"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "converge_ms")["workloads"].append(_ROWS_CELL)
    bench["per_layer"].append(
        {"name": "collective_ms.rows4", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "ICI exchange",
         "moves": "converge_ms", "workloads": [_ROWS_CELL]})
    return _ROWS_CELL, "converge_ms"


def _only_added(old, new) -> bool:
    """``new`` is ``old`` with entries added: keys to objects, items at
    the end of lists, nothing changed or taken away."""
    if isinstance(old, dict):
        return isinstance(new, dict) and all(
            k in new and _only_added(v, new[k]) for k, v in old.items())
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(
            _only_added(a, b) for a, b in zip(old, new))
    return old == new


def _tree(root):
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = fh.read()
    return out


@pytest.mark.parametrize("case", ["traffic", "fleet_driver"])
def test_a_cell_from_new_files_only(case, tmp_path):
    """A later PR adds files and BENCHMARK.json entries; nothing that
    exists is edited.  The new cell runs correct on as many devices as
    it has chips, and the copy's own fault and control tests find it
    not correct."""
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "go_crdt_playground_tpu"),
               tmp_path / "go_crdt_playground_tpu")
    bench = json.loads(json.dumps(BENCH))
    add = {"traffic": _new_traffic, "fleet_driver": _new_fleet_driver}
    cell, e2e = add[case](str(tmp_path), bench)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    code = ("import json, sys; sys.path[:0] = ['.', 'benchmark/tests'];"
            "import conftest; from benchmark import harness;"
            "harness.place_cache = lambda: None;"
            f"print(json.dumps([conftest.run_cell({cell!r}, seed=5,"
            " seconds=1, trace=t) for t in (0, 1)]))")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line, traced = json.loads(r.stdout.strip().splitlines()[-1])
    chips = bench["workloads"][-1]["chips"]
    assert line["correct"] and traced["correct"], line["checks"]
    assert line["device"]["count"] == traced["device"]["count"] == chips
    assert {e2e, "setup_s"} <= set(line["metrics"])
    assert line["info"]["compiles_in_window"] == 0
    listed = {m["name"] for m in bench["per_layer"]
              if harness.applies(m, cell)}
    assert set(traced["metrics"]) <= listed
    if case == "fleet_driver":
        # the copy's own tests: its contract, and each fault and the
        # control of the new cell, come out as they must
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-rA",
             "-p", "no:cacheprovider", "benchmark/tests/test_contract.py",
             "benchmark/tests/test_faults.py", "-k", "rows4 or contract"],
            cwd=tmp_path, capture_output=True, text=True, timeout=900,
            env=env)
        assert r.returncode == 0, r.stdout[-3000:]
        passed = [ln for ln in r.stdout.splitlines()
                  if ln.startswith("PASSED")]
        for fault in ("_fleet_unchanged", "_fleet_half_rows",
                      "_fleet_altered_answer", "_fleet_no_exchange"):
            assert any(f"{cell}-{fault}]" in ln for ln in passed), fault
        assert any(f"test_control_is_not_correct[{cell}]" in ln
                   for ln in passed)
    # nothing that exists was edited
    before, after = _tree(harness.ROOT), _tree(str(tmp_path))
    assert all(after.get(p) == b for p, b in before.items())
    assert _only_added(BENCH, harness.load_json(
        str(tmp_path / "BENCHMARK.json")))
