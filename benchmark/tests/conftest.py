"""CPU rehearsals of the benchmark: JAX on the CPU with four devices, so
a cell rehearses on as many devices as it has chips (up to four), at
the sizes its configuration file gives under ``rehearsal``, with no
persistent compile cache (a CPU entry is useless on the chip).

This runs before anything imports jax: the device count is fixed at
the backend's first use."""

import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (re.sub(
    r"--xla_force_host_platform_device_count=\S*", "",
    os.environ.get("XLA_FLAGS", ""))
    + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    from benchmark import harness

    monkeypatch.setattr(harness, "place_cache", lambda: None)


def run_cell(cell: str, seed: int = 2**33 + 17, seconds: float = 1.5,
             trace: int = 0, control: bool = False, root=None):
    """One CPU run of a cell at its configuration's rehearsal sizes;
    returns its result line."""
    import io
    import json
    import time

    from benchmark import harness

    bench = harness.load_json(os.path.join(root or harness.ROOT,
                                           "BENCHMARK.json"))
    _, config = harness.cell_of(bench, cell)
    sizes = harness.load_json(os.path.join(
        root or harness.ROOT, config["file"]))["rehearsal"]
    out = io.StringIO()
    harness.run(["--workload", cell, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", str(trace)], time.monotonic(),
                allow_cpu=True, sizes=sizes, stdout=out, control=control)
    return json.loads(out.getvalue().strip().splitlines()[-1])
