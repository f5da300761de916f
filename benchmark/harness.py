"""The harness: one cell, one run, one result line.

Everything specific to a configuration, a traffic mix or a metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` (the ``file`` of the configuration) names
  its ``driver``: ``drivers/<driver>.py`` builds the system, warms it
  up, runs the measured window and checks the outputs, and declares
  its ``FAMILY`` (``served`` or ``fleet``), the faults its cells are
  tested against; the file's ``rehearsal`` holds the sizes a CPU
  rehearsal overrides (``benchmark/tests``), which a run on the chip
  never reads;
* ``traffic/<traffic>.json``: the mix's parameters, read by
  ``gen.py`` (served) or the driver (fleet);
* ``e2e/<metric>.py`` and ``layers/<metric>.py``: each metric's reader,
  ``value(ctx) -> float | None`` over what the driver recorded;
* ``peaks.json``: the chip's peaks by ``device_kind``.

A reader that finds nothing to read returns None and the metric is left
out of the line.  A driver finds the cell's devices, the first
``chips`` that JAX lists, in ``ctx["devices"]``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A metric, driver or byte-model file as a module (names may hold
    dots, so they are loaded by path)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, workload: str):
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share q
    of the values at or below it (inf counts as a value)."""
    vals = sorted(values)
    if not vals:
        return math.nan
    return vals[max(0, math.ceil(q * len(vals)) - 1)]


def place_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, holding every program, so only a cell's first run in a
    checkout compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, allow_cpu: bool) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if len(devs) < chips or (not allow_cpu and d.platform != "tpu"):
        raise SystemExit(
            f"no accelerator for this cell: JAX sees {len(devs)} "
            f"{d.platform} device(s), the cell asks for {chips} TPU chip(s)")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(count: int) -> int:
    """Peak bytes in use on the fullest chip since the process began."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:count]]
    return int(max(peaks))


def start_trace(log_dir: str) -> None:
    """The profiler, host TraceMe spans (JAX's own and the harness's) on,
    the Python function tracer off: it would slow the served host path
    the trace is there to show."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


class CompileCounter:
    """Counts XLA compilations (backend compiles, cache hits excluded)
    between ``start`` and ``stop``: the window must compile nothing."""

    def __init__(self):
        import jax

        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def start(self):
        self.n, self.on = 0, True

    def stop(self) -> int:
        self.on = False
        return self.n


def parse(argv):
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run(argv, t_start: float, allow_cpu: bool = False,
        sizes: dict = None, stdout=None, control: bool = False) -> dict:
    """One run of one cell.  ``allow_cpu`` (any platform, still with
    as many devices as the cell has chips) and ``sizes`` (overrides of
    the configuration's sizes) are for the CPU rehearsals only;
    ``control`` runs the cell's control (``control.py``)."""
    args = parse(argv)
    stdout = stdout or sys.stdout
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config = cell_of(bench, args.workload)
    cfg = load_json(os.path.join(ROOT, config["file"]))
    if sizes:
        cfg = {**cfg, **sizes}
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    place_cache()
    device = device_info(int(cell["chips"]), allow_cpu)
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    import jax

    ctx = {"cell": cell, "config": cfg, "traffic": traffic,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "t_start": t_start,
           "device": device, "devices": jax.devices()[:device["count"]],
           "compiles": CompileCounter(),
           "control": control,
           "peaks": peaks["devices"].get(device["kind"])}
    if ctx["peaks"] is None and not allow_cpu:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} "
                         f"in benchmark/peaks.json")
    driver = load_module(os.path.join(BENCH, "drivers",
                                      cfg["driver"] + ".py"))
    driver.run(ctx)
    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in bench[group]:
        if not applies(m, cell["name"]):
            continue
        if m["name"] == "setup_s":
            value = ctx["setup_s"]
        else:
            sub = "layers" if args.trace else "e2e"
            value = load_module(os.path.join(
                BENCH, sub, m["name"] + ".py")).value(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {**device, "memory_peak_bytes": ctx["memory_peak_bytes"]}
    if args.trace and ctx.get("trace_red"):
        dev["busy_s"] = ctx["trace_red"]["busy_s"]
        dev["window_s"] = ctx["trace_red"]["window_s"]
    checks = ctx["checks"]
    correct = all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    result = {"correct": correct, "attempted": ctx["attempted"],
              "failed": ctx["failed"], "metrics": metrics, "device": dev}
    if args.trace and ctx.get("trace_red"):
        result["breakdown"] = ctx["trace_red"]["breakdown"]
    result["info"] = ctx.get("info", {})
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    print(json.dumps(result), file=stdout, flush=True)
    return result


def main(argv, t_start: float) -> int:
    run(argv, t_start)
    return 0
