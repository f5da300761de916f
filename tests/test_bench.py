"""bench.py without a chip: its static models, tiny-shape ladder steps,
artifact guards, and its refusal to measure off the chip."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def test_time_drop_round_compiles_and_runs():
    """The droprate capture's on-chip timing program must compile and
    execute on CPU CI: it only ever ran under on_tpu before, so a break
    surfaced at the END of a live TPU session (after the convergence
    sweeps) — the most expensive possible place to find it."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    state0 = bench.build_state(96, 32, 8)
    offsets = jnp.asarray(gossip.dissemination_offsets(96), jnp.uint32)
    for rate in (0.0, 0.3):
        # tiny scan: this proves compile+execute, not a stable rate
        per_round = bench._time_drop_round(state0, offsets, rate, 96,
                                           start=4, min_delta=1e-4,
                                           repeats=1)
        assert per_round > 0.0


def test_northstar_ici_model_math():
    """The v5e-4 projection must be a traffic model, not linear
    scaling: block-aligned dissemination offsets ship
    whole packed blocks over the ring cut; intra-block offsets are free.
    Pins the arithmetic at the north-star shape."""
    m = bench.northstar_ici_model(1.2, 1 << 20, 256, 256, n_chips=4)
    # PackedAWSetDeltaState row: vv+processed (2*256*4) + 4 dot arrays
    # (4*256*4) + 2 bitpacked membership rows (2*32) + actor (4)
    assert m["packed_row_bytes"] == 2 * 256 * 4 + 4 * 256 * 4 + 64 + 4
    # 20 offsets, blk=2^18: only 2^18 (1 hop) and 2^19 (2 hops) cross
    assert [c["offset"] for c in m["crossing_rounds"]] == [1 << 18, 1 << 19]
    assert [c["ring_hops"] for c in m["crossing_rounds"]] == [1, 2]
    assert m["ici_link_bytes"] == (1 << 18) * m["packed_row_bytes"] * 3
    assert m["compute_s"] == 0.3
    assert m["ici_s"] == round(m["ici_link_bytes"] / 45e9, 4)
    assert m["model_s"] == max(m["compute_s"], m["ici_s"])
    assert m["serialized_bound_s"] == round(m["compute_s"] + m["ici_s"], 4)
    # ICI-bound regime: with 64 chips compute shrinks and the ring cut
    # dominates, so the model must NOT report the linear number
    m64 = bench.northstar_ici_model(1.2, 1 << 20, 256, 256, n_chips=64)
    assert m64["model_s"] == m64["ici_s"] > m64["compute_s"]


def test_new_ladder_steps_run_at_tiny_shapes(monkeypatch):
    """The round-5 ladder steps (dot-word configs, AWSet-only config 5)
    must run end-to-end at tiny shapes in CI — a signature or dispatch
    break must not first surface mid-capture in a live TPU window."""
    orig = bench._scan_round_rate

    def quick(*a, **k):
        k.update(min_delta=1e-3, max_n=32, repeats=2)
        return orig(*a, **k)

    monkeypatch.setattr(bench, "_scan_round_rate", quick)
    r3 = bench.measure_config3_dotpacked(128, 64, 64)
    r4 = bench.measure_config4_dotpacked(128, 64, 64)
    r5 = bench.measure_config5_awset(256, 64, 64)
    for r in (r3, r4, r5):
        assert r["value"] > 0, r["metric"]
        assert r["repeats"] >= 1


def test_roofline_row_bytes_and_artifact(tmp_path, monkeypatch, capsys):
    """The static HBM model's row-bytes must match the regime notes'
    audited figures (config 3: 3,328 B/row bool, 100.3MB
    aligned round; DESIGN 11: ~2.1KB dot-word, ~6.7KB delta bool)."""
    assert bench._row_bytes(256, 256, "awset", "bool") == 3328
    assert bench._row_bytes(256, 256, "awset", "dots") == 2080
    assert bench._row_bytes(256, 256, "delta", "bool") == 6656
    assert bench._row_bytes(256, 256, "delta", "dots") == 4160
    monkeypatch.chdir(tmp_path)   # no BENCH_LADDER.json here
    out = bench.run_roofline()
    assert (tmp_path / "ROOFLINE.json").exists()
    by_cfg = {r["config"]: r for r in out["rows"]}
    assert by_cfg["config3"]["aligned_round_mb"] == 100.3
    assert by_cfg["config3"]["roofline_round_ms"] == 0.1225
    assert by_cfg["config3_dotpacked"]["roofline_rate"] > \
        by_cfg["config3"]["roofline_rate"] * 1.5
    assert "measured_rate" not in by_cfg["config3"]
    json.loads(capsys.readouterr().out.strip())


def test_ingest_ladder_refuses_cpu_overwrite_of_tpu_artifact(tmp_path,
                                                             monkeypatch,
                                                             capsys):
    """The BENCH_r03/r05 footgun, fenced for the ingest ladder: a
    CPU(-fallback) run must refuse to overwrite an on-chip
    BENCH_INGEST.json — and must still write a fresh or same-platform
    artifact."""
    out = str(tmp_path / "BENCH_INGEST.json")
    with open(out, "w") as f:
        json.dump({"platform": "tpu", "curve": [{"committed": True}]}, f)
    # measure_ingest monkeypatched out: the guard must trip BEFORE any
    # measurement (a refused run should not even initialize legs)
    monkeypatch.setattr(bench, "measure_ingest",
                        lambda *a, **k: pytest.fail("measured anyway"))
    assert bench.run_ingest(out=out) is None
    with open(out) as f:
        assert json.load(f)["curve"] == [{"committed": True}]
    assert "refusing" in capsys.readouterr().out

    # same-platform (cpu over cpu) proceeds
    with open(out, "w") as f:
        json.dump({"platform": "cpu"}, f)
    monkeypatch.setattr(
        bench, "measure_ingest",
        lambda *a, **k: [{"batch": 8, "keys_per_op": 1,
                          "wal_bytes_ratio": 4.0}])
    art = bench.run_ingest(out=out)
    assert art["platform"] == "cpu"
    with open(out) as f:
        assert json.load(f)["curve"][0]["batch"] == 8


def test_run_ladder_writes_canonical_order(tmp_path, monkeypatch, capsys):
    """The ladder runs and records configs 1-5 in canonical order, one
    JSON line each, each labelled with its platform."""
    monkeypatch.chdir(tmp_path)
    order = []

    def mk(name):
        def fn(*a, **k):
            order.append(name)
            return {"metric": f"{name}: stub", "value": 1.0, "unit": "x"}
        return fn

    for name, attr in [("config1", "measure_config1"),
                       ("config2", "measure_config2"),
                       ("config3_dotpacked", "measure_config3_dotpacked"),
                       ("config4", "measure_config4"),
                       ("config4_dotpacked", "measure_config4_dotpacked"),
                       ("config4ref", "measure_config4_reference"),
                       ("config5", "measure_config5"),
                       ("config5_awset", "measure_config5_awset")]:
        monkeypatch.setattr(bench, attr, mk(name))
    monkeypatch.setattr(bench, "measure_spec_baseline",
                        lambda full=True: (1.0, [1.0]))
    monkeypatch.setattr(bench, "measure_tpu",
                        lambda full=False: (1.0, {}) if full else 1.0)
    results = bench.run_ladder()
    canonical = list(bench._LADDER_ORDER)
    assert order == [s for s in canonical if s != "config3"]
    assert [r["metric"].split(":")[0] for r in results] == canonical
    assert {r["platform"] for r in results} == {"cpu"}
    with open(tmp_path / "BENCH_LADDER.json") as f:
        assert json.load(f) == results
    printed = [json.loads(ln) for ln in
               capsys.readouterr().out.strip().splitlines()]
    assert printed == results


@pytest.mark.parametrize("mode", [[], ["--ladder"], ["--northstar"]])
def test_measurement_modes_fail_off_the_chip(tmp_path, mode):
    """Off the chip bench.py exits non-zero, prints no rate and writes
    no artifact."""
    import os
    import subprocess

    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__).resolve()), *mode],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert not list(tmp_path.iterdir())
