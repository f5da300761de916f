"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas kernels in
interpret mode), and its refusal to run anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("phase", [
    "fleet_merge", "delta_fleet", "served_store", "kernels", "mesh_serve",
    "sharded_delta_sync"])
def test_phase_runs_tiny(phase):
    """Every phase at a tiny size: its own checks pass and it reports
    one JSON-serializable record.  The two --chips 4 phases run on four
    of the conftest's virtual CPU devices."""
    if phase == "fleet_merge":
        rec = chip_smoke.phase_fleet_merge(128, 128, 16, kernel="pallas")
        assert rec["rounds"] == 7 and rec["oracle_pairs"] == 64
        assert rec["clocks_converged"]
    elif phase == "delta_fleet":
        rec = chip_smoke.phase_delta_fleet(128, 128, 16, kernel="pallas")
        assert set(rec["runs"]) == {"v2", "strict_reference"}
        assert all(r["members_converged"] for r in rec["runs"].values())
        assert rec["runs"]["v2"]["clocks_converged"]
    elif phase == "served_store":
        rec = chip_smoke.phase_served_store(1024, 300)
        assert rec["acked"] == 300 and rec["batch_errors"] == 0
        assert rec["ingest_dispatches"] > 0
        assert rec["mosaic_ingest"] is False   # interpret mode here
    elif phase == "kernels":
        rec = chip_smoke.phase_kernels(128, 256)
        assert len(rec["cases"]) == 3 + 2 * 2 * 4 + 1 + 2 + 2
    elif phase == "mesh_serve":
        rec = chip_smoke.phase_mesh_serve(1024, 300)
        assert len(set(rec["mesh_shards"])) == 4
    else:
        rec = chip_smoke.phase_sharded_delta_sync(4, 64, 128, 16)
        assert len(rec["paths"]) == 6
        # every path at the full replica count, several per device
        assert all("xR256" in p for p in rec["paths"][:5])
    assert rec["phase"] == phase
    json.dumps(rec)


def test_rows_checker_catches_one_wrong_row():
    """The per-round bitwise check, over several row chunks: the XLA
    round itself passes, and one flipped lane in one row fails."""
    import jax.numpy as jnp

    import bench
    from go_crdt_playground_tpu.ops.merge import merge_pairwise
    from go_crdt_playground_tpu.parallel import gossip

    R = 256
    state = bench.build_state(R, 64, 16)
    got = gossip.gossip_round(state, gossip.ring_perm(R, 3), kernel="xla")
    chunk_equal, n_chunks, size = chip_smoke._rows_checker(
        lambda d, s: merge_pairwise(d, s)[0], R, max_rows=64)
    assert (n_chunks, size) == (4, 64)

    def check(g):
        return [bool(chunk_equal(state, g, jnp.uint32(3),
                                 jnp.uint32(c * size)))
                for c in range(n_chunks)]

    assert check(got) == [True] * 4
    bad = got._replace(present=got.present.at[130, 5].set(
        ~got.present[130, 5]))
    assert check(bad) == [True, True, False, True]


def test_op_stream_is_seeded_and_in_range():
    a = chip_smoke.op_stream(2000, 4096, seed=3)
    b = chip_smoke.op_stream(2000, 4096, seed=3)
    assert all(ka == kb and np.array_equal(x, y)
               for (ka, x), (kb, y) in zip(a, b))
    kinds = np.asarray([k for k, _ in a])
    assert 0.85 < (kinds == 0).mean() < 0.95
    sizes = np.asarray([len(keys) for _, keys in a])
    assert sizes.min() >= 1 and sizes.max() <= 8
    assert max(int(keys.max()) for _, keys in a) < 4096


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_cpu_without_running_a_phase():
    proc = _run(str(REPO))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "phase" not in proc.stdout


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("chips", [1, 4])
def test_main_sizes_and_last_line(monkeypatch, capsys, chips):
    """main() — the only place the real sizes live — calls each phase at
    the sizes the issue fixes and prints the ok line last; a phase that
    ran off the Mosaic kernel fails the run with no ok line."""
    import jax

    from go_crdt_playground_tpu.utils import compile_cache

    calls = []

    def stub(name, **extra):
        def fn(*args):
            calls.append((name, args))
            return {"phase": name, **extra}
        return fn

    monkeypatch.setattr(jax, "devices", lambda: [_FakeTpu()] * chips)
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "c")
    pallas = {"kernel": "pallas", "mosaic": True}
    for name, extra in [
            ("phase_fleet_merge", pallas),
            ("phase_delta_fleet", {"runs": {"v2": pallas}}),
            ("phase_served_store", {"ingest_regime": "pallas:k=128",
                                    "mosaic_ingest": True}),
            ("phase_kernels", {}),
            ("phase_mesh_serve", {}), ("phase_sharded_delta_sync", {})]:
        monkeypatch.setattr(chip_smoke, name, stub(name, **extra))
    argv = ["--chips", "4"] if chips == 4 else []
    assert chip_smoke.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": chips}}
    if chips == 4:
        assert calls == [("phase_mesh_serve", (1 << 20, 20_000)),
                         ("phase_sharded_delta_sync",
                          (4, 25_024, 256, 256))]
        return
    assert calls == [("phase_fleet_merge", (1_000_000, 256, 256)),
                     ("phase_delta_fleet", (100_032, 256, 256)),
                     ("phase_served_store", (1 << 20, 20_000)),
                     ("phase_kernels", (256, 8192))]
    calls.clear()
    assert chip_smoke.main(["--phase", "kernels"]) == 0
    assert calls == [("phase_kernels", (256, 8192))]
    with pytest.raises(SystemExit):
        chip_smoke.main(["--phase", "mesh_serve"])
    capsys.readouterr()
    monkeypatch.setattr(chip_smoke, "phase_fleet_merge",
                        stub("phase_fleet_merge", kernel="xla",
                             mosaic=False))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
