"""Demo CLI (python -m go_crdt_playground_tpu): the reference's go-test
walkthrough, a converging fleet, and the Merger bridge service — the
whole operational surface, driven as a user would."""

import os
import re
import signal
import subprocess
import sys

from go_crdt_playground_tpu.__main__ import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scenario_command_passes(capsys):
    assert main(["scenario"]) == 0
    out = capsys.readouterr().out
    # the walkthrough must actually demonstrate the property, spec and
    # packed alike, with the canonical Go rendering
    assert "add-wins holds: True" in out
    assert '(B 1)  "Bob"' in out  # the concurrent re-add's dot survives
    assert out.count("[(A 2), (B 1)]") >= 2  # spec A and B agree on VVs


def test_gossip_command_converges(capsys):
    assert main(["gossip", "--replicas", "8"]) == 0
    out = capsys.readouterr().out
    assert re.search(
        r"8 replicas \(full-state gossip\) converged in \d+ "
        r"dissemination rounds", out)


def test_gossip_command_delta_with_drops_converges(capsys):
    """The resilience story from the shell: delta semantics + lossy
    exchanges still converge (SURVEY §5.3 — drops only delay)."""
    assert main(["gossip", "--replicas", "8", "--delta",
                 "--drop-rate", "0.3"]) == 0
    out = capsys.readouterr().out
    assert re.search(
        r"8 replicas \(delta gossip under 30% drop\) converged in \d+ "
        r"dissemination rounds", out)


def test_serve_command_end_to_end(tmp_path):
    """`python -m go_crdt_playground_tpu serve` as a real subprocess:
    parse the printed address, ping, run one merge through the packed
    kernels over TCP, then SIGINT for a clean exit."""
    import queue
    import threading

    from __graft_entry__ import _scrubbed_cpu_env
    from go_crdt_playground_tpu.bridge.service import MergerClient
    from go_crdt_playground_tpu.models.spec import AWSet, VersionVector

    # stderr to a file (nothing to drain, content survives for
    # diagnostics); the address line is read under a hard deadline so a
    # child wedged before printing can never hang the suite
    err_path = tmp_path / "serve.err"
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "go_crdt_playground_tpu", "serve",
             "--port", "0"],
            env=_scrubbed_cpu_env(1),  # the child stays off the chip
            cwd=REPO,  # the package is not pip-installed
            stdout=subprocess.PIPE, stderr=err_f, text=True)
    try:
        lines: "queue.Queue[str]" = queue.Queue()
        threading.Thread(target=lambda: lines.put(proc.stdout.readline()),
                         daemon=True).start()
        try:
            line = lines.get(timeout=120)
        except queue.Empty:
            raise AssertionError(
                "serve printed no address within 120s; stderr:\n"
                + err_path.read_text()[-3000:])
        m = re.search(r"listening on ([\d.]+):(\d+)", line)
        assert m, (f"no address line: {line!r}; stderr:\n"
                   + err_path.read_text()[-3000:])
        host, port = m.group(1), int(m.group(2))
        with MergerClient(host, port, timeout=120.0) as client:
            assert client.ping()
            a = AWSet(actor=0, version_vector=VersionVector([0, 0]))
            b = AWSet(actor=1, version_vector=VersionVector([0, 0]))
            a.add("Anne")
            b.add("Bob")
            merged = client.merge(a, b)
            assert merged.sorted_values() == ["Anne", "Bob"]
        proc.send_signal(signal.SIGINT)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_serve_ingest_command_end_to_end(tmp_path):
    """`serve --ingest` as a real subprocess: parse the address, submit
    ops through the serve client, read membership back, then SIGTERM
    for a graceful drain (the drain summary line is the contract the
    serve soak's parent also reads)."""
    from __graft_entry__ import _scrubbed_cpu_env
    from go_crdt_playground_tpu.serve import ServeClient

    err_path = tmp_path / "ingest.err"
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "go_crdt_playground_tpu", "serve",
             "--ingest", "--elements", "64", "--actors", "2",
             "--durable-dir", str(tmp_path / "n0"), "--flush-ms", "1"],
            env=_scrubbed_cpu_env(1), cwd=REPO,
            stdout=subprocess.PIPE, stderr=err_f, text=True)
    try:
        import queue
        import threading

        lines: "queue.Queue[str]" = queue.Queue()
        threading.Thread(target=lambda: lines.put(proc.stdout.readline()),
                         daemon=True).start()
        try:
            line = lines.get(timeout=120)
        except queue.Empty:
            raise AssertionError(
                "serve --ingest printed no address within 120s; stderr:\n"
                + err_path.read_text()[-3000:])
        m = re.search(r"listening on ([\d.]+):(\d+)", line)
        assert m, (f"no address line: {line!r}; stderr:\n"
                   + err_path.read_text()[-3000:])
        with ServeClient((m.group(1), int(m.group(2))),
                         timeout=120.0) as client:
            client.add(1, 2, 3)
            client.delete(2)
            members, vv = client.members()
        assert members == [1, 3]
        assert int(vv[0]) == 4  # 3 add ticks + 1 del tick
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0
        assert re.search(r"drained: 2 ops acked, ingest p99 ", out), out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_gossip_command_rejects_certain_loss():
    """--drop-rate 1.0 can never converge; the parser fails fast with a
    clean error instead of grinding the full round budget."""
    import pytest

    with pytest.raises(SystemExit) as exc:
        main(["gossip", "--drop-rate", "1.0"])
    assert exc.value.code == 2  # argparse usage error


def test_serve_ingest_rejects_malformed_peer():
    """--peer without a port is a clean argparse error, not an int('')
    traceback at startup."""
    import pytest

    with pytest.raises(SystemExit) as exc:
        main(["serve", "--ingest", "--peer", "otherhost"])
    assert exc.value.code == 2


def test_gossip_command_seed_flag(capsys):
    """--seed feeds the drop-mask PRNG so shell users can sample
    independent loss realizations (ADVICE r4); every seed still
    converges (drops only delay convergence, SURVEY §5.3)."""
    assert main(["gossip", "--replicas", "8", "--drop-rate", "0.3",
                 "--seed", "7"]) == 0
    assert "converged in" in capsys.readouterr().out


def test_gossip_command_schedule_flag(capsys):
    """--schedule exposes the library's pairing schedules from the
    shell; the random schedule derives its pairings from --seed."""
    assert main(["gossip", "--replicas", "8",
                 "--schedule", "random", "--seed", "5"]) == 0
    assert "random rounds" in capsys.readouterr().out
    assert main(["gossip", "--replicas", "8", "--schedule", "ring"]) == 0
    assert "ring rounds" in capsys.readouterr().out


def test_platform_flag_pins_backend(capsys):
    """--platform cpu pins the backend in-process.  Asserting the
    config value pins the wiring itself — under the conftest the
    scenario would pass even without the pin."""
    import jax

    assert main(["--platform", "cpu", "scenario"]) == 0
    assert jax.config.jax_platforms == "cpu"
    assert "add-wins holds: True" in capsys.readouterr().out
