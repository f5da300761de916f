"""ConnHost (serve/host.py): the listener/reader/conn-slot plumbing the
frontend and router used to hand-copy from each other, extracted so
accept-path fixes land once.  jax-free and cheap: pure socket plumbing.
"""

import socket
import threading
import time

import pytest

from go_crdt_playground_tpu.net import framing
from go_crdt_playground_tpu.serve.host import ConnHost


def _dial(addr, timeout=5.0):
    return socket.create_connection(addr, timeout=timeout)


def test_dispatch_roundtrip_and_unknown_frame_closes():
    def dispatch(session, msg_type, body):
        if msg_type == 99:
            session.send(100, body.upper())
            return True
        session.send(framing.MSG_ERROR, b"unknown verb")
        return False

    host = ConnHost(dispatch, thread_name="test-host")
    addr = host.listen()
    try:
        conn = _dial(addr)
        try:
            framing.send_frame(conn, 99, b"abc")
            t, b = framing.recv_frame(conn, timeout=5.0)
            assert (t, b) == (100, b"ABC")
            # an unknown frame ends the connection (the MSG_ERROR
            # reply is best-effort: the close may tear it off first)
            framing.send_frame(conn, 50, b"")
            with pytest.raises((framing.RemoteError,
                                framing.TruncatedFrame, OSError)):
                framing.recv_frame(conn, timeout=5.0)
        finally:
            conn.close()
    finally:
        host.stop_accepting()
        host.close_sessions(0.5)


def test_closed_listener_refuses_new_dials():
    """THE shared-host regression (pre-extraction, frontend.py and
    router.py each carried this fix by hand): a bare listener close
    does not wake the blocked accept loop on this kernel, and until it
    wakes the kernel keeps COMPLETING new dials into the backlog — so
    "stopped accepting" must mean refused-at-the-kernel, which only
    shutdown-before-close delivers."""
    host = ConnHost(lambda s, t, b: True, thread_name="test-host")
    addr = host.listen()
    live = _dial(addr)
    live.close()
    host.stop_accepting()
    # every new dial must now fail outright — never accepted-then-idle
    for _ in range(3):
        with pytest.raises(OSError):
            c = _dial(addr, timeout=2.0)
            c.close()  # unreachable; close if the dial wrongly landed
    host.close_sessions(0.5)


def test_connection_slot_cap_sheds_and_recovers():
    host = ConnHost(lambda s, t, b: True, thread_name="test-host",
                    max_conns=1)
    addr = host.listen()
    try:
        c1 = _dial(addr)
        time.sleep(0.1)  # let the accept loop take the only slot
        # second dial: TCP-accepted then immediately dropped by the gate
        c2 = _dial(addr)
        c2.settimeout(5.0)
        assert c2.recv(1) == b"", "shed dial was not closed"
        c2.close()
        c1.close()
        # the released slot admits again (reader teardown is async)
        deadline = time.monotonic() + 10.0
        admitted = False
        while time.monotonic() < deadline and not admitted:
            c3 = _dial(addr)
            c3.settimeout(0.3)
            try:
                c3.recv(1)
            except socket.timeout:
                admitted = True  # still open: the slot took us
            except OSError:
                time.sleep(0.05)
            finally:
                c3.close()
        assert admitted, "released slot never admitted a new dial"
    finally:
        host.stop_accepting()
        host.close_sessions(0.5)


def test_sessions_registry_and_shared_flush_window():
    """close_sessions drains under ONE shared deadline and empties the
    registry; readers observe their session closing."""
    stop = threading.Event()

    def dispatch(session, msg_type, body):
        session.send(msg_type, body)
        return not stop.is_set()

    host = ConnHost(dispatch, thread_name="test-host")
    addr = host.listen()
    conns = [_dial(addr) for _ in range(3)]
    for i, c in enumerate(conns):
        framing.send_frame(c, 99, b"x")
        framing.recv_frame(c, timeout=5.0)
    assert len(host.sessions()) == 3
    host.stop_accepting()
    t0 = time.monotonic()
    host.close_sessions(flush_timeout_s=1.0)
    assert time.monotonic() - t0 < 5.0, "flush was per-session, not shared"
    assert host.sessions() == []
    for c in conns:
        c.close()


def test_dial_accepted_as_the_drain_sweeps_is_closed():
    """A dial accept() returned just before stop_accepting, registered
    only after close_sessions swept the sessions: nothing else would
    ever close it, so the accept loop sheds it itself."""
    host = ConnHost(lambda s, t, b: True, thread_name="test-host")
    server_end, client_end = socket.socketpair()

    class _Listener:
        def __init__(self):
            self.calls = 0

        def accept(self):
            self.calls += 1
            if self.calls > 1:
                raise OSError("listener closed")
            # the drain runs between accept()'s return and registration
            host._draining.set()
            host.close_sessions(0.5)
            return server_end, ("127.0.0.1", 1)

    host._listener = _Listener()
    host._accept_loop()
    client_end.settimeout(5.0)
    try:
        assert client_end.recv(1) == b""  # closed, not left open
    finally:
        client_end.close()
