"""Protocol-contract analyzer suite (DESIGN.md §15 W001-W004 + M001).

Two halves:

* **adversarial codec vectors** — the W003 harness run standalone over
  the full codec registry (roundtrip / truncation-at-every-boundary /
  garble / varint inflation), plus hand-crafted oversized and
  out-of-range vectors per body family, including the regression
  vectors for the true positive the harness found during development
  (``decode_members`` shipped without the uint32 range check and the
  count-exceeds-body allocation guard every sibling decoder carries);
* **seeded-injection tests** — each pass is fed a planted violation
  (deleted dispatch arm, stale ignore, lost fallthrough, bare literal
  reject code, asymmetric codec, untyped-error decoder, bare
  recv_frame, phantom metric, stale committed report) and must fire.
  A gate that cannot fail proves nothing.
"""

import json
import os

import numpy as np
import pytest

from go_crdt_playground_tpu.analysis import (codec_symmetry,
                                             metrics_contract,
                                             protocol_contract)
from go_crdt_playground_tpu.analysis.codec_symmetry import (CodecSpec,
                                                            build_codecs,
                                                            check_codec)
from go_crdt_playground_tpu.analysis.protocol_contract import \
    DispatcherSpec
from go_crdt_playground_tpu.net.framing import ProtocolError
from go_crdt_playground_tpu.serve import protocol

PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "go_crdt_playground_tpu")


# ---------------------------------------------------------------------------
# W003 harness, standalone over the real registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", build_codecs(), ids=lambda s: s.name)
def test_codec_contract(spec):
    """Per msg type: roundtrip identity, truncation at every boundary
    varint, seeded garble, and varint inflation — all typed."""
    rng = np.random.default_rng(1234)
    findings = check_codec(spec, rng, n_samples=3, n_garbles=12)
    assert not findings, [f.render() for f in findings]


def test_registry_covers_every_wire_module_codec():
    findings, stats = codec_symmetry.check_coverage(PKG, build_codecs())
    assert not findings, [f.render() for f in findings]
    assert stats["codec_functions"] >= 40


# ---------------------------------------------------------------------------
# Hand-crafted adversarial vectors (committed, seeded by construction)
# ---------------------------------------------------------------------------


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        if v < 0x80:
            out.append(v)
            return bytes(out)
        out.append((v & 0x7F) | 0x80)
        v >>= 7


def test_members_vv_entry_over_uint32_is_typed():
    """REGRESSION (the W003 true positive): a 5-byte varint vv entry
    in a MEMBERS reply raised OverflowError THROUGH the client reader
    thread instead of the typed ProtocolError — decode_members was the
    one decoder without the range check."""
    body = (_varint(7)          # req_id
            + _varint(0)        # no members
            + _varint(1)        # one vv entry
            + _varint(1 << 32))  # > uint32
    with pytest.raises(ProtocolError):
        protocol.decode_members(body)


def test_members_count_beyond_body_is_typed_not_alloc():
    """A hostile vv count must fail BEFORE np.zeros ever sees it."""
    body = (_varint(7) + _varint(0)
            + _varint(1 << 40))  # vv count: ~10^12 entries, 3 bytes left
    with pytest.raises(ProtocolError):
        protocol.decode_members(body)
    # member count beyond body likewise
    body = _varint(7) + _varint(1 << 40)
    with pytest.raises(ProtocolError):
        protocol.decode_members(body)


def test_frontier_reply_oversized_count_is_typed():
    body = (_varint(7) + bytes([0])  # flags
            + _varint(1 << 40))      # array count beyond body
    with pytest.raises(ProtocolError):
        protocol.decode_frontier_reply(body)


def test_op_oversized_key_count_is_typed():
    body = (_varint(7) + bytes([protocol.OP_ADD]) + _varint(0)
            + _varint(1 << 40))  # k elements, none present
    with pytest.raises(ProtocolError):
        protocol.decode_op(body)


def test_lane_section_claims_more_lanes_than_universe():
    from go_crdt_playground_tpu.utils import wire

    E, A = 8, 2
    body = (_varint(E)
            + wire._encode_vv_py(np.zeros(A, np.uint32))
            + _varint(E + 1))  # lane section claims E+1 lanes
    with pytest.raises(ValueError):
        wire.decode_payload_lanes(body, E, A)


def test_lane_id_outside_universe_is_typed():
    from go_crdt_playground_tpu.utils import wire

    E, A = 8, 2
    body = (_varint(E)
            + wire._encode_vv_py(np.zeros(A, np.uint32))
            + _varint(1) + _varint(E) + _varint(0) + _varint(1)  # lane E
            + _varint(0))
    with pytest.raises(ValueError):
        wire.decode_payload_lanes(body, E, A)


def test_summary_digest_count_mismatch_is_typed():
    from go_crdt_playground_tpu.net import digestsync

    E, A, GS = 16, 4, 4
    good = digestsync.encode_summary(
        0, E, GS, np.zeros(A, np.uint32), np.zeros(A, np.uint32),
        np.zeros(4, np.uint32))
    digestsync.decode_summary(good, E, A)  # sanity
    bad = digestsync.encode_summary(
        0, E, GS, np.zeros(A, np.uint32), np.zeros(A, np.uint32),
        np.zeros(3, np.uint32))  # one group short
    with pytest.raises(ProtocolError):
        digestsync.decode_summary(bad, E, A)


# ---------------------------------------------------------------------------
# Seeded injections: every pass must be able to fire
# ---------------------------------------------------------------------------


def test_w003_detects_asymmetric_codec():
    """A codec whose decode drifted from its encode (drops a field)."""
    spec = CodecSpec(
        name="planted-asym",
        encode=lambda a, b: bytes([a, b]),
        decode=lambda body: (body[0], 0) if len(body) == 2
        else (_ for _ in ()).throw(ValueError("short")),
        gen=lambda rng: (int(rng.integers(1, 100)),
                         int(rng.integers(1, 100))),
        expected=lambda args: args,
        typed_errors=(ValueError,), covers=())
    rng = np.random.default_rng(0)
    findings = check_codec(spec, rng, n_samples=2, n_garbles=2)
    assert any("roundtrip mismatch" in f.message for f in findings)


def test_w003_detects_untyped_decoder_error():
    """A decoder raising IndexError on truncation (the reader-thread
    killer) is a finding, not a pass."""
    spec = CodecSpec(
        name="planted-untyped",
        encode=lambda v: _varint(v) + bytes(2),
        decode=lambda body: (body[0], body[1], body[2]),  # IndexError
        gen=lambda rng: (int(rng.integers(0, 50)),),
        expected=lambda args: None,
        typed_errors=(ValueError,), covers=(),
        compare=lambda got, want: True)
    rng = np.random.default_rng(0)
    findings = check_codec(spec, rng, n_samples=1, n_garbles=0)
    assert any("UNTYPED IndexError" in f.message for f in findings)


_PLANTED_DIALECT = '''\
from go_crdt_playground_tpu.net import framing

MSG_A = 1
MSG_B = 2
MSG_R = 3  # protocol-ignore: reply — planted reply frame


class D:
    def _dispatch(self, session, msg_type, body):
        if msg_type == MSG_A:
            return True
        session.send(framing.MSG_ERROR, b"?")
        return False

    def _read_loop(self):
        msg_type = 0
        if msg_type == MSG_R:
            return framing.ProtocolError
        return None
'''


def _plant(tmp_path, source):
    mod = tmp_path / "planted.py"
    mod.write_text(source)
    return str(tmp_path), "planted.py"


def _specs(rel):
    return (
        DispatcherSpec("planted", rel, "D._dispatch", (rel,),
                       "server", "MSG_ERROR"),
        DispatcherSpec("planted-client", rel, "D._read_loop", (rel,),
                       "client", "ProtocolError"),
    )


def test_w001_detects_deleted_dispatch_arm(tmp_path):
    root, rel = _plant(tmp_path, _PLANTED_DIALECT)
    findings, stats = protocol_contract.check_dispatchers(
        root, _specs(rel))
    holes = [f for f in findings if "no handler arm" in f.message]
    assert len(holes) == 1 and "MSG_B" in holes[0].symbol
    # the client spec is satisfied: MSG_R has a reply arm
    assert stats["dispatchers"]["planted-client"]["required"] == ["MSG_R"]


def test_w001_annotated_hole_is_clean(tmp_path):
    src = _PLANTED_DIALECT.replace(
        "        session.send(framing.MSG_ERROR",
        "        # protocol-ignore: MSG_B — planted exclusion\n"
        "        session.send(framing.MSG_ERROR")
    root, rel = _plant(tmp_path, src)
    findings, _ = protocol_contract.check_dispatchers(root, _specs(rel))
    assert not findings, [f.render() for f in findings]


def test_w001_stale_ignore_is_a_finding(tmp_path):
    src = _PLANTED_DIALECT.replace(
        "        if msg_type == MSG_A:",
        "        # protocol-ignore: MSG_A — planted stale ignore\n"
        "        if msg_type == MSG_A:")
    root, rel = _plant(tmp_path, src)
    findings, _ = protocol_contract.check_dispatchers(root, _specs(rel))
    assert any("stale protocol-ignore" in f.message for f in findings)


def test_w001_lost_fallthrough_is_a_finding(tmp_path):
    src = _PLANTED_DIALECT.replace(
        '        session.send(framing.MSG_ERROR, b"?")\n', "")
    src = src.replace("MSG_B = 2\n", "")  # isolate the fallthrough check
    root, rel = _plant(tmp_path, src)
    findings, _ = protocol_contract.check_dispatchers(
        root, _specs(rel)[:1])
    assert any("fallthrough" in f.message for f in findings)


def test_w001_reply_constant_needs_client_arm(tmp_path):
    src = _PLANTED_DIALECT.replace("        if msg_type == MSG_R:\n"
                                   "            return framing."
                                   "ProtocolError\n",
                                   "        del msg_type\n")
    src = src.replace("    def _read_loop(self):\n",
                      "    def _read_loop(self):\n"
                      "        err = framing.ProtocolError\n")
    root, rel = _plant(tmp_path, src)
    findings, _ = protocol_contract.check_dispatchers(
        root, _specs(rel)[1:])
    assert any("MSG_R" in (f.symbol or "") for f in findings)


def test_w002_registry_bijection_holds():
    findings, stats = protocol_contract.check_reject_registry()
    assert not findings, [f.render() for f in findings]
    assert stats["codes"] == stats["constants"] == \
        stats["exception_classes"] >= 6


def test_w002_detects_unregistered_reject_code(tmp_path):
    mod = tmp_path / "planted_reject.py"
    mod.write_text(
        "from go_crdt_playground_tpu.serve import protocol\n"
        "def f(session, req_id):\n"
        "    session.send(18, protocol.encode_reject(req_id, 99, 'x'))\n"
        "    session.send(18, protocol.encode_reject(\n"
        "        req_id, protocol.REJECT_BOGUS, 'y'))\n")
    findings, stats = protocol_contract.check_reject_call_sites(
        [str(mod)])
    msgs = [f.message for f in findings]
    assert any("bare literal" in m for m in msgs)
    assert any("REJECT_BOGUS" in m for m in msgs)
    assert stats["reject_sites"] == 2


def test_w004_detects_bare_recv_frame(tmp_path):
    mod = tmp_path / "planted_recv.py"
    mod.write_text(
        "from go_crdt_playground_tpu.net import framing\n"
        "def f(sock):\n"
        "    framing.recv_frame(sock)\n"                # bare: finding
        "    framing.recv_frame(sock, timeout=1.0)\n"   # bare: finding
        "    framing.recv_frame(sock, 1.0, 4096)\n"     # explicit
        "    framing.recv_frame(sock, max_body=4096)\n")  # explicit
    findings, stats = protocol_contract.check_frame_caps([str(mod)])
    assert len(findings) == 2 and stats["recv_frame_sites"] == 4


def test_w002_keyword_form_code_is_checked(tmp_path):
    """Review regression: a bare literal riding ``code=...`` keyword
    form must not slip past the call-site lint."""
    mod = tmp_path / "planted_kw.py"
    mod.write_text(
        "from go_crdt_playground_tpu.serve import protocol\n"
        "def f(req_id):\n"
        "    return protocol.encode_reject(req_id, code=99, "
        "reason='x')\n")
    findings, stats = protocol_contract.check_reject_call_sites(
        [str(mod)])
    assert stats["reject_sites"] == 1
    assert any("bare literal" in f.message for f in findings)


def test_w004_relative_import_is_not_exempt(tmp_path):
    """Review regression: ``from ..net import framing`` (relative) and
    the direct relative recv_frame import must still be attributed to
    the armored framing module."""
    mod = tmp_path / "planted_rel.py"
    mod.write_text(
        "from ..net import framing\n"
        "from .framing import recv_frame\n"
        "def f(sock):\n"
        "    framing.recv_frame(sock)\n"
        "    recv_frame(sock)\n")
    findings, stats = protocol_contract.check_frame_caps([str(mod)])
    assert len(findings) == 2 and stats["recv_frame_sites"] == 2


def test_w004_ignores_foreign_recv_frame(tmp_path):
    """bridge/service.py's own struct-framed recv_frame must not be
    misattributed to the armored framing one."""
    mod = tmp_path / "own_framing.py"
    mod.write_text(
        "def recv_frame(sock):\n"
        "    return 0, b''\n"
        "def f(sock):\n"
        "    recv_frame(sock)\n")
    findings, stats = protocol_contract.check_frame_caps([str(mod)])
    assert not findings and stats["recv_frame_sites"] == 0


def test_w004_package_has_no_bare_recv_frame():
    """The acceptance pin: every recv_frame call site in the package
    passes an explicit cap (serve client, peer exchange, digest
    exchange — the PR's found-and-fixed bare reads stay fixed)."""
    py_files = []
    for dirpath, _d, filenames in os.walk(PKG):
        if "__pycache__" in dirpath:
            continue
        py_files.extend(os.path.join(dirpath, fn) for fn in filenames
                        if fn.endswith(".py"))
    findings, stats = protocol_contract.check_frame_caps(py_files)
    assert not findings, [f.render() for f in findings]
    assert stats["recv_frame_sites"] >= 9


def test_m001_detects_phantom_metric(tmp_path):
    pkg = tmp_path / "pkg.py"
    pkg.write_text(
        "def f(recorder):\n"
        "    recorder.count('serve.real.metric')\n")
    tool = tmp_path / "planted_soak.py"
    tool.write_text(
        "def adjudicate(counters):\n"
        "    assert counters.get('serve.phantom.metric', 0) > 0\n"
        "    assert counters.get('serve.real.metric', 0) > 0\n")
    doc = tmp_path / "DESIGN.md"
    doc.write_text("`serve.real.metric` is documented.\n")
    findings, stats = metrics_contract.check(
        [str(pkg)], [str(tool)], [str(doc)])
    errs = [f for f in findings if f.severity == "error"]
    assert len(errs) == 1 and errs[0].symbol == "serve.phantom.metric"


def test_m001_fstring_pattern_covers_classified_reference(tmp_path):
    pkg = tmp_path / "pkg.py"
    pkg.write_text(
        "def f(recorder, cls):\n"
        "    recorder.count(f'sync.failures.{cls}')\n")
    tool = tmp_path / "planted_soak.py"
    tool.write_text("NAME = 'sync.failures.remote'\n")
    doc = tmp_path / "DESIGN.md"
    doc.write_text("`sync.failures.<class>` per failure class.\n")
    findings, _ = metrics_contract.check(
        [str(pkg)], [str(tool)], [str(doc)])
    assert not findings, [f.render() for f in findings]


def test_m001_undocumented_emission_is_a_warning(tmp_path):
    pkg = tmp_path / "pkg.py"
    pkg.write_text(
        "def f(recorder):\n"
        "    recorder.count('serve.undocumented.metric')\n")
    doc = tmp_path / "DESIGN.md"
    doc.write_text("nothing here\n")
    findings, _ = metrics_contract.check([str(pkg)], [], [str(doc)])
    assert len(findings) == 1
    assert findings[0].severity == "warning"
    assert findings[0].symbol == "serve.undocumented.metric"


def test_m001_sees_span_names(tmp_path):
    """Spans are emissions too: ``Recorder.time`` and ``obs.timed``
    names must be documented like any counter."""
    pkg = tmp_path / "pkg.py"
    pkg.write_text(
        "def f(recorder, timed):\n"
        "    with recorder.time('serve.phase_s'):\n"
        "        pass\n"
        "    with timed(recorder, 'wal.other_s'):\n"
        "        pass\n"
        "    recorder.observe_deferred('process.late_s', 1.0)\n")
    doc = tmp_path / "DESIGN.md"
    doc.write_text("`serve.phase_s` is documented.\n")
    findings, _ = metrics_contract.check([str(pkg)], [], [str(doc)])
    assert sorted(f.symbol for f in findings) == ["process.late_s",
                                                  "wal.other_s"]
    assert all(f.severity == "warning" for f in findings)


def test_annotation_kinds_do_not_shadow_on_one_statement():
    """Review regression: a guarded-by above a statement plus a
    trailing protocol-ignore on the statement line must BOTH resolve —
    the kind-filtered lookup can't be shadowed by the other kind."""
    from go_crdt_playground_tpu.analysis.annotations import (
        KIND_GUARDED_BY, KIND_PROTOCOL_IGNORE, parse_annotations)

    src = ("class C:\n"
           "    def __init__(self):\n"
           "        # guarded-by: _lock\n"
           "        self.x = 1  # protocol-ignore: reply — planted\n")
    a = parse_annotations(src)
    g = a.on_lines(4, 4, KIND_GUARDED_BY)
    assert g is not None and g.arg == "_lock"
    p = a.on_lines(4, 4, KIND_PROTOCOL_IGNORE)
    assert p is not None and p.arg.startswith("reply")


def test_report_freshness_regeneration_run_is_clean(tmp_path):
    """Review regression: the documented F001 fix command (default
    --out == the committed path) must exit 0 on its FIRST run and
    write an artifact free of the stale-against-itself finding."""
    from go_crdt_playground_tpu.analysis.__main__ import main

    path = tmp_path / "ANALYSIS_REPORT.json"
    path.write_text(json.dumps({"passes": {"only": {}}}))  # stale
    rc = main(["--fast", "--skip-runtime", "--out", str(path),
               "--committed-report", str(path)])
    assert rc == 0
    fresh = json.loads(path.read_text())
    assert fresh["ok"] and fresh["n_findings"] == 0
    assert fresh["passes"]["report_freshness"]["stats"]["mode"] == \
        "regenerating"


def test_report_freshness_detects_stale_pass_list(tmp_path):
    from go_crdt_playground_tpu.analysis.__main__ import (
        REGISTERED_PASSES, check_report_freshness)
    from go_crdt_playground_tpu.analysis.report import Report

    stale = {"passes": {name: {} for name in REGISTERED_PASSES
                        if name != "codec_symmetry"}}
    path = tmp_path / "ANALYSIS_REPORT.json"
    path.write_text(json.dumps(stale))
    report = Report()
    check_report_freshness(report, str(path))
    assert report.errors() and "stale" in report.errors()[0].message

    fresh = {"passes": {name: {} for name in REGISTERED_PASSES}}
    path.write_text(json.dumps(fresh))
    report2 = Report()
    check_report_freshness(report2, str(path))
    assert not report2.errors()


# ---------------------------------------------------------------------------
# The real tree is clean (the acceptance criterion, test-speed slice)
# ---------------------------------------------------------------------------


def test_router_links_scale_reply_cap_with_universe():
    """Review regression: the router's downstream clients must size
    their reply cap from E — a donor SLICE_STATE reply scales with the
    universe, and the flat 64MB client default would make a
    large-universe reshard permanently impossible."""
    from go_crdt_playground_tpu.serve.client import ServeClient
    from go_crdt_playground_tpu.shard.router import ShardRouter

    E = 16 << 20  # a universe whose slice cap exceeds the 64MB floor
    r = ShardRouter({"s0": ("127.0.0.1", 1)}, E)
    try:
        link = r.link("s0")
        assert link.max_reply_body == 16 * E + 4096
        assert link.max_reply_body > ServeClient.MAX_REPLY_BODY
        small = ShardRouter({"s0": ("127.0.0.1", 1)}, 64)
        try:
            assert (small.link("s0").max_reply_body
                    == ServeClient.MAX_REPLY_BODY)
        finally:
            small.close()
    finally:
        r.close()


def test_real_dispatchers_are_exhaustive():
    findings, stats = protocol_contract.check_dispatchers(PKG)
    assert not findings, [f.render() for f in findings]
    assert set(stats["dialect_constants"]) == {"serve/protocol.py",
                                               "net/framing.py"}
    assert set(stats["dispatchers"]) == {"frontend", "router", "peer",
                                         "serve-client"}
    # the router's driven-verb exclusions are on record, not silent
    # (MSG_WAL_SYNC: standbys tail their primary SHARD, not the router)
    assert stats["dispatchers"]["router"]["ignored"] == [
        "MSG_DSUM", "MSG_FRONTIER", "MSG_GC", "MSG_SLICE_PULL",
        "MSG_SLICE_PUSH", "MSG_WAL_SYNC"]
    # every reply frame the servers ignore is armed in the client
    client = stats["dispatchers"]["serve-client"]
    assert set(client["required"]) <= set(client["handled"])
