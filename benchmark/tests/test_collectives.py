"""The exchange between chips as the trace shows it: each device's
collective intervals (``trace_reduce.collective_intervals``) on
hand-made events, and the readers over them (``readers.collective_s``,
``readers.ici_share``)."""

import types

import pytest

from benchmark import harness, readers, trace_reduce

_T = "u32[64,256]{1,0}"
_PAIRS = "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}"


def _permute(n: int) -> str:
    return (f"%collective-permute.{n} = {_T} collective-permute({_T} "
            f"%p.{n}), {_PAIRS}")


def _start(op: str, n: int) -> str:
    return (f"%{op}-start.{n} = ({_T}, {_T}, u32[], u32[]) "
            f"{op}-start({_T} %p.{n}), {_PAIRS}")


def _done(op: str, n: int) -> str:
    return (f"%{op}-done.{n} = {_T} {op}-done(({_T}, {_T}, u32[], u32[]) "
            f"%{op}-start.{n})")


def _fusion(n: int) -> str:
    return f"%fusion.{n} = {_T} fusion({_T} %p.{n}), kind=kLoop"


def test_a_synchronous_permute_is_its_own_event():
    ev = [(_fusion(1), 0, 10), (_permute(2), 10, 25), (_fusion(3), 25, 40)]
    assert trace_reduce.collective_intervals(ev, 0, 100) == [(10, 25)]


def test_an_async_pair_spans_start_to_done():
    ev = [(_start("collective-permute", 1), 10, 12), (_fusion(1), 12, 30),
          (_done("collective-permute", 1), 30, 34)]
    assert trace_reduce.collective_intervals(ev, 0, 100) == [(10, 34)]


def test_async_pairs_match_by_the_start_they_name():
    # .1 starts first and ends last; .12 must not take .1's start
    ev = [(_start("all-reduce", 1), 10, 11), (_start("all-reduce", 12), 20, 21),
          (_done("all-reduce", 12), 30, 32), (_done("all-reduce", 1), 40, 45)]
    assert trace_reduce.collective_intervals(ev, 0, 100) == \
        [(10, 45), (20, 32)]


def test_a_pair_whose_done_falls_outside_the_window_is_clipped():
    ev = [(_start("collective-permute", 1), 80, 81),
          (_done("collective-permute", 1), 120, 125),
          (_start("all-gather", 2), 5, 6), (_done("all-gather", 2), 15, 18)]
    assert trace_reduce.collective_intervals(ev, 10, 100) == \
        [(10, 18), (80, 100)]
    # a start whose done the trace does not hold runs to the window's
    # end; a done whose start it does not hold began before the window
    ev = [(_start("collective-permute", 3), 90, 91),
          (_done("collective-permute", 4), 20, 25)]
    assert trace_reduce.collective_intervals(ev, 10, 100) == \
        [(10, 25), (90, 100)]


def test_other_async_ops_and_other_ops_are_not_collectives():
    ev = [("%copy-start.1 = (u32[8]{0}, u32[8]{0}, u32[]) copy-start("
           "u32[8]{0} %x)", 0, 1),
          ("%copy-done.1 = u32[8]{0} copy-done((u32[8]{0}, u32[8]{0}, "
           "u32[]) %copy-start.1)", 5, 6), (_fusion(2), 6, 9)]
    assert trace_reduce.collective_intervals(ev, 0, 100) == []


def _ctx(collectives, peaks=None, window=1.0):
    return {"trace_red": {"collectives": collectives, "window_s": window},
            "peaks": peaks, "shapes": {"R": 1024}}


def test_overlapping_collectives_on_a_device_count_once():
    ev = [(_permute(1), 10, 30), (_start("all-reduce", 2), 20, 21),
          (_done("all-reduce", 2), 45, 50)]
    iv = trace_reduce.collective_intervals(ev, 0, 100)
    assert iv == [(10, 30), (20, 50)]
    assert trace_reduce.covered_s(iv) == 40
    assert readers.collective_s(_ctx([iv])) == 40


def test_a_device_with_no_collectives_counts_as_none_busy():
    busy = trace_reduce.collective_intervals([(_permute(1), 0, 0.5)], 0, 1)
    idle = trace_reduce.collective_intervals([(_fusion(1), 0, 0.5)], 0, 1)
    assert idle == []
    assert readers.collective_s(_ctx([busy, idle])) == 0.25
    assert readers.collective_s(_ctx([idle])) == 0.0


def test_readers_return_none_without_a_trace():
    assert readers.collective_s({}) is None
    assert readers.ici_share({}, "delta_ring") is None
    peaks = {"ici_bytes_per_s": 200e9}
    assert readers.ici_share({"trace_red": None, "peaks": peaks},
                             "delta_ring") is None
    # a trace with no collective on any device: no share, not 0
    assert readers.ici_share(_ctx([[]], peaks), "delta_ring") is None


def test_ici_share_reads_fifty_per_cent(monkeypatch):
    """Two devices each in a collective for 0.5 s (1 s summed) move
    1e11 bytes between them: half of a 200 GB/s link."""
    seen = {}

    def model(path):
        seen["path"] = path
        return types.SimpleNamespace(
            traced_ici_bytes=lambda red, shapes: 1e11)

    monkeypatch.setattr(harness, "load_module", model)
    ctx = _ctx([[(0.0, 0.25), (0.25, 0.5)], [(0.5, 1.0)]],
               {"ici_bytes_per_s": 200e9})
    assert readers.ici_share(ctx, "rows") == pytest.approx(50.0)
    assert seen["path"].endswith("roofline/rows.py")
    # no peak for the interconnect: no share
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    assert readers.ici_share(ctx, "rows") is None
