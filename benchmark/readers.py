"""Shared arithmetic of the metric readers under ``e2e/`` and
``layers/``: each reader file names one metric and calls one of these
on the run's context (what the driver recorded)."""

from __future__ import annotations

import math
import os

import numpy as np

from benchmark import harness, trace_reduce
from benchmark.loadgen import ACKED


def _window(ctx):
    ops, warm = ctx["ops"], int(ctx["ops"]["warm"])
    return ops, slice(warm, ops["outcome"].size)


def acked_per_s(ctx):
    """Ops acked inside the window, counted at the client, per second."""
    ops, win = _window(ctx)
    t0 = ctx["t0"]
    at = ops["acked_at"][win]
    inside = (at >= t0) & (at <= t0 + ctx["seconds"])
    return float(np.sum(inside)) / ctx["seconds"]


def ack_quantile_ms(ctx, q: float):
    """Quantile over every op due in the window of ack time minus due
    time; an op shed, failed or never answered counts as infinite."""
    ops, win = _window(ctx)
    due = ops["due"][win]
    if np.all(np.isnan(due)):
        return None
    lat = ops["acked_at"][win] - (ctx["t0"] + due)
    lat = np.where(ops["outcome"][win] == ACKED, lat, math.inf)
    v = harness.quantile(lat.tolist(), q)
    return v * 1e3


def due_late_ms(ctx):
    """Open loop: per op sent in the window, send time minus due time
    in ms; None in a closed loop (no op is due)."""
    ops, win = _window(ctx)
    due = ops["due"][win]
    if np.all(np.isnan(due)):
        return None
    late = ops["sent"][win] - (ctx["t0"] + due)
    return late[~np.isnan(late)] * 1e3


def late_quantile_ms(ctx, q: float):
    """Quantile of send time minus due time: how late the generator
    sent (open loop)."""
    late = due_late_ms(ctx)
    return None if late is None else harness.quantile(late.tolist(), q)


def slot_late_ms(ctx, conn_of: np.ndarray, in_flight: int) -> np.ndarray:
    """Closed loop: per op sent in the window, send time minus the reply
    that freed its slot, in ms.  A connection sends its ops in stream
    order, the first ``in_flight`` at once, and its m-th later one when
    its m-th reply of the window (in reply order) frees a slot."""
    ops, win = _window(ctx)
    sent, acked_at = ops["sent"][win], ops["acked_at"][win]
    late = []
    for c in np.unique(conn_of[win]):
        mine = np.nonzero((conn_of[win] == c) & ~np.isnan(sent))[0]
        freed = np.sort(acked_at[mine])
        m = mine.size - in_flight
        if m > 0:
            late.append(sent[mine[in_flight:]] - freed[:m])
    return np.concatenate(late) * 1e3 if late else np.zeros(0)


def _obs(ctx, name):
    """Window diff of a Recorder stream: (n, sum)."""
    a = ctx["rec0"]["observations"].get(name, {"n": 0, "sum": 0.0})
    b = ctx["rec1"]["observations"].get(name, {"n": 0, "sum": 0.0})
    return b["n"] - a["n"], b["sum"] - a["sum"]


def _counter(ctx, name):
    return (ctx["rec1"]["counters"].get(name, 0)
            - ctx["rec0"]["counters"].get(name, 0))


def apply_ms(ctx):
    """Mean time of the replica node's durable apply per batch
    (``serve.batch.apply_s``: H2D, kernel, delta pull, WAL append and
    fsync), over the batches of the window."""
    n, s = _obs(ctx, "serve.batch.apply_s")
    return s / n * 1e3 if n else None


def batch_rows(ctx):
    """Mean live ops per applied batch in the window."""
    n, s = _obs(ctx, "serve.batch.occupancy")
    return s / n if n else None


def pull_ms(ctx):
    """The batch delta's pull and record build per batch: the compact
    pull (``wal.delta.pull_s``) plus the dense record
    (``wal.delta.dense_s``) where the compact form overflowed."""
    n_compact, s_compact = _obs(ctx, "wal.delta.pull_s")
    n_dense, s_dense = _obs(ctx, "wal.delta.dense_s")
    batches = _counter(ctx, "serve.batches")
    if not (n_compact or n_dense) or not batches:
        return None
    return (s_compact + s_dense) / batches * 1e3


def overflow_share(ctx):
    """Per cent of batches whose on-device fixed-K delta compaction
    overflowed (counter ``ingest.compact_overflow``), over the batches
    whose compact form was pulled (``wal.delta.pull_s``): the program
    counts an overflow just as that pull's span ends, so a window's
    edge cannot split the two (``serve.batches``, counted after the
    acks, can: a share over 100).  0 where no compact form exists (no
    on-device compaction, as on the CPU)."""
    if "ingest.compact_overflow" not in ctx["rec1"]["counters"]:
        return None  # a program that does not count it
    if not _counter(ctx, "serve.batches"):
        return None
    pulled, _ = _obs(ctx, "wal.delta.pull_s")
    over = _counter(ctx, "ingest.compact_overflow")
    return 100.0 * over / pulled if pulled else 0.0


# the served path's phases as the program spans them: the batcher's
# loop (wait, pack, then the durable apply: dispatch, the compact pull
# or the dense record, fsync), the checkpoint on the supervisor thread
# and the process's GC pauses
PHASES = {"wait": "serve.batch.wait_s", "pack": "serve.batch.pack_s",
          "apply": "serve.batch.apply_s",
          "dispatch": "node.ingest.dispatch_s",
          "pull": "wal.delta.pull_s", "dense": "wal.delta.dense_s",
          "fsync": "wal.fsync_s", "checkpoint": "node.checkpoint_s",
          "gc": "process.gc_s"}


def phase_split(ctx):
    """Each phase's window time per batch in ms, the batch cycle, and
    what of the cycle no span of the loop covers (the acks and the
    loop's own work); with the window's counts.  None without
    batches."""
    batches = _counter(ctx, "serve.batches")
    if not batches:
        return None
    ms = {k: _obs(ctx, name)[1] * 1e3 / batches
          for k, name in PHASES.items()}
    ms["cycle"] = (ctx["t1"] - ctx["t0"]) * 1e3 / batches
    ms["unspanned"] = ms["cycle"] - ms["wait"] - ms["pack"] - ms["apply"]
    return {"batches": batches,
            "compact_overflow": _counter(ctx, "ingest.compact_overflow"),
            "checkpoints": _obs(ctx, "node.checkpoint_s")[0],
            "ms_per_batch": ms}


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the
    device."""
    red = ctx.get("trace_red")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def roofline(ctx, model: str):
    """Per cent of the HBM roofline a kernel reached over the window:
    the bytes its calls must move (``roofline/<model>.py``, from the
    shapes) over its summed device time, against the chip's bandwidth.
    The model's ``flops`` is 0: these kernels are integer selects and
    bound by bytes."""
    red = ctx.get("trace_red")
    if not red or not ctx.get("peaks"):
        return None
    mod = harness.load_module(os.path.join(harness.BENCH, "roofline",
                                           model + ".py"))
    total_bytes, seconds = mod.traced_bytes(red, ctx["shapes"])
    if seconds <= 0:
        return None
    return 100.0 * total_bytes / seconds / ctx["peaks"]["hbm_bytes_per_s"]


def collective_s(ctx):
    """Seconds of the traced window in which a collective ran (the
    exchange between chips), per device: the union of each device's
    collective intervals (``trace_reduce.collective_intervals``),
    averaged over the devices.  None without a device trace."""
    red = ctx.get("trace_red")
    if not red or not red["collectives"]:
        return None
    per = [trace_reduce.covered_s(iv) for iv in red["collectives"]]
    return sum(per) / len(per)


def ici_share(ctx, model: str):
    """Per cent of the chips' interconnect bandwidth the exchange
    reached over the window: the bytes the collectives must move
    (``roofline/<model>.py``'s ``traced_ici_bytes``, from the shapes,
    summed over the devices) over the collective seconds summed over
    the devices, against one chip's ``ici_bytes_per_s``.  None where
    no collective ran or no bytes are due."""
    red = ctx.get("trace_red")
    peak = (ctx.get("peaks") or {}).get("ici_bytes_per_s")
    if not red or not peak:
        return None
    seconds = sum(trace_reduce.covered_s(iv) for iv in red["collectives"])
    if seconds <= 0:
        return None
    mod = harness.load_module(os.path.join(harness.BENCH, "roofline",
                                           model + ".py"))
    total_bytes = mod.traced_ici_bytes(red, ctx["shapes"])
    if total_bytes <= 0:
        return None
    return 100.0 * total_bytes / seconds / peak
