"""gc_ms.open: garbage-collection pause time per second of window, span process.gc_s (layer: host process)."""

from benchmark.readers import _obs


def value(ctx):
    if "process.gc_s" not in ctx["rec1"]["observations"]:
        return None
    _, s = _obs(ctx, "process.gc_s")
    return s * 1e3 / (ctx["t1"] - ctx["t0"])
