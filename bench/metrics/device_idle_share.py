"""Device: share of the traced window in which no operation ran on the
chip, in percent: 1 - (union of op intervals) / window."""
from harness import trace as tr


def read(ctx):
    t = ctx["trace"]
    span = t.window[1] - t.window[0]
    return 100.0 * (1.0 - tr.busy_ns(t.ops, t.window) / span)
