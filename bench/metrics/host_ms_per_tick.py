"""Engine tick: host milliseconds per tick, the mean over the window's
``step`` spans of the span's length less the ``decode:wait`` and
``prefill:wait`` spans inside it (the host blocked on the device).  The
window's idle gaps, each named by the innermost engine span open at its
midpoint, go to the notes, with the share that falls inside a program's
run on the device."""
import bisect
import json

from harness import inside
from harness import trace as tr

WAITS = ("decode:wait", "prefill:wait")


def read(ctx):
    t = ctx["trace"]
    a, b = t.window
    spans = inside.spans(ctx)
    steps = [(s, e) for n, s, e in spans if n == "step" and a <= s <= e <= b]
    waits = [(s, e) for n, s, e in spans if n in WAITS]
    if not steps or not waits:
        return None
    host = 0.0
    for s, e in steps:
        host += (e - s) - sum(min(e, we) - max(s, ws)
                              for ws, we in waits if ws < e and we > s)
    gaps = tr.idle_gaps(t.ops, t.window)
    runs = sorted((s, s + d) for _, s, d in t.modules)
    starts = [s for s, _ in runs]
    in_runs = 0
    for g, n in gaps:
        i = bisect.bisect_right(starts, g + n / 2) - 1
        if i >= 0 and g + n / 2 <= runs[i][1]:
            in_runs += n
    ctx["notes"].append(
        f"idle gaps by engine span (s): "
        f"{json.dumps(tr.name_gaps(gaps, spans))}; of the idle time "
        f"{in_runs / 1e9:.6g} s falls inside program runs")
    return host / len(steps) / 1e6
