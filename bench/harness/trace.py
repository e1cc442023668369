"""Reduction of a JAX profiler trace to what the per-layer metrics read.

``Trace`` holds, in one time base (nanoseconds of the trace):

* ``ops``: device operations ``(name, start, dur)`` of the first device
  plane's op line, the unit the idle share and the kernel times are
  taken from; each name is the HLO instruction's (``short_name``);
* ``modules``: device executions of whole programs ``(name, start, dur)``;
* ``spans``: host spans ``(name, start, end)`` the engine wrote through
  ``jax.profiler.TraceAnnotation`` (``Tracer(annotate=True)``);
* ``window``: ``(start, end)`` of the harness's own ``bench_window`` span.

The functions below take plain lists, so a test can feed them a small
hand-made trace.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench_window"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    spans: list
    window: tuple
    layout: list = dataclasses.field(default_factory=list)  # planes, lines


def load(directory: str, span_names) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, spans, window = [], [], [], None
    device_planes = 0
    wanted = set(span_names) | {WINDOW_SPAN}
    layout = []
    for plane in pd.planes:
        layout.append(f"{plane.name}: " + ", ".join(
            f"{ln.name}({sum(1 for _ in ln.events)})" for ln in plane.lines))
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            device_planes += 1
            if device_planes > 1:
                continue  # one chip's timeline
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(short_name(e.name), e.start_ns, e.duration_ns)
                            for e in line.events]
                elif line.name in MODULE_LINES:
                    modules += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        end = e.start_ns + e.duration_ns
                        if e.name == WINDOW_SPAN:
                            window = (e.start_ns, end)
                        else:
                            spans.append((e.name, e.start_ns, end))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return Trace(ops, modules, spans, window, layout)


def clip(events, window):
    """Events ``(name, start, dur)`` cut to the window."""
    a, b = window
    out = []
    for name, s, d in events:
        s2, e2 = max(s, a), min(s + d, b)
        if e2 > s2:
            out.append((name, s2, e2 - s2))
    return out


def busy_intervals(events):
    """Union of the events' intervals, as sorted ``(start, end)``."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(events, window) -> float:
    return float(sum(e - s for s, e in busy_intervals(clip(events, window))))


def idle_gaps(events, window):
    """``(start, length)`` of each stretch of the window with no op."""
    a, b = window
    gaps, t = [], a
    for s, e in busy_intervals(clip(events, window)):
        if s > t:
            gaps.append((t, s - t))
        t = max(t, e)
    if b > t:
        gaps.append((t, b - t))
    return gaps


HLO_OP = re.compile(r"%?([^\s=]+)\s*=\s*\(?([a-z0-9]+\[[^\]]*\])?")


def short_name(text: str) -> str:
    """A TPU trace names an op by its whole HLO text
    (``%copy.7 = bf16[40,2200]{...} copy(...)``): keep the instruction's
    name and its (first) output type, ``copy.7 bf16[40,2200]``."""
    m = HLO_OP.match(text)
    if not m:
        return text
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def base_name(name: str) -> str:
    """An op's name without the numeric suffix of its instruction
    (``fusion.12 f32[8]`` -> ``fusion f32[8]``)."""
    return re.sub(r"[.:]\d+(?= |$)", "", name, count=1)


def kernel_ns(events, window, prefixes) -> float:
    """Summed device time of the ops whose name starts with a prefix."""
    return float(sum(d for n, _, d in clip(events, window)
                     if n.startswith(tuple(prefixes))))


def top_ops(events, window, k=10):
    tot = collections.Counter()
    for n, _, d in clip(events, window):
        tot[base_name(n)] += d
    return [[n, v / 1e9] for n, v in tot.most_common(k)]


def name_gaps(gaps, spans, k=10):
    """Idle seconds grouped by the innermost host span open at each gap's
    midpoint ("no span": the host was between engine ticks)."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    tot = collections.Counter()
    for g0, glen in gaps:
        mid = g0 + glen / 2
        i = bisect.bisect_right(starts, mid)
        best = None
        for name, s, e in spans[max(0, i - 64):i]:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        tot[best[0] if best else "no span"] += glen
    return [[n, v / 1e9] for n, v in tot.most_common(k)]
