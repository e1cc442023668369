"""What happens inside the engine's tick and inside the compiled programs,
for the readers of the engine's own spans and of the programs' named
scopes.

``run.py`` keeps, of the engine's host spans, the names it labels idle
gaps by, and its trace names each device op by its HLO instruction
alone.  This module adds, for the same traced window:

* ``spans(ctx)``: every engine span ``(name, start, end)`` named in
  ``ENGINE_SPANS``, read again from the profiler trace ``run.py`` keeps
  in ``TRACE_DIR`` until its readers are done;
* ``program_ops(ctx, program)``: the window's runs of a program and, for
  each device op inside them, its named-scope path and duration.  The
  path is the ``op_name`` metadata of the op's instruction in the
  compiled program, which this process still holds (the executables JAX
  keeps for its jitted functions).  XLA names a fusion after its root
  instruction and gives it the root's metadata, so a fused op counts for
  the scope of its root.

A test hands both in through ``ctx`` (``"engine_spans"``, and
``"op_scopes"`` as ``op_scopes`` returns it).
"""
from __future__ import annotations

import bisect
import gc
import os
import re

from harness import trace as tr
from harness.readers import program_runs

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".trace")  # run.py's
ENGINE_SPANS = ("step", "schedule",
                "prefill", "prefill:prepare", "dispatch:prefill_paged",
                "prefill:wait", "prefill:boundary",
                "decode", "decode:prepare", "dispatch:decode_paged_sample",
                "decode:wait", "emit")
OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def spans(ctx) -> list:
    """Engine spans ``(name, start, end)`` of the traced run, in the
    trace's time base; none where the trace holds none."""
    if "engine_spans" not in ctx:
        try:
            ctx["engine_spans"] = tr.load(TRACE_DIR, ENGINE_SPANS).spans
        except (FileNotFoundError, ValueError):
            ctx["engine_spans"] = []
    return ctx["engine_spans"]


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def sub_scope(path: str, scope: str) -> str:
    """The scope one level below ``scope`` in ``path`` ("" if none)."""
    parts = path.split("/")
    i = parts.index(scope) + 1
    return parts[i] if i < len(parts) else ""


def hlo_scopes(text: str) -> dict:
    """``{op short name: op_name path}`` of every instruction of an HLO
    module's text that carries an ``op_name``."""
    out = {}
    for line in text.splitlines():
        m = OP_NAME.search(line)
        if m:
            out[tr.short_name(line.strip().removeprefix("ROOT "))] = \
                m.group(1)
    return out


def op_scopes(ctx) -> dict:
    """``{module name: [hlo_scopes of each compiled executable]}`` for the
    programs that ran in the window."""
    if "op_scopes" not in ctx:
        try:  # where jit keeps its compiled programs
            from jax._src.interpreters.pxla import MeshExecutable
        except ImportError:
            MeshExecutable = ()
        names = {n.split("(")[0] for n, _, _ in ctx["trace"].modules}
        out: dict = {}
        for o in gc.get_objects():
            if not isinstance(o, MeshExecutable):
                continue
            try:
                modules = o.xla_executable.hlo_modules()
            except (AttributeError, RuntimeError):
                continue
            for m in modules:
                if m.name in names:
                    out.setdefault(m.name, []).append(
                        hlo_scopes(m.to_string()))
        ctx["op_scopes"] = out
    return ctx["op_scopes"]


def program_ops(ctx, program: str):
    """``(runs, ops)``: the window's runs ``(name, start, dur)`` of the
    programs whose name contains ``program``, and ``(scope path, dur)``
    of each op that starts inside one ("" where the path is unknown).
    Each run is matched to the compiled executable of its module that
    holds the most of its ops."""
    t = ctx["trace"]
    if "_ops_by_start" not in ctx:
        ops = sorted(tr.clip(t.ops, t.window), key=lambda o: o[1])
        ctx["_ops_by_start"] = ops, [o[1] for o in ops]
    ops, starts = ctx["_ops_by_start"]
    tables = ctx.setdefault("_run_scopes", {})
    runs, out = program_runs(ctx, program), []
    for name, s, d in runs:
        inside = ops[bisect.bisect_left(starts, s):
                     bisect.bisect_right(starts, s + d)]
        if name not in tables:
            tables[name] = max(
                op_scopes(ctx).get(name.split("(")[0], []),
                key=lambda c: sum(op in c for op, _, _ in inside),
                default={})
        out += [(tables[name].get(op, ""), dur) for op, _, dur in inside]
    return runs, out
