"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader gets ``ctx`` (see ``run.py``: the trace, the window's
dispatches, counters and requests) and returns a number, or None when
the run holds nothing for it to read; the names of kernels and programs
it looks for sit in the reader's own file.
"""
from __future__ import annotations

from harness import trace as tr
from harness import work


def program_runs(ctx, name_part: str) -> list:
    """Device executions ``(name, start, dur)`` of the programs whose
    name contains ``name_part``, inside the traced window."""
    t = ctx["trace"]
    return [e for e in tr.clip(t.modules, t.window) if name_part in e[0]]


def prefill_ms_per_ktok(ctx, program: str):
    runs = program_runs(ctx, program)
    toks = sum(sum(d.tokens) for d in ctx["dispatches"]
               if d.kind == "prefill")
    if not runs or not toks:
        return None
    return sum(d for _, _, d in runs) / 1e6 / (toks / 1000.0)


def roofline(ctx, kernels, count) -> float | None:
    """Percent of the kernel's roofline: the larger of FLOPs / peak FLOP/s
    and bytes / peak bytes/s, over the kernels' summed device time.
    ``count(model, dispatch)`` -> (flops, bytes)."""
    t = ctx["trace"]
    ns = tr.kernel_ns(t.ops, t.window, kernels)
    if ns <= 0:
        return None
    fl = by = 0.0
    for d in ctx["dispatches"]:
        f, b = count(ctx["model"], d)
        fl += f
        by += b
    pk = ctx["peaks"]
    t_fl, t_by = fl / pk["bf16_flop_s"], by / pk["hbm_byte_s"]
    ctx["notes"].append(
        f"{kernels[0]}: {fl:.6g} FLOP, {by:.6g} B over {ns / 1e9:.6g} s; "
        f"bound by {'compute' if t_fl >= t_by else 'HBM bandwidth'}")
    return 100.0 * max(t_fl, t_by) / (ns / 1e9)


def step_mfu(ctx) -> float | None:
    t = ctx["trace"]
    if not ctx["dispatches"]:
        return None
    fl = work.model_flops(ctx["model"], ctx["dispatches"], ctx["outputs"])
    secs = (t.window[1] - t.window[0]) / 1e9
    return 100.0 * fl / (secs * ctx["peaks"]["bf16_flop_s"])
