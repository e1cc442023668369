"""Incoherence transforms: device milliseconds of the QuIP transforms
(ops under the named scope ``incoherence``: ``x/D``, V and Uᵀ of every
quantized linear) per run of the decode program.  Each sub-scope's share
and the prefill program's transforms per run go to the notes."""
import collections

from harness import inside

PROGRAM = "_forward_paged_sample"
PREFILL = "_forward_prefill_paged"
SCOPE = "incoherence"


def read(ctx):
    runs, ops = inside.program_ops(ctx, PROGRAM)
    by_sub = collections.Counter()
    for path, d in ops:
        if inside.in_scope(path, SCOPE):
            by_sub[inside.sub_scope(path, SCOPE)] += d
    ns = sum(by_sub.values())
    if not ns:
        return None
    shares = ", ".join(f"{k or '(none)'} {100 * v / ns:.1f}%"
                       for k, v in sorted(by_sub.items()))
    p_runs, p_ops = inside.program_ops(ctx, PREFILL)
    p_ns = sum(d for path, d in p_ops if inside.in_scope(path, SCOPE))
    ctx["notes"].append(
        f"incoherence: decode {ns / 1e6:.6g} ms over {len(runs)} runs "
        f"({shares}); prefill {p_ns / 1e6:.6g} ms over {len(p_runs)} runs")
    return ns / len(runs) / 1e6
