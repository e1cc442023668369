"""Adapter programs: device milliseconds of the sampler (ops under the
named scope ``sampler``) per run of the decode program.  The device time
of the eager first-token draws (programs that start inside a
``prefill:boundary`` span) goes to the notes."""
from harness import inside

PROGRAM = "_forward_paged_sample"
SCOPE = "sampler"


def read(ctx):
    runs, ops = inside.program_ops(ctx, PROGRAM)
    ns = sum(d for path, d in ops if inside.in_scope(path, SCOPE))
    if not ns:
        return None
    t = ctx["trace"]
    bounds = [(s, e) for n, s, e in inside.spans(ctx)
              if n == "prefill:boundary"]
    eager = sum(d for _, s, d in t.modules
                if any(bs <= s <= be for bs, be in bounds))
    ctx["notes"].append(
        f"sampler: {ns / 1e6:.6g} ms over {len(runs)} decode runs; eager "
        f"first-token draws {eager / 1e6:.6g} ms of device time in "
        f"{len(bounds)} prefill:boundary spans")
    return ns / len(runs) / 1e6
