"""Adapter programs: device milliseconds per execution of the decode
program (decode with the on-device sampler), from the trace."""
from harness.readers import program_runs

PROGRAM = "_forward_paged_sample"


def read(ctx):
    runs = program_runs(ctx, PROGRAM)
    if not runs:
        return None
    return sum(d for _, _, d in runs) / len(runs) / 1e6
