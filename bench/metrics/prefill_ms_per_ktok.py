"""Adapter programs: device milliseconds of the prefill program per 1000
real prompt tokens it processed (padding excluded), from the trace."""
from harness.readers import prefill_ms_per_ktok

PROGRAM = "_forward_prefill_paged"


def read(ctx):
    return prefill_ms_per_ktok(ctx, PROGRAM)
