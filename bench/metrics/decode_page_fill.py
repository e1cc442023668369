"""Paged decode dispatch: share of the paged decode kernel's grid that
covers live pages, 100 x ``decode_pages_live`` / ``decode_pages_attended``
from the engine's counters over the window (live: the pages each real
lane's context and new token fill; attended: lanes dispatched x the
block table's power-of-two width)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_pages_attended"):
        return None
    return 100.0 * c["decode_pages_live"] / c["decode_pages_attended"]
