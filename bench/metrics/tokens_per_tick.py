"""Scheduler: tokens the engine processed per tick in the window,
(``prefill_tokens`` + ``decode_tokens``) / ``steps`` from its counters."""


def read(ctx):
    c = ctx["counters"]
    if not c["steps"]:
        return None
    return (c["prefill_tokens"] + c["decode_tokens"]) / c["steps"]
