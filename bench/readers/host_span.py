"""Milliseconds per period of one of the benchmark's own host spans
(``dispatch``: from the call into the entry point to its return, before
the result is awaited), summed over the traced window."""


def read(ctx, span):
    total = sum(e - s for n, s, e in ctx.spans if n == span)
    if not ctx.periods or total <= 0:
        return None
    return 1000.0 * total / ctx.periods
