"""Milliseconds per period of the program's own host spans (the
``jax.profiler.TraceAnnotation`` spans of ``api/engine.py``'s entry
points, read by `program_trace`), summed over the names given and counted
inside the benchmark's traced calls.  None where the trace has none."""
import program_trace


def read(ctx, spans):
    tr = program_trace.for_context(ctx)
    if tr is None or not ctx.periods:
        return None
    total = tr.span_seconds(tuple(spans))
    if total <= 0:
        return None
    return 1000.0 * total / ctx.periods
