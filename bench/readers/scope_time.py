"""Device milliseconds per period of the leaf ops under one of the
program's top-level scopes (the ``jax.named_scope`` stages of
``api/engine.py``, read from each op's HLO ``op_name`` by
`program_trace`), averaged over the chips used.  None where no op carries
the scope: a program without scopes, or a stage that did not run."""
import program_trace


def read(ctx, scope):
    tr = program_trace.for_context(ctx)
    if tr is None or not ctx.periods:
        return None
    total = sum(o.dur for o in ctx.leaf_ops()
                if program_trace.top_scope(tr.op_name(o)) == scope)
    if total <= 0:
        return None
    return 1000.0 * total / ctx.n_chips / ctx.periods
