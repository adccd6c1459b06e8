"""Device milliseconds per period of the ops that the given source
modules made (matched by path suffix), averaged over the chips used.
Container ops (while loops, conditionals) are left out: their bodies'
ops are counted themselves."""


def read(ctx, modules):
    ops = [o for o in ctx.leaf_ops()
           if any(o.source.endswith(m) for m in modules)]
    if not ops or not ctx.periods:
        return None
    return 1000.0 * sum(o.dur for o in ops) / ctx.n_chips / ctx.periods
