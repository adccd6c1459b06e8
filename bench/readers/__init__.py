"""Per-layer metric readers, each ``read(ctx, **args)`` on a
`tracing.Context`, found by the name a metric's file gives."""
