"""Host time per engine step (layer: engine): over the harness's
``bench.step`` spans around ``Engine.step()``, the span's length minus the
device-busy time inside it, summed and divided by the number of steps."""


def read(ctx):
    steps = ctx.trace.spans_named("bench.step")
    if not steps:
        return None
    host = sum((b - a) * 1e-9 - ctx.trace.busy_within(a, b)
               for a, b in steps)
    return 1e3 * host / len(steps)
