"""The paper forward's share of the bf16 peak (layer: model step,
``models/simple.py``): analytic PFP operations of LeNet-5 at the request's
batch (first conv 2 GEMMs, the rest 3; activations and pools not
counted) times the forward calls, over the forward program's device time
times the bf16 peak. The cell computes in fp32 at the highest matmul
precision, whose peak is a fraction of the bf16 one: the share is of the
bf16 peak all the same, so that it compares across cells."""

from bench.costs import pfp

PROGRAM = r"jit_forward|jit\(forward\)"


def read(ctx):
    seconds, n = ctx.trace.modules(PROGRAM)
    if not n:
        return None
    flops = n * pfp.lenet5_flops(ctx.conf, ctx.cell["traffic"]["batch"])
    return 100.0 * flops / (seconds * ctx.peak["bf16_flops_per_s"])
