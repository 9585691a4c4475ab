"""Model-step share of the bf16 peak (layer: model step, ``models/lm.py``
through the engine's prefill and decode programs): the analytic PFP
operations of the tokens served in the window (each prompt token once,
each fed decode token once; padding rows and the SVI second opinion not
counted) over the device time of the prefill and decode programs times
the chip's bf16 peak."""

PROGRAMS = r"batch_chunk_step|decode_step"


def read(ctx):
    seconds, n = ctx.trace.modules(PROGRAMS)
    if not n or not ctx.served_flops:
        return None
    return 100.0 * ctx.served_flops / (
        seconds * ctx.peak["bf16_flops_per_s"])
