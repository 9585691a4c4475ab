"""The routed experts' share of the model step's device time (layer: moe,
``nn/moe.py``): device time of the expert computation inside the engine's
prefill and decode programs, over the device time of those programs.

An operation counts where it is the grouped expert kernel
(``pfp_dense_grouped_pallas``), where its name holds one layer's
held-expert weights (``[E,d_model,d_ff]`` or ``[E,d_ff,d_model]``: the
copies the layer scan cuts for the kernel), or where its detail carries
the ``pfp.moe.experts`` scope. The TPU trace's op events carry HLO names
and no ``tf_op`` stat, so there the scope never matches: the metric is
the kernel and the weight copies, and leaves out the router, dispatch,
combine and shared experts (the other ``pfp.moe.*`` scopes). None where
no such operation ran (a program without the kernel, or a dense model)."""

PROGRAMS = r"batch_chunk_step|decode_step"


def pattern(conf: dict) -> str:
    e, d, f = conf["num_experts"], conf["d_model"], conf["d_ff"]
    return (r"pfp\.moe\.experts|pfp_dense_grouped_pallas(\.\d+)? = |"
            rf"\[{e},{d},{f}\]|\[{e},{f},{d}\]")


def read(ctx):
    moe, n = ctx.trace.ops(pattern(ctx.conf), module=PROGRAMS)
    total, programs = ctx.trace.modules(PROGRAMS)
    if not n or not programs:
        return None
    return 100.0 * moe / total
