"""The grouped expert kernel's share of its roofline at decode (layer:
kernels, ``kernels/pfp_moe.py``): the least time of the kernel's calls in
the decode program over their device time. The least time
(``bench/costs/moe.py``) is that of reading the fp32 mu and srm of the
held experts the window's decode passes reached, once per projection and
pass, and their rows, against HBM bandwidth (or the operations against the
bf16 peak, whichever is longer); the reached experts and the rows are the
engine's decode counters, which ``bench/systems/moe_serving.py`` names in
a ``bench.moe_decode`` host span after each step. The kernel's calls in
the prefill program are not covered yet. None where the kernel did not
run or no span names the counters."""

import re

from bench.costs import moe

# The kernel's own operation ("%pfp_dense_grouped_pallas.3 = ..."), not
# the small ops its wrapper's scope also names in their detail.
KERNEL = r"pfp_dense_grouped_pallas(\.\d+)? = "
PROGRAM = r"decode_step"
COUNTERS = re.compile(r"^bench\.moe_decode:held=(\d+),touched=(\d+)$")


def read(ctx):
    seconds, calls = ctx.trace.ops(KERNEL, module=PROGRAM)
    counts = [COUNTERS.match(name) for name, a, b in ctx.trace.spans
              if ctx.trace.lo <= a <= ctx.trace.hi]
    counts = [m for m in counts if m]
    if not calls or seconds <= 0 or not counts:
        return None
    rows, touched = (int(g) for g in counts[-1].groups())
    return 100.0 * moe.least_time_s(ctx.conf, ctx.peak, rows, touched) / \
        seconds
