"""Operations and bytes of the grouped PFP expert kernel
(``kernels/pfp_moe.py`` ``pfp_dense_grouped_pallas``), from shapes.

One call is one projection (up, gate or down) of one MoE layer over the
rows routed to the experts held here. Per row it is a Gaussian dense
layer in the SRM formulation, three GEMMs: 6*K*N operations. Bytes: the
``mu`` and ``srm`` of each held expert that some row reaches, at the
dtype the kernel reads them (it reads the stored fp32 weights and casts
each tile), each row's input mean and srm, and each row's output mean
and variance (fp32). An expert no row reaches is not read by the kernel
and not counted: one chip's share sees about 1.5 rows an expert a decode
step, so a step reaches about four fifths of its 16 experts, where the
deployment's 6 rows an expert reach all of them.
"""
from __future__ import annotations

from bench.costs import pfp


def projections(cfg: dict):
    """(K, N) of a routed expert's projections: up, gate, down."""
    d, f = cfg["d_model"], cfg["d_ff"]
    out = [(d, f), (d, f), (f, d)]
    if not cfg.get("gated_mlp", True):
        out.remove((d, f))
    return out


def grouped_flops(rows: float, k: int, n: int) -> float:
    return 6 * rows * k * n


def grouped_bytes(rows: float, k: int, n: int, experts: float, *,
                  w_bytes: int, act_bytes: int, out_bytes: int) -> float:
    """The (mu, srm) of ``experts`` experts once, each row's (mean, srm) in
    and (mean, var) out."""
    return (2 * experts * k * n * w_bytes + 2 * rows * k * act_bytes
            + 2 * rows * n * out_bytes)


def least_time_s(cfg: dict, peak: dict, rows: float, touched: float
                 ) -> float:
    """The least time of the calls of a set of passes whose MoE layers
    routed ``rows`` rows to held experts and reached ``touched`` held
    experts (both summed over layers and passes): every projection reads
    the reached experts' weights and the rows once. The larger of all the
    operations over the bf16 peak and all the bytes over HBM bandwidth,
    which is at most the sum of the calls' own least times."""
    flops = sum(grouped_flops(rows, k, n) for k, n in projections(cfg))
    nbytes = sum(grouped_bytes(rows, k, n, touched, w_bytes=4, act_bytes=2,
                               out_bytes=4) for k, n in projections(cfg))
    return pfp.least_time_s(flops, nbytes, peak)
