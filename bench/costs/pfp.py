"""Operations and bytes the PFP algorithm needs per operator, from shapes.

* A Gaussian dense layer in the SRM formulation (paper Eq. 4 + Eq. 12) is
  three GEMMs: ``x_mu @ w_mu``, ``x_srm @ w_srm`` and ``x_mu^2 @ w_mu^2``:
  6*M*K*N operations (a multiply and an add per MAC).
* The first layer, fed deterministic inputs (Eq. 13), is two GEMMs:
  ``x @ w_mu`` and ``x^2 @ w_var``: 4*M*K*N.
* Mean-field attention (scores from means, ``A @ v_mu`` and
  ``A^2 @ v_var``) is three products per head and query: 6*ctx*head_dim.
* Bytes: each operand once, at the dtype the timed program gives it.
Elementwise work (activations, norms, the square of a mean) is not
counted: it is small beside the GEMMs and runs on the vector units.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def dense_flops(m: int, k: int, n: int, *, deterministic_input=False) -> int:
    return (4 if deterministic_input else 6) * m * k * n


def dense_bytes(m: int, k: int, n: int, *, act_bytes: int, w_bytes: int,
                out_bytes: int, deterministic_input=False) -> int:
    """x (mean, srm; one array if deterministic), w (mean, srm), out
    (mean, var)."""
    x = (1 if deterministic_input else 2) * m * k * act_bytes
    return x + 2 * k * n * w_bytes + 2 * m * n * out_bytes


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# -- a dense (granite-family) PFP decoder LM -----------------------------------
def lm_dense_shapes(cfg: dict):
    """(K, N) of every Gaussian dense layer one token passes through, in
    order: per layer q, k, v, o, up, gate, down; then the head."""
    d, f = cfg["d_model"], cfg["d_ff"]
    a = cfg["num_heads"] * cfg["head_dim"]
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    per_layer = [(d, a), (d, kv), (d, kv), (a, d), (d, f), (d, f), (f, d)]
    if not cfg.get("gated_mlp", True):
        per_layer.remove((d, f))
    return per_layer * cfg["num_layers"] + [(d, cfg["vocab_size"])]


def lm_token_flops(cfg: dict, ctx_len: int) -> int:
    """PFP operations for one token at context length ``ctx_len`` (keys it
    attends to, itself included): every dense layer plus attention. The
    embedding output is Gaussian, so no layer takes the Eq. 13 shortcut."""
    dense = sum(dense_flops(1, k, n) for k, n in lm_dense_shapes(cfg))
    attn = 6 * ctx_len * cfg["num_heads"] * cfg["head_dim"]
    return dense + cfg["num_layers"] * attn


def lm_tokens_flops(cfg: dict, first_pos: int, last_pos: int) -> int:
    """Sum of :func:`lm_token_flops` over positions first_pos..last_pos
    (inclusive; a token at position p attends to p + 1 keys)."""
    if last_pos < first_pos:
        return 0
    n = last_pos - first_pos + 1
    dense = sum(dense_flops(1, k, nn) for k, nn in lm_dense_shapes(cfg))
    ctx_sum = (first_pos + 1 + last_pos + 1) * n // 2
    return n * dense + cfg["num_layers"] * 6 * cfg["num_heads"] * \
        cfg["head_dim"] * ctx_sum


# -- the paper's LeNet-5 -------------------------------------------------------
def lenet5_gemms(cfg: dict, batch: int):
    """(M, K, N, deterministic_input) of LeNet-5's GEMMs at ``batch``:
    the convs as im2col GEMMs over SAME-padded outputs."""
    s, c_in = cfg["image_size"], cfg["in_channels"]
    c0, c1 = cfg["conv_channels"]
    k = cfg["kernel_size"]
    w0, w1 = cfg["dense_widths"]
    s1, s2 = s // 2, s // 4
    return [
        (batch * s * s, k * k * c_in, c0, True),
        (batch * s1 * s1, k * k * c0, c1, False),
        (batch, s2 * s2 * c1, w0, False),
        (batch, w0, w1, False),
        (batch, w1, cfg["num_classes"], False),
    ]


def lenet5_flops(cfg: dict, batch: int) -> int:
    return sum(dense_flops(m, k, n, deterministic_input=det)
               for m, k, n, det in lenet5_gemms(cfg, batch))
