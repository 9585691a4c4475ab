"""bench/costs against operation and byte counts worked out by hand."""
from __future__ import annotations

import pytest

from bench import spec
from bench.costs import pfp


def test_dense_counts():
    # SRM: three GEMMs of 2*M*K*N; the first layer (Eq. 13) two.
    assert pfp.dense_flops(2, 3, 5) == 3 * 2 * 2 * 3 * 5
    assert pfp.dense_flops(2, 3, 5, deterministic_input=True) == 2 * 60
    # bf16 decode row: x mean + srm (2*16*4096*2 B), w mean + srm
    # (2*4096*1024*2 B), out mean + var (2*16*1024*2 B).
    assert pfp.dense_bytes(16, 4096, 1024, act_bytes=2, w_bytes=2,
                           out_bytes=2) == \
        262144 + 16777216 + 65536


def test_least_time_takes_the_binding_bound():
    peak = pfp.peaks("TPU v5 lite")
    # 16 rows of a 4096 x 14336 bf16 layer are bound by bytes
    f = pfp.dense_flops(16, 4096, 14336)
    b = pfp.dense_bytes(16, 4096, 14336, act_bytes=2, w_bytes=2,
                        out_bytes=2)
    assert pfp.least_time_s(f, b, peak) == pytest.approx(b / 819e9)
    # 4096 rows are bound by operations
    f = pfp.dense_flops(4096, 4096, 14336)
    assert pfp.least_time_s(f, b, peak) == pytest.approx(f / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        pfp.peaks("cpu")


def test_granite_token_flops_by_hand():
    cfg = spec.config("granite-8b")
    d, f, v = 4096, 14336, 49152
    per_layer = d * 4096 + 2 * d * 1024 + 4096 * d + 3 * d * f
    macs = 2 * per_layer + d * v
    assert macs == 637534208
    attn = 2 * 6 * 10 * 32 * 128            # 2 layers, context 10
    assert pfp.lm_token_flops(cfg, 10) == 6 * macs + attn
    # positions 5..7 attend to 6, 7 and 8 keys
    assert pfp.lm_tokens_flops(cfg, 5, 7) == sum(
        pfp.lm_token_flops(cfg, c) for c in (6, 7, 8))
    assert pfp.lm_tokens_flops(cfg, 7, 6) == 0


def test_lenet5_by_hand():
    cfg = spec.config("paper-lenet5")
    gemms = pfp.lenet5_gemms(cfg, 1)
    assert gemms == [(784, 25, 6, True), (196, 150, 16, False),
                     (1, 784, 120, False), (1, 120, 84, False),
                     (1, 84, 10, False)]
    macs = 117600 + 470400 + 94080 + 10080 + 840      # 693,000 a image
    assert sum(m * k * n for m, k, n, _ in gemms) == macs
    assert pfp.lenet5_flops(cfg, 100) == 100 * (
        4 * 117600 + 6 * (470400 + 94080 + 10080 + 840))
