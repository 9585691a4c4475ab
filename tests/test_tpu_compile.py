"""The Pallas kernels of the main path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
primitives it does not lower (``lax.erf``, ``optimization_barrier``) or
blocks that overrun the scoped VMEM. Here each kernel is lowered and
compiled for one chip of a *described* v5e:2x2 at the widths the paper
models and granite-8b serving reach. Nothing runs. ``ops._interpret()``
still sees the CPU, so each case calls its ``*_pallas`` function with
``interpret=False``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.pfp_activations import (pfp_activation_pallas,
                                           pfp_glu_pallas)
from repro.kernels.pfp_attention import (pfp_attention_cache_pallas,
                                         pfp_attention_paged_pallas)
from repro.kernels.pfp_dense import pfp_dense_pallas
from repro.kernels.pfp_fused import pfp_norm_dense_act_pallas
from repro.kernels.pfp_maxpool import pfp_maxpool2d_pallas
from repro.kernels.pfp_moe import (pfp_dense_batched_pallas,
                                   pfp_dense_grouped_pallas)
from repro.nn.moe import row_tile
from repro.kernels.pfp_norms import pfp_rmsnorm_pallas
from repro.tuning import search

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
# granite-8b widths
D, FF, HEADS, KV_HEADS, HEAD_DIM = 4096, 14336, 32, 8, 128
# deepseek-moe-16b expert GEMM: (experts, capacity, d_model, d_expert)
MOE = (64, 128, 2048, 1408)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back from the
    # persistent cache; keep it out of the way while these compile.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; returns its HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernel is in, not an emulation
    return text


def _round_up(x, m):
    return -(-x // m) * m


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_dense_decode_rows(one_chip, dtype):
    m = 8  # decode slots
    _compile(lambda a, b, c, d: pfp_dense_pallas(
        a, b, c, d, block_m=8, block_n=128, block_k=512),
        one_chip, ((m, D), dtype), ((m, D), dtype), ((D, FF), dtype),
        ((D, FF), dtype))


def test_dense_first_layer_paper_mlp(one_chip):
    # 784 -> 100 at batch 100; ops pads K to the 128-lane block.
    k = _round_up(784, 128)
    _compile(lambda x, w, v: pfp_dense_pallas(
        x, x, w, v, block_m=100, block_n=100, block_k=128,
        first_layer=True),
        one_chip, ((100, k), F32), ((k, 100), F32), ((k, 100), F32))


@pytest.mark.parametrize("kind,rows,cols", [
    ("relu", 100, 100),          # paper MLP hidden layer, batch 100
    ("relu", 100 * 28 * 28, 6),  # LeNet-5 conv0 output, batch 100
    ("silu", 64, FF),            # granite gate, one prefill chunk
])
def test_activation(one_chip, kind, rows, cols):
    br, bc = min(256, rows), min(512, cols)
    rp = _round_up(rows, br)
    _compile(lambda a, b: pfp_activation_pallas(
        a, b, kind=kind, block_rows=br, block_cols=bc),
        one_chip, ((rp, cols), F32), ((rp, cols), F32))


@pytest.mark.parametrize("kind,shape,dtype", [
    ("relu", (100, 28, 28, 6), F32),   # LeNet-5 at batch 100: conv0,
    ("relu", (100, 14, 14, 16), F32),  # conv1,
    ("relu", (100, 120), F32),         # dense0,
    ("relu", (100, 84), F32),          # dense1
    ("silu", (16, FF), BF16),          # granite gate: 16 decode slots,
    ("silu", (4096, FF), BF16),        # one lockstep prefill round
])
def test_activation_wrapper_blocks(one_chip, monkeypatch, kind, shape, dtype):
    # Through ops.pfp_activation, so the view and blocks are the ones the
    # wrapper picks; only the interpret switch is turned for the chip.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    _compile(lambda a, b: ops.pfp_activation(a, b, kind=kind, impl="kernel"),
             one_chip, (shape, dtype), (shape, dtype))


@pytest.mark.parametrize("shape", [(100, 28, 28, 6), (100, 14, 14, 16)])
def test_maxpool_lenet5(one_chip, shape):
    _compile(lambda a, b: pfp_maxpool2d_pallas(a, b),
             one_chip, (shape, F32), (shape, F32))


@pytest.mark.parametrize("act", [None, "silu"])
def test_rmsnorm_prefill_rows(one_chip, act):
    # 256 rows x 4096 at the old fixed block_rows=256 overran VMEM; the
    # wrapper now derives the row block from d and the VMEM budget.
    rows = 256
    bm = ops._norm_rows_that_fit("rmsnorm", rows, D, 256)
    assert bm < 256
    _compile(lambda a, b, g: pfp_rmsnorm_pallas(
        a, b, g, d=D, act=act, block_rows=bm),
        one_chip, ((rows, D), F32), ((rows, D), F32), ((1, D), F32))


def test_glu_product(one_chip):
    _compile(lambda a, b, c, d: pfp_glu_pallas(
        a, b, c, d, block_rows=64, block_cols=512),
        one_chip, *[((64, FF), F32)] * 4)


def test_decode_cache_attention(one_chip):
    b, s = 4, 64
    _compile(lambda q, k, v, vv, qs, kl: pfp_attention_cache_pallas(
        q, k, v, vv, qs, kl, scale=HEAD_DIM ** -0.5, block_q=1,
        block_k=64),
        one_chip, ((b, HEADS, 1, HEAD_DIM), BF16),
        *[((b, KV_HEADS, s, HEAD_DIM), BF16)] * 3, ((b,), I32), ((b,), I32))


@pytest.mark.parametrize("tq,prefetch", [(1, 1), (16, 1), (1, 4)])
def test_paged_attention(one_chip, tq, prefetch):
    b, pages, page_size, per_seq = 4, 32, 16, 4
    _compile(lambda q, k, v, vv, t, qs, kl: pfp_attention_paged_pallas(
        q, k, v, vv, t, qs, kl, scale=HEAD_DIM ** -0.5, block_q=tq,
        prefetch=prefetch),
        one_chip, ((b, HEADS, tq, HEAD_DIM), BF16),
        *[((pages, KV_HEADS, page_size, HEAD_DIM), BF16)] * 3,
        ((b, per_seq), I32), ((b,), I32), ((b,), I32))


def _dense_batched_blocks(schedule):
    get = schedule.block
    return dict(block_e=get("block_e", 1), block_c=get("block_c", 128),
                block_n=get("block_n", 128), block_k=get("block_k", 512),
                dims=schedule.axis("dims"), k_order=schedule.axis("k_order"))


@pytest.mark.parametrize("which", ["default", "tuner_top"])
def test_dense_batched_moe_widths(one_chip, which):
    e, c, k, n = MOE
    sched = (search.DEFAULT_SCHEDULES["dense_batched"] if which == "default"
             else search.candidates("dense_batched", MOE)[0])
    blocks = _dense_batched_blocks(sched)
    np_ = _round_up(n, blocks["block_n"])
    _compile(lambda a, b, w, s: pfp_dense_batched_pallas(a, b, w, s,
                                                         **blocks),
             one_chip, ((e, c, k), F32), ((e, c, k), F32),
             ((e, k, np_), F32), ((e, k, np_), F32))


@pytest.mark.parametrize("tokens", [16, 4096])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["up", "down"])
def test_dense_grouped_moe_widths(one_chip, tokens, k, n):
    """deepseek-moe-16b's grouped expert GEMM at one chip's share (16 held
    experts, top-6): bf16 rows in the worst-case buffer nn/moe.py lays
    out, fp32 expert weights read as stored, at decode (16 tokens) and a
    prefill round (4,096 rows)."""
    held, top_k = 16, 6
    bm = row_tile(tokens * top_k)
    m = _round_up(tokens * top_k + held * (bm - 1), bm)
    bk, bn = ops.grouped_blocks(k, n, 4)
    _compile(lambda a, b, w, s, te, nt: pfp_dense_grouped_pallas(
        a, b, w, s, te, nt, block_m=bm, block_n=bn, block_k=bk),
        one_chip, ((m, k), BF16), ((m, k), BF16), ((held, k, n), F32),
        ((held, k, n), F32), ((m // bm,), I32), ((1,), I32))


def test_fused_norm_dense_act(one_chip):
    m = 8
    _compile(lambda a, b, g, bb, w, s: pfp_norm_dense_act_pallas(
        a, b, g, bb, w, s, d=D, k128=D, block_m=8, block_n=128,
        block_k=512),
        one_chip, ((m, D), F32), ((m, D), F32), ((1, D), F32), ((1, D), F32),
        ((D, FF), F32), ((D, FF), F32))
