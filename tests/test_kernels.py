"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes per the kernel contract — including the
paper-config ragged (non-MXU-aligned) shapes under non-default tuned
schedules, so every padding path is pinned for every block choice the
autotuner may select."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.pfp_activations import pfp_activation_pallas
from repro.kernels.pfp_attention import pfp_attention_pallas
from repro.kernels.pfp_dense import pfp_dense_pallas
from repro.tuning import search
from repro.tuning.schedules import Schedule

KEY = jax.random.PRNGKey(0)


def _gauss_pair(key, shape, scale=1.0):
    k1, k2 = jax.random.split(key)
    mu = scale * jax.random.normal(k1, shape, jnp.float32)
    var = scale * jax.nn.softplus(jax.random.normal(k2, shape))
    return mu, var


@pytest.mark.parametrize("m,k,n", [
    (128, 512, 128), (256, 1024, 256), (64, 128, 64),
    (33, 100, 53),       # unaligned -> padded path
    (1, 784, 100),       # paper MLP first layer, batch 1
])
def test_pfp_dense_kernel_shapes(m, k, n):
    kx, kw = jax.random.split(jax.random.fold_in(KEY, m * k + n))
    mu_x, var_x = _gauss_pair(kx, (m, k))
    srm_x = var_x + jnp.square(mu_x)
    mu_w, var_w = _gauss_pair(kw, (k, n), 0.1)
    srm_w = var_w + jnp.square(mu_w)
    got = ops.pfp_dense(mu_x, srm_x, mu_w, srm_w, impl="kernel")
    want = ops.pfp_dense(mu_x, srm_x, mu_w, srm_w, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4)


def test_pfp_dense_kernel_bf16_inputs():
    kx, kw = jax.random.split(KEY)
    mu_x, var_x = _gauss_pair(kx, (128, 256))
    mu_w, var_w = _gauss_pair(kw, (256, 128), 0.1)
    srm_x = var_x + mu_x ** 2
    srm_w = var_w + mu_w ** 2
    args16 = [a.astype(jnp.bfloat16) for a in (mu_x, srm_x, mu_w, srm_w)]
    mu, var = pfp_dense_pallas(*args16, interpret=True)
    assert mu.dtype == jnp.float32  # fp32 accumulate
    rmu, rvar = ref.pfp_dense_ref(*args16)
    np.testing.assert_allclose(mu, rmu, rtol=1e-5, atol=1e-5)
    # The kernel squares in bf16 (as the MXU path would); the oracle squares
    # after upcast — agreement is bounded by bf16 epsilon on the squares.
    np.testing.assert_allclose(var, rvar, rtol=1e-3, atol=2e-2)


# Paper-config ragged shapes: MLP dense-1 at batch 100 (M=100, K=784,
# N=100) plus deliberately prime-ish dims. Every schedule here exercises a
# different padding path (block > dim, block ∤ dim, K-padding with zeros).
@pytest.mark.parametrize("m,k,n", [
    (100, 784, 100),     # paper MLP dense-1 at batch 100
    (100, 100, 10),      # paper MLP head
    (13, 57, 9),         # everything ragged
])
@pytest.mark.parametrize("blocks", [
    (8, 128, 128), (32, 256, 256), (128, 128, 512), (256, 512, 896),
])
def test_pfp_dense_ragged_shapes_under_schedules(m, k, n, blocks):
    bm, bn, bk = blocks
    sched = Schedule.make("dense", block_m=bm, block_n=bn, block_k=bk)
    kx, kw = jax.random.split(jax.random.fold_in(KEY, m * 31 + k * 7 + n))
    mu_x, var_x = _gauss_pair(kx, (m, k))
    srm_x = var_x + jnp.square(mu_x)
    mu_w, var_w = _gauss_pair(kw, (k, n), 0.1)
    srm_w = var_w + jnp.square(mu_w)
    got = ops.pfp_dense(mu_x, srm_x, mu_w, srm_w, impl="kernel",
                        schedule=sched)
    want = ops.pfp_dense(mu_x, srm_x, mu_w, srm_w, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tq,tk", [(77, 131), (100, 132), (1, 97)])
@pytest.mark.parametrize("bq,bk", [(16, 32), (64, 64), (128, 256)])
def test_attention_ragged_shapes_under_schedules(tq, tk, bq, bk):
    sched = Schedule.make("attention", block_q=bq, block_k=bk)
    ks = jax.random.split(jax.random.fold_in(KEY, tq * 131 + tk), 4)
    B, H, D = 2, 3, 64
    q = jax.random.normal(ks[0], (B, H, tq, D))
    k = jax.random.normal(ks[1], (B, H, tk, D))
    vm = jax.random.normal(ks[2], (B, H, tk, D))
    vv = jax.nn.softplus(jax.random.normal(ks[3], (B, H, tk, D)))
    scale = D ** -0.5
    got = ops.pfp_attention(q, k, vm, vv, scale=scale, causal=True,
                            impl="kernel", schedule=sched)
    want = ops.pfp_attention(q, k, vm, vv, scale=scale, causal=True,
                             impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(1, 28, 28, 6), (3, 14, 14, 16)])
@pytest.mark.parametrize("br,bc", [(8, 128), (64, 64), (512, 256)])
def test_maxpool_ragged_shapes_under_schedules(shape, br, bc):
    sched = Schedule.make("maxpool2d", block_rows=br, block_cols=bc)
    mu, var = _gauss_pair(jax.random.fold_in(KEY, shape[1] * br), shape)
    got = ops.pfp_maxpool2d(mu, var, impl="kernel", schedule=sched)
    want = ops.pfp_maxpool2d(mu, var, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,d", [(26, 48), (100, 100)])
@pytest.mark.parametrize("br", [8, 64, 512])
def test_norms_ragged_shapes_under_schedules(rows, d, br):
    kx, kg = jax.random.split(jax.random.fold_in(KEY, rows * br + d))
    mu, var = _gauss_pair(kx, (rows, d))
    gain = jax.random.normal(kg, (d,))
    bias = jax.random.normal(jax.random.fold_in(kg, 1), (d,))
    for op, args in (("rmsnorm", (mu, var, gain)),
                     ("layernorm", (mu, var, gain, bias))):
        fn = ops.pfp_rmsnorm if op == "rmsnorm" else ops.pfp_layernorm
        sched = Schedule.make(op, block_rows=br)
        got = fn(*args, rep="var", impl="kernel", schedule=sched)
        want = fn(*args, rep="var", impl="xla")
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)


def test_pfp_dense_first_layer_kernel():
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (64, 784))
    mu_w, var_w = _gauss_pair(kw, (784, 100), 0.1)
    got = ops.pfp_dense(x, x, mu_w, var_w, impl="kernel", first_layer=True)
    want = ref.pfp_dense_first_layer_ref(x, mu_w, var_w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("kind", ["relu", "gelu", "silu"])
@pytest.mark.parametrize("shape", [(256, 512), (3, 7, 33), (100,)])
def test_activation_kernels(kind, shape):
    mu, var = _gauss_pair(jax.random.fold_in(KEY, hash(kind) % 1000 + len(shape)), shape)
    got = ops.pfp_activation(mu, var, kind=kind, impl="kernel")
    want = ops.pfp_activation(mu, var, kind=kind, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


# The activation's callers at the paper's and granite's widths: LeNet-5's
# conv0, conv1, dense0 and dense1 ReLUs at batch 100, granite's gate SiLU
# over 16 decode slots in bf16.
_ACT_CASES = [
    ("relu", (100, 28, 28, 6), jnp.float32),
    ("relu", (100, 14, 14, 16), jnp.float32),
    ("relu", (100, 120), jnp.float32),
    ("relu", (100, 84), jnp.float32),
    ("silu", (16, 14336), jnp.bfloat16),
]


def _act_inputs(shape, dtype):
    mu, var = _gauss_pair(jax.random.fold_in(KEY, sum(shape)), shape)
    return mu.astype(dtype), var.astype(dtype)


def _row_blocks(mu, var, kind):
    """The activation kernel in 8-row blocks (all rows below 8) over the
    caller's own trailing axis, at most 128 lanes wide: the tiling each
    view of ``ops.pfp_activation`` must agree with."""
    shape = mu.shape
    cols = shape[-1]
    mu2, var2 = mu.reshape(-1, cols), var.reshape(-1, cols)
    m = mu2.shape[0]
    bm, bn = min(m, 8), min(cols, 128)
    mu2, var2 = (ops._pad_to(ops._pad_to(a, bm, 0), bn, 1)
                 for a in (mu2, var2))
    mo, so = pfp_activation_pallas(mu2, var2, kind=kind, block_rows=bm,
                                   block_cols=bn, interpret=True)
    return mo[:m, :cols].reshape(shape), so[:m, :cols].reshape(shape)


@pytest.mark.parametrize("kind,shape,dtype", _ACT_CASES)
def test_activation_view_bit_equal_to_row_blocks(kind, shape, dtype):
    mu, var = _act_inputs(shape, dtype)
    got = ops.pfp_activation(mu, var, kind=kind, impl="kernel")
    want = _row_blocks(mu, var, kind)
    np.testing.assert_array_equal(got[0], want[0])
    if shape[-1] != 6:
        np.testing.assert_array_equal(got[1], want[1])
        return
    # XLA:CPU rounds the SRM's a*b + c*d of the ReLU differently in a loop
    # 6 elements wide than in a longer one, with no Pallas involved: it
    # contracts one product or the other into an FMA (jit of MOMENT_FNS
    # alone reads the same). Held here to one rounding of each product,
    # at the scale of the largest output; on the TPU the outputs are
    # bit-identical.
    scale = float(jnp.max(jnp.abs(want[1])))
    np.testing.assert_allclose(got[1], want[1], rtol=2.0 ** -23,
                               atol=2.0 ** -22 * scale)


@pytest.mark.parametrize("kind,shape,dtype", _ACT_CASES)
def test_activation_view_matches_oracle(kind, shape, dtype):
    mu, var = _act_inputs(shape, dtype)
    got = ops.pfp_activation(mu, var, kind=kind, impl="kernel")
    want = ops.pfp_activation(mu, var, kind=kind, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


def _activation_grid(monkeypatch, shape, schedule=None):
    """The 2-D view and blocks ``ops.pfp_activation`` hands its kernel."""
    seen = []

    def spy(mu, var, *, kind, block_rows, block_cols, interpret):
        seen.append((mu.shape, block_rows, block_cols))
        return jnp.zeros(mu.shape), jnp.zeros(mu.shape)

    monkeypatch.setattr(ops, "pfp_activation_pallas", spy)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    jax.eval_shape(lambda a, b: ops.pfp_activation(
        a, b, impl="kernel", schedule=schedule), x, x)
    (view, bm, bn), = seen
    return view, bm, bn


@pytest.mark.parametrize("shape,view,max_steps", [
    ((100, 28, 28, 6), (6, 78400), 64),    # transposed: rows on lanes
    ((100, 14, 14, 16), (16, 19600), 64),
    ((100, 120), (100, 120), 1),
    ((16, 14336), (16, 14336), 28),        # lane-dense: the input itself
    ((4096, 14336), (4096, 14336), 448),
])
def test_activation_view_and_blocks(monkeypatch, shape, view, max_steps):
    (rows, lanes), bm, bn = _activation_grid(monkeypatch, shape)
    assert lanes % 128 == 0 and bn % 128 == 0 and bm % 8 == 0
    assert rows % bm == 0 and lanes % bn == 0
    assert (rows // bm) * (lanes // bn) <= max_steps
    # the view, padded to whole blocks by less than one block
    assert 0 <= rows - view[0] < bm and 0 <= lanes - view[1] < bn


@pytest.mark.parametrize("shape", [(100, 28, 28, 6), (100, 120), (33, 100),
                                   (16, 14336)])
@pytest.mark.parametrize("blocks", [None, (8, 128), (64, 256), (512, 512)])
def test_activation_cost_model_counts_the_wrapper_grid(monkeypatch, shape,
                                                       blocks):
    sched = None if blocks is None else Schedule.make(
        "activation", block_rows=blocks[0], block_cols=blocks[1])
    (rows, lanes), bm, bn = _activation_grid(monkeypatch, shape, sched)
    cost = search.cost_summary(
        "activation", (int(np.prod(shape[:-1])), shape[-1]),
        sched or search.DEFAULT_SCHEDULES["activation"])
    assert cost.grid_steps == (rows // bm) * (lanes // bn)
    assert cost.mxu_aligned


@pytest.mark.parametrize("shape", [(2, 8, 12, 7), (1, 28, 28, 6), (3, 14, 14, 16)])
def test_maxpool_kernel(shape):
    mu, var = _gauss_pair(jax.random.fold_in(KEY, shape[1]), shape)
    got = ops.pfp_maxpool2d(mu, var, impl="kernel")
    want = ops.pfp_maxpool2d(mu, var, impl="xla")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tq,tk,causal,bq,bk", [
    (128, 128, True, 64, 64),
    (100, 132, True, 32, 32),     # unaligned
    (64, 256, False, 64, 128),    # cross-attention style
    (1, 96, True, 1, 32),         # decode-like
])
def test_attention_kernel(tq, tk, causal, bq, bk):
    ks = jax.random.split(jax.random.fold_in(KEY, tq * tk), 4)
    B, H, D = 2, 3, 64
    q = jax.random.normal(ks[0], (B, H, tq, D))
    k = jax.random.normal(ks[1], (B, H, tk, D))
    vm = jax.random.normal(ks[2], (B, H, tk, D))
    vv = jax.nn.softplus(jax.random.normal(ks[3], (B, H, tk, D)))
    scale = D ** -0.5
    got = pfp_attention_pallas(q, k, vm, vv, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, interpret=True)
    want = ref.pfp_attention_ref(q, k, vm, vv, scale, causal=causal)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)


def test_attention_kernel_matches_model_attention():
    """Kernel oracle == the mean-field attention used by the LM stack."""
    from repro.core.gaussian import GaussianTensor
    from repro.core.pfp_attention import pfp_attention

    ks = jax.random.split(KEY, 4)
    B, H, T, D = 1, 2, 32, 16
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    vm = jax.random.normal(ks[2], (B, H, T, D))
    vv = jax.nn.softplus(jax.random.normal(ks[3], (B, H, T, D)))
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    out = pfp_attention(
        GaussianTensor.deterministic(q),
        GaussianTensor.deterministic(k),
        GaussianTensor.from_mean_var(vm, vv),
        scale=D ** -0.5, mask=mask)
    want = ref.pfp_attention_ref(q, k, vm, vv, D ** -0.5, causal=True)
    np.testing.assert_allclose(out.mean, want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out.var, want[1], rtol=1e-4, atol=1e-5)


def test_in_kernel_erf_matches_lax_erf():
    # Mosaic has no lowering for lax.erf, so the ReLU and max-pool kernels
    # evaluate XLA's fp32 rational approximation themselves. Run it inside
    # a Pallas kernel (interpret mode) over a range wide enough to cover
    # both saturated tails and every sign.
    from jax.experimental import pallas as pl

    from repro.kernels.pfp_activations import erf

    def kernel(x_ref, o_ref):
        o_ref[...] = erf(x_ref[...])

    def run(x):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            x.shape, jnp.float32), interpret=True)(x)

    x = jnp.linspace(-12.0, 12.0, 256 * 1024, dtype=jnp.float32)
    x = x.reshape(256, 1024)
    got = run(x)
    want = jax.lax.erf(x)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # odd, bounded, and exactly +-1 where fp32 erf saturates
    np.testing.assert_array_equal(run(-x), -got)
    assert float(jnp.max(jnp.abs(got))) <= 1.0
    np.testing.assert_array_equal(got[x >= 4.5], 1.0)
