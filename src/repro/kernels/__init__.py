"""PFP Pallas TPU kernels — the compute hot-spots the paper optimizes.

Paper (TVM/ARM)                      ->  here (Pallas/TPU)
  joint PFP dense operator               pfp_dense.py   (3 MXU matmuls/tile)
  PFP ReLU / moment-matched act          pfp_activations.py (VPU, fused mu+srm)
  vectorized Max Pool k=2                pfp_maxpool.py (Clark tournament)
  — (beyond paper: transformers)         pfp_attention.py (flash-style joint
                                          mean/variance online softmax)
                                         pfp_norms.py (fused RMSNorm/LayerNorm
                                          with optional activation epilogue)
                                         pfp_activations.py::pfp_glu_pallas
                                          (SRM gated product)

`ops.py` holds the jit'd public wrappers (shape plumbing, padding,
interpret mode on the CPU backend only); `ref.py` the pure-jnp oracles every
kernel is validated against.

Models do NOT import this package directly: every PFP op resolves through
the impl-dispatch registry in ``repro.core.dispatch``, where each op is
registered once with its ``'xla'`` (pure-jnp / pjit graph) and
``'kernel'`` (these Pallas wrappers) implementation. ``Context(impl=...)``
— or ``repro.core.dispatch.set_default_impl`` — flips an entire model
forward between the two stacks; the parity suite
(tests/test_impl_dispatch.py) pins the two implementations of every op to
each other, and ``ref.py``/tests/test_kernels.py pin the kernels to the
Monte-Carlo-validated moment algebra underneath.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
