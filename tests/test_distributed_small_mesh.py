"""Distributed correctness on a small host-device mesh (subprocess).

The main test process must keep seeing ONE device (kernels, benches), so
these tests spawn a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 and assert inside it:
  * sharded SVI train step == single-device train step (bitwise-ish)
  * elastic checkpoint restore across mesh shapes
  * compressed_psum (int8 all-gather-sum) inside shard_map ~= psum
  * the launch drivers run end to end
"""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.training.optimizer import Adam
    from repro.training.train_loop import (TrainState, init_train_state,
                                           make_svi_train_step)

    cfg = reduced_config("granite-8b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = Adam(learning_rate=1e-3)

    def fwd(p, batch, ctx):
        logits, aux, _ = lm.forward(p, cfg, batch, ctx)
        return logits, aux

    step = make_svi_train_step(fwd, opt, num_data=1000)
    B, T = 4, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                          cfg.vocab_size),
             "targets": jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                                           cfg.vocab_size)}
    key = jax.random.PRNGKey(3)

    # single device
    s0 = init_train_state(params, opt)
    s1, m1 = jax.jit(step)(s0, batch, key)

    # 4x2 mesh, sharded
    mesh = make_mesh((4, 2), ("data", "model"))
    p_sh = shlib.params_shardings(jax.eval_shape(lambda: params), mesh)
    st_sh = TrainState(params=p_sh,
                       opt_state=type(s0.opt_state)(
                           step=shlib.replicated(mesh), m=p_sh, v=p_sh),
                       step=shlib.replicated(mesh))
    b_sh = shlib.batch_shardings(jax.eval_shape(lambda: batch), mesh)
    s0d = jax.device_put(init_train_state(params, opt), st_sh)
    with mesh:
        s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh,
                                             shlib.replicated(mesh)))(
            s0d, jax.device_put(batch, b_sh), key)

    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
        (float(m1["loss"]), float(m2["loss"]))
    l1 = jax.tree_util.tree_leaves(s1.params)
    l2 = jax.tree_util.tree_leaves(s2.params)
    # Sharded psums reorder the f32 gradient reductions. On the first Adam
    # step m/(sqrt(v)+eps) is ~sign(g), so an element whose near-zero
    # gradient flips sign under reordering moves a full +-lr in opposite
    # directions: bound the drift by 2*lr (2e-3) rather than relative error.
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=2.5e-3)
    print("SHARDED==SINGLE OK")
    """)
    assert "SHARDED==SINGLE OK" in out


def test_elastic_checkpoint_across_mesh_shapes(tmp_path):
    out = _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.training.checkpoint import CheckpointManager

    cfg = reduced_config("yi-6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager({str(tmp_path)!r})

    mesh8 = make_mesh((4, 2), ("data", "model"))
    sh8 = shlib.params_shardings(jax.eval_shape(lambda: params), mesh8)
    p8 = jax.device_put(params, sh8)
    mgr.save(1, p8, blocking=True)

    # restore onto a DIFFERENT mesh (2x2 — "after losing half the nodes")
    mesh4 = make_mesh((2, 2), ("data", "model"))
    sh4 = shlib.params_shardings(jax.eval_shape(lambda: params), mesh4)
    restored, step = mgr.restore(params, shardings=sh4)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


def test_compressed_psum_in_shard_map():
    out = _run("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.training.compression import compressed_psum

    mesh = make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def exact(v):
        return jax.lax.psum(v, "data")

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def compressed(v):
        return compressed_psum(v, "data")

    a = exact(x)
    b = compressed(x)
    err = float(jnp.max(jnp.abs(a - b)))
    scale = float(jnp.max(jnp.abs(a)))
    assert err < 0.05 * scale + 1e-3, (err, scale)
    print("COMPRESSED_PSUM OK", err)
    """)
    assert "COMPRESSED_PSUM OK" in out


@pytest.mark.parametrize("driver,extra", [
    ("repro.launch.train", ["--steps", "6", "--batch", "4", "--seq", "32",
                            "--reduced", "--mesh", "4,2"]),
    ("repro.launch.serve", ["--tokens", "3", "--batch", "2", "--mesh", "2,4"]),
])
def test_launch_drivers_run(driver, extra, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    cmd = [sys.executable, "-m", driver, "--devices", "8"] + extra
    if driver.endswith("train"):
        cmd += ["--ckpt-dir", str(tmp_path)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
