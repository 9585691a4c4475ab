"""A fine-grained MoE PFP decoder LM (deepseek-moe-16b at one chip's expert
share) served by the repository's engine.

The engine is built as ``lm_serving`` builds it (``launch/serve.py``
``serving_parts``: paged Gaussian KV cache, chunked prefill, uncertainty
router, bf16 compute), with the page pool the configuration's
``page_budget`` gives (``--page-budget``). The weights differ: each
routed expert is
drawn by role, layer and *global* expert index, scaled 1/sqrt(fan in of
one expert), so the chip's share holds what the deployment's chip would
and ``bench/reference/moe_lm.py`` draws the same experts.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from bench import weights
from bench.systems import lm_serving

# What the program must be able to express to run this configuration: the
# leading dense layer's width, the gate rule and the expert share.
NEEDS = ("dense_d_ff", "norm_topk_prob", "routed_experts", "expert_offset")
EXPERTS = "moe.experts."


def model_config(conf: dict):
    """The repository's ModelConfig; a program without the fields this
    configuration sets cannot run it, and says so."""
    from repro.configs.base import ModelConfig

    have = {f.name for f in dataclasses.fields(ModelConfig)}
    missing = [k for k in NEEDS if k in conf and k not in have]
    if missing:
        raise RuntimeError(f"the program's ModelConfig has no {missing}: "
                           f"it cannot run {conf['name']}")
    return lm_serving.model_config(conf)


def expert_role(role: str, expert: int) -> str:
    """Role of one routed expert's weight: ``moe.experts.w_up.37``."""
    return f"{role}.{expert}"


def weight_means(mcfg, key, role: str, layers, shape):
    """Means of one role's weight in each of ``layers``, stacked: each as
    ``weights.weight_mean`` draws it alone, from its own key, and drawn
    together. A routed expert role's (E, ...) weight is one draw per held
    expert, by global index ``expert_offset + e``, at one expert's fan
    in."""
    shape = tuple(shape)
    if role.endswith(".b"):
        return jnp.zeros((len(layers),) + shape, jnp.float32)
    if role.startswith(EXPERTS):
        per = shape[1:]
        keys = [[weights.role_key(key, expert_role(
            role, mcfg.expert_offset + e), layer) for e in range(shape[0])]
            for layer in layers]
    else:
        per = shape
        keys = [weights.role_key(key, role, layer) for layer in layers]
    scale = weights.fan_in_scale(role, per)
    keys = jnp.asarray(keys)
    flat = keys.reshape((-1,) + keys.shape[-1:])
    draws = jax.vmap(lambda k: scale * jax.random.truncated_normal(
        k, -2.0, 2.0, per, jnp.float32))(flat)
    return draws.reshape((len(layers),) + shape)


def param_tree(mcfg, key, sigma: float):
    """The program's PFP parameter tree, drawn as ``lm_serving.param_tree``
    draws it but for the routed experts (:func:`weight_means`). Call
    under jit."""
    from repro.bayes.convert import svi_to_pfp
    from repro.models import lm
    from repro.nn.module import is_bayes_param

    shapes = jax.eval_shape(
        lambda: svi_to_pfp(lm.init_params(mcfg, jax.random.PRNGKey(0))))
    lpg = len(mcfg.pattern)

    def leaf(path, x):
        role, grouped, block = lm_serving._role(path)
        if not is_bayes_param(x):
            return jnp.ones(x.shape, x.dtype)      # norm gains
        shape = x["mu"].shape
        if grouped:
            mu = weight_means(mcfg, key, role,
                              [g * lpg + block for g in range(shape[0])],
                              shape[1:])
        else:
            mu = weight_means(mcfg, key, role, [0], shape)[0]
        return {"mu": mu, "srm": jnp.square(mu) + sigma ** 2}

    return jax.tree_util.tree_map_with_path(leaf, shapes,
                                            is_leaf=is_bayes_param)


def routed_probe(conf: dict, seed: int, x_mu, x_var):
    """The program's routed part of the first MoE layer (the held experts,
    gated; the shared experts left out) on (R, d_model) normed inputs,
    given as mean and variance: ``nn.moe.moe_apply``'s inference path
    under ``lm.moe_options``, in the engine's Context (PFP, bf16 compute,
    the configuration's impl), on that layer's router and experts drawn
    as :func:`param_tree` draws them. Returns (mean, variance), (R,
    d_model) fp32. ``bench/reference/moe_lm.py`` compares it with its own
    routed part of the layer."""
    from repro.bayes.convert import svi_to_pfp
    from repro.core.gaussian import VAR, GaussianTensor
    from repro.core.modes import Mode
    from repro.models import lm
    from repro.nn.module import Context, is_bayes_param
    from repro.nn.moe import moe_apply

    mcfg = model_config(conf)
    dt = jnp.dtype(conf["compute_dtype"])
    ctx = Context(mode=Mode.PFP, impl=conf["impl"], compute_dtype=dt)
    layer = mcfg.pattern.index("moe")     # its block in the first group
    shapes = jax.eval_shape(lambda: svi_to_pfp(lm.init_params(
        mcfg, jax.random.PRNGKey(0))))["stack"][f"b{layer}"]["moe"]
    routed = {k: v for k, v in shapes.items() if k != "shared"}

    def run(key, mu, var):
        def leaf(path, x):
            role = "moe." + ".".join(str(getattr(p, "key", p)) for p in path)
            m = weight_means(mcfg, key, role, [layer], x["mu"].shape[1:])[0]
            return {"mu": m, "srm": jnp.square(m) + conf["sigma_init"] ** 2}

        params = jax.tree_util.tree_map_with_path(leaf, routed,
                                                  is_leaf=is_bayes_param)
        x = GaussianTensor(mu[None].astype(dt), var[None].astype(dt), VAR)
        out, _ = moe_apply(params, x, ctx, aux_loss=False,
                           **lm.moe_options(mcfg))
        return (out.mean[0].astype(jnp.float32),
                out.var[0].astype(jnp.float32))

    return jax.jit(run)(weights.seed_key(seed), jnp.asarray(x_mu),
                        jnp.asarray(x_var))


# A zero-length host span the harness keeps (``bench.`` prefix) after each
# engine step, naming the engine's cumulative decode routing counters:
# ``bench.moe_decode:held=<rows>,touched=<experts>``. The counters restart
# with the window (``Engine.reset_metrics``), so the window's last span
# gives the window's decode passes (``bench/metrics/
# expert_gemm_roofline.py``).
DECODE_SPAN = "bench.moe_decode"


def decode_span(held: int, touched: int) -> str:
    return f"{DECODE_SPAN}:held={held},touched={touched}"


class System(lm_serving.System):
    """The served model of one run: weights, mesh and the engine builder.
    It prints (stderr) the seconds its weights and engine parts took, and
    at the end the steps in which a waiting request found a free slot but
    not the pages its admission reserves, and the fewest free pages."""

    def __init__(self, conf: dict, cell: dict, seed: int):
        from repro.launch import serve
        from repro.launch.mesh import make_mesh

        t0 = time.perf_counter()
        self.conf = conf
        self.seed = seed
        self.mcfg = model_config(conf)
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.params = jax.jit(
            lambda k: param_tree(self.mcfg, k, conf["sigma_init"]))(
                weights.seed_key(seed))
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        self.args = lm_serving.serve_args(conf, cell, seed)
        self.args.page_budget = conf["engine"].get("page_budget")
        self.parts = serve.serving_parts(self.args, self.mcfg, self.params,
                                         self.mesh)
        self.page_blocked_steps = 0
        self.min_free_pages = None
        print(f"[moe_serving] weights_s={t1 - t0:.3f} "
              f"engine_parts_s={time.perf_counter() - t1:.3f}",
              file=sys.stderr, flush=True)

    def step(self, engine) -> None:
        waiting, free = len(engine.scheduler), engine.pool.free_slots
        admitted = engine.metrics.admitted
        super().step(engine)
        if waiting and free and \
                engine.metrics.admitted - admitted < min(waiting, free):
            self.page_blocked_steps += 1
        pages = engine.pool.free_pages
        self.min_free_pages = (pages if self.min_free_pages is None
                               else min(self.min_free_pages, pages))
        c = engine.metrics
        with jax.profiler.TraceAnnotation(decode_span(
                c.moe_decode_held_assignments, c.moe_decode_experts_touched)):
            pass

    def free(self) -> None:
        print(f"[moe_serving] page_blocked_steps={self.page_blocked_steps} "
              f"min_free_pages={self.min_free_pages}", file=sys.stderr,
              flush=True)
        super().free()
