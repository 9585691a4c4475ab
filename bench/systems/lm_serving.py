"""A dense PFP decoder LM served by the repository's engine.

The engine is built the way ``launch/serve.py`` builds it
(``serving_parts``: paged Gaussian KV cache, chunked prefill, uncertainty
router, bf16 compute, ``impl`` from the configuration). Only the weights
come from here: ``bench.weights`` draws every leaf of the program's
parameter tree from the run's seed in one jitted call on the device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import weights


def model_config(conf: dict):
    """The repository's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in conf.items()
                          if k in fields and k != "name"},
                       name=conf["name"])


def _role(path) -> tuple:
    """(role, group-axis?) of a parameter-tree path: ``stack/b0/attn/wq/w``
    -> ('attn.wq.w', True); ``embed/table`` -> ('embed.table', False)."""
    keys = [str(getattr(p, "key", p)) for p in path]
    if keys[0] == "stack":
        return ".".join(keys[2:]), True, int(keys[1][1:])
    return ".".join(keys), False, 0


def param_tree(mcfg, key, sigma: float):
    """The program's PFP parameter tree ({'mu', 'srm'} leaves, norm gains
    1), every mean drawn by role and layer from ``key``. Call under jit."""
    from repro.bayes.convert import svi_to_pfp
    from repro.models import lm
    from repro.nn.module import is_bayes_param

    shapes = jax.eval_shape(
        lambda: svi_to_pfp(lm.init_params(mcfg, jax.random.PRNGKey(0))))
    lpg = len(mcfg.pattern)

    def leaf(path, x):
        role, grouped, block = _role(path)
        if not is_bayes_param(x):
            return jnp.ones(x.shape, x.dtype)      # norm gains
        shape = x["mu"].shape
        if grouped:
            per = shape[1:]
            mu = jnp.stack([weights.weight_mean(
                key, role, g * lpg + block, per,
                weights.fan_in_scale(role, per)) for g in range(shape[0])])
        else:
            mu = weights.weight_mean(key, role, 0, shape,
                                     weights.fan_in_scale(role, shape))
        return {"mu": mu, "srm": jnp.square(mu) + sigma ** 2}

    return jax.tree_util.tree_map_with_path(leaf, shapes,
                                            is_leaf=is_bayes_param)


def serve_args(conf: dict, cell: dict, seed: int):
    """``launch/serve.py`` arguments for this configuration and cell."""
    from repro.launch import serve

    eng, router = conf["engine"], cell["router"]
    return serve.parse_args([
        "--impl", conf["impl"], "--page-size", str(eng["page_size"]),
        "--batch", str(eng["slots"]),
        "--prompt-len", str(eng["max_prompt"]),
        "--tokens", str(eng["max_output"]),
        "--prefill-chunk", str(eng["prefill_chunk"]),
        f"--mi-continue={router['mi_continue']}",
        f"--mi-abstain={router['mi_abstain']}",
        "--escalate-samples", str(router["escalate_samples"]),
        "--seed", str(seed)])


class System:
    """The served model of one run: weights, mesh and the engine builder."""

    kind = "serving"

    def __init__(self, conf: dict, cell: dict, seed: int):
        from repro.launch import serve
        from repro.launch.mesh import make_mesh

        self.conf = conf
        self.seed = seed
        self.mcfg = model_config(conf)
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.params = jax.jit(
            lambda k: param_tree(self.mcfg, k, conf["sigma_init"]))(
                weights.seed_key(seed))
        jax.block_until_ready(self.params)
        self.args = serve_args(conf, cell, seed)
        self.parts = serve.serving_parts(self.args, self.mcfg, self.params,
                                         self.mesh)

    @property
    def vocab_size(self) -> int:
        return self.mcfg.vocab_size

    @property
    def max_prompt(self) -> int:
        return self.conf["engine"]["max_prompt"]

    def new_engine(self):
        with self.mesh:
            return self.parts.build_engine(0)

    def step(self, engine) -> None:
        with self.mesh:
            engine.step()

    def free(self) -> None:
        """Drop the served weights (the reference runs after this)."""
        from repro.serving.engine import engine as engine_mod

        engine_mod.clear_shared_pass_cache()
        self.parts = None
        self.params = None

