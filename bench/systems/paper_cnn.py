"""The paper's LeNet-5 as a PFP classifier: the repository's jitted
``lenet5_forward`` under ``Context(mode=PFP, impl=...)``, then
``pfp_predictive_metrics`` (paper Eq. 11 logit sampling) in the same
program. One call takes a batch of images and returns, per image, the
logit mean and variance, the prediction, the entropy and the MI."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights


def param_tree(conf: dict, key):
    """The program's PFP parameter tree for LeNet-5 ({'mu', 'srm'}
    leaves), every mean drawn by role from ``key``. Call under jit."""
    from repro.bayes.convert import svi_to_pfp
    from repro.models.simple import lenet5_init
    from repro.nn.module import is_bayes_param

    shapes = jax.eval_shape(lambda: svi_to_pfp(lenet5_init(
        jax.random.PRNGKey(0), num_classes=conf["num_classes"],
        in_channels=conf["in_channels"])))
    sigma = conf["sigma_init"]

    def leaf(path, x):
        role = ".".join(str(getattr(p, "key", p)) for p in path)
        shape = x["mu"].shape
        mu = weights.weight_mean(key, role, 0, shape,
                                 weights.fan_in_scale(role, shape))
        return {"mu": mu, "srm": jnp.square(mu) + sigma ** 2}

    return jax.tree_util.tree_map_with_path(leaf, shapes,
                                            is_leaf=is_bayes_param)


class System:
    kind = "batch"

    def __init__(self, conf: dict, cell: dict, seed: int):
        from repro.bayes.metrics import pfp_predictive_metrics
        from repro.core.modes import Mode
        from repro.models.simple import lenet5_forward
        from repro.nn.module import Context

        self.conf = conf
        self.precision = conf["matmul_precision"]
        impl, n = conf["impl"], conf["num_logit_samples"]
        with jax.default_matmul_precision(self.precision):
            self.params = jax.jit(lambda k: param_tree(conf, k))(
                weights.seed_key(seed))
        self.key = weights.logit_sample_key(seed)

        def forward(params, x, key, idx):
            out = lenet5_forward(params, x, Context(mode=Mode.PFP,
                                                    impl=impl))
            m = pfp_predictive_metrics(jax.random.fold_in(key, idx),
                                       out.mean, out.var, num_samples=n)
            return out.mean, out.var, m["pred"], m["total"], m["mi"]

        self.forward = jax.jit(forward)

    def call(self, images, idx: int):
        """One request: host images (B, H, W, C) in, host outputs out."""
        with jax.default_matmul_precision(self.precision):
            out = self.forward(self.params, images, self.key, idx)
        return jax.device_get(out)

    def free(self) -> None:
        self.params = None
