"""Smoke run of the PFP system on one TPU chip: the quickest proof that the
main path still starts, compiles its kernels and agrees with its XLA twin.

    python chip_smoke.py [--seed S]       # one chip: paper + serving phases
    python chip_smoke.py --four-chip      # four chips: tensor-parallel only

Phases (plain functions, so a CPU test can call them at reduced size):

* ``paper_phase``   paper-mlp and paper-lenet5 at their published widths on
  a procedural Dirty-MNIST batch at batch sizes 1, 10 and 100: the PFP
  forward on the Pallas kernels against the XLA impl under highest matmul
  precision, and an N-sample SVI forward's entropy and MI beside PFP's.
* ``serving_phase`` granite-8b at its published widths, cut to 2 layers,
  served through ``launch/serve.py``'s engine path (paged Gaussian KV
  cache, kernel impl, bf16 compute, a Poisson trace of 8 requests, router
  thresholds that send every token to the SVI second opinion); prefill
  and first-decode logits and MI are checked against the same engine on
  the XLA impl, and any kernel->XLA dispatch fallback fails the phase.
* ``four_chip_phase`` (``--four-chip``) the same granite cut served
  tensor-parallel on mesh (1, 4) by the serve sharding rules against a
  (1, 1) mesh on device 0 of the same process.

Every line but the last reports a phase; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script refuses to run unless JAX's default backend is a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.bayes.convert import svi_to_pfp  # noqa: E402
from repro.bayes.metrics import (pfp_predictive_metrics,  # noqa: E402
                                 predictive_metrics_from_samples)
from repro.configs import get_config  # noqa: E402
from repro.core import dispatch  # noqa: E402
from repro.core.modes import Mode  # noqa: E402
from repro.data.dirty_mnist import (make_ambiguous, make_clean,  # noqa: E402
                                    make_ood)
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.simple import (lenet5_forward, lenet5_init,  # noqa: E402
                                 mlp_forward, mlp_init)
from repro.nn.module import Context  # noqa: E402
from repro.serving.batcher import Request  # noqa: E402
from repro.serving.engine import run_load  # noqa: E402

# Random weights get a posterior scale at which the variance channel
# carries signal: at the init default sigma 1e-4, sigma^2/mu^2 is ~1e-4 to
# ~1e-5, so the SRM difference below (and bf16 rounding) would leave both
# impls comparing noise. Trained BNNs have posteriors this wide or wider.
PAPER_SIGMA = 1e-2


def serve_sigma(d_model: int) -> float:
    """Posterior sigma with sigma^2 = 0.5 E[mu^2] at fan-in ``d_model``
    (mu ~ N(0, 1/fan_in) truncated at 2 sd has E[mu^2] ~ 0.77/fan_in), so
    var/srm of every weight sits well above bf16's 2^-8."""
    return (0.5 / d_model) ** 0.5


# -- tolerances: every comparison below is a max error normalised by the
# reference's largest magnitude (``_rel_err``) unless it says otherwise.
# Paper models, fp32, highest matmul precision: kernel and XLA run the same
# fp32 math and differ in accumulation order (a few ulps) and in erf: the
# kernels' polynomial and the backend's lax.erf each err by ~3e-7 near
# +-1, and ReLU's left tail mu*cdf + pdf cancels to ~0, which makes that a
# 1e-4 relative difference of LeNet-5's logits (2.3e-4 measured on CPU).
PAPER_MEAN_TOL = 1e-3
# Logit variances come from Eq. 12's SRM difference E[x^2]E[w^2] - (mu mu)^2,
# whose cancellation amplifies those ulps by srm/var (~1e1 at PAPER_SIGMA).
PAPER_VAR_TOL = 1e-3
# Serving, bf16 compute: every op rounds its output to bf16 (2^-8 relative),
# and kernel and XLA round values that differ in the last fp32 bits, so a
# rounding can land one bf16 ulp apart per op; over 2 layers and the head
# that reaches a few 1e-2 of the largest logit.
SERVE_MEAN_TOL = 5e-2
# Variances: the dense kernel squares bf16 means in bf16 (2^-9 relative)
# where XLA squares them in fp32, and the SRM difference amplifies that by
# srm/var (~3 at serve_sigma): ~1e-2 per layer.
SERVE_VAR_TOL = 1e-1
# MI (nats) of 32 keyed logit samples, same keys in both engines: it moves
# with the logit moments above; absolute, since MI can be ~0.
SERVE_MI_TOL = 5e-2
# Four chips vs one, XLA impl in both: tensor-parallel matmuls split K and
# add partial sums in another order, in bf16 activations as above.
TP_MEAN_TOL = SERVE_MEAN_TOL
TP_VAR_TOL = SERVE_VAR_TOL
TP_MI_TOL = SERVE_MI_TOL
# A four-chip run must spread params and pages: every device holds at least
# this share of the bytes the (1, 4) run placed in all (an even split of the
# sharded leaves is 1/4; norms and biases are replicated).
TP_MIN_DEVICE_SHARE = 0.15

# The serving cut: published widths, depth cut to 2 layers.
SERVE_LAYERS = 2


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def _peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (the persistent cache turns a hit into ~0 here)."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    @contextlib.contextmanager
    def installed(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self)


def _bytes_by_device(tree) -> dict:
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return out


def _report(phase: str, **fields) -> None:
    parts = []
    for k, v in fields.items():
        parts.append(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}")
    print(f"[{phase}] " + " ".join(parts), flush=True)


# ---------------------------------------------------------------------------
# (a) paper path
# ---------------------------------------------------------------------------
def dirty_mnist_batch(n: int, seed: int) -> np.ndarray:
    """(n, 28, 28) procedural Dirty-MNIST images: clean, ambiguous and OOD
    thirds, interleaved so every prefix of the batch mixes all three."""
    k = -(-n // 3)
    parts = [make_clean(k, seed)[0], make_ambiguous(k, seed + 1)[0],
             make_ood(k, seed + 2)]
    return np.stack(parts, axis=1).reshape(3 * k, 28, 28)[:n]


def paper_phase(*, seed: int = 0, batches=(1, 10, 100),
                svi_samples: int = 32) -> bool:
    """PFP kernel vs XLA on the paper's MLP and LeNet-5; SVI beside it."""
    key = jax.random.PRNGKey(seed)
    images = dirty_mnist_batch(max(batches), seed)
    models = {
        "paper-mlp": (mlp_forward, mlp_init(key, sigma_init=PAPER_SIGMA),
                      lambda x: x.reshape(x.shape[0], -1)),
        "paper-lenet5": (lenet5_forward,
                         lenet5_init(key, sigma_init=PAPER_SIGMA),
                         lambda x: x[..., None]),
    }
    ok = True
    for name, (forward, svi_params, shape_in) in models.items():
        pfp_params = svi_to_pfp(svi_params)

        def pfp(params, x, impl, forward=forward):
            out = forward(params, x, Context(mode=Mode.PFP, impl=impl))
            return out.mean, out.var

        def svi(params, x, k, forward=forward):
            def one(kk):
                return forward(params, x, Context(mode=Mode.SVI, key=kk))
            return jax.lax.map(one, jax.random.split(k, svi_samples))

        for b in batches:
            x = jnp.asarray(shape_in(images[:b]))
            clock = _CompileClock()
            with clock.installed(), dispatch.count_fallbacks() as fb, \
                    jax.default_matmul_precision("highest"):
                got = jax.jit(pfp, static_argnums=2)(pfp_params, x, "kernel")
                want = jax.jit(pfp, static_argnums=2)(pfp_params, x, "xla")
                samples = jax.jit(svi)(svi_params, x,
                                       jax.random.fold_in(key, b))
                got, want, samples = jax.block_until_ready(
                    (got, want, samples))
            err_m, err_v = _rel_err(got[0], want[0]), _rel_err(got[1], want[1])
            pm = pfp_predictive_metrics(jax.random.fold_in(key, 7), *got,
                                        num_samples=svi_samples)
            sm = predictive_metrics_from_samples(samples)
            passed = (err_m <= PAPER_MEAN_TOL and err_v <= PAPER_VAR_TOL
                      and not fb)
            ok &= passed
            _report("paper", model=name, batch=b, ok=passed,
                    max_err_mean=err_m, tol_mean=PAPER_MEAN_TOL,
                    max_err_var=err_v, tol_var=PAPER_VAR_TOL,
                    pfp_entropy=float(jnp.mean(pm["total"])),
                    pfp_mi=float(jnp.mean(pm["mi"])),
                    svi_entropy=float(jnp.mean(sm["total"])),
                    svi_mi=float(jnp.mean(sm["mi"])),
                    svi_samples=svi_samples, fallbacks=dict(fb),
                    compile_s=clock.seconds, peak_bytes=_peak_bytes())
    return ok


# ---------------------------------------------------------------------------
# (b) serving path
# ---------------------------------------------------------------------------
def serving_config(cfg=None):
    """granite-8b at published widths with its depth cut (SERVE_LAYERS)
    and a posterior scale from :func:`serve_sigma`."""
    cfg = cfg or get_config("granite-8b")
    return dataclasses.replace(cfg, num_layers=SERVE_LAYERS,
                               sigma_init=serve_sigma(cfg.d_model))


def serve_args(impl: str, seed: int):
    """``launch/serve.py`` arguments of the smoke run: paged KV cache,
    Poisson arrivals, and thresholds that escalate every token (MI >= 0 >
    mi_continue) and abstain on none (mi_abstain = inf)."""
    return serve.parse_args([
        "--impl", impl, "--page-size", "16", "--batch", "4",
        "--requests", "8", "--rate", "0.5", "--prompt-len", "24",
        "--tokens", "4", "--prefill-chunk", "16", "--mi-continue=-1",
        "--mi-abstain", "inf", "--escalate-samples", "4",
        "--seed", str(seed)])


@dataclasses.dataclass
class ServeRun:
    summary: dict
    streams: dict          # uid -> (tokens, MI trace)
    prefill: tuple         # (mean, var) logit rows after a 1-token request
    decode: tuple          # (mean, var) logit rows after its first decode
    mi: list               # MI of the probe's prefill and decode logits
    fallbacks: dict
    compile_s: float
    pool_bytes: dict       # device id -> bytes of the KV page pool
    in_use: dict           # device id -> bytes_in_use while serving


def serve_once(cfg, params, mesh, impl: str, seed: int) -> ServeRun:
    """Serve the Poisson trace through ``launch/serve.py``'s engine path,
    then probe one prompt on fresh engines (which reuse the compiled
    passes): the logits after its prefill, and after its first decode."""
    args = serve_args(impl, seed)
    clock = _CompileClock()
    with clock.installed(), dispatch.count_fallbacks() as fb, mesh:
        parts = serve.serving_parts(args, cfg, params, mesh)
        engine = parts.build_engine(0)
        trace = parts.make_trace()
        summary = run_load(engine, trace)
        pool_bytes = _bytes_by_device(engine.pool.states)
        in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in mesh.devices.flat}
        prompt = np.asarray(trace[0].prompt)

        def probe(new_tokens):
            eng = parts.build_engine(0)
            req = Request(uid=10_000 + new_tokens, prompt=prompt.copy(),
                          max_new_tokens=new_tokens)
            eng.submit(req)
            eng.step()
            rows = tuple(np.asarray(a) for a in eng.logit_buffers)
            eng.run_until_idle()
            return rows, [float(m) for m in req.mi_trace]

        prefill, mi_pre = probe(1)
        decode, mi_dec = probe(2)
    streams = {r.uid: (list(r.generated), [float(m) for m in r.mi_trace])
               for r in engine.finished}
    return ServeRun(summary, streams, prefill, decode, mi_pre[:1] + mi_dec[1:2],
                    dict(fb), clock.seconds, pool_bytes, in_use)


def _compare_runs(got: ServeRun, want: ServeRun, tol_mean, tol_var, tol_mi):
    errs = {
        "max_err_prefill_mean": _rel_err(got.prefill[0], want.prefill[0]),
        "max_err_prefill_var": _rel_err(got.prefill[1], want.prefill[1]),
        "max_err_decode_mean": _rel_err(got.decode[0], want.decode[0]),
        "max_err_decode_var": _rel_err(got.decode[1], want.decode[1]),
        "max_err_mi": float(np.max(np.abs(np.subtract(got.mi, want.mi)))),
    }
    passed = (len(got.mi) == len(want.mi) == 2
              and errs["max_err_prefill_mean"] <= tol_mean
              and errs["max_err_decode_mean"] <= tol_mean
              and errs["max_err_prefill_var"] <= tol_var
              and errs["max_err_decode_var"] <= tol_var
              and errs["max_err_mi"] <= tol_mi)
    return passed, errs


def serving_phase(cfg=None, *, seed: int = 0) -> bool:
    """granite-8b (cut to 2 layers) through the serving engine, kernel impl
    against the XLA impl on the same params, trace and keys."""
    cfg = serving_config(cfg)
    full = get_config(cfg.name)
    _report("serve-cut", arch=cfg.name, num_layers=f"{full.num_layers}->"
            f"{cfg.num_layers}", pattern_period=len(cfg.pattern),
            d_model=cfg.d_model, heads=f"{cfg.num_heads}/{cfg.num_kv_heads}",
            head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
            sigma_init=f"{full.sigma_init:g}->{cfg.sigma_init:.4g}")
    mesh = make_mesh((1, 1), ("data", "model"))
    clock = _CompileClock()
    with clock.installed():
        params = serve.init_serving_params(cfg, seed, mesh)
        jax.block_until_ready(params)
    runs = {impl: serve_once(cfg, params, mesh, impl, seed)
            for impl in ("kernel", "xla")}
    k, x = runs["kernel"], runs["xla"]
    passed, errs = _compare_runs(k, x, SERVE_MEAN_TOL, SERVE_VAR_TOL,
                                 SERVE_MI_TOL)
    s = k.summary
    served = s["completed"] >= 1 and s["escalations"] >= 1
    # Every op on granite's path has a kernel: any fallback is a failure.
    passed = passed and served and not k.fallbacks
    _report("serve", ok=passed, **errs, tol_mean=SERVE_MEAN_TOL,
            tol_var=SERVE_VAR_TOL, tol_mi=SERVE_MI_TOL,
            requests_served=s["completed"], escalations=s["escalations"],
            tokens=s["tokens_generated"],
            same_tokens_as_xla=k.streams.keys() == x.streams.keys() and all(
                k.streams[u][0] == x.streams[u][0] for u in k.streams),
            fallbacks=k.fallbacks or 0,
            compile_s=clock.seconds + k.compile_s + x.compile_s,
            peak_bytes=_peak_bytes())
    return passed


# ---------------------------------------------------------------------------
# four chips: tensor-parallel serving vs one chip
# ---------------------------------------------------------------------------
def _min_share(by_device: dict, devices) -> float:
    """Smallest share of the bytes in ``by_device`` that one of ``devices``
    holds (0 where a device holds none)."""
    total = sum(by_device.values()) or 1
    return min(by_device.get(d.id, 0) for d in devices) / total


def four_chip_phase(cfg=None, *, seed: int = 0) -> bool:
    """granite (same cut) on mesh (1, 4) through the serve sharding rules
    against mesh (1, 1) on device 0: same seed and trace. Both run the XLA
    impl: Mosaic kernels are not partitioned automatically, and the kernel
    stack has no per-op shard_map yet, so the tensor-parallel path is XLA's."""
    cfg = serving_config(cfg)
    devices = jax.devices()[:4]
    if len(devices) < 4:
        _report("four-chip", ok=False, error=f"{len(devices)} devices")
        return False
    mesh4 = make_mesh((1, 4), ("data", "model"))
    clock = _CompileClock()  # params init; serve_once clocks itself
    with clock.installed():
        params4 = serve.init_serving_params(cfg, seed, mesh4)
    tp = serve_once(cfg, params4, mesh4, "xla", seed)
    placed = _bytes_by_device(params4)
    del params4
    mesh1 = jax.sharding.Mesh(np.array(devices[:1]).reshape(1, 1),
                              ("data", "model"))
    with clock.installed():
        params1 = serve.init_serving_params(cfg, seed, mesh1)
    one = serve_once(cfg, params1, mesh1, "xla", seed)
    passed, errs = _compare_runs(tp, one, TP_MEAN_TOL, TP_VAR_TOL, TP_MI_TOL)
    same_tokens = tp.streams.keys() == one.streams.keys() and all(
        tp.streams[u][0] == one.streams[u][0] for u in tp.streams)
    mi_err = max((abs(a - b) for u in tp.streams if u in one.streams
                  for a, b in zip(tp.streams[u][1], one.streams[u][1])),
                 default=0.0)
    shares = {"params": _min_share(placed, devices),
              "page_pool": _min_share(tp.pool_bytes, devices),
              "bytes_in_use": _min_share(tp.in_use, devices)}
    passed = (passed and same_tokens and mi_err <= TP_MI_TOL
              and min(shares.values()) >= TP_MIN_DEVICE_SHARE
              and tp.summary["completed"] >= 1)
    _report("four-chip", ok=passed, **errs, max_err_stream_mi=mi_err,
            same_tokens=same_tokens, tol_mean=TP_MEAN_TOL, tol_var=TP_VAR_TOL,
            tol_mi=TP_MI_TOL, requests_served=tp.summary["completed"],
            escalations=tp.summary["escalations"],
            param_bytes_by_device=placed, pool_bytes_by_device=tp.pool_bytes,
            bytes_in_use_by_device=tp.in_use,
            min_device_share=shares, tol_share=TP_MIN_DEVICE_SHARE,
            compile_s=clock.seconds + tp.compile_s + one.compile_s,
            peak_bytes_by_device=[_peak_bytes(d) for d in devices])
    return passed


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip tensor-parallel phase")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default backend is {backend!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    dev = jax.devices()[0]
    _report("device", platform=dev.platform, kind=dev.device_kind,
            count=len(jax.devices()), jax=jax.__version__)
    phases = ([four_chip_phase] if args.four_chip
              else [paper_phase, serving_phase])
    ok = True
    for phase in phases:
        t0 = time.perf_counter()
        try:
            passed = phase(seed=args.seed)
        except Exception as e:  # report the phase, run the others
            import traceback
            traceback.print_exc()
            _report(phase.__name__, ok=False, error=repr(e)[:300])
            passed = False
        _report(phase.__name__, ok=passed,
                wall_s=time.perf_counter() - t0)
        ok &= passed
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
