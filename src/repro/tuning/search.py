"""Per-op schedule search spaces and the shared analytic cost model.

Generalizes (and replaces) the ad-hoc ``vmem_bytes`` / ``arithmetic_intensity``
helpers that used to live in ``benchmarks/bench_table2_schedules.py``: every
quantity that decides a TPU schedule — per-grid-step VMEM working set, MXU
alignment, arithmetic intensity, grid-step count — is computed HERE, for
every tunable op, from the logical shape key and a candidate
:class:`~repro.tuning.schedules.Schedule`.

The search space is deliberately structural: candidates are enumerated from
small per-axis menus, clamped to the (padded) problem shape, de-duplicated,
and filtered by the cost model (must fit VMEM). On a real TPU the
measurement harness times the survivors; off-TPU (Pallas interpret mode,
where wall clock measures the interpreter, not the schedule) the cost-model
ranking picks the winner.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import os
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.tuning.schedules import (DEFAULT_SCHEDULES, OP_AXES,
                                    OP_BLOCK_NAMES, Schedule)

# Scoped VMEM budget per kernel by device-kind substring (lowercased
# match): the Mosaic compiler's default scoped limit, which is what a
# kernel's pipelined blocks must fit — not the physical VMEM. v4/v5 cores
# default to 16 MiB, Trillium (v6) to 32 MiB.
_VMEM_MB_BY_KIND = (("v6", 32), ("trillium", 32), ("v5", 16), ("v4", 16))
VMEM_HEADROOM = 0.75
# Off the chip (Pallas interpret mode on the CPU) schedules are ranked for
# this target, so CPU tuning still produces schedules a v5e compiles.
CPU_TARGET_KIND = "TPU v5 lite"


@functools.lru_cache(maxsize=1)
def vmem_limit_bytes() -> int:
    """Scoped VMEM budget of the device running this process (from
    ``obs/runmeta.device_kind``; the CPU ranks for ``CPU_TARGET_KIND``).
    A TPU kind missing from the table raises rather than guess. Override
    with ``REPRO_VMEM_LIMIT_BYTES`` for tests / unlisted targets."""
    env = os.environ.get("REPRO_VMEM_LIMIT_BYTES")
    if env:
        return int(env)
    from repro.obs.runmeta import device_kind

    kind = device_kind()
    if kind.lower() == "cpu":
        kind = CPU_TARGET_KIND
    for tag, mb in _VMEM_MB_BY_KIND:
        if tag in kind.lower():
            return mb * 2 ** 20
    raise ValueError(f"no scoped-VMEM budget known for device kind {kind!r}; "
                     "add it to tuning/search.py or set "
                     "REPRO_VMEM_LIMIT_BYTES")

_SUBLANE = 8    # fp32 sublane multiple
_LANE = 128     # lane multiple (MXU/VPU width)

ShapeKey = Tuple[int, ...]


def _round_up(x: int, base: int) -> int:
    return -(-int(x) // base) * base


def _steps(dim: int, block: int) -> int:
    return -(-_round_up(dim, block) // block)


def _pipelined(blocks: int, *, scratch: int = 0, temps: int = 0) -> int:
    """VMEM bytes of one grid step (fp32 elements in): Pallas double-buffers
    every blocked operand and result so the next block's DMA overlaps this
    step's compute; scratch accumulators and in-body temporaries are
    single."""
    return (2 * blocks + scratch + temps) * 4


@dataclasses.dataclass(frozen=True)
class CostSummary:
    """Analytic per-schedule cost figures (no hardware required)."""

    vmem_bytes: int          # per-grid-step VMEM working set (fp32,
    #                          double-buffered blocks included)
    flops: int               # whole-op FLOPs
    bytes_moved: int         # whole-op HBM traffic estimate (fp32)
    grid_steps: int          # total grid size after padding
    mxu_aligned: bool
    fits_vmem: bool

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes_moved, 1)


def cost_summary(op: str, shape_key: ShapeKey, schedule: Schedule) -> CostSummary:
    if op not in OP_BLOCK_NAMES:
        raise ValueError(f"unknown tunable op {op!r}")
    get = schedule.block
    if op in ("dense", "dense_first", "dense_var"):
        m, k, n = shape_key
        bm = min(get("block_m", 128), _round_up(m, _SUBLANE))
        bn = min(get("block_n", 128), _round_up(n, _LANE))
        bk = min(get("block_k", 512), _round_up(k, _LANE))
        # Eq. 12 joint kernel: mu/srm tiles for x and w, 3 matmuls, 3
        # accumulators. Eq. 13 first-layer variant: one x tile, mu/var
        # weight tiles, 2 matmuls, 2 accumulators. Eq. 7 'var' variant:
        # mu/var tiles for both operands, 4 matmuls, 2 accumulators (all
        # variance terms are additive — no mu^2 correction scratch).
        n_mm = {"dense": 3, "dense_first": 2, "dense_var": 4}[op]
        x_bufs = 1 if op == "dense_first" else 2
        n_acc = 2 if op == "dense_var" else n_mm
        flops = n_mm * 2 * m * n * k
        # In the (M/bm, N/bn, K/bk) grid each x tile is re-read once per
        # N-block and each w tile once per M-block (K is the inner
        # sequential axis): small bm re-streams the whole weight matrix.
        io = (x_bufs * m * k * _steps(n, bn) + 2 * k * n * _steps(m, bm)
              + 2 * m * n) * 4
        # In-body temporaries: the squared tiles and one dot result.
        temps = bm * bk + bk * bn + bm * bn
        if schedule.axis("k_order") == "unrolled":
            # Grid is (M/bm, N/bn); full K strips stay resident and the
            # K-tile loop runs inside the kernel body, accumulators in
            # values.
            kp = _round_up(k, bk)
            vmem = _pipelined(x_bufs * bm * kp + 2 * kp * bn + 2 * bm * bn,
                              temps=temps + n_acc * bm * bn)
            steps = _steps(m, bm) * _steps(n, bn)
        else:  # "mnk" / "nmk": same footprint, K innermost either way
            # Outputs double as accumulators; dense's mu^2 correction
            # lives in one scratch tile.
            vmem = _pipelined(x_bufs * bm * bk + 2 * bk * bn + 2 * bm * bn,
                              scratch=(n_acc - 2) * bm * bn, temps=temps)
            steps = _steps(m, bm) * _steps(n, bn) * _steps(k, bk)
        aligned = bm % _SUBLANE == 0 and bn % _LANE == 0 and bk % _LANE == 0
    elif op == "dense_batched":
        # Batched-expert MoE kernel: E independent Eq. 12 dense problems
        # on an E-leading grid, ``block_e`` experts resident per step.
        # Per-expert tile footprints are the dense kernel's, scaled by
        # block_e; the grid-step count divides by block_e — THAT is the
        # term the expert-grid blocking axis buys (the vmapped baseline
        # is structurally block_e=1: one grid step per expert tile).
        e, c, k, n = shape_key
        be = min(get("block_e", 1), max(e, 1))
        bc = min(get("block_c", 128), _round_up(c, _SUBLANE))
        bn = min(get("block_n", 128), _round_up(n, _LANE))
        bk = min(get("block_k", 512), _round_up(k, _LANE))
        flops = 3 * 2 * e * c * n * k
        # Same re-read structure as dense, per expert: x tiles re-read
        # once per N-block, w tiles once per C-block.
        io = (2 * e * c * k * _steps(n, bn) + 2 * e * k * n * _steps(c, bc)
              + 2 * e * c * n) * 4
        temps = be * (bc * bk + bk * bn + bc * bn)
        if schedule.axis("k_order") == "unrolled":
            kp = _round_up(k, bk)
            vmem = _pipelined(be * (2 * bc * kp + 2 * kp * bn + 2 * bc * bn),
                              temps=temps + 3 * be * bc * bn)
            steps = _steps(e, be) * _steps(c, bc) * _steps(n, bn)
        else:
            vmem = _pipelined(be * (2 * bc * bk + 2 * bk * bn + 2 * bc * bn),
                              scratch=be * bc * bn, temps=temps)
            steps = (_steps(e, be) * _steps(c, bc) * _steps(n, bn)
                     * _steps(k, bk))
        aligned = bc % _SUBLANE == 0 and bn % _LANE == 0 and bk % _LANE == 0
    elif op in ("attention", "attention_cache", "attention_paged"):
        # The cache/paged variants run the same online-softmax core over
        # the same (b, h, hkv, tq, tk, d) shape key; attention_paged has no
        # block_k axis (its K block is the pool's page size), so the
        # default stands in for the footprint estimate.
        b, h, hkv, tq, tk, d = shape_key
        bq = min(get("block_q", 128), _round_up(tq, _SUBLANE))
        bk = min(get("block_k", 128), _round_up(tk, _SUBLANE))
        # Scalar-prefetch depth (paged only): pf pages of KV are resident
        # per grid step, shrinking the K grid by the same factor.
        pf = int(schedule.axis("prefetch")) if op == "attention_paged" else 1
        vmem = _pipelined(
            bq * d + 3 * bk * pf * d         # q tile + k/v_mu/v_var tiles
            + 2 * bq * d,                    # two outputs
            scratch=2 * bq * d + 2 * bq * _LANE,  # acc_mu/acc_var, max/norm
            temps=3 * bq * bk)               # score, p and p^2 of one page
        flops = b * h * tq * tk * (6 * d + 8)
        io = (b * h * tq * d * 3 + b * hkv * tk * d * 3 * _steps(tq, bq)) * 4
        steps = b * h * _steps(tq, bq) * _steps(tk, bk * pf)
        aligned = bq % _SUBLANE == 0 and bk % _SUBLANE == 0
    elif op in ("activation", "glu_product", "maxpool2d"):
        rows, cols = _elementwise_rows_cols(op, shape_key)
        br = min(get("block_rows", 256), _round_up(rows, _SUBLANE))
        bc = min(get("block_cols", 512), _round_up(cols, _LANE))
        tiles = {"activation": 4, "glu_product": 6, "maxpool2d": 10}[op]
        vmem = _pipelined(tiles * br * bc, temps=tiles * br * bc)
        per_elem = {"activation": 50, "glu_product": 2, "maxpool2d": 60}[op]
        flops = per_elem * rows * cols
        io = tiles * rows * cols * 4
        steps = _steps(rows, br) * _steps(cols, bc)
        aligned = br % _SUBLANE == 0 and bc % _LANE == 0
    elif op == "norm_dense_act":
        # Fused norm -> dense -> activation unit: grid (M/bm, N/bn); the
        # full (padded) K axis stays resident per step — x mu/second
        # strips + gain/bias vectors + w mu/srm strips, three
        # accumulators (mu / srm / mu^2 correction).
        m, k, n = shape_key
        kp = _round_up(k, _LANE)
        bm = min(get("block_m", 128), _round_up(m, _SUBLANE))
        bn = min(get("block_n", 128), _round_up(n, _LANE))
        vmem = _pipelined(2 * bm * kp + 2 * kp + 2 * kp * bn + 2 * bm * bn,
                          temps=3 * bm * kp + 3 * bm * bn)
        flops = 3 * 2 * m * n * k + 12 * m * k + 50 * m * n
        # The fusion's whole point: x is normalized in-kernel, so the
        # norm's intermediate never round-trips HBM.
        io = (2 * m * k * _steps(n, bn) + 2 * k * n * _steps(m, bm)
              + 2 * m * n) * 4
        steps = _steps(m, bm) * _steps(n, bn)
        aligned = bm % _SUBLANE == 0 and bn % _LANE == 0
    else:  # rmsnorm / layernorm: full (padded) feature axis stays resident
        rows, d = shape_key
        dp = _round_up(d, _LANE)
        br = min(get("block_rows", 256), _round_up(rows, _SUBLANE))
        # two row strips in, two out, plus var/srm of the strip in-body
        vmem = _pipelined(4 * br * dp + 2 * dp, temps=2 * br * dp)
        flops = 12 * rows * d
        io = 4 * rows * d * 4
        if schedule.axis("epilogue") == "split":
            # Separate activation kernel: one extra HBM round-trip for
            # the (mu, var) intermediate.
            io += 4 * rows * d * 4
        steps = _steps(rows, br)
        aligned = br % _SUBLANE == 0
    return CostSummary(
        vmem_bytes=vmem, flops=flops, bytes_moved=io, grid_steps=steps,
        mxu_aligned=aligned,
        fits_vmem=vmem <= vmem_limit_bytes() * VMEM_HEADROOM,
    )


def _elementwise_rows_cols(op: str, shape_key: ShapeKey) -> Tuple[int, int]:
    if op == "maxpool2d":
        n, h, w, c = shape_key
        return n * (h // 2) * (w // 2), c
    rows, cols = shape_key
    return rows, cols


def score(op: str, shape_key: ShapeKey, schedule: Schedule):
    """Sort key: higher is better. Aligned schedules beat unaligned, then
    arithmetic intensity, then fewer grid steps (less invocation overhead)."""
    c = cost_summary(op, shape_key, schedule)
    return (c.fits_vmem, c.mxu_aligned, c.arithmetic_intensity, -c.grid_steps)


# ---------------------------------------------------------------------------
# Analytic time model + calibration hook
# ---------------------------------------------------------------------------
# Uncalibrated machine constants (v5e-class ballpark). Their absolute
# values barely matter: tuning/measure.py fits per-(op, backend)
# multipliers onto the three terms from real timings, and it is those
# fitted coefficients — not these constants — that re-rank candidates.
PEAK_FLOPS_PER_S = 100e12
HBM_BYTES_PER_S = 800e9
STEP_OVERHEAD_S = 1e-6


def time_features(op: str, shape_key: ShapeKey,
                  schedule: Schedule) -> Tuple[float, float, float]:
    """The three additive terms of the analytic time model, in seconds:
    (compute-bound, memory-bound, grid-invocation overhead)."""
    c = cost_summary(op, shape_key, schedule)
    return (c.flops / PEAK_FLOPS_PER_S,
            c.bytes_moved / HBM_BYTES_PER_S,
            c.grid_steps * STEP_OVERHEAD_S)


def predicted_seconds(op: str, shape_key: ShapeKey, schedule: Schedule,
                      calibration: Optional[Mapping] = None) -> float:
    """Predicted wall clock under the (optionally calibrated) time model.

    ``calibration`` is the per-(op, backend) dict that
    ``tuning.measure.fit_calibration`` produces — its ``coef`` triple
    rescales the compute / memory / overhead terms.
    """
    coef = (1.0, 1.0, 1.0)
    if calibration:
        coef = tuple(float(c) for c in calibration.get("coef", coef))
    return sum(c * x
               for c, x in zip(coef, time_features(op, shape_key, schedule)))


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------
_DENSE_MENU = {"block_m": (8, 16, 32, 64, 128, 256),
               "block_n": (128, 256, 512),
               "block_k": (128, 256, 512, 1024)}

_AXIS_MENU: Dict[str, Dict[str, Sequence[int]]] = {
    "dense": _DENSE_MENU,
    "dense_first": _DENSE_MENU,
    "dense_var": _DENSE_MENU,
    "dense_batched": {"block_e": (1, 2, 4, 8),
                      "block_c": (8, 16, 32, 64, 128, 256),
                      "block_n": (128, 256, 512),
                      "block_k": (128, 256, 512, 1024)},
    "attention": {"block_q": (16, 32, 64, 128, 256),
                  "block_k": (32, 64, 128, 256, 512)},
    "attention_cache": {"block_q": (16, 32, 64, 128, 256),
                        "block_k": (32, 64, 128, 256, 512)},
    "attention_paged": {"block_q": (8, 16, 32, 64, 128, 256)},
    "activation": {"block_rows": (8, 64, 128, 256, 512),
                   "block_cols": (128, 256, 512)},
    "glu_product": {"block_rows": (8, 64, 128, 256, 512),
                    "block_cols": (128, 256, 512)},
    "maxpool2d": {"block_rows": (8, 64, 128, 256, 512),
                  "block_cols": (128, 256)},
    "rmsnorm": {"block_rows": (8, 16, 64, 128, 256, 512)},
    "layernorm": {"block_rows": (8, 16, 64, 128, 256, 512)},
    "norm_dense_act": {"block_m": (8, 16, 32, 64, 128, 256),
                       "block_n": (128, 256, 512)},
}

# The dim of the logical shape each block axis tiles, per op — used to clamp
# menu values so candidates never exceed the padded problem.
_DENSE_DIM = {"block_m": (0, _SUBLANE), "block_n": (2, _LANE),
              "block_k": (1, _LANE)}

_AXIS_DIM = {
    "dense": _DENSE_DIM,
    "dense_first": _DENSE_DIM,
    "dense_var": _DENSE_DIM,
    "dense_batched": {"block_e": (0, 1), "block_c": (1, _SUBLANE),
                      "block_k": (2, _LANE), "block_n": (3, _LANE)},
    "attention": {"block_q": (3, _SUBLANE), "block_k": (4, _SUBLANE)},
    "attention_cache": {"block_q": (3, _SUBLANE), "block_k": (4, _SUBLANE)},
    "attention_paged": {"block_q": (3, _SUBLANE)},
    "rmsnorm": {"block_rows": (0, _SUBLANE)},
    "layernorm": {"block_rows": (0, _SUBLANE)},
    "norm_dense_act": {"block_m": (0, _SUBLANE), "block_n": (2, _LANE)},
}


def _clamped_axis_values(op: str, name: str, shape_key: ShapeKey) -> List[int]:
    menu = _AXIS_MENU[op][name]
    if op in ("activation", "glu_product", "maxpool2d"):
        rows, cols = _elementwise_rows_cols(op, shape_key)
        dim = rows if name == "block_rows" else cols
        align = _SUBLANE if name == "block_rows" else _LANE
    else:
        idx, align = _AXIS_DIM[op][name]
        dim = shape_key[idx]
    limit = _round_up(dim, align)
    vals = sorted({min(v, limit) for v in menu})
    return vals


def candidates(op: str, shape_key: ShapeKey, *,
               limit: int | None = None,
               calibration: Optional[Mapping] = None) -> List[Schedule]:
    """Enumerate the filtered, ranked schedule space for ``op`` at
    ``shape_key``. Always non-empty: the default schedule is included (its
    clamped form always fits — it is what runs today). Best-ranked first.

    The space is the cross product of the clamped block-shape menus and
    the op's categorical axes (dimension_semantics, K-loop order, fused
    epilogue, scalar-prefetch depth). With ``calibration`` (a fitted
    per-(op, backend) coefficient record) candidates are re-ranked by
    calibrated predicted seconds instead of the raw heuristic tuple."""
    if op not in OP_BLOCK_NAMES:
        raise ValueError(f"unknown tunable op {op!r}")
    names = OP_BLOCK_NAMES[op]
    axes = [_clamped_axis_values(op, name, shape_key) for name in names]
    cat = OP_AXES.get(op, {})
    cat_names = tuple(cat)
    all_names = names + cat_names
    pool = {Schedule.make(op, **dict(zip(all_names, combo)))
            for combo in itertools.product(*axes, *cat.values())}
    pool.add(DEFAULT_SCHEDULES[op])
    # describe() tie-break: a total, hash-seed-independent order so the
    # tuner is deterministic across processes.
    if calibration:
        ranked = sorted(
            pool,
            key=lambda s: (not cost_summary(op, shape_key, s).fits_vmem,
                           predicted_seconds(op, shape_key, s, calibration),
                           s.describe()))
    else:
        ranked = sorted(pool,
                        key=lambda s: (score(op, shape_key, s), s.describe()),
                        reverse=True)
    kept = [s for s in ranked if cost_summary(op, shape_key, s).fits_vmem]
    if not kept:  # paranoid: never return an empty space
        kept = [DEFAULT_SCHEDULES[op]]
    if limit is not None:
        kept = kept[:limit]
    return kept
