"""Batch traffic: one closed-loop client sending requests
of ``batch`` images each; a request is timed from the host call to the
per-image outputs on the host.

Images are procedural Dirty-MNIST (a copy of ``data/dirty_mnist.py``'s
renderer, kept here so that the yardstick does not move with the
program): clean digits, blends of two digits and textures, interleaved so
every batch mixes all three. A pool of ``pool_batches`` batches is made
from the seed during set-up and request i sends batch ``order[i % pool]``.

Parameters (the cell's ``traffic`` object): ``batch``, ``pool_batches``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

from bench.stats import percentile

_GRID = 28
_FONT = {
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    3: ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}
_BLUR = np.array([0.25, 0.5, 0.25], np.float32)


def _blur(img, k, axes=(0, 1)):
    for ax in axes:
        img = np.apply_along_axis(lambda m: np.convolve(m, k, "same"), ax,
                                  img)
    return img


def _digit(d: int, rng) -> np.ndarray:
    g = np.array([[float(c) for c in r] for r in _FONT[d]], np.float32)
    scale = rng.uniform(2.6, 3.4)
    h, w = int(7 * scale), int(5 * scale)
    big = g[np.ix_((np.arange(h) / scale).astype(int).clip(0, 6),
                   (np.arange(w) / scale).astype(int).clip(0, 4))]
    shear = rng.uniform(-0.15, 0.15)
    out = np.zeros((_GRID, _GRID), np.float32)
    oy = rng.integers(0, _GRID - h + 1)
    ox = rng.integers(0, _GRID - w + 1)
    for r in range(h):
        x0 = np.clip(ox + int(round(shear * (r - h / 2))), 0, _GRID - w)
        out[oy + r, x0:x0 + w] = np.maximum(out[oy + r, x0:x0 + w], big[r])
    out = _blur(out, _BLUR) + rng.normal(0, 0.05, out.shape)
    return np.clip(out, 0.0, 1.0).astype(np.float32)


def _texture(rng) -> np.ndarray:
    yy, xx = np.meshgrid(np.arange(_GRID), np.arange(_GRID), indexing="ij")
    kind = rng.integers(0, 3)
    if kind == 0:
        f, th = rng.uniform(0.3, 1.5), rng.uniform(0, np.pi)
        img = 0.5 + 0.5 * np.sin(f * (np.cos(th) * xx + np.sin(th) * yy))
    elif kind == 1:
        s = rng.integers(2, 6)
        img = ((yy // s + xx // s) % 2).astype(np.float32)
    else:
        img = _blur(rng.normal(0, 1, (_GRID, _GRID)),
                    np.ones(5, np.float32) / 5)
        img = (img - img.min()) / (np.ptp(img) + 1e-9)
    return np.clip(img + rng.normal(0, 0.05, img.shape), 0, 1)


def images(n: int, seed: int) -> np.ndarray:
    """(n, 28, 28, 1) float32: clean, blended and texture images in turn."""
    rng = np.random.default_rng(seed)
    out = np.empty((n, _GRID, _GRID, 1), np.float32)
    for i in range(n):
        kind = i % 3
        if kind == 0:
            img = _digit(int(rng.integers(0, 10)), rng)
        elif kind == 1:
            a = int(rng.integers(0, 10))
            b = (a + int(rng.integers(1, 10))) % 10
            w = rng.uniform(0.35, 0.65)
            img = np.clip(w * _digit(a, rng) + (1 - w) * _digit(b, rng), 0, 1)
        else:
            img = _texture(rng)
        out[i, :, :, 0] = img
    return out


@dataclasses.dataclass
class Record:
    index: int          # request number, folded into its sampling key
    batch: int          # pool batch it sent
    start: float
    end: float
    outputs: tuple = None


class Driver:
    def __init__(self, system, traffic: dict, seed: int, annotate):
        self.system = system
        self.annotate = annotate
        b, n = traffic["batch"], traffic["pool_batches"]
        self.pool = images(b * n, seed).reshape(n, b, _GRID, _GRID, 1)
        self.order = np.random.default_rng(seed + 1).permutation(n)
        self.records: List[Record] = []

    def batch_of(self, i: int) -> int:
        return int(self.order[i % len(self.order)])

    def warm(self) -> None:
        self.system.call(self.pool[0], 0)

    def run(self, seconds: float) -> None:
        t0 = time.perf_counter()
        i = 0
        while True:
            start = time.perf_counter() - t0
            if start >= seconds:
                break
            k = self.batch_of(i)
            with self.annotate("bench.call"):
                out = self.system.call(self.pool[k], i)
            self.records.append(Record(i, k, start,
                                       time.perf_counter() - t0, out))
            i += 1


def end_to_end(records: List[Record], seconds: float) -> dict:
    lat = [r.end - r.start for r in records if r.end <= seconds]
    return {"batch_p95_ms": 1e3 * percentile(lat, 95)} if lat else {}


class Runner:
    """The batch cell's run: warm-up, window, end-to-end numbers and the
    check of sampled requests' outputs against the reference."""

    def __init__(self, system, conf: dict, cell: dict, seed: int,
                 seconds: float, annotate):
        self.system, self.conf, self.cell = system, conf, cell
        self.seed, self.seconds = seed, seconds
        self.driver = Driver(system, cell["traffic"], seed, annotate)

    def warm(self) -> None:
        self.driver.warm()

    def run(self) -> None:
        self.driver.run(self.seconds)

    @property
    def attempted(self) -> int:
        return sum(1 for r in self.driver.records if r.start < self.seconds)

    @property
    def failed(self) -> int:
        return 0

    def end_to_end(self) -> dict:
        return end_to_end(self.driver.records, self.seconds)

    def diagnostics(self) -> dict:
        return {"requests": len(self.driver.records)}

    def served_flops(self) -> float:
        return 0.0

    def release(self) -> None:
        pass

    def sample(self, most: int = 16) -> list:
        """Up to ``most`` requests of the window, drawn from the seed."""
        recs = self.driver.records
        pick = np.random.default_rng(self.seed + 2).permutation(
            len(recs))[:most]
        return [(recs[i].index, self.driver.pool[recs[i].batch],
                 recs[i].outputs) for i in sorted(pick)]

    def check(self, control: bool = False) -> dict:
        """The numbers compared for ``correct`` (see the reference's
        ``compare``); with ``control``, the reference at the precision
        below the configuration's (``control_precision``) stands in the
        program's place."""
        from bench import spec
        from bench.weights import logit_sample_key

        ref = spec.load_module("reference", self.conf["reference"])
        out = ref.compare(self.conf, self.seed, logit_sample_key(self.seed),
                          self.sample(),
                          control=self.conf["control_precision"] if control
                          else None)
        out["requests_checked"] = min(16, len(self.driver.records))
        return out
