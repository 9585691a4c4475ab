"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is first normalised (``normalise``) to plain data: planes, their
lines, and events ``[name, start_ns, duration_ns, detail]``, keeping every
device plane and, of the host planes, only the harness's own spans
(``bench.*``). ``Trace`` then answers, for the traced window (the
``bench.window`` host span):

* busy time: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops``), averaged over the devices that ran any;
* device time of the operations or programs (line ``XLA Modules``) whose
  name or detail matches a pattern, and the program each operation ran in;
* idle gaps, each named by the innermost harness span it fell in.

``busy_s``/``window_s`` are what the result's ``device`` block reports; the
metric files under ``bench/metrics/`` read the rest.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DETAIL_STATS = ("long_name", "tf_op", "hlo_op", "name", "kernel_details")


def normalise(path: str) -> dict:
    """Plain-data form of an ``.xplane.pb`` file (see module doc)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                detail = ""
                if device and line.name == OPS_LINE:
                    stats = dict(e.stats)
                    detail = " ".join(str(stats[k])[:160]
                                      for k in _DETAIL_STATS if k in stats)
                events.append([e.name, int(e.start_ns), int(e.duration_ns),
                               detail])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> int:
    return max(0, min(b, hi) - max(a, lo))


class Trace:
    def __init__(self, norm: dict):
        self.spans: List[Tuple[str, int, int]] = []
        self.devices: Dict[str, dict] = {}
        for plane in norm["planes"]:
            if plane["name"].startswith("/device:"):
                ops, mods = [], []
                for line in plane["lines"]:
                    if line["name"] == OPS_LINE:
                        ops += line["events"]
                    elif line["name"] == MODULES_LINE:
                        mods += line["events"]
                if ops:
                    self.devices[plane["name"]] = {"ops": ops,
                                                   "modules": mods}
            else:
                for line in plane["lines"]:
                    for name, t, d, *_ in line["events"]:
                        if name.startswith(SPAN_PREFIX):
                            self.spans.append((name, t, t + d))
        win = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if win:
            self.lo, self.hi = win[0][1], win[0][2]
        else:
            ts = [e[1] for d in self.devices.values() for e in d["ops"]]
            te = [e[1] + e[2] for d in self.devices.values()
                  for e in d["ops"]]
            self.lo, self.hi = (min(ts), max(te)) if ts else (0, 0)
        self.spans.sort(key=lambda s: s[1])
        for dev in self.devices.values():
            dev["busy"] = _union([(t, t + d) for _, t, d, *_ in dev["ops"]
                                  if _clip(t, t + d, self.lo, self.hi)])
            mods = sorted((t, t + d, n) for n, t, d, *_ in dev["modules"])
            dev["mod_starts"] = [m[0] for m in mods]
            dev["mods"] = mods

    # -- totals ----------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices used."""
        if not self.devices:
            return 0.0
        tot = sum(_clip(a, b, self.lo, self.hi)
                  for dev in self.devices.values() for a, b in dev["busy"])
        return tot * 1e-9 / len(self.devices)

    def idle_share(self) -> Optional[float]:
        """Percent of the window in which no device ran an operation;
        None where the trace has no device or no window."""
        if self.window_s <= 0 or not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def busy_within(self, a: int, b: int) -> float:
        """Busy seconds inside [a, b] (ns), averaged over devices."""
        if not self.devices:
            return 0.0
        tot = 0
        for dev in self.devices.values():
            busy = dev["busy"]
            i = max(0, bisect.bisect_right(busy, (a, a)) - 1)
            while i < len(busy) and busy[i][0] < b:
                tot += _clip(busy[i][0], busy[i][1], a, b)
                i += 1
        return tot * 1e-9 / len(self.devices)

    def module_of(self, dev: dict, t: int) -> Optional[str]:
        i = bisect.bisect_right(dev["mod_starts"], t) - 1
        if i >= 0 and dev["mods"][i][0] <= t < dev["mods"][i][1]:
            return dev["mods"][i][2]
        return None

    def modules(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of program executions whose name matches, in
        the window, summed over devices."""
        rx = re.compile(pattern)
        tot, n = 0, 0
        for dev in self.devices.values():
            for a, b, name in dev["mods"]:
                c = _clip(a, b, self.lo, self.hi)
                if c and rx.search(name):
                    tot += c
                    n += 1
        return tot * 1e-9, n

    def ops(self, pattern: str, module: Optional[str] = None
            ) -> Tuple[float, int]:
        """(seconds, count) of operations whose name or detail matches
        ``pattern``, inside programs matching ``module`` when given."""
        rx = re.compile(pattern)
        mx = re.compile(module) if module else None
        tot, n = 0, 0
        for dev in self.devices.values():
            for name, t, d, *rest in dev["ops"]:
                c = _clip(t, t + d, self.lo, self.hi)
                if not c or not (rx.search(name) or
                                 (rest and rx.search(rest[0]))):
                    continue
                if mx is not None:
                    mod = self.module_of(dev, t)
                    if mod is None or not mx.search(mod):
                        continue
                tot += c
                n += 1
        return tot * 1e-9, n

    # -- breakdown -------------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[list]:
        """The device operations that took most time in the window, by
        name with its trailing instance number removed."""
        tot: Dict[str, int] = {}
        for dev in self.devices.values():
            for name, t, d, *_ in dev["ops"]:
                c = _clip(t, t + d, self.lo, self.hi)
                if c:
                    key = re.sub(r"[.:]\d+$", "", name)
                    tot[key] = tot.get(key, 0) + c
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / len(self.devices)] for k, v in ranked]

    def span_at(self, t: int) -> str:
        """The innermost harness span (other than the window) holding t."""
        best = None
        for name, a, b in self.spans:
            if a > t:
                break
            if name != WINDOW_SPAN and a <= t < b and (
                    best is None or b - a < best[2] - best[1]):
                best = (name, a, b)
        return best[0] if best else "none"

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle intervals of the first device in the window."""
        if not self.devices:
            return [(self.lo, self.hi)]
        busy = next(iter(self.devices.values()))["busy"]
        out, cur = [], self.lo
        for a, b in busy:
            if a > cur:
                out.append((cur, min(a, self.hi)))
            cur = max(cur, b)
        if cur < self.hi:
            out.append((cur, self.hi))
        return [(a, b) for a, b in out if b > a]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest idle gaps, each named by the harness span it fell
        in (at its midpoint)."""
        longest = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at((a + b) // 2), (b - a) * 1e-9]
                for a, b in longest]

    def spans_named(self, name: str) -> List[Tuple[int, int]]:
        return [(a, b) for s, a, b in self.spans
                if s == name and _clip(a, b, self.lo, self.hi)]
