"""Every BENCHMARK.json entry resolves to its files by name, and names,
units and keys keep to the benchmark's rules."""
from __future__ import annotations

import json
import re

import pytest

from bench import spec

MAN = spec.manifest()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head|expansion|expand|experts_per|top_k|d_model|d_ff")


def test_top_level_keys_and_sizes():
    assert set(MAN) == TOP_KEYS
    assert (spec.MANIFEST.stat().st_size) <= 64 * 1024
    assert 1 <= MAN["run_seconds"] <= 51 and \
        isinstance(MAN["run_seconds"], int)
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (spec.ROOT / p).is_dir()
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"])
            assert (spec.ROOT / word).exists()


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entry_keys_and_names(section):
    entries = MAN[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end",
                                              "per_layer") else set()
        assert ENTRY_KEYS[section] <= set(e) <= ENTRY_KEYS[section] | extra
        assert spec.NAME_RE.match(e["name"]), e["name"]
        if "unit" in e:
            assert spec.UNIT_RE.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200
                assert "\n" not in e[k] and "\t" not in e[k]


def test_configs_resolve_and_reduce_no_width():
    used = {w["config"] for w in MAN["workloads"]}
    files = set()
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        conf = spec.config(c["name"])
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert spec.NAME_RE.match(key)
            assert not WIDTH.search(key), f"{key} is a width"
        spec.load_module("systems", conf["system"])
        spec.load_module("reference", conf["reference"])


def test_workloads_resolve():
    configs = {c["name"] for c in MAN["configs"]}
    pairs = set()
    four = 0
    for w in MAN["workloads"]:
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200
        cell = spec.workload(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        mod = spec.load_module("traffic", cell["traffic"]["kind"])
        assert hasattr(mod, "Runner")
        # every number compared has a limit: a positive number set from
        # chip readings, or null until they are taken (every run then
        # reads correct false)
        lim = cell["limits"]
        assert lim and all(v is None or v > 0 for v in lim.values())
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_metrics_resolve_and_every_cell_reports_enough():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert hasattr(spec.load_module("metrics", m["name"]), "read")
        for c in m.get("workloads", cells):
            assert c in cells
            moved = e2e[m["moves"]]
            assert c in moved.get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        reports = [m for m in MAN["end_to_end"]
                   if c in m.get("workloads", cells)]
        assert len(reports) >= 2
        assert any(c in m.get("workloads", cells) for m in MAN["per_layer"])


def test_layers_are_named_in_perf_md():
    perf = (spec.ROOT / "PERF.md").read_text()
    for m in MAN["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def test_files_under_paths_are_named_from_name_characters():
    for f in (spec.ROOT / "bench").rglob("*"):
        if "__pycache__" in f.parts or f.is_dir():
            continue
        rel = f.relative_to(spec.ROOT).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_./-]+", rel), rel


def test_peaks_have_a_source():
    peaks = json.loads((spec.BENCH / "peaks.json").read_text())
    assert peaks["source"] and peaks["devices"]["TPU v5 lite"][
        "bf16_flops_per_s"] == 197e12
