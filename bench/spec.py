"""Where the harness finds a cell's files, by name, and the naming rules
``BENCHMARK.json`` keeps to."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import sys
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def workload(name: str) -> dict:
    """The cell's own file, ``bench/workloads/<name>.json``."""
    return load_json(BENCH / "workloads" / f"{name}.json")


def config(name: str) -> dict:
    """The configuration as it is run, ``bench/configs/<name>.json``."""
    return load_json(BENCH / "configs" / f"{name}.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module. Names may hold dots
    (``device_idle_share.serve``), so the file is loaded by path."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench.{kind}.{name}".replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(man: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` ('end_to_end' | 'per_layer') this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in man[section]
            if "workloads" not in m or cell in m["workloads"]]
