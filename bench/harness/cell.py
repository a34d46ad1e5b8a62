"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration is ``bench/configs/<name>.json`` (its ``file``
in ``BENCHMARK.json``); it names the entry adapter
(``bench/entries/<entry>.py``) that drives the system under test and the
plain reference beside it (``bench/configs/<reference>.py``).  The mix
is ``bench/traffic/<traffic>.json``; it names its key distribution
(``bench/distributions/<distribution>.py``).  A per-layer metric is
``bench/metrics/<name>.py``.  A new cell, mix, configuration or metric
is therefore new files plus an entry in ``BENCHMARK.json``, with no
edit to a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read the cell ``name`` and the files it names.

    Raises:
        KeyError: no workload of that name in ``BENCHMARK.json``.
        FileNotFoundError: a file the cell names is missing.
    """
    spec = json.loads((root / "BENCHMARK.json").read_text())
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(workloads)}")
    w = workloads[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"]
                         if _reported_in(m, name)),
        per_layer=tuple(m for m in spec["per_layer"]
                        if _reported_in(m, name)),
    )


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (``kind``: entries, configs,
    distributions or metrics)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
