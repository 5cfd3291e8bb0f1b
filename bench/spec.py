"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration and
traffic mix; every part lives in a file of its own under ``bench/``:

  bench/configs/<config>.json       the deployment (its ``file`` entry)
  bench/traffic/<mix>.json          the traffic mix
  bench/generators/<name>.py        the request generator a mix names
  bench/datasets/<name>.py          the key-set generator a config names
  bench/layer_metrics/<metric>.py   the reader of one per-layer metric

so a later change adds a configuration, a mix or a metric by adding files
and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic file's contents
    traffic: str            # the mix's name
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                traffic=w["traffic"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_module(kind: str, name: str, root: Path = ROOT):
    """Import ``root/bench/<kind>/<name>.py`` (metric names may hold dots,
    so modules load by path, not by package name)."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
