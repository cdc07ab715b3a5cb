"""Find a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` lists the cells; a cell names a configuration (its
file under ``bench/configs``), a traffic mix (``bench/traffic/<name>.json``)
and, through the metric entries, readers ``bench/metrics/<name>.py``.
Adding a cell or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import copy
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def load_module(path: Path):
    """Import a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    traffic_name: str
    end_to_end: list      # metric entries this cell reports
    per_layer: list
    seed: int

    @property
    def seed_words(self) -> list:
        return [self.seed & 0xFFFFFFFF, (self.seed >> 32) & 0xFFFFFFFF]

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py")

    def reference(self, name: str):
        return load_module(BENCH / "reference" / f"{name}.py")


def _applies(entry: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric is read in the cells it lists, or else in every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry["moves"] in e2e_names


def load_cell(name: str, seed: int, overrides: dict | None = None,
              root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    over = overrides or {}
    config = _merge(config, over.get("config", {}))
    traffic = _merge(traffic, over.get("traffic", {}))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                traffic_name=w["traffic"], end_to_end=e2e,
                per_layer=per_layer, seed=seed)
