"""The benchmark's description: BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name, so a later change adds files
and entries and edits none:

    <file named by the configs entry>   configuration (bench/configs/*.json)
    bench/traffic/<mix>.json            traffic mix parameters
    bench/metrics/<metric>.py           reader of one per-layer metric
    bench/flops/<family>.py             FLOP and byte counts of a family
    bench/reference/<family>.py         plain float32 reference forward
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR.name


def load_benchmark(root: Path = REPO_ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def metrics_of(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics a cell reports. A per-layer
    metric without a `workloads` list goes to every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": per}


def load_cell(name: str, root: Path = REPO_ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    if config.get("name") != cfg_entry["name"]:
        raise ValueError(f"{cfg_entry['file']} names {config.get('name')!r}, "
                         f"BENCHMARK.json {cfg_entry['name']!r}")
    traffic = json.loads(
        (root / BENCH_DIR.name / "traffic" / f"{w['traffic']}.json").read_text())
    traffic.setdefault("name", w["traffic"])
    m = metrics_of(bench, name)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=m["end_to_end"],
                per_layer=m["per_layer"], root=root)


def load_module(bench_dir: Path, kind: str, name: str) -> ModuleType:
    """bench/<kind>/<name>.py, loaded from its path (names may hold dots)."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
