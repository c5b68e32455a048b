"""A scratch benchmark root for the CPU tests: a copy of bench/ plus the
tiny configurations and mixes under tests/data, described by
tests/data/benchmark.json. Nothing under the real bench/ is edited."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_root(tmp: Path) -> Path:
    dst = tmp / BENCH.name
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".*"))
    for kind in ("configs", "traffic"):
        for f in (HERE / "data" / kind).iterdir():
            shutil.copy(f, dst / kind / f.name)
    shutil.copy(HERE / "data" / "benchmark.json", tmp / "BENCHMARK.json")
    return tmp


def add_entry(root: Path, section: str, entry: dict) -> None:
    """Append one entry to the scratch root's BENCHMARK.json."""
    path = root / "BENCHMARK.json"
    b = json.loads(path.read_text())
    b[section].append(entry)
    path.write_text(json.dumps(b, indent=1))
