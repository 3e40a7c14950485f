"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is ``configs/<config>.json``, a traffic mix
``traffic/<traffic>.json``, a metric's reader ``metrics/<metric>.py`` and
a cell's correctness limits ``limits/<workload>.json``; the runner that
drives a mix is ``runners/<mix["runner"]>.py``.  Adding any of them is
adding a file and a manifest entry: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name in the manifest has no file, or a file is malformed."""


def _json(path: Path) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None


def manifest(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def workload(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return _json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return _json(bench_dir / "traffic" / f"{name}.json")


def limits(workload_name: str, bench_dir: Path = BENCH_DIR
           ) -> Dict[str, Any]:
    return _json(bench_dir / "limits" / f"{workload_name}.json")


def module(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """Import ``<kind>/<name>.py`` (names may hold dots and dashes)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"missing {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(man: Dict[str, Any], workload_name: str, trace: bool
                 ) -> List[Dict[str, Any]]:
    """The metrics a run of this cell reports: its end-to-end metrics
    with ``trace`` off, its per-layer metrics with it on.  A metric
    without a ``workloads`` list applies to every cell."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group
            if workload_name in m.get("workloads", [workload_name])]
