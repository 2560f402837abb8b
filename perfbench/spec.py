"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's ``file`` holds the graph family and its sizes; the
family's generator is ``perfbench/families/<family>.py``.  The traffic mix
is ``perfbench/traffic/<traffic>.json``.  A metric is
``perfbench/metrics/<name>.py``, whose ``read(run)`` returns its value, or
None where the run has nothing to read.  Adding any of these is adding
files and entries; no code here names one of them.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Metric:
    name: str
    unit: str
    reader: object  # module with read(run)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    family: object  # module with generate(params, seed)
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json ({known})")


def _metrics(entries, cell_name, root: Path):
    out = []
    for entry in entries:
        if cell_name not in entry.get("workloads", [cell_name]):
            continue
        reader = _load_module(
            root / "perfbench" / "metrics" / f"{entry['name']}.py",
            f"perfbench_metric_{entry['name'].replace('.', '_').replace('-', '_')}",
        )
        out.append(Metric(entry["name"], entry["unit"], reader))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise SpecError(f"{bench_file} does not exist")
    bench = json.loads(bench_file.read_text())
    work = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], work["config"], "config")
    config = json.loads((root / conf["file"]).read_text())
    traffic_file = root / "perfbench" / "traffic" / f"{work['traffic']}.json"
    if not traffic_file.is_file():
        raise SpecError(f"{traffic_file} does not exist")
    family = _load_module(
        root / "perfbench" / "families" / f"{config['family']}.py",
        f"perfbench_family_{config['family']}",
    )
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config_name=conf["name"],
        config=config,
        traffic_name=work["traffic"],
        traffic=json.loads(traffic_file.read_text()),
        family=family,
        end_to_end=_metrics(bench["end_to_end"], name, root),
        per_layer=_metrics(bench["per_layer"], name, root),
    )
