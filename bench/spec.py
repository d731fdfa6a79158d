"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

Each piece sits in a file of its own, so that adding a configuration, a
traffic mix or a metric adds files and entries and edits none:

  - a configuration: the ``file`` its ``configs`` entry names;
  - a traffic mix:   ``bench/traffic/<traffic>.json``;
  - a metric:        ``bench/metrics/<metric>.py``, whose ``read(run)``
                     returns the number, or None where the run holds
                     nothing to read;
  - the peaks:       ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)
    root: Path = REPO


def load(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _listed(metric: dict, cell: str, default: bool) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else default


def cell(spec: dict, name: str, root: Path = REPO) -> Cell:
    """The cell ``name`` with its configuration, traffic mix and the
    metrics it reports: an end-to-end metric without a ``workloads`` key
    is reported everywhere; a per-layer one without it wherever the
    end-to-end metric it ``moves`` is."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _listed(m, name, True)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _listed(m, name, m["moves"] in names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer, root=root)


def reader(metric: str, root: Path = REPO):
    """``read`` of ``bench/metrics/<metric>.py``, loaded by path (metric
    names may hold dots)."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    if mod_spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = REPO) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
