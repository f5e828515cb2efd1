"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration and a traffic mix.
Its configuration lives in the file that the ``configs`` entry names, its
traffic in ``bench/traffic/<traffic>.json``, each per-layer metric it
reports in ``bench/metrics/<metric>.py``, and what its architecture
(``model_type``) asks of the harness in ``bench/arch/<model_type>.py``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    end_to_end: list      # BENCHMARK.json end_to_end entries for this cell
    per_layer: list       # BENCHMARK.json per_layer entries for this cell


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file, from its
    architecture (``bench/arch``)."""
    from bench import arch

    return arch.load(config).model_config(config)
