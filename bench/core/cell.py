"""Find a cell's files by the names in `BENCHMARK.json`.

    BENCHMARK.json            the cell: its config, traffic and chips
    bench/configs/<config>.json, <config>.py    sizes; plain reference
    bench/traffic/<traffic>.json                the training mix
    bench/limits/<workload>.json                the comparison's limits
                                                and the stated round plan
    bench/metrics/<metric>.py                   one per-layer reader

Adding a cell, a configuration or a metric adds files and entries; no
file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    model: object            # the configuration's reference module
    traffic: dict
    limits: dict             # number -> {"limit": ..., ...}
    plan: dict               # the stated round plan (`plan.py`)
    end_to_end: list         # BENCHMARK.json metric entries for this cell
    per_layer: list


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg_path = root / cfg_entry["file"]
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = (json.loads(limits_path.read_text())
              if limits_path.exists() else {})
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads(cfg_path.read_text()),
        model=_load_module(cfg_path.with_suffix(".py"),
                           f"bench_config_{w['config']}"),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=limits.get("limits", {}), plan=limits.get("plan", {}),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """`read(ctx) -> float | None` of bench/metrics/<name>.py."""
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_")).read
