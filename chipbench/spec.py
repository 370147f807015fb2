"""The benchmark's description, read from data: ``BENCHMARK.json`` at the
root of the checkout, one file per configuration (``configs/``), one per
traffic mix (``traffic/``), one reader per per-layer metric (``metrics/``)
and one operation and byte count per kernel (``kernels/``).  Everything is
found by the name that ``BENCHMARK.json`` gives it, so a later change adds a
cell, a metric or a kernel count by adding files and entries only."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json``: a configuration under a traffic
    mix, with the metrics it reports."""
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, name, names))
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> ModuleType:
    """``metrics/<metric>.py``: ``read(window) -> float | None``."""
    return _module(HERE / "metrics" / f"{metric}.py")


def kernel(name: str) -> ModuleType:
    """``kernels/<name>.py``: the kernel's trace name, the span it runs in
    and ``work(call, model) -> (flops, bytes)``."""
    return _module(HERE / "kernels" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``; a device
    missing from the table is an error."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
