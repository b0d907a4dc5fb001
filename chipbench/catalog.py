"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CatalogError(Exception):
    """A name in ``BENCHMARK.json`` that resolves to no file."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    reader: object | None  # the metric's module (per-layer metrics only)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    generator: object  # module with ``window(entry, problem, traffic, seed, seconds)``
    entry: object  # module with ``open(problem, config)``
    problem: object  # module with ``build(config, chips, overrides)``
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CatalogError(f"missing file {os.path.relpath(path, ROOT)}")


def _module(package: str, name: str):
    try:
        return importlib.import_module(f"chipbench.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"chipbench.{package}.{name}":
            raise CatalogError(f"no chipbench/{package}/{name}.py")
        raise


def _generator(mix: str, traffic: dict) -> str:
    if "generator" not in traffic:
        raise CatalogError(f"chipbench/traffic/{mix}.json names no generator")
    return traffic["generator"]


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, entry and
    metrics loaded; raises :class:`CatalogError` for a name that
    resolves to no file."""
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CatalogError(
            f"unknown workload {name!r}; known: {sorted(work)}"
        )
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(
        os.path.join(root, "chipbench", "traffic", f"{w['traffic']}.json")
    )
    e2e = tuple(Metric(m["name"], m["unit"], None)
                for m in bench["end_to_end"] if _applies(m, name))
    layer = tuple(Metric(m["name"], m["unit"], _module("metrics", m["name"]))
                  for m in bench["per_layer"] if _applies(m, name))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        generator=_module("generators", _generator(w["traffic"], traffic)),
        entry=_module("entries", config["entry"]),
        problem=_module("problems", config["problem"]),
        end_to_end=e2e, per_layer=layer,
    )
