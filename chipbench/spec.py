"""Loads a cell: ``BENCHMARK.json`` -> configuration, traffic and metric files.

The harness is driven by data. A cell names a configuration and a traffic
mix; the configuration's file is given in ``BENCHMARK.json``, a traffic mix
is ``<path>/traffic/<name>.json`` and a per-layer metric is the reader
``<path>/metrics/<name>.py`` under any directory of ``paths``. Adding a
cell, a configuration, a traffic mix or a metric is adding files and one
entry; no file that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


# what the runner sets from a configuration's shapes; a file states a shape once
ENGINE_RESERVED = frozenset(
    {"sm_factory", "n_shards", "n_replicas", "mesh", "window", "device_store",
     "device_store_kw"}
)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1
    readers: dict  # per-layer metric name -> read(ctx)


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"{path}: {e}") from None


def _find(root: Path, paths: list, rel: str) -> Path:
    for p in paths:
        cand = root / p / rel
        if cand.is_file():
            return cand
    raise SpecError(f"no {rel} under any of paths={paths}")


def _load_reader(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path}: a metric reader defines read(ctx)")
    return mod.read


def engine_options(config: dict, accepted) -> dict:
    """A configuration's ``engine`` object: the engine's keyword options that
    the deployment states beyond its shapes (its batching, its pipe depth, its
    read path), passed on as they stand. ``accepted`` holds the names of the
    engine's parameters. A file without the key states none."""
    name = config.get("name", "?")
    options = config.get("engine", {})
    if not isinstance(options, dict):
        raise SpecError(f"{name}: engine: an object of options, not {options!r}")
    for key, value in options.items():
        if key in ENGINE_RESERVED:
            raise SpecError(
                f"{name}: engine.{key}: the runner sets it from the "
                "configuration's shapes, and a file states a shape once"
            )
        if key not in accepted:
            raise SpecError(
                f"{name}: engine.{key}: not an option of the engine; it takes "
                f"{sorted(set(accepted) - ENGINE_RESERVED)}"
            )
        if isinstance(value, (dict, list)):
            raise SpecError(f"{name}: engine.{key}: a JSON scalar, not {value!r}")
    return dict(options)


def load_benchmark(root: Path = REPO_ROOT) -> dict:
    return _load_json(Path(root) / "BENCHMARK.json")


def load_cell(name: str, root: Path = REPO_ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    paths = bench["paths"]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{name}: unknown config {w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(_find(root, paths, f"traffic/{w['traffic']}.json"))
    e2e = tuple(bench["end_to_end"])
    layer = tuple(bench["per_layer"])
    readers = {
        m["name"]: _load_reader(_find(root, paths, f"metrics/{m['name']}.py"))
        for m in layer
    }
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer, readers)
