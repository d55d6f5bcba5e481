"""Benchmark data: ``BENCHMARK.json`` and the files its names point to.

Every configuration, traffic mix, cell limit and per-layer metric is a file
of its own, found by name:

    bench/configs/<config>.json     model sizes, as run, with source and cuts
    bench/traffic/<traffic>.json    engine geometry, length sets, request rate
    bench/cells/<cell>.json         limits of the numbers ``correct`` compares
    bench/metrics/<metric>.py       ``read(run) -> float | None``

so a later change adds a cell, a configuration or a metric by adding files
and entries, never by editing the harness.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"{what} name {name!r} is not 1-64 of [A-Za-z0-9_.-] "
                         "starting with a letter, digit or _")
    return name


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(root / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    return _load_json(BENCH_DIR / "traffic" / f"{check_name(name, 'traffic')}.json")


def load_cell_limits(name: str) -> dict:
    return _load_json(BENCH_DIR / "cells" / f"{check_name(name, 'cell')}.json")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced (a metric with a ``workloads``
    list only in the cells it names)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read`` function."""
    path = BENCH_DIR / "metrics" / f"{check_name(metric, 'metric')}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(cfgd: dict):
    """``repro`` ``ModelConfig`` from a configuration file. Every size and
    every wave-index knob is written in the file, so the program's own
    registry cannot change what a cell runs; keys that are not fields of
    ``ModelConfig`` (source, reduced, assumed, deployment) are notes."""
    import dataclasses

    from repro.configs.base import AttnConfig, ModelConfig, RetroConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfgd.items()
          if k in names and k not in ("attn", "retro", "source")}
    attn = dict(cfgd["attn"])
    attn["pattern"] = tuple(attn.get("pattern", ("g",)))
    return ModelConfig(attn=AttnConfig(**attn),
                       retro=RetroConfig(**cfgd["retro"]),
                       source=cfgd["source"], **kw)
