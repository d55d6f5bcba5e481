"""Benchmark data: ``BENCHMARK.json`` and the files its names point to.

Every configuration, traffic mix, cell limit and per-layer metric is a file
of its own, found by name:

    bench/configs/<config>.json     model sizes, as run, with source and cuts
    bench/traffic/<traffic>.json    engine geometry, length sets, request rate
    bench/cells/<cell>.json         limits of the numbers ``correct`` compares
    bench/metrics/<metric>.py       ``read(run) -> float | None``
    bench/blocks/<block>.py         a configuration's layers: weights,
                                    reference, matmul count (``load_block``)

so a later change adds a cell, a configuration or a metric by adding files
and entries, never by editing the harness.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import typing
from functools import lru_cache
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BLOCKS_DIR = BENCH_DIR / "blocks"
BLOCK_FUNCTIONS = ("make_params", "final_hidden", "decode_matmul_flops")
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


def _load_module(path: Path, mod_name: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read`` function."""
    path = BENCH_DIR / "metrics" / f"{check_name(metric, 'metric')}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", metric)
    return _load_module(path, mod_name).read


@lru_cache(maxsize=None)
def _block_module(path: Path):
    """One module per block file for the process, so that its jitted
    functions and caches are built once."""
    mod = _load_module(path, "bench_block_" + re.sub(r"\W", "_", path.stem))
    missing = [f for f in BLOCK_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"block {path} does not define {missing}")
    return mod


def load_block(cfgd: dict):
    """The configuration's block module, ``bench/blocks/<block>.py`` named by
    its required ``block`` key: ``make_params(cfgd, seed)`` (all weights in
    the program's layout, on the device, in the served type),
    ``final_hidden(cfgd, seed, tokens, rows, patches=None, quant=None)``
    (the float32 ``HIGHEST`` reference's final-norm hidden states) and
    ``decode_matmul_flops(cfgd)`` (matmul operations of one slot's decode
    step)."""
    if "block" not in cfgd:
        raise KeyError("configuration has no 'block' key: it names the module "
                       "bench/blocks/<block>.py that computes its layers")
    path = BLOCKS_DIR / f"{check_name(cfgd['block'], 'block')}.py"
    if not path.is_file():
        raise FileNotFoundError(f"block {cfgd['block']!r} has no module at "
                                f"{path}")
    return _block_module(path)


def _build(cls, value: dict):
    """``cls(**value)``, list values of tuple-typed fields made tuples;
    an unknown key raises."""
    hints = typing.get_type_hints(cls)
    kw = {k: tuple(v) if isinstance(v, list)
          and typing.get_origin(hints.get(k)) is tuple else v
          for k, v in value.items()}
    return cls(**kw)


def model_config(cfgd: dict):
    """``repro`` ``ModelConfig`` from a configuration file. Every size and
    every wave-index knob is written in the file, so the program's own
    registry cannot change what a cell runs. A field typed as one of the
    sub-configs (``AttnConfig``, ``MoEConfig``, ``SSMConfig``,
    ``RetroConfig``, also under ``Optional``) is built from its dict; keys
    that are not fields of ``ModelConfig`` (block, published, reduced,
    assumed, deployment) are notes."""
    from repro.configs.base import (AttnConfig, ModelConfig, MoEConfig,
                                    RetroConfig, SSMConfig)
    subs = (AttnConfig, MoEConfig, SSMConfig, RetroConfig)
    hints = typing.get_type_hints(ModelConfig)
    kw = {}
    for f in dataclasses.fields(ModelConfig):
        if f.name not in cfgd:
            continue
        value = cfgd[f.name]
        sub = [c for c in (hints[f.name], *typing.get_args(hints[f.name]))
               if c in subs]
        kw[f.name] = _build(sub[0], value) if sub and value is not None \
            else value
    return ModelConfig(**kw)
