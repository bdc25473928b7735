"""Where the benchmark finds what a cell is made of.

Everything specific to one cell, configuration, traffic mix, per-layer
metric or kernel sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  configs/<config>.json    the model configuration as it is run
  traffic/<traffic>.json   the job: batch, sequence, codec, route, optimizer
  cells/<workload>.json    the limits of the check that decides `correct`
  metrics/<metric>.py      read(ctx) -> number or None, for a per-layer metric
  costs/<kernel>.py        cost(...) -> (operations, bytes) from shapes
  families/<family>.py     a model family, by the configuration's "family":
                           the plain reference model (init_params(c, key),
                           loss_fn(params, tokens, labels, c, mm)) and
                           ops_per_token(c, seq)
  peaks.json               the chips' peaks, by device_kind

So a later change adds a cell, a configuration, a model family or a metric
by adding files and entries, and edits none of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class BenchmarkError(RuntimeError):
    """The benchmark's own files do not define what a run asks for."""


def _json(*parts):
    path = os.path.join(*parts)
    if not os.path.exists(path):
        raise BenchmarkError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchmarkError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _json(root, "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # BENCHMARK.json entries this cell reports
    per_layer: tuple


def _reports(entry: dict, cell: str, e2e_names=()) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return not e2e_names or entry["moves"] in e2e_names


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(HERE, "configs", f"{w['config']}.json")
    if config["name"] != configs[w["config"]]["name"]:
        raise BenchmarkError(f"configs/{w['config']}.json names {config['name']!r}")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(
        m for m in bench["per_layer"] if _reports(m, name, e2e_names)
    )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=_json(HERE, "traffic", f"{w['traffic']}.json"),
        limits=_json(HERE, "cells", f"{name}.json")["limits"],
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reader(metric: str):
    """The per-layer metric's reader: read(ctx) -> number or None."""
    return _module("metrics", metric).read


def cost(kernel: str):
    """The kernel's cost function: (operations, bytes) from shapes."""
    return _module("costs", kernel).cost


def family(name: str):
    """The model family's module, ``families/<name>.py``, loaded once a
    process, so that a family that takes another's functions shares them."""
    key = f"_bench_families_{name}"
    if key not in sys.modules:
        sys.modules[key] = _module("families", name)
    return sys.modules[key]


def peaks(device_kind: str) -> dict:
    """The chip's peaks. A device that is not in the table is an error."""
    table = _json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]
