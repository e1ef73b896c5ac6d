"""Cells, configurations and traffic mixes, found by the names that
``BENCHMARK.json`` gives them.

A cell is one ``workloads`` entry of ``BENCHMARK.json``. Its files:

* ``configs/<config>.json``   the configuration as it is run;
* ``traffic/<traffic>.json``  the parameters its generator reads;
* ``generators/<kind>.py``    the generator of each mix ``kind``, with the
  arrival processes, length distributions and inputs it names in
  ``generators/{arrivals,lengths,inputs}/<name>.py``;
* ``workloads/<cell>.json``   the deployment's settings and the driver;
* ``drivers/<driver>.py``     the loop that drives the timed path;
* ``metrics/<metric>.py``     one reader per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]       # benchmarks/chip
REPO = ROOT.parents[1]
BENCHMARK = REPO / "BENCHMARK.json"


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict              # configs/<config>.json
    traffic_name: str
    traffic: dict             # traffic/<traffic>.json
    settings: dict            # workloads/<cell>.json
    end_to_end: tuple         # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` with every file it names, read from disk."""
    bench = _read_json(BENCHMARK) if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        have = sorted(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {have}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=entry["chips"],
        config_name=conf["name"], config=_read_json(REPO / conf["file"]),
        traffic_name=entry["traffic"],
        traffic=_read_json(ROOT / "traffic" / f"{entry['traffic']}.json"),
        settings=_read_json(ROOT / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` under the benchmark root (a metric
    reader, a driver, a generator or a work count) by its file name."""
    mod_name = f"chipbench_{kind}_{name}"
    for c in "./-":
        mod_name = mod_name.replace(c, "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(
        mod_name, ROOT / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict):
    """The program's configuration object, built from the file's numbers
    (not from the program's own registry)."""
    from repro.configs.base import SpikingConfig
    from repro.configs.impulse_snn import SNNModelConfig
    return SNNModelConfig(
        arch_id=config["name"],
        layer_sizes=tuple(config["layer_sizes"]),
        conv_spec=tuple(tuple(c) for c in config["conv_spec"]),
        in_shape=tuple(config["in_shape"]),
        spiking=SpikingConfig(
            neuron=config["neuron"], timesteps=config["timesteps"],
            threshold=config["threshold"], leak=config["leak"],
            w_bits=config["w_bits"], v_bits=config["v_bits"]),
        timesteps=config["timesteps"], task=config["task"])
