"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's file is
the ``file`` of its ``configs`` entry, the mix is
``benchmark/traffic/<mix>.json``, a per-layer metric is
``benchmark/metrics/<name>.json`` (a metric named ``<metric>.<tag>`` with no
file of its own is read as ``<metric>``'s file says) and a configuration's
plain reference is ``benchmark/references/<reference>.py``.  Nothing here
knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
# the checkout: BENCHMARK.json and every file it names are found under it
# (a test points this at a copy that holds more cells)
ROOT = os.path.dirname(HERE)

class ManifestError(ValueError):
    """BENCHMARK.json or one of the files it names is not usable."""


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from None
    except ValueError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file
    mix: dict             # the traffic mix's file
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list       # metric files (benchmark/metrics/<name>.json) of this cell


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def metric_file(entry: dict) -> dict:
    """The data file of a ``per_layer`` entry.  ``<metric>.<tag>`` with no
    file of its own is ``<metric>`` again for the cells its ``workloads`` key
    lists, tied to another end-to-end metric: the reader and its arguments
    are ``<metric>``'s, everything else the entry's."""
    name = entry["name"]
    folder = os.path.join(ROOT, "benchmark", "metrics")
    base = name.rsplit(".", 1)[0]
    if base != name and not os.path.exists(os.path.join(folder, f"{name}.json")):
        return {**metric_file({"name": base}), **entry}
    spec = _load(os.path.join(folder, f"{name}.json"))
    if spec.get("name") != name:
        raise ManifestError(f"benchmark/metrics/{name}.json names {spec.get('name')!r}")
    return spec


def cell(name: str) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json: have "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if conf is None:
        raise ManifestError(f"workload {name!r} names no configuration of BENCHMARK.json")
    config = _load(os.path.join(ROOT, conf["file"]))
    mix = _load(os.path.join(ROOT, "benchmark", "traffic", f"{entry['traffic']}.json"))
    if entry["chips"] not in (1, 4):
        raise ManifestError(f"workload {name!r}: chips {entry['chips']}")
    if config.get("chips", entry["chips"]) != entry["chips"]:
        raise ManifestError(
            f"workload {name!r} asks for {entry['chips']} chips, its "
            f"configuration is laid out for {config['chips']}"
        )
    if config.get("link_delay_ms", 0):
        raise ManifestError(
            "link_delay_ms is accepted for the WAN cell to come and not yet "
            "honoured: the harness has no delaying link (PERF.md, Open questions)"
        )
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[metric_file(m) for m in bench["per_layer"] if _in_cell(m, name)],
    )


def reference(config: dict):
    """The configuration's plain reference, loaded from its own file."""
    name = config["reference"]
    path = os.path.join(ROOT, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_reference_{name}", path)
    if spec is None or not os.path.exists(path):
        raise ManifestError(f"no plain reference at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
