"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``configs/<config>.json``, ``traffic/<mix>.json``,
``generators/<name>.py``, ``drivers/<name>.py``, ``layers/<metric>.json`` and
``readers/<name>.py`` and one entry each in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(workload: str, root: str = ROOT) -> dict:
    """Everything one run needs: the workload entry, its configuration file,
    its traffic file, the metrics that list it."""
    bench = benchmark(root)
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {workload!r}; known: {known}")
    w = rows[0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "workload": w,
        "config": _load(os.path.join(root, cfg["file"])),
        "traffic": _load(os.path.join(HERE, "traffic",
                                      w["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def layer(metric: str) -> dict:
    return _load(os.path.join(HERE, "layers", metric + ".json"))


def plugin(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module (``perfbench`` is on
    ``sys.path``)."""
    return importlib.import_module(f"{kind}.{name}")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       "an unknown chip is an error, never a default")
    return table["devices"][device_kind]
