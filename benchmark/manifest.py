"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by name, so that a
later PR adds entries and files and edits none that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell(manifest: Dict, name: str) -> Dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r}; have "
        f"{[w['name'] for w in manifest['workloads']]}"
    )


def config_path(manifest: Dict, config_name: str, root: str = ROOT) -> str:
    for c in manifest["configs"]:
        if c["name"] == config_name:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {config_name!r}")


def load_config(manifest: Dict, config_name: str, root: str = ROOT) -> Dict:
    with open(config_path(manifest, config_name, root),
              encoding="utf-8") as f:
        return json.load(f)


def traffic_path(traffic_name: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmark", "traffic", traffic_name + ".json")


def layer_metric_path(metric_name: str, root: str = ROOT) -> str:
    return os.path.join(
        root, "benchmark", "layer_metrics", metric_name + ".py"
    )


def load_layer_metric(metric_name: str, root: str = ROOT):
    """The reader module of one per-layer metric: ``LAYER``, ``UNIT``,
    ``MOVES``, ``SOURCE`` and ``read(facts) -> float | None``."""
    path = layer_metric_path(metric_name, root)
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + re.sub(r"\W", "_", metric_name), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_for(manifest: Dict, section: str, workload: str) -> List[Dict]:
    """The metrics of ``section`` that this cell reports: those with no
    ``workloads`` key, and those that list the cell."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or workload in m["workloads"]
    ]
