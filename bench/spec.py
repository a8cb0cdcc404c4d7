"""Find a cell's pieces by name: configuration, traffic mix, metrics.

`BENCHMARK.json` names every cell, configuration and metric.  Each
configuration is the JSON file its entry names, and its ``kind`` names
the driver that runs it, ``bench/drivers/<kind>.py``; each traffic mix
is ``bench/traffic/<name>.json``; each metric is read by
``bench/metrics/<name>.py``, a module with ``read(ctx)`` that returns a
number, or None where the run gave it nothing to read.  A new cell,
configuration, kind, mix or metric is a new file and a new entry; no
existing file changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = find(bench["configs"], name, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(name: str, here: str = HERE) -> dict:
    with open(os.path.join(here, "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_driver(kind: str, here: str = HERE):
    """The module ``bench/drivers/<kind>.py`` (see `drivers`)."""
    if not os.path.isfile(os.path.join(here, "drivers", f"{kind}.py")):
        raise KeyError(f"no driver for configuration kind {kind!r} "
                       f"(bench/drivers/{kind}.py)")
    return importlib.import_module(f"drivers.{kind}")


def is_correct(checks: Dict[str, tuple]) -> bool:
    """A run is correct when every number compared is within its limit."""
    return all(v <= lim for v, lim in checks.values())


def metrics_for(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced.  A metric without ``workloads`` goes to every
    cell (a per-layer one: every cell that reports what it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in moved]


def load_reader(name: str, here: str = HERE):
    """The module ``bench/metrics/<name>.py``."""
    path = os.path.join(here, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(bench: dict, cell: str, traced: bool, ctx) -> Dict:
    """``{name: {"value", "unit"}}`` for every metric the cell reports
    and whose reader found something to read."""
    out: Dict[str, Dict] = {}
    for m in metrics_for(bench, cell, traced):
        v: Optional[float] = load_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def peaks(device_kind: str, here: str = HERE) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
