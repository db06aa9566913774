"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix. Its files are:

* ``BENCHMARK.json`` ``configs[].file``: the configuration (dataset);
* ``bench/traffic/<traffic>.json``: the solver spec the mix drives;
* ``bench/workloads/<cell>.json``: rounds, target and the limits of the
  comparison that decides ``correct``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a cell or a metric is adding files and entries;
no list in code names them.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's own file, with its entry, configuration and traffic
    under ``entry``, ``config_data`` and ``traffic_data``."""
    bench = bench or benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{', '.join(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cell = _read(os.path.join(BENCH, "workloads", f"{name}.json"))
    cell["entry"] = entry
    cell["config_data"] = _read(os.path.join(ROOT,
                                             configs[entry["config"]]["file"]))
    cell["traffic_data"] = _read(os.path.join(BENCH, "traffic",
                                              f"{entry['traffic']}.json"))
    return cell


def metrics_for(name: str, bench: dict | None = None, *, traced: bool):
    """The metric entries a run of cell ``name`` reports: the end-to-end
    ones untraced, the per-layer ones traced. A per-layer entry with a
    ``workloads`` list applies to those cells only; one without applies
    to every cell."""
    bench = bench or benchmark()
    if not traced:
        return bench["end_to_end"]
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name])]
