"""Find what a cell names: its configuration, its traffic mix, its metrics.

Every lookup goes by the name written in `BENCHMARK.json` to a file of the
same name, so a cell, a configuration, a mix or a metric is added by adding
files and entries, never by editing this code.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_of(spec: dict, cell: dict) -> dict:
    entry = find(spec["configs"], cell["config"], "configuration")
    return load_json(ROOT / entry["file"])


def traffic_of(cell: dict) -> dict:
    return load_json(BENCH / "traffic" / f"{cell['traffic']}.json")


def metrics_of(spec: dict, cell: dict, traced: bool) -> list[dict]:
    """The cell's metrics: end-to-end ones untraced, per-layer ones traced.

    A metric with a ``workloads`` list belongs to those cells only."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def peaks_of(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
