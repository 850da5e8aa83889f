"""Finds a cell's data files by the names in them, and refuses what it
does not know.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
every ``layer_metrics/*.json`` whose ``workloads`` lists the cell belongs to
it. A configuration names its model family, a cell and its mix their kind:
``harness/family_<family>.py`` and ``harness/<kind>.py``, modules found by
name as the data files are. A later PR adds a cell, a configuration, a mix,
a metric, a family or a kind as a new file and edits none that is here.
"""

from __future__ import annotations

import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

CELL_KEYS = {"name", "kind", "config", "traffic", "chips", "geometry",
             "trace_seconds", "trace_steps", "check", "why", "who",
             "rehearsal"}
CELL_REQUIRED = {"name", "kind", "config", "traffic", "chips", "check", "why",
                 "who"}
# what every family's configuration carries; the model's own keys are its
# family module's ``CONFIG_REQUIRED`` and ``CONFIG_KEYS``
CONFIG_REQUIRED = {"name", "source", "reduced", "assumed", "deployment",
                   "published", "torch_dtype"}
CONFIG_KEYS = CONFIG_REQUIRED | {"family", "model_type", "weights",
                                 "precision"}
DEFAULT_FAMILY = "llama"
METRIC_KEYS = {"name", "layer", "unit", "better", "source", "moves",
               "workloads", "reader", "module", "args", "what"}
METRIC_REQUIRED = METRIC_KEYS - {"module", "args"}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class CellError(ValueError):
    """A data file is missing, misnamed or holds a key nobody reads."""


def check_name(name) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise CellError(
            f"name {name!r}: at most 64 letters, digits, '_', '.', '-', "
            f"starting with a letter, a digit or '_'")
    return name


def _load(directory: str, name: str, base: str | None = None) -> dict:
    check_name(name)
    path = os.path.join(base or BENCH_DIR, directory, f"{name}.json")
    if not os.path.isfile(path):
        raise CellError(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as fp:
        data = json.load(fp)
    if not isinstance(data, dict):
        raise CellError(f"{path}: not a JSON object")
    return data


def harness_module(what: str, module: str):
    """``harness/<module>.py``, found by the name a data file gives."""
    name = f"benchmarks.harness.{module}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as exc:
        if not name.startswith(exc.name or "\0"):
            raise           # the module is there; what it imports is not
        raise CellError(
            f"{what}: no file benchmarks/harness/{module}.py") from None


def family_of(config: dict, what: str | None = None):
    """The module of the configuration's model family: its config keys, its
    map onto the program, its reference and costs, and what a serve cell
    compares."""
    family = check_name(config.get("family", DEFAULT_FAMILY))
    what = what or f"configuration {config.get('name')!r}"
    return harness_module(f"{what}: family {family!r}", f"family_{family}")


def kind_module(what: str, kind):
    """The module of a kind of cell; it has ``run``."""
    module = harness_module(f"{what}: kind {check_name(kind)!r}", kind)
    if not callable(getattr(module, "run", None)):
        raise CellError(f"{what}: benchmarks/harness/{kind}.py has no run(), "
                        f"so {kind!r} is no kind of cell")
    return module


def _check_keys(what: str, data: dict, allowed: set, required: set):
    unknown = sorted(set(data) - allowed)
    if unknown:
        raise CellError(f"{what}: unknown key(s) {unknown}")
    missing = sorted(required - set(data))
    if missing:
        raise CellError(f"{what}: missing key(s) {missing}")


def load_config(name: str, base: str | None = None) -> dict:
    data = _load("configs", name, base)
    family = family_of(data, f"configs/{name}.json")
    _check_keys(f"configs/{name}.json", data,
                CONFIG_KEYS | set(family.CONFIG_KEYS),
                CONFIG_REQUIRED | set(family.CONFIG_REQUIRED))
    if data["name"] != name:
        raise CellError(f"configs/{name}.json names itself {data['name']!r}")
    for key in data["reduced"]:
        check_name(key)
        if key not in data["published"]:
            raise CellError(
                f"configs/{name}.json: reduced key {key!r} has no published "
                f"value beside it")
    return data


def load_traffic(name: str, base: str | None = None) -> dict:
    data = _load("traffic", name, base)
    kind_module(f"traffic/{name}.json", data.get("kind"))
    return data


def load_cell(name: str, base: str | None = None) -> dict:
    """The cell with its configuration under ``config_data`` and its mix
    under ``traffic_data``."""
    cell = _load("workloads", name, base)
    kind = kind_module(f"workloads/{name}.json", cell.get("kind"))
    _check_keys(f"workloads/{name}.json", cell,
                CELL_KEYS | set(getattr(kind, "CELL_KEYS", ())),
                CELL_REQUIRED)
    if cell["name"] != name:
        raise CellError(f"workloads/{name}.json names itself "
                        f"{cell['name']!r}")
    if cell["chips"] not in (1, 4):
        raise CellError(f"workloads/{name}.json: chips is 1 or 4")
    cell = dict(cell)
    cell["config_data"] = load_config(cell["config"], base)
    cell["traffic_data"] = load_traffic(cell["traffic"], base)
    if cell["traffic_data"]["kind"] != cell["kind"]:
        raise CellError(
            f"workloads/{name}.json is of kind {cell['kind']!r}, its "
            f"traffic of kind {cell['traffic_data']['kind']!r}")
    return cell


def load_layer_metrics(cell_name: str, base: str | None = None) -> list:
    """Every per-layer metric whose file lists this cell, by name."""
    directory = os.path.join(base or BENCH_DIR, "layer_metrics")
    found = []
    for entry in sorted(os.listdir(directory)):
        if not entry.endswith(".json"):
            continue
        name = entry[:-len(".json")]
        data = _load("layer_metrics", name, base)
        _check_keys(f"layer_metrics/{entry}", data, METRIC_KEYS,
                    METRIC_REQUIRED)
        if data["name"] != name:
            raise CellError(f"layer_metrics/{entry} names itself "
                            f"{data['name']!r}")
        if data["source"] not in SOURCES:
            raise CellError(f"layer_metrics/{entry}: source must be one of "
                            f"{SOURCES}")
        if data["better"] not in ("lower", "higher"):
            raise CellError(f"layer_metrics/{entry}: better is lower|higher")
        for workload in data["workloads"]:
            check_name(workload)
        if cell_name in data["workloads"]:
            found.append(data)
    return found


def rehearsed(cell: dict) -> dict:
    """The cell at its rehearsal sizes (tiny widths for a CPU run that
    proves control flow and no number)."""
    over = cell.get("rehearsal") or {}
    out = dict(cell)
    out["config_data"] = {**cell["config_data"], **over.get("config", {})}
    out["traffic_data"] = {**cell["traffic_data"], **over.get("traffic", {})}
    for key in {"geometry", "check"} | set(over) - {"config", "traffic"}:
        out[key] = {**cell.get(key, {}), **over.get(key, {})}
    return out

