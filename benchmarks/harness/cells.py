"""Finds a cell's data files by the names in them, and refuses what it
does not know.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
every ``layer_metrics/*.json`` whose ``workloads`` lists the cell belongs to
it. A later PR adds a cell, a configuration, a mix or a metric as a new file
and edits none that is here.
"""

from __future__ import annotations

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
CONFIG_REQUIRED = {"name", "source", "reduced", "assumed", "deployment",
                   "published", "hidden_size", "intermediate_size",
                   "num_attention_heads", "num_key_value_heads", "head_dim",
                   "num_hidden_layers", "vocab_size", "rope_theta",
                   "rms_norm_eps", "tie_word_embeddings", "torch_dtype"}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "max_position_embeddings", "hidden_act", "sliding_window",
    "weights", "precision"}
METRIC_KEYS = {"name", "layer", "unit", "better", "source", "moves",
               "workloads", "reader", "module", "args", "what"}
METRIC_REQUIRED = METRIC_KEYS - {"module", "args"}
KINDS = ("serve", "train")
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


def _check_keys(what: str, data: dict, allowed: set, required: set):
    unknown = sorted(set(data) - allowed)
    if unknown:
        raise CellError(f"{what}: unknown key(s) {unknown}")
    missing = sorted(required - set(data))
    if missing:
        raise CellError(f"{what}: missing key(s) {missing}")


def load_config(name: str, base: str | None = None) -> dict:
    data = _load("configs", name, base)
    _check_keys(f"configs/{name}.json", data, CONFIG_KEYS, CONFIG_REQUIRED)
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
    if data.get("kind") not in KINDS:
        raise CellError(f"traffic/{name}.json: kind must be one of {KINDS}")
    return data


def load_cell(name: str, base: str | None = None) -> dict:
    """The cell with its configuration under ``config_data`` and its mix
    under ``traffic_data``."""
    cell = _load("workloads", name, base)
    _check_keys(f"workloads/{name}.json", cell, CELL_KEYS, CELL_REQUIRED)
    if cell["name"] != name:
        raise CellError(f"workloads/{name}.json names itself "
                        f"{cell['name']!r}")
    if cell["kind"] not in KINDS:
        raise CellError(f"workloads/{name}.json: kind must be one of {KINDS}")
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
    out["geometry"] = {**cell.get("geometry", {}),
                       **over.get("geometry", {})}
    out["check"] = {**cell["check"], **over.get("check", {})}
    return out


def llama_fields(config: dict) -> dict:
    """The published keys under the names ``models/llama.LlamaConfig``
    takes (dtype stays the dataclass's default, bfloat16)."""
    if config["torch_dtype"] != "bfloat16":
        raise CellError("only bfloat16 configurations run here")
    return {
        "vocab_size": int(config["vocab_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "embed_dim": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "mlp_dim": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
    }
