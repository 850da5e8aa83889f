import json
import os
import shutil

import pytest

from benchmarks.harness import cells, family_llama


def test_benchmark_json_lists_what_the_loader_finds():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    configs = {c["name"]: c for c in bench["configs"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    for workload in bench["workloads"]:
        cell = cells.load_cell(workload["name"])
        assert cell["config"] == workload["config"]
        assert cell["traffic"] == workload["traffic"]
        assert cell["chips"] == workload["chips"]
        assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
        config = configs[cell["config"]]
        assert config["file"] == f"benchmarks/configs/{cell['config']}.json"
        assert config["reduced"] == cell["config_data"]["reduced"]
        assert config["source"] == cell["config_data"]["source"]
        for metric in cells.load_layer_metrics(workload["name"]):
            entry = listed[metric["name"]]
            for key in ("unit", "better", "source", "layer", "moves",
                        "workloads"):
                assert entry[key] == metric[key], (metric["name"], key)
    found = {m["name"] for w in bench["workloads"]
             for m in cells.load_layer_metrics(w["name"])}
    assert found == set(listed)


@pytest.fixture()
def copy(tmp_path):
    base = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), base / sub)
    return base


def _edit(path, **changes):
    with open(path) as fp:
        data = json.load(fp)
    data.update(changes)
    with open(path, "w") as fp:
        json.dump(data, fp)


def test_a_new_cell_is_found_by_its_name(copy):
    shutil.copy(copy / "workloads" / "m7b-serve-chat.json",
                copy / "workloads" / "m7b-serve-other.json")
    _edit(copy / "workloads" / "m7b-serve-other.json",
          name="m7b-serve-other")
    cell = cells.load_cell("m7b-serve-other", base=str(copy))
    assert cell["config_data"]["hidden_size"] == 4096
    assert cells.load_layer_metrics("m7b-serve-other", base=str(copy)) == []


@pytest.mark.parametrize("where, change", [
    ("workloads/m7b-serve-chat.json", {"surprise": 1}),
    ("configs/mistral-7b-v0.3.json", {"hidden": 4096}),
    ("layer_metrics/step_mfu.serve.json", {"why": "x"}),
    ("workloads/m7b-serve-chat.json", {"chips": 2}),
    ("workloads/m7b-serve-chat.json", {"name": "other"}),
])
def test_unknown_keys_and_wrong_values_are_refused(copy, where, change):
    _edit(copy / where, **change)
    with pytest.raises(cells.CellError):
        cells.load_cell("m7b-serve-chat", base=str(copy))
        cells.load_layer_metrics("m7b-serve-chat", base=str(copy))


@pytest.mark.parametrize("name", ["a b", "a/b", "", "x" * 65, "-lead",
                                  "tab\t", "grμk"])
def test_names_outside_the_alphabet_are_refused(name):
    with pytest.raises(cells.CellError):
        cells.load_cell(name)


def test_rehearsal_changes_sizes_not_the_cell():
    cell = cells.load_cell("m7b-serve-chat")
    tiny = cells.rehearsed(cell)
    assert tiny["config_data"]["hidden_size"] < 4096
    assert cell["config_data"]["hidden_size"] == 4096
    fields = family_llama.llama_fields(cell["config_data"])
    assert fields["n_layers"] == 16 and fields["embed_dim"] == 4096
    assert fields["n_kv_heads"] == 8 and fields["rope_theta"] == 1e6
