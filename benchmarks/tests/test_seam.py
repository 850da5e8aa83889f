"""The family seam: a configuration of another model family, a cell of
another kind, its ``correct`` by the family's own comparison and a metric
over a new reader module come as added files alone (``data/seam``), what
the loader does not know is still refused, and the two cells that are here
load to what they loaded to before the seam."""

import argparse
import json
import os
import shutil
import sys
import types

import pytest

from benchmarks import harness
from benchmarks.harness import cells, family_llama, readers, serve

HERE = os.path.dirname(os.path.abspath(__file__))
SEAM = os.path.join(HERE, "data", "seam")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TOY_MODULES = ("family_toy", "reference_toy", "costs_toy", "readers_toy",
               "toy")


def _tree(directory) -> dict:
    return {os.path.relpath(os.path.join(base, name), directory):
            os.path.getmtime(os.path.join(base, name))
            for base, _dirs, names in os.walk(directory) for name in names
            if not name.endswith(".pyc")}


@pytest.fixture()
def toy(tmp_path, monkeypatch):
    """The toy modules where ``benchmarks.harness`` finds them by name, with
    no file under ``benchmarks/harness/`` written; a copy of the toy data
    files as ``base``."""
    before = _tree(os.path.dirname(harness.__file__))
    monkeypatch.setattr(harness, "__path__", list(harness.__path__)
                        + [os.path.join(SEAM, "harness")])
    base = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(SEAM, "base"), base)
    yield base
    for name in TOY_MODULES:
        sys.modules.pop(f"benchmarks.harness.{name}", None)
        if hasattr(harness, name):
            delattr(harness, name)
    assert _tree(os.path.dirname(harness.__file__)) == before


def _edit(path, drop=(), **changes):
    with open(path) as fp:
        data = json.load(fp)
    data.update(changes)
    for key in drop:
        del data[key]
    with open(path, "w") as fp:
        json.dump(data, fp)


def _args(seed=7):
    return argparse.Namespace(seed=seed, seconds=2.0, trace=1, rehearse=1)


# -- (a) another family, another kind, from added files ------------------------
def test_another_family_and_kind_load_run_and_are_read(toy):
    cell = cells.load_cell("toy-cell", base=str(toy))
    assert cell["config_data"]["family"] == "toy"
    assert cell["config_data"]["num_experts"] == 8
    assert cell["steps_per_block"] == 3      # a key the kind declares
    family = cells.family_of(cell["config_data"])
    assert family.__name__ == "benchmarks.harness.family_toy"
    fields = family.fields(cell["config_data"])
    assert fields["experts_per_token"] == 2 and fields["block"] == 4
    metrics = cells.load_layer_metrics("toy-cell", base=str(toy))
    assert [m["name"] for m in metrics] == ["step_mfu.toy",
                                            "steps_per_block.toy"]

    kind = cells.kind_module("workloads/toy-cell.json", cell["kind"])
    assert kind.__name__ == "benchmarks.harness.toy"
    line = json.loads(kind.run(cell, metrics, _args(), DEVICE, 0.0))
    assert line["correct"] is True and line["attempted"] == 3
    assert line["compared"]["step_score_gap_max"] == {
        "value": 0.0, "limit": 0.0, "ok": True}
    assert list(line)[-1] == "compared"
    # a reader module of its own, and the reader that is here over the
    # family's own cost function: 3 requests of 5 + 8 tokens, 2 layers, 2 of
    # 8 experts of 3 x 64 x 32, over 2 s at the toy's peak of 1e9
    assert line["metrics"]["steps_per_block.toy"] == {
        "value": 3.0, "unit": "steps"}
    flops = 3 * 13 * 2 * 2 * (3 * 64 * 32 * 2)
    assert line["metrics"]["step_mfu.toy"]["value"] == pytest.approx(
        100.0 * flops / 2.0 / 1e9)


def test_the_familys_own_comparison_decides_correct(toy, monkeypatch):
    cell = cells.load_cell("toy-cell", base=str(toy))
    kind = cells.kind_module("workloads/toy-cell.json", cell["kind"])
    sound = kind.program

    def altered(cell, fields, seed):
        finished = sound(cell, fields, seed)
        finished[1]["scores"][2] += 0.25     # a score altered where produced
        return finished

    monkeypatch.setattr(kind, "program", altered)
    line = json.loads(kind.run(cell, [], _args(), DEVICE, 0.0))
    assert line["correct"] is False
    assert line["compared"]["step_score_gap_max"]["value"] \
        == pytest.approx(0.25)


def test_rehearsal_reaches_a_kinds_own_keys(toy):
    _edit(toy / "workloads" / "toy-cell.json",
          rehearsal={"steps_per_block": {"x": 1}})
    _edit(toy / "workloads" / "toy-cell.json", steps_per_block={"y": 2})
    tiny = cells.rehearsed(cells.load_cell("toy-cell", base=str(toy)))
    assert tiny["steps_per_block"] == {"y": 2, "x": 1}
    assert tiny["geometry"] == {}


# -- (b) what the loader does not know is still refused ------------------------
def test_without_its_family_the_configuration_is_refused(toy):
    _edit(toy / "configs" / "toy-moe.json", drop=["family"])
    with pytest.raises(cells.CellError) as refused:
        cells.load_cell("toy-cell", base=str(toy))
    for key in ("num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "norm_topk_prob", "block_length"):
        assert key in str(refused.value)


def test_a_family_without_a_module_is_refused_by_the_files_name(toy):
    _edit(toy / "configs" / "toy-moe.json", family="nosuch")
    with pytest.raises(cells.CellError) as refused:
        cells.load_cell("toy-cell", base=str(toy))
    assert "benchmarks/harness/family_nosuch.py" in str(refused.value)
    assert "configs/toy-moe.json" in str(refused.value)


@pytest.fixture()
def copy(tmp_path):
    base = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), base / sub)
    return base


@pytest.mark.parametrize("where, change, named", [
    ("configs/mistral-7b-v0.3.json", {"num_experts": 8}, "num_experts"),
    ("configs/mistral-7b-v0.3.json", {"family": "llama", "block_length": 4},
     "block_length"),
    ("configs/mistral-7b-v0.3.json", {"family": "a b"}, "name 'a b'"),
    ("workloads/m7b-serve-chat.json", {"steps_per_block": 3},
     "steps_per_block"),
    ("workloads/m7b-serve-chat.json", {"kind": "nosuch"},
     "benchmarks/harness/nosuch.py"),
    ("workloads/m7b-serve-chat.json", {"kind": "train"}, "traffic of kind"),
    ("workloads/m7b-serve-chat.json", {"kind": "costs"}, "no run()"),
    ("traffic/chat-closed32.json", {"kind": "nosuch"},
     "benchmarks/harness/nosuch.py"),
    ("traffic/chat-closed32.json", {"kind": "a.b"},
     "benchmarks/harness/a.b.py"),
])
def test_strictness_is_kept(copy, where, change, named):
    _edit(copy / where, **change)
    with pytest.raises(cells.CellError) as refused:
        cells.load_cell("m7b-serve-chat", base=str(copy))
    assert named in str(refused.value)


def test_a_missing_required_key_and_an_unpublished_cut_are_refused(copy):
    _edit(copy / "configs" / "mistral-7b-v0.3.json", drop=["head_dim"])
    with pytest.raises(cells.CellError, match="missing.*head_dim"):
        cells.load_config("mistral-7b-v0.3", base=str(copy))
    _edit(copy / "configs" / "mistral-nemo-12b.json",
          reduced=["num_hidden_layers", "vocab_size"])
    with pytest.raises(cells.CellError, match="vocab_size.*no published"):
        cells.load_config("mistral-nemo-12b", base=str(copy))


def test_a_module_that_is_there_and_imports_what_is_not_raises_as_it_is(
        toy, tmp_path, monkeypatch):
    broken = tmp_path / "more"
    broken.mkdir()
    (broken / "family_broken.py").write_text("import no_such_package_xyz\n")
    monkeypatch.setattr(harness, "__path__",
                        list(harness.__path__) + [str(broken)])
    with pytest.raises(ModuleNotFoundError, match="no_such_package_xyz"):
        cells.family_of({"name": "x", "family": "broken"})


# -- (c) the cells that are here load to what they loaded to -------------------
@pytest.mark.parametrize("name", ["m7b-serve-chat", "nemo-train-lora"])
def test_the_cells_load_to_what_they_did_on_the_parent(name):
    """``data/parent_cells.json`` is ``cells.load_cell`` of both cells as
    the commit before the seam (6e0bc82) printed it."""
    with open(os.path.join(HERE, "data", "parent_cells.json")) as fp:
        parent = json.load(fp)
    cell = cells.load_cell(name)
    assert cell == parent[name]
    assert "family" not in cell["config_data"]
    assert cells.family_of(cell["config_data"]) is family_llama
    assert family_llama.fields is family_llama.llama_fields
    assert family_llama.costs.__name__ == "benchmarks.harness.costs"
    assert family_llama.reference.__name__ == "benchmarks.harness.reference"


# -- what a serve cell hands the server and keeps of the answer ----------------
class _Graph:
    def __init__(self):
        self.added = None

    def set_topology(self, _topology):
        pass

    def add_model(self, name, **kw):
        self.added = (name, kw)
        return types.SimpleNamespace(object=types.SimpleNamespace(engine=0))

    def to_mock_server(self):
        return None


PARENT_ADD_MODEL = {
    "class_name": "mlrun_tpu.serving.llm.LLMModelServer",
    "model_preset": "mistral-7b-v0.3", "continuous_batching": True,
    "paged": True, "page_size": 128, "slots": 32, "max_len": 2048,
    "n_pages": 512, "warmup": True, "max_new_tokens": 128}


@pytest.mark.parametrize("server, request_extra", [
    ({}, {}),
    ({"prefix_cache": False, "speculative": {"k": 4}},
     {"block_steps": 3, "return_scores": True}),
])
def test_serve_hands_server_and_request_on_and_keeps_the_answer(
        monkeypatch, server, request_extra):
    import mlrun_tpu
    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

    graph = _Graph()
    monkeypatch.setattr(mlrun_tpu, "new_function", lambda *a, **kw: graph)
    monkeypatch.delitem(MODEL_PRESETS, "mistral-7b-v0.3", raising=False)
    cell = cells.load_cell("m7b-serve-chat")
    if server:
        cell = dict(cell, server=server, request=request_extra)
    serving = serve.ServeCell(cell)
    serving.build()
    assert graph.added == ("llm", {**PARENT_ADD_MODEL, **server})
    config = MODEL_PRESETS.pop("mistral-7b-v0.3")(n_layers=3)
    assert type(config).__name__ == "LlamaConfig"
    assert (config.n_layers, config.embed_dim, config.n_kv_heads) \
        == (3, 4096, 8)

    sent = []
    answer = {"outputs": [[5, 6]], "timing": [{"wall_s": 0.1}],
              "step_scores": [[0.5, 0.25]]}
    serving.server = types.SimpleNamespace(
        test=lambda path, body: sent.append((path, body)) or answer)
    record = serving.request([1, 2, 3])
    assert sent == [("/v2/models/llm/infer",
                     {**request_extra, "inputs": [[1, 2, 3]],
                      "timing": True})]
    assert list(sent[0][1])[-2:] == ["inputs", "timing"]
    assert record["tokens"] == [5, 6] and record["timing"] == {"wall_s": 0.1}
    assert record["body"] is answer


def test_a_server_key_that_the_geometry_sets_is_refused(monkeypatch):
    import mlrun_tpu

    monkeypatch.setattr(mlrun_tpu, "new_function",
                        lambda *a, **kw: _Graph())
    cell = dict(cells.load_cell("m7b-serve-chat"), server={"slots": 8})
    with pytest.raises(cells.CellError, match="slots"):
        serve.ServeCell(cell).build()


def test_serve_cell_keys_are_the_kinds_to_allow(copy):
    _edit(copy / "workloads" / "m7b-serve-chat.json",
          server={"prefix_cache": False}, request={"block_steps": 3})
    cell = cells.load_cell("m7b-serve-chat", base=str(copy))
    assert cell["server"] == {"prefix_cache": False}
    _edit(copy / "workloads" / "nemo-train-lora.json", server={})
    with pytest.raises(cells.CellError, match="server"):
        cells.load_cell("nemo-train-lora", base=str(copy))


# -- readers reckon with the cell's family -------------------------------------
def test_readers_take_the_cost_functions_from_the_context():
    fields = family_llama.fields(cells.load_config("mistral-7b-v0.3"))
    finished = [{"prompt": [1] * 10, "tokens": [2] * 4}]
    ctx = {"fields": fields, "chips": 1, "window_s": 2.0,
           "finished": finished, "peak": {"bf16_flops_per_s": 1e12}}
    own = readers.window_mfu(ctx, cost="serve_requests")
    assert own == pytest.approx(
        100.0 * family_llama.costs.serve_request_flops(fields, 10, 4)
        / 2.0 / 1e12)
    other = types.SimpleNamespace(serve_request_flops=lambda f, p, o: 5e11)
    assert readers.window_mfu(dict(ctx, costs=other),
                              cost="serve_requests") == pytest.approx(25.0)
