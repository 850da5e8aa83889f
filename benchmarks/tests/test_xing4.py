"""The ``xing4`` family as added files: its configuration's keys, its costs
against hand counts, the comparison that decides a serve cell's ``correct``
on a hand-made sample that passes and on one for each planted fault, its
readers on hand-made ticks, and the cell's rehearsal end to end."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import (
    cells,
    costs_xing4,
    family_xing4,
    readers,
    readers_xing4,
    reference_xing4 as ref,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "xing4-serve-longdoc"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    small = cells.rehearsed(cell)
    return small, family_xing4.fields(small["config_data"])


@pytest.fixture()
def copy(tmp_path):
    base = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), base / sub)
    return base


def _edit(path, drop=(), **changes):
    with open(path) as fp:
        data = json.load(fp)
    data.update(changes)
    for key in drop:
        del data[key]
    with open(path, "w") as fp:
        json.dump(data, fp)


# -- the configuration --------------------------------------------------------
def test_the_cell_loads_with_its_family(cell):
    config = cell["config_data"]
    assert config["family"] == "xing4" and config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "first_k_dense_replace": 2}
    assert cell["server"] == {"prefill_chunk": 1024} and cell["chips"] == 1
    assert cell["geometry"] == {"page_size": 128, "slots": 32,
                                "max_len": 8192, "n_pages": 2048,
                                "max_new_tokens": 128}
    f = family_xing4.fields(config)
    assert f["n_layers"] == 7 and f["first_k_dense"] == 1
    assert f["embed_dim"] == 3584 and f["vocab_size"] == 131072
    assert (f["q_lora_rank"], f["kv_lora_rank"]) == (768, 512)
    assert (f["nope_dim"], f["rope_dim"], f["v_dim"]) == (128, 64, 128)
    assert (f["n_experts"], f["top_k"], f["n_shared_experts"]) == (64, 4, 1)
    assert f["routed_scale"] == 2.0 and f["hc_mult"] == 4
    assert f["hc_iters"] == 20 and f["hc_clamp"] == 30.0
    assert family_xing4.pad_length(cell) == 7936 + 128
    for key in ("hc_eps", "rope_pairing", "num_nextn_predict_layers",
                "hc_streams", "hc_sinkhorn_order", "torch_dtype"):
        assert key in config["assumed"], key
    assert "NOT instantiated" in config["assumed"][
        "num_nextn_predict_layers"]
    names = {m["name"] for m in cells.load_layer_metrics(CELL)}
    assert names == {
        "step_mfu.xing4", "mla_decode_roofline.xing4",
        "mla_decode_share.xing4", "mla_prefill_roofline.xing4",
        "mla_prefill_share.xing4", "moe_experts_roofline.xing4",
        "moe_experts_share.xing4", "decode_tick_p50_ms.xing4",
        "tick_host_share.xing4", "device_idle_share.xing4",
        "request_p50_ms.xing4", "prefill_share.xing4"}
    with pytest.raises(cells.CellError, match="served here"):
        family_xing4.train_model(f)
    config_object = family_xing4.preset(f)()
    assert config_object.latent_cache and config_object.n_moe_layers == 6
    assert round(config_object.param_count() * 2 / 1e9, 2) == 11.08


def test_the_mix_is_as_the_issue_gives_it(cell):
    from benchmarks.harness.traffic import prompt_lengths

    mix = cell["traffic_data"]
    assert mix["clients"] == 32 and mix["output_tokens"] == 128
    assert mix["shared_prefix_tokens"] == 0 and mix["ramp_seconds"] == 6.0
    lengths = prompt_lengths(mix["prompt_tokens"])
    assert len(lengths) == 32 and min(lengths) == 3148 \
        and max(lengths) == 7860 and sum(lengths) / 32 == 5504


def test_benchmark_json_lists_the_cell():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["file"] == \
        "benchmarks/configs/xing4.0-29b-a4b.json"
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("serve_tokens_per_s", "request_p95_ms"):
        assert by_name[name]["workloads"][-1] == CELL
    ours = [m for m in bench["per_layer"] if m["name"].endswith(".xing4")]
    assert len(ours) == 12
    assert all(m["workloads"] == [CELL] for m in ours)
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].endswith(".xing4")}
    assert {m["layer"] for m in ours} <= layers


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_number_is_as_the_catalog_has_it(cell):
    with open(CATALOG) as fp:
        rows = [json.loads(line) for line in fp if line.strip()]
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    config = cell["config_data"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


@pytest.mark.parametrize("drop, change", [
    ((), {"num_experts": 64}),                  # a key the family lacks
    (("kv_lora_rank",), {}),                    # a required one missing
    (("hc_mult",), {}),
    ((), {"reduced": ["num_hidden_layers", "n_routed_experts"]}),
    ((), {"family": "xing5"}),                  # a family with no module
])
def test_config_keys_are_refused(copy, drop, change):
    _edit(copy / "configs" / "xing4.0-29b-a4b.json", drop=drop, **change)
    with pytest.raises(cells.CellError):
        cells.load_cell(CELL, base=str(copy))


@pytest.mark.parametrize("change", [
    {"torch_dtype": "float16"}, {"scoring_func": "softmax"},
    {"n_group": 8}, {"rope_scaling": None}, {"moe_layer_freq": 2},
    {"mhc_h_res_clamp_min": -10}, {"tie_word_embeddings": True},
])
def test_what_the_program_does_not_run_is_refused(cell, change):
    with pytest.raises(cells.CellError):
        family_xing4.fields({**cell["config_data"], **change})


# -- costs, against hand counts -----------------------------------------------
def test_costs_against_hand_counts(cell):
    f = family_xing4.fields(cell["config_data"])
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                 + 512 * 32 * 256 + 4096 * 3584)
    assert costs_xing4.attention_params(f) == attention == 28_409_856
    assert costs_xing4.mixing_params(f) == 2 * 14336 * 24 == 688_128
    assert costs_xing4.expert_params(f) == 3 * 3584 * 1024 == 11_010_048
    assert costs_xing4.expert_bytes(f) == 22_020_096            # 22.02 MB
    active = (7 * (attention + 688_128) + 3 * 3584 * 9216
              + 6 * (3584 * 64 + 5 * 11_010_048))
    assert costs_xing4.active_params(f) == active
    assert costs_xing4.latent_row_bytes(f) == 1152
    assert costs_xing4.expanded_pair_flops(f) == 2 * 32 * 320
    assert costs_xing4.absorbed_pair_flops(f) == 2 * 32 * (576 + 512)
    decode = costs_xing4.mla_decode_call(f, rows=32, tokens=32 * 5000.0)
    assert decode["flops"] == 2 * 32 * 1088 * 160000.0
    assert decode["bytes"] == 1152 * 160000.0 + 32 * 32 * 1088 * 2
    # about 60 FLOPs a byte: under the chip's ridge, so memory bounds it
    assert 55 < decode["flops"] / decode["bytes"] < 61
    pairs = 1024 * 2048 + 1024 * 1025 / 2      # a chunk after two others
    chunk = costs_xing4.mla_prefill_call(f, tokens=1024, pairs=pairs)
    assert chunk["flops"] == 2 * 32 * 320 * pairs
    assert chunk["bytes"] == 1152 * 3072 + 1024 * 32 * 320 * 2
    experts = costs_xing4.moe_experts_call(f, pairs=4096 * 6, touched=384)
    assert experts["flops"] == 2 * 11_010_048 * 24576
    assert experts["bytes"] == 22_020_096 * 384


def test_request_flops_by_hand(tiny):
    _small, f = tiny
    layers = 2 * costs_xing4.active_params(f)
    head = 2 * f["embed_dim"] * f["vocab_size"]
    expanded, absorbed = costs_xing4.expanded_pair_flops(f), \
        costs_xing4.absorbed_pair_flops(f)
    # a prompt of 3 and 2 output tokens: 4 positions are fed, the prompt's
    # attend 1 + 2 + 3 keys expanded, the one decoded position 4 absorbed
    want = 4 * layers + f["n_layers"] * (6 * expanded + 4 * absorbed) \
        + 2 * head
    assert costs_xing4.serve_request_flops(f, 3, 2) == want
    assert costs_xing4.serve_request_flops(f, 3, 1) == \
        3 * layers + f["n_layers"] * 6 * expanded + head


# -- correct, on a hand-made sample -------------------------------------------
@pytest.fixture(scope="module")
def sample(tiny):
    """Two requests whose tokens are the reference's own greedy choices."""
    small, f = tiny
    weights = ref.make_weights(f, 0)
    rng = np.random.default_rng(5)
    records = []
    for index, length in enumerate((40, 25)):
        prompt = rng.integers(1, f["vocab_size"], length).tolist()
        tokens = []
        for _ in range(6):
            logits = ref.forward(f, weights, prompt + tokens)
            tokens.append(int(np.asarray(logits[-1]).argmax()))
        records.append({"index": index, "prompt": prompt, "tokens": tokens})
    return records


def _check(tiny, sample, **limits):
    small, f = tiny
    cell = {**small, "check": {"sample_requests": 2, "limits": {
        "served_logit_gap_max": 0.05, "served_logit_gap_mean": 0.01,
        "served_logit_gap_p50": 0.01, "served_logit_gap_over_half": 0.05,
        **limits}}}
    return family_xing4.serve_check(cell, f, sample)


def test_a_sound_sample_is_correct(tiny, sample):
    compared = _check(tiny, sample)
    assert set(compared) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "served_logit_gap_p50", "served_logit_gap_over_half"}
    assert set(compared) <= set(family_xing4.READ)
    assert all(entry["ok"] for entry in compared.values())
    assert compared["served_logit_gap_max"]["served_tokens"] == 12
    assert compared["served_logit_gap_max"]["value"] < 1e-4
    assert compared["served_logit_gap_max"]["reference_s"] > 0


def test_an_altered_token_fails_the_widest_gap(tiny, sample):
    _small, f = tiny
    compared = _check(tiny, family_xing4.altered_token(f, sample))
    assert not compared["served_logit_gap_max"]["ok"]
    assert "request 1 token 5" == compared["served_logit_gap_max"]["where"]


def test_only_the_limits_named_are_compared(tiny, sample):
    small, f = tiny
    cell = {**small, "check": {"sample_requests": 2, "limits": {
        "served_logit_gap_mean": 0.01}}}
    assert set(family_xing4.serve_check(cell, f, sample)) == {
        "served_logit_gap_mean"}
    empty = family_xing4.serve_check(cell, f, [])
    assert not empty["served_logit_gap_mean"]["ok"]


def test_readings_name_the_control_and_every_fault(tiny, sample):
    small, f = tiny
    got = list(family_xing4.serve_readings(small, f, [sample, sample], 1))
    assert len(got) == 2 and "control_int8_served_logit_gap_max" not in \
        got[1]
    first = got[0]
    assert first["program_served_logit_gap_max"] < 1e-4
    for fault in ref.FAULTS[1:] + ("control_int8", "altered_token"):
        for key in family_xing4.READ:
            assert f"{fault}_{key}" in first
    # each fault of the reference, and the control, moves the mean gap of
    # the reference's own tokens off zero
    moved = [name for name in ref.FAULTS[1:] + ("control_int8",)
             if first[f"{name}_served_logit_gap_mean"] > 1e-3]
    assert len(moved) >= 5, first


# -- the readers, on hand-made ticks ------------------------------------------
def _tick(**over):
    record = {"t0": 1.0, "t1": 1.1, "rows": 2, "kind": "plain",
              "prefill_tokens": 0, "prefill_ctx_tokens": 0,
              "ctx_tokens": 100, "expert_pairs": 8, "experts_touched": 6,
              "expert_load_max": 3}
    return {**record, **over}


def test_readers_on_hand_made_ticks(tiny):
    small, f = tiny
    chunk = 64
    pairs = chunk * chunk + chunk * (chunk + 1) // 2   # the second chunk
    ticks = [_tick(),
             _tick(t0=1.2, t1=1.3, prefill_tokens=chunk,
                   prefill_ctx_tokens=pairs, expert_pairs=8 + 256,
                   experts_touched=6 + 16),
             _tick(t0=1.4, t1=1.5, rows=0, expert_pairs=0)]
    ctx = {"ticks": ticks, "traced": (0.0, 2.0), "fields": f,
           "costs": costs_xing4, "cell": small}
    for reader, pattern in ((readers_xing4.mla_decode_roofline, "mla_p"),
                            (readers_xing4.mla_prefill_roofline, "mla_f"),
                            (readers_xing4.experts_roofline, "^gmm")):
        assert reader(ctx, pattern=pattern) is None     # no trace, no peak
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    layers, moe_layers = f["n_layers"], f["n_layers"] - f["first_k_dense"]
    trace = {"op_seconds": {"mla_paged_decode": 0.5, "mla_flash": 0.25,
                            "gmm": 1.0, "fusion": 0.25},
             "op_counts": {"mla_paged_decode": 2 * layers,
                           "mla_flash": 2 * layers,
                           "gmm": 3 * moe_layers * 3}, "busy_s": 2.0}
    full = {**ctx, "trace": trace, "peak": peak}

    def least(call):
        return max(call["flops"] / 1e9, call["bytes"] / 1e6)

    decode = costs_xing4.mla_decode_call(f, 2, 100)
    assert readers_xing4.mla_decode_roofline(
        full, pattern="mla_paged_decode") == pytest.approx(
            100.0 * layers * 2 * least(decode) / 0.5)
    prefill = costs_xing4.mla_prefill_call(f, chunk, pairs)
    # the second chunk of 64 runs the kernel over two blocks a layer
    assert readers_xing4.mla_prefill_roofline(
        full, pattern="mla_flash") == pytest.approx(
            100.0 * layers * least(prefill) / 0.25)
    experts = [costs_xing4.moe_experts_call(f, 8, 6),
               costs_xing4.moe_experts_call(f, 264, 22)]
    assert readers_xing4.experts_roofline(
        full, pattern="^gmm") == pytest.approx(
            100.0 * sum(least(c) for c in experts) / 1.0)
    assert readers.op_share(full, pattern="mla_flash") == 12.5
    # a log and a trace that do not describe the same interval: nothing
    trace["op_counts"]["gmm"] = 3 * moe_layers * 9
    trace["op_counts"]["mla_paged_decode"] = 9 * layers
    assert readers_xing4.experts_roofline(full, pattern="^gmm") is None
    assert readers_xing4.mla_decode_roofline(
        full, pattern="mla_paged_decode") is None


# -- the cell's rehearsal, end to end -----------------------------------------
def test_rehearsal_exits_3_with_a_whole_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MLT_ATTN_INTERPRET="1")
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483903",
         "--seconds", "4", "--trace", "1", "--rehearse", "1"],
        env=env, cwd=cells.ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3 and done.stdout == ""
    line = json.loads(next(
        text for text in reversed(done.stderr.splitlines())
        if text.startswith('{"correct"')))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and list(line)[-1] == "compared"
    assert set(line["compared"]) == {
        "served_logit_gap_mean", "served_logit_gap_p50",
        "served_logit_gap_over_half", "compiles_in_window",
        "failed_requests"}
    assert line["compared"]["served_logit_gap_mean"]["served_tokens"] == 16
    # no chip: no time, no share of a peak; the ledger's own are there
    assert "request_p50_ms.xing4" in line["metrics"]
    assert "step_mfu.xing4" not in line["metrics"]
    assert "mla_decode_roofline.xing4" not in line["metrics"]
