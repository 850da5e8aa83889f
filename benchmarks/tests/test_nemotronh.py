"""The ``nemotronh`` family as added files: its configuration's keys and the
fields made of them (the router's width from ``published``, the experts
held, the pattern cut with the depth), its costs against hand counts, the
comparison that decides a serve cell's ``correct`` on a hand-made sample
that passes and on the controls and planted faults, its readers on
hand-made ticks, and the cell's rehearsal end to end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import (
    cells,
    costs_nemotronh,
    family_nemotronh,
    readers,
    readers_nemotronh,
    reference_nemotronh as ref,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron-serve-chat128"
METRICS = {
    "ssm_decode_roofline.nemotron", "ssm_decode_share.nemotron",
    "ssd_prefill_roofline.nemotron", "ssd_prefill_share.nemotron",
    "moe_experts_roofline.nemotron", "moe_experts_share.nemotron",
    "paged_decode_share.nemotron", "step_mfu.nemotron",
    "decode_tick_p50_ms.nemotron", "tick_host_share.nemotron",
    "tick_rows_mean.nemotron", "device_idle_share.nemotron",
    "request_p50_ms.nemotron", "prefill_share.nemotron"}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    small = cells.rehearsed(cell)
    return small, family_nemotronh.fields(small["config_data"])


# -- the configuration --------------------------------------------------------
def test_the_cell_loads_with_its_family(cell):
    config = cell["config_data"]
    assert config["family"] == "nemotronh"
    assert set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["n_routed_experts"] == 128
    assert config["published"]["vocab_size"] == 131072
    assert cell["chips"] == 1 and "server" not in cell
    assert cell["geometry"] == {"page_size": 128, "slots": 128,
                                "max_len": 1280, "n_pages": 1280,
                                "max_new_tokens": 256}
    assert cell["check"]["sample_requests"] == 8
    f = family_nemotronh.fields(config)
    # the router keeps the published width, 16 experts are held, and the
    # pattern is cut with the depth
    assert f["n_experts"] == 128 and f["experts_held"] == (0, 16)
    assert f["top_k"] == 6 and f["routed_scale"] == 2.5
    assert f["n_layers"] == 26 and f["pattern"] == \
        config["published"]["hybrid_override_pattern"][:26]
    assert [costs_nemotronh.kind_layers(f, k)
            for k in ("ssm", "mlp", "attn")] == [12, 11, 3]
    assert f["embed_dim"] == 2688 and f["vocab_size"] == 16384
    assert (f["n_heads"], f["n_kv_heads"], f["head_dim"]) == (32, 2, 128)
    assert (f["ssm_heads"], f["ssm_head_dim"], f["ssm_groups"],
            f["ssm_state"], f["conv_kernel"], f["chunk_size"]) == \
        (64, 64, 8, 128, 4, 128)
    assert (f["expert_dim"], f["shared_dim"]) == (1856, 3712)
    assert family_nemotronh.pad_length(cell) == 1024 + 256
    for key in ("state_dtype", "no_rotary", "column_order", "d_inner",
                "time_step_limit", "seeded_parameters", "torch_dtype"):
        assert key in config["assumed"], key
    assert {m["name"] for m in cells.load_layer_metrics(CELL)} == METRICS
    with pytest.raises(cells.CellError, match="served here"):
        family_nemotronh.train_model(f)
    config_object = family_nemotronh.preset(f)()
    assert config_object.recurrent_state
    assert (config_object.cache_layers, config_object.state_layers) == (3, 12)
    assert round(config_object.param_count() * 2 / 1e9, 2) == 5.21


def test_the_mix_is_as_the_issue_gives_it(cell):
    from benchmarks.harness.traffic import prompt_lengths

    mix = cell["traffic_data"]
    assert mix["clients"] == 128 and mix["output_tokens"] == 256
    assert mix["shared_prefix_tokens"] == 0 and mix["ramp_seconds"] == 6.0
    assert mix["drain_seconds"] == 60.0
    lengths = prompt_lengths(mix["prompt_tokens"])
    assert len(lengths) == 128 and min(lengths) >= 32 \
        and max(lengths) == 1024
    assert sorted(lengths)[64] in range(195, 206)       # median 200


def test_benchmark_json_lists_the_cell():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    assert CELL in [w["name"] for w in bench["workloads"]]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["file"] == "benchmarks/configs/nemotron-3-nano-30b-a3b.json"
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("serve_tokens_per_s", "request_p95_ms"):
        assert CELL in by_name[name]["workloads"]
    ours = [m for m in bench["per_layer"] if m["name"].endswith(".nemotron")]
    assert {m["name"] for m in ours} == METRICS
    assert all(m["workloads"] == [CELL] for m in ours)
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].endswith(".nemotron")}
    assert {m["layer"] for m in ours} <= layers
    assert all(len(e["why"]) <= 200
               for e in bench["configs"] + bench["workloads"])


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_number_is_as_the_catalog_has_it(cell):
    with open(CATALOG) as fp:
        rows = [json.loads(line) for line in fp if line.strip()]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    config = cell["config_data"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


@pytest.mark.parametrize("change", [
    {"torch_dtype": "float16"}, {"n_group": 8}, {"mlp_hidden_act": "silu"},
    {"tie_word_embeddings": True}, {"mlp_bias": True},
    {"hybrid_override_pattern": "MEMEM*"}, {"n_shared_experts": 2},
])
def test_what_the_program_does_not_run_is_refused(cell, change):
    with pytest.raises(cells.CellError):
        family_nemotronh.fields({**cell["config_data"], **change})


# -- costs, against hand counts -----------------------------------------------
def test_costs_against_hand_counts(cell):
    f = family_nemotronh.fields(cell["config_data"])
    assert costs_nemotronh.d_inner(f) == 4096
    assert costs_nemotronh.state_elements(f) == 64 * 64 * 128
    assert costs_nemotronh.ssm_params(f) == \
        2688 * 10304 + 4096 * 2688 == 38_707_200
    assert costs_nemotronh.attention_params(f) == \
        2 * 2688 * 4096 + 2 * 2688 * 256 == 23_396_352
    assert costs_nemotronh.expert_params(f) == 2 * 2688 * 1856 == 9_977_856
    assert costs_nemotronh.expert_bytes(f) == 19_955_712        # 19.96 MB
    assert costs_nemotronh.held_share(f) == 0.125
    active = (12 * 38_707_200 + 3 * 23_396_352
              + 11 * (2688 * 128 + 2 * 2688 * 3712 + 6 * 0.125 * 9_977_856))
    assert costs_nemotronh.active_params(f) == active
    assert costs_nemotronh.recurrence_flops(f) == 3_145_728     # 3.1 MFLOP
    decode = costs_nemotronh.ssm_decode_call(f, rows=128)
    assert decode["flops"] == 128 * 3_145_728
    per_row = 2 * 2_097_152 + (4096 + 2048) * 2 + 64 * 4 + 4096 * 4
    assert decode["bytes"] == 128 * per_row
    # under 1 FLOP a byte: memory bounds it
    assert decode["flops"] / decode["bytes"] < 1.0
    prefill = costs_nemotronh.ssd_prefill_call(f, tokens=200, calls=1)
    per_token = 8 * 2 * 64 * 128 + 64 * 2 * 64 * 64 + 64 * 4 * 128 * 64
    assert prefill["flops"] == per_token * 200
    assert prefill["bytes"] == 200 * ((4096 + 2048) * 2 + 64 * 4 + 4096 * 4) \
        + 2 * 2_097_152
    experts = costs_nemotronh.moe_experts_call(f, pairs=96 * 11,
                                               touched=16 * 11)
    assert experts["flops"] == 2 * 9_977_856 * 1056
    assert experts["bytes"] == 19_955_712 * 176
    paged = costs_nemotronh.paged_decode_call(f, rows=128, context=300)
    assert paged["bytes"] == 2 * 2 * 128 * 2 * 38400 + 2 * 128 * 4096 * 2


def test_request_flops_by_hand(tiny):
    _small, f = tiny
    layers = 2 * costs_nemotronh.active_params(f) \
        + 2 * costs_nemotronh.recurrence_flops(f)           # two M layers
    head = 2 * f["embed_dim"] * f["vocab_size"]
    pair = costs_nemotronh.attention_pair_flops(f)          # one * layer
    # a prompt of 3 and 2 output tokens: 4 positions are fed and attend
    # 1 + 2 + 3 + 4 keys
    assert costs_nemotronh.serve_request_flops(f, 3, 2) == \
        4 * layers + 10 * pair + 2 * head
    assert costs_nemotronh.serve_request_flops(f, 3, 1) == \
        3 * layers + 6 * pair + head


# -- correct, on a hand-made sample -------------------------------------------
@pytest.fixture(scope="module")
def sample(tiny):
    """Two requests whose tokens are the reference's own greedy choices."""
    _small, f = tiny
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
    return family_nemotronh.serve_check(cell, f, sample)


def test_a_sound_sample_is_correct(tiny, sample):
    compared = _check(tiny, sample)
    assert set(compared) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "served_logit_gap_p50", "served_logit_gap_over_half"}
    assert all(entry["ok"] for entry in compared.values())
    assert compared["served_logit_gap_max"]["served_tokens"] == 12
    assert compared["served_logit_gap_max"]["value"] < 1e-4


def test_an_altered_token_fails_the_widest_gap(tiny, sample):
    _small, f = tiny
    compared = _check(tiny, family_nemotronh.altered_token(f, sample))
    assert not compared["served_logit_gap_max"]["ok"]
    assert "request 1 token 5" == compared["served_logit_gap_max"]["where"]


def test_only_the_limits_named_are_compared(tiny, sample):
    small, f = tiny
    cell = {**small, "check": {"sample_requests": 2, "limits": {
        "served_logit_gap_mean": 0.01}}}
    assert set(family_nemotronh.serve_check(cell, f, sample)) == {
        "served_logit_gap_mean"}
    empty = family_nemotronh.serve_check(cell, f, [])
    assert not empty["served_logit_gap_mean"]["ok"]


def test_readings_name_the_controls_and_every_fault(tiny, sample):
    small, f = tiny
    got = list(family_nemotronh.serve_readings(small, f, [sample, sample],
                                               1))
    assert len(got) == 2 and "control_int8_served_logit_gap_max" not in \
        got[1]
    first = got[0]
    assert first["program_served_logit_gap_max"] < 1e-4
    names = ref.FAULTS[1:] + ("control_int8", "control_bf16_state",
                              "altered_token")
    for name in names:
        for key in family_nemotronh.READ:
            assert f"{name}_{key}" in first
    # the faults of the reference and the int8 control move the gaps of the
    # reference's own tokens off zero
    moved = [name for name in ref.FAULTS[1:] + ("control_int8",)
             if first[f"{name}_served_logit_gap_max"] > 1e-3]
    assert len(moved) >= 7, first


# -- the readers, on hand-made ticks ------------------------------------------
def _tick(**over):
    record = {"t0": 1.0, "t1": 1.1, "rows": 3, "kind": "plain",
              "prefill_tokens": 0, "prefill_ctx_tokens": 0,
              "prefill_dispatches": 0, "state_rows": 3, "state_tokens": 0,
              "ctx_tokens": 100, "expert_pairs": 8, "experts_touched": 6,
              "expert_load_max": 3}
    return {**record, **over}


def test_readers_on_hand_made_ticks(tiny):
    small, f = tiny
    ticks = [_tick(),
             _tick(t0=1.2, t1=1.3, prefill_tokens=70, state_tokens=70,
                   prefill_dispatches=2, expert_pairs=8 + 64,
                   experts_touched=6 + 8),
             _tick(t0=1.4, t1=1.5, rows=0, state_rows=0, expert_pairs=0)]
    ctx = {"ticks": ticks, "traced": (0.0, 2.0), "fields": f,
           "costs": costs_nemotronh, "cell": small}
    for reader, pattern in (
            (readers_nemotronh.ssm_decode_roofline, "ssm_decode"),
            (readers_nemotronh.ssd_prefill_roofline, "ssd_prefill"),
            (readers_nemotronh.experts_roofline, "^gmm")):
        assert reader(ctx, pattern=pattern) is None     # no trace, no peak
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    ssm_layers = costs_nemotronh.kind_layers(f, "ssm")
    moe_layers = costs_nemotronh.kind_layers(f, "mlp")
    trace = {"op_seconds": {"ssm_decode": 0.5, "ssd_prefill": 0.25,
                            "gmm": 1.0, "fusion": 0.25},
             "op_counts": {"ssm_decode": 2 * ssm_layers,
                           "ssd_prefill": 2 * ssm_layers,
                           "gmm": 2 * moe_layers * 4}, "busy_s": 2.0}
    full = {**ctx, "trace": trace, "peak": peak}

    def least(call):
        return max(call["flops"] / 1e9, call["bytes"] / 1e6)

    assert readers_nemotronh.ssm_decode_roofline(
        full, pattern="ssm_decode") == pytest.approx(
            100.0 * ssm_layers * 2 * least(
                costs_nemotronh.ssm_decode_call(f, 3)) / 0.5)
    assert readers_nemotronh.ssd_prefill_roofline(
        full, pattern="ssd_prefill") == pytest.approx(
            100.0 * ssm_layers * least(
                costs_nemotronh.ssd_prefill_call(f, 70, 2)) / 0.25)
    experts = [costs_nemotronh.moe_experts_call(f, 8, 6),
               costs_nemotronh.moe_experts_call(f, 72, 14)]
    # two ticks and two prefill dispatches: four dispatches of two products
    assert readers_nemotronh.experts_roofline(
        full, pattern="^gmm") == pytest.approx(
            100.0 * sum(least(c) for c in experts) / 1.0)
    assert readers.op_share(full, pattern="ssd_prefill") == 12.5
    # a program that keeps no such counters (the parent): nothing, no raise
    bare = [{k: v for k, v in t.items()
             if k not in ("state_rows", "state_tokens",
                          "prefill_dispatches")} for t in ticks]
    older = {**full, "ticks": bare}
    assert readers_nemotronh.ssm_decode_roofline(
        older, pattern="ssm_decode") is None
    assert readers_nemotronh.ssd_prefill_roofline(
        older, pattern="ssd_prefill") is None
    # a log and a trace that do not describe the same interval: nothing
    trace["op_counts"]["gmm"] = 2 * moe_layers * 9
    trace["op_counts"]["ssm_decode"] = 9 * ssm_layers
    assert readers_nemotronh.experts_roofline(full, pattern="^gmm") is None
    assert readers_nemotronh.ssm_decode_roofline(
        full, pattern="ssm_decode") is None


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
    assert "request_p50_ms.nemotron" in line["metrics"]
    assert "tick_rows_mean.nemotron" in line["metrics"]
    assert "step_mfu.nemotron" not in line["metrics"]
    assert "ssm_decode_roofline.nemotron" not in line["metrics"]
