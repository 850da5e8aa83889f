"""The ``laguna`` family as added files: its configuration's keys and the
fields made of them (the per-layer lists cut with the depth, the rope of
each kind), its costs against hand counts, the comparison that decides a
serve cell's ``correct`` on a hand-made sample that passes and on the
control and planted faults, its readers on hand-made ticks, and the cell's
rehearsal end to end. Nothing here pins where the cell sits in
``BENCHMARK.json`` or the whole set of a cell's metrics: a later cell or
metric that lists this one leaves these tests as they are."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import (
    cells,
    costs_laguna,
    family_laguna,
    readers,
    readers_laguna,
    readers_nemotronh,
    reference_laguna as ref,
)

CELL = "laguna-serve-longdoc"
OURS = {
    "window_decode_roofline.laguna", "window_decode_share.laguna",
    "window_prefill_roofline.laguna", "window_prefill_share.laguna",
    "moe_experts_roofline.laguna", "moe_experts_share.laguna",
    "paged_decode_share.laguna", "step_mfu.laguna",
    "decode_tick_p50_ms.laguna", "tick_host_share.laguna",
    "device_idle_share.laguna", "request_p50_ms.laguna",
    "prefill_share.laguna"}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    small = cells.rehearsed(cell)
    return small, family_laguna.fields(small["config_data"])


# -- the configuration --------------------------------------------------------
def test_the_cell_loads_with_its_family(cell):
    config = cell["config_data"]
    assert config["family"] == "laguna"
    assert set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"}
    assert config["published"]["num_hidden_layers"] == 40
    assert cell["chips"] == 1 and cell["server"] == {"prefill_chunk": 1024}
    assert cell["geometry"] == {"page_size": 128, "slots": 32,
                                "max_len": 8192, "n_pages": 2048,
                                "max_new_tokens": 128}
    f = family_laguna.fields(config)
    assert f["n_layers"] == 7 and f["layer_types"] == tuple(
        config["published"]["layer_types"][:7])
    assert [costs_laguna.kind_layers(f, k)
            for k in ("full", "window", "mlp")] == [2, 5, 6]
    assert (costs_laguna.heads(f, "full"), costs_laguna.heads(f, "window"),
            f["n_kv_heads"], f["head_dim"]) == (48, 64, 8, 128)
    assert (f["n_experts"], f["top_k"], f["expert_dim"], f["shared_dim"],
            f["routed_scale"]) == (256, 8, 512, 512, 2.5)
    assert (f["sliding_window"], f["rope_partial"], f["rope_factor"],
            f["window_rope_theta"]) == (512, 0.5, 64.0, 10000.0)
    assert family_laguna.pad_length(cell) == 7936 + 128
    for key in ("router_scores", "gating", "no_attention_output_gate",
                "shared_expert", "norms", "window", "rotary_dims",
                "torch_dtype"):
        assert key in config["assumed"], key
    assert OURS <= {m["name"] for m in cells.load_layer_metrics(CELL)}
    with pytest.raises(cells.CellError, match="served here"):
        family_laguna.train_model(f)
    program = family_laguna.preset(f)()
    assert program.window_cache
    assert (program.cache_layers, program.state_layers) == (2, 5)
    assert round(program.param_count() * 2 / 1e9, 2) == 11.13


def test_benchmark_json_lists_the_cell():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    assert CELL in [w["name"] for w in bench["workloads"]]
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs.2")
    assert entry["file"] == "benchmarks/configs/laguna-xs.2.json"
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("serve_tokens_per_s", "request_p95_ms"):
        assert CELL in by_name[name]["workloads"]
    ours = [m for m in bench["per_layer"] if m["name"] in OURS]
    assert {m["name"] for m in ours} == OURS
    assert all(m["workloads"] == [CELL] for m in ours)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in OURS}
    assert {m["layer"] for m in ours} <= layers
    assert all(len(e["why"]) <= 200
               for e in bench["configs"] + bench["workloads"])


@pytest.mark.parametrize("change", [
    {"torch_dtype": "float16"}, {"tie_word_embeddings": True},
    {"gating": False}, {"moe_apply_router_weight_on_input": True},
    {"layer_types": ["full_attention"] * 7},
    {"num_attention_heads_per_layer": [48] * 6},
    {"partial_rotary_factor": 1.0},
])
def test_what_the_program_does_not_run_is_refused(cell, change):
    with pytest.raises(cells.CellError):
        family_laguna.fields({**cell["config_data"], **change})


# -- costs, against hand counts -----------------------------------------------
def test_costs_against_hand_counts(cell):
    f = family_laguna.fields(cell["config_data"])
    assert costs_laguna.attention_params(f, "window") == \
        2 * 2048 * 8192 + 2 * 2048 * 1024 == 37_748_736
    assert costs_laguna.attention_params(f, "full") == \
        2 * 2048 * 6144 + 2 * 2048 * 1024 == 29_360_128
    assert costs_laguna.expert_params(f) == 3 * 2048 * 512 == 3_145_728
    assert costs_laguna.expert_bytes(f) == 6_291_456          # 6.29 MB
    active = (2 * 29_360_128 + 5 * 37_748_736 + 3 * 2048 * 8192
              + 6 * (2048 * 256 + 3 * 2048 * 512 + 8 * 3_145_728))
    assert costs_laguna.active_params(f) == active
    decode = costs_laguna.window_decode_call(f, rows=32, keys=32 * 512)
    # 4,096 B a key a layer, plus q and the output of 64 heads a row
    assert decode["bytes"] == 4096 * 32 * 512 + 2 * 32 * 64 * 128 * 2
    assert decode["flops"] == 2 * 2 * 64 * 128 * 32 * 512
    prefill = costs_laguna.window_prefill_call(f, pairs=1000, tokens=64,
                                               calls=1)
    assert prefill["flops"] == 4 * 64 * 128 * 1000
    assert prefill["bytes"] == 2 * 64 * 64 * 128 * 2 + 4096 * (64 + 512)
    experts = costs_laguna.moe_experts_call(f, pairs=256, touched=100)
    assert experts["flops"] == 2 * 3_145_728 * 256
    assert experts["bytes"] == 6_291_456 * 100
    paged = costs_laguna.paged_decode_call(f, rows=32, context=5000)
    assert paged["flops"] == 4 * 48 * 128 * 32 * 5000
    assert costs_laguna.window_pairs(f, 0, 1024) == \
        511 * 512 // 2 + 513 * 512
    assert costs_laguna.window_pairs(f, 1024, 1024) == 1024 * 512


def test_request_flops_by_hand(tiny):
    _small, f = tiny
    width = f["sliding_window"]
    fed = 40 + 3                             # a prompt of 40, 4 tokens out
    window = sum(min(p + 1, width) for p in range(fed))
    assert costs_laguna.serve_request_flops(f, 40, 4) == \
        fed * 2.0 * costs_laguna.active_params(f) \
        + 2 * fed * (fed + 1) // 2 * costs_laguna.attention_pair_flops(
            f, "full") \
        + 5 * window * costs_laguna.attention_pair_flops(f, "window") \
        + 4 * 2.0 * f["embed_dim"] * f["vocab_size"]


# -- correct, on a hand-made sample -------------------------------------------
@pytest.fixture(scope="module")
def sample(tiny):
    """Two requests longer than the window whose tokens are the
    reference's own greedy choices."""
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
    return family_laguna.serve_check(cell, f, sample)


def test_a_sound_sample_is_correct(tiny, sample):
    compared = _check(tiny, sample)
    assert all(entry["ok"] for entry in compared.values())
    assert compared["served_logit_gap_max"]["served_tokens"] == 12
    assert compared["served_logit_gap_max"]["value"] < 1e-4


def test_an_altered_token_fails_the_widest_gap(tiny, sample):
    _small, f = tiny
    compared = _check(tiny, family_laguna.altered_token(f, sample))
    assert not compared["served_logit_gap_max"]["ok"]
    assert "request 1 token 5" == compared["served_logit_gap_max"]["where"]


def test_readings_name_the_control_and_every_fault(tiny, sample):
    small, f = tiny
    got = list(family_laguna.serve_readings(small, f, [sample, sample], 1))
    assert len(got) == 2 and "control_int8_served_logit_gap_max" not in \
        got[1]
    first = got[0]
    assert first["program_served_logit_gap_max"] < 1e-4
    for name in ref.FAULTS[1:] + ("control_int8", "altered_token"):
        for key in family_laguna.READ:
            assert f"{name}_{key}" in first
    # the faults of the reference and the int8 control move the gaps of the
    # reference's own tokens off zero
    moved = [name for name in ref.FAULTS[1:] + ("control_int8",)
             if first[f"{name}_served_logit_gap_max"] > 1e-3]
    assert len(moved) >= 6, first


# -- the readers, on hand-made ticks ------------------------------------------
def _tick(**over):
    record = {"t0": 1.0, "t1": 1.1, "rows": 3, "kind": "plain",
              "prefill_tokens": 0, "prefill_ctx_tokens": 0,
              "prefill_dispatches": 0, "window_keys": 3 * 16,
              "window_prefill_pairs": 0, "ctx_tokens": 100,
              "expert_pairs": 8, "experts_touched": 6, "expert_load_max": 3}
    return {**record, **over}


def test_readers_on_hand_made_ticks(tiny):
    small, f = tiny
    ticks = [_tick(),
             _tick(t0=1.2, t1=1.3, prefill_tokens=70, prefill_dispatches=2,
                   window_prefill_pairs=1000, expert_pairs=8 + 64,
                   experts_touched=6 + 8),
             _tick(t0=1.4, t1=1.5, rows=0, window_keys=0, expert_pairs=0)]
    ctx = {"ticks": ticks, "traced": (0.0, 2.0), "fields": f,
           "costs": costs_laguna, "cell": small}
    for reader, pattern in (
            (readers_laguna.window_decode_roofline, "window_decode"),
            (readers_laguna.window_prefill_roofline, "window_prefill")):
        assert reader(ctx, pattern=pattern) is None     # no trace, no peak
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    window = costs_laguna.kind_layers(f, "window")
    moe = costs_laguna.kind_layers(f, "mlp")
    trace = {"op_seconds": {"window_decode": 0.5, "window_prefill": 0.25,
                            "gmm": 1.0, "fusion": 0.25},
             "op_counts": {"window_decode": 2 * window,
                           "window_prefill": 2 * window,
                           "gmm": 3 * moe * 4}, "busy_s": 2.0}
    full = {**ctx, "trace": trace, "peak": peak}

    def least(call):
        return max(call["flops"] / 1e9, call["bytes"] / 1e6)

    assert readers_laguna.window_decode_roofline(
        full, pattern="window_decode") == pytest.approx(
            100.0 * window * 2 * least(
                costs_laguna.window_decode_call(f, 3, 48)) / 0.5)
    assert readers_laguna.window_prefill_roofline(
        full, pattern="window_prefill") == pytest.approx(
            100.0 * window * least(
                costs_laguna.window_prefill_call(f, 1000, 70, 2)) / 0.25)
    experts = [costs_laguna.moe_experts_call(f, 8, 6),
               costs_laguna.moe_experts_call(f, 72, 14)]
    # two ticks and two prefill dispatches: four dispatches of three products
    assert readers_nemotronh.experts_roofline(
        full, pattern="^gmm", products=3) == pytest.approx(
            100.0 * sum(least(c) for c in experts) / 1.0)
    assert readers.op_share(full, pattern="window_prefill") == 12.5
    # a program that keeps no such counters (the parent): nothing, no raise
    bare = [{k: v for k, v in t.items()
             if k not in ("window_keys", "window_prefill_pairs")}
            for t in ticks]
    older = {**full, "ticks": bare}
    assert readers_laguna.window_decode_roofline(
        older, pattern="window_decode") is None
    assert readers_laguna.window_prefill_roofline(
        older, pattern="window_prefill") is None
    # a log and a trace that do not describe the same interval: nothing
    trace["op_counts"]["window_decode"] = 9 * window
    assert readers_laguna.window_decode_roofline(
        full, pattern="window_decode") is None


# -- the cell's rehearsal, end to end -----------------------------------------
def test_rehearsal_exits_3_with_a_whole_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MLT_ATTN_INTERPRET="1")
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483911",
         "--seconds", "4", "--trace", "1", "--rehearse", "1"],
        env=env, cwd=cells.ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3 and done.stdout == ""
    line = json.loads(next(
        text for text in reversed(done.stderr.splitlines())
        if text.startswith('{"correct"')))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and list(line)[-1] == "compared"
    assert {"served_logit_gap_mean", "served_logit_gap_p50",
            "served_logit_gap_over_half", "compiles_in_window",
            "failed_requests"} <= set(line["compared"])
    assert line["compared"]["served_logit_gap_mean"]["served_tokens"] == 16
    # no chip: no time, no share of a peak; the ledger's own are there
    assert "request_p50_ms.laguna" in line["metrics"]
    assert "step_mfu.laguna" not in line["metrics"]
    assert "window_decode_roofline.laguna" not in line["metrics"]
