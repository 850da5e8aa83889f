"""The ``sdar`` family as added files: its configuration's keys, its costs
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
    costs_sdar,
    family_sdar,
    readers,
    readers_sdar,
    reference_sdar as ref,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell("sdar-serve-chat")


@pytest.fixture(scope="module")
def tiny(cell):
    small = cells.rehearsed(cell)
    fields = family_sdar.fields(small["config_data"])
    return small, fields, ref.make_weights(fields, 0)


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


# -- the configuration ---------------------------------------------------------
def test_the_cell_loads_with_its_family(cell):
    config = cell["config_data"]
    assert config["family"] == "sdar" and config["reduced"] == [
        "num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert cell["server"] == {"denoising_steps": 4,
                              "remasking": "low_confidence_static"}
    assert cell["request"] == {"return_unmask_pass": True}
    fields = family_sdar.fields(config)
    assert fields["n_layers"] == 6 and fields["n_experts"] == 128
    assert fields["top_k"] == 8 and fields["expert_dim"] == 768
    assert fields["embed_dim"] == 2048 and fields["vocab_size"] == 151936
    assert fields["n_heads"] == 32 and fields["n_kv_heads"] == 4
    assert fields["block_length"] == 4 and fields["mask_token_id"] < 151936
    assert cell["geometry"]["page_size"] % fields["block_length"] == 0
    assert family_sdar.pad_length(cell, fields) == 1152
    names = [m["name"] for m in cells.load_layer_metrics("sdar-serve-chat")]
    assert {"step_mfu.sdar", "moe_experts_roofline.sdar",
            "moe_experts_share.sdar", "tokens_per_row_pass.sdar"} <= set(names)
    assert all(name.endswith(".sdar") for name in names)
    with pytest.raises(cells.CellError, match="served here"):
        family_sdar.train_model(fields)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_number_is_as_the_catalog_has_it(cell):
    with open(CATALOG) as fp:
        rows = [json.loads(line) for line in fp if line.strip()]
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    config = cell["config_data"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


@pytest.mark.parametrize("drop, change", [
    ((), {"num_shared_experts": 1}),            # a key the family lacks
    (("block_length",), {}),                    # a required one missing
    (("num_experts_per_tok",), {}),
    ((), {"reduced": ["num_hidden_layers", "num_experts"]}),
    ((), {"family": "sdar2"}),                  # a family with no module
])
def test_config_keys_are_refused(copy, drop, change):
    _edit(copy / "configs" / "sdar-30b-a3b-chat.json", drop=drop, **change)
    with pytest.raises(cells.CellError):
        cells.load_cell("sdar-serve-chat", base=str(copy))


@pytest.mark.parametrize("change", [
    {"torch_dtype": "float16"}, {"mlp_only_layers": [0]},
    {"decoder_sparse_step": 2}, {"use_sliding_window": True},
])
def test_what_the_program_does_not_run_is_refused(cell, change):
    with pytest.raises(cells.CellError):
        family_sdar.fields({**cell["config_data"], **change})


# -- costs, against hand counts -----------------------------------------------
def test_costs_against_hand_counts(cell):
    f = family_sdar.fields(cell["config_data"])
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert costs_sdar.attention_params(f) == attention == 18_874_368
    assert costs_sdar.expert_params(f) == 3 * 2048 * 768 == 4_718_592
    assert costs_sdar.expert_bytes(f) == 9_437_184             # 9.44 MB
    assert costs_sdar.layer_params(f) == attention + 2048 * 128 \
        + 128 * 4_718_592 == 623_116_288                        # 623.1 M
    assert costs_sdar.active_layer_params(f) == attention + 262_144 \
        + 8 * 4_718_592
    weights = 6 * 623_116_288 + 2 * 2048 * 151936
    assert round(weights * 2 / 1e9, 2) == 8.72                  # GB, bf16
    call = costs_sdar.moe_experts_call(f, pairs=1024 * 6, touched=128 * 6)
    assert call["flops"] == 2 * 4_718_592 * 6144
    assert call["bytes"] == 9_437_184 * 768
    chunk = costs_sdar.paged_chunk_call(f, 4, prefix_tokens=1000.0, rows=2.0)
    assert chunk["flops"] == 4 * 32 * 128 * 4 * 1000.0
    assert chunk["bytes"] == 2 * 4 * 128 * 2 * 1000.0 \
        + 2 * 4 * 32 * (128 * 6 + 4)


def test_request_flops_follow_the_generation_rule():
    f = {"block_length": 4, "n_layers": 1, "embed_dim": 8, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 4, "n_experts": 4, "top_k": 2,
         "expert_dim": 8, "vocab_size": 16}
    layer = 2 * ((8 * 8 + 2 * 8 * 4 + 8 * 8) + 8 * 4 + 2 * 3 * 8 * 8)
    head = 2 * 8 * 16
    attn = lambda context: 4 * 2 * 4 * context          # noqa: E731
    # P = 6, N = 5 at 4 steps: 4 leading positions prefilled; the first
    # block opens with 2 known, 2 masked (2 passes + commit, head at 2 + 1);
    # the second whole (4 passes + commit, head at 4 + 3 + 2 + 1), though
    # the answer is cut after its third lane; N = 9 takes a third block
    want = 4 * layer + 4 * attn(4)
    want += 3 * 4 * (layer + attn(8)) + head * (2 + 1)
    want += 5 * 4 * (layer + attn(12)) + head * 10
    assert costs_sdar.serve_request_flops(f, 6, 5) == want
    want += 5 * 4 * (layer + attn(16)) + head * 10
    assert costs_sdar.serve_request_flops(f, 6, 9) == want
    # two steps a block: [2, 2] then a commit; [1, 1] in the first block
    two = 4 * layer + 4 * attn(4)
    two += 3 * 4 * (layer + attn(8)) + head * (2 + 1)
    two += 2 * (3 * 4 * layer + head * (4 + 2)) + 3 * 4 * (attn(12)
                                                           + attn(16))
    assert costs_sdar.serve_request_flops(f, 6, 9, steps=2) == two


# -- correct: a hand-made sample, and one for each planted fault ---------------
@pytest.fixture(scope="module")
def sample(tiny):
    """Three requests answered by the reference's own generation: what a
    sound program would have served."""
    small, fields, weights = tiny
    rng = np.random.default_rng(4)
    records = []
    for index, length in enumerate((9, 14, 23)):
        prompt = rng.integers(1, 510, length).tolist()
        tokens, passes, record = ref.generate(fields, weights, prompt, 8, 4)
        said = {p["base"] + lane: float(p["confidence"][lane])
                for p in record for lane in p["unmasked"]}
        records.append({"index": index, "prompt": prompt, "tokens": tokens,
                        "timing": {"phases": {}},
                        "body": {"outputs": [tokens],
                                 "unmask_pass": [passes],
                                 "unmask_confidence": [[
                                     said[length + i] for i in range(8)]]}})
    return records


def _check(tiny, sample, **limits):
    small, fields, _ = tiny
    cell = dict(small, check={**small["check"], "limits": {
        "denoise_logit_gap_max": 1e-3, "denoise_choice_gap_max": 1e-3,
        "denoise_logit_gap_mean": 1e-3, "denoise_choice_gap_mean": 1e-3,
        "denoise_confidence_gap_median": 1e-3, **limits}})
    return family_sdar.serve_check(cell, fields, sample)


def test_a_sound_sample_is_correct(tiny, sample):
    compared = _check(tiny, sample)
    assert all(entry["ok"] for entry in compared.values())
    assert compared["denoise_logit_gap_max"]["value"] < 1e-4
    assert compared["denoise_choice_gap_max"]["value"] < 1e-4
    assert compared["denoise_logit_gap_max"]["passes"] == 12
    assert compared["denoise_logit_gap_mean"]["lanes"] >= 10
    assert compared["denoise_choice_gap_mean"]["value"] < 1e-4
    assert compared["denoise_confidence_gap_median"]["value"] < 1e-4
    assert compared["denoise_schedule_faults"]["value"] == 0
    small, fields, _ = tiny
    for record in sample:
        picks = family_sdar.checked_passes(small, fields, record)
        assert len(picks) == 4 and picks[0][0] == ref.blocks_of(
            len(record["prompt"]), 8, 4)[-1]
        assert picks == family_sdar.checked_passes(small, fields, record)


def test_an_altered_token_fails_the_logit_gap(tiny, sample):
    small, fields, _ = tiny
    broken = family_sdar.altered_token(small, fields, sample)
    assert sum(a["tokens"] != b["tokens"]
               for a, b in zip(broken, sample)) == 1
    compared = _check(tiny, broken)
    assert not compared["denoise_logit_gap_max"]["ok"]
    assert compared["denoise_logit_gap_max"]["value"] > 0.5
    assert not compared["denoise_logit_gap_mean"]["ok"]


def test_left_to_right_unmasking_fails_a_gap(tiny, sample):
    small, fields, _ = tiny
    turned = family_sdar.left_to_right(small, fields, sample)
    assert any(family_sdar.unmask_pass_of(a) != family_sdar.unmask_pass_of(b)
               for a, b in zip(turned, sample))
    compared = _check(tiny, turned)
    assert compared["denoise_schedule_faults"]["ok"]    # the counts hold
    assert not (compared["denoise_logit_gap_mean"]["ok"]
                and compared["denoise_choice_gap_mean"]["ok"])
    assert not compared["denoise_confidence_gap_median"]["ok"]


@pytest.mark.parametrize("fault", ["causal_block", "drop_expert"])
def test_a_fault_in_the_reference_shows(tiny, sample, fault):
    small, fields, weights = tiny
    got = family_sdar.denoise_gaps(small, fields, weights, sample,
                                   fault=fault)
    assert max(got["logit_gap_mean"], got["choice_gap_mean"]) > 1e-3
    assert got["confidence_gap_median"] > 1e-3


def test_the_int8_control_is_told_from_the_program(tiny, sample):
    small, fields, weights = tiny
    got = family_sdar.denoise_gaps(small, fields, weights, sample,
                                   quant="int8")
    assert got["confidence_gap_median"] > 1e-3


@pytest.mark.parametrize("passes, faults", [
    ([0, 1, 2, 3, 0, 1, 2, 3], 0),
    ([0, 0, 2, 3, 0, 1, 2, 3], 1),          # two in one pass at 4 steps
    ([0, 1, 2, 4, 0, 1, 2, 3], 1),          # a pass the rule does not have
    ([3, 1, 0, 2, 2, 3, 1, 0], 0),          # any order, the counts hold
    ([0, 1, 2, 3, 0, 1, 2], 2),             # not one pass a token
])
def test_the_schedule_is_held_exactly(tiny, passes, faults):
    small, fields, _ = tiny
    record = {"index": 0, "prompt": [1, 2, 3, 4], "tokens": [5] * 8,
              "body": {"unmask_pass": [passes]}}
    assert family_sdar.schedule_faults(small, fields, record) == faults


def test_no_sample_and_no_unmask_pass_are_not_correct(tiny, sample):
    assert not any(e["ok"] for e in _check(tiny, []).values())
    only_limits = _check(tiny, sample)
    assert "denoise_logit_gap_mean" in only_limits    # a limit: compared
    small, fields, _ = tiny
    fewer = family_sdar.serve_check(dict(small, check={
        **small["check"], "limits": {"denoise_logit_gap_max": 1e-3,
                                     "denoise_choice_gap_max": 1e-3}}),
        fields, sample)
    assert set(fewer) == {"denoise_logit_gap_max", "denoise_choice_gap_max",
                          "denoise_schedule_faults"}
    bare = [dict(r, body={"outputs": [r["tokens"]]}) for r in sample]
    compared = _check(tiny, bare)
    assert not compared["denoise_logit_gap_max"]["ok"]
    assert not compared["denoise_schedule_faults"]["ok"]


def test_readings_name_every_fault(tiny, sample):
    small, fields, _ = tiny
    entries = list(family_sdar.serve_readings(small, fields,
                                              [sample, sample], 1))
    assert len(entries) == 2 and "control_int8_logit_gap" not in entries[1]
    first = entries[0]
    assert first["program_logit_gap"] < 1e-4 and first["passes"] == 12
    for name in ("control_int8", "altered_token", "left_to_right",
                 "causal_block", "drop_expert"):
        assert max(first[f"{name}_logit_gap_mean"],
                   first[f"{name}_choice_gap_mean"]) > 1e-4, name


# -- the readers, on hand-made ticks -------------------------------------------
def _tick(**over):
    record = {"t0": 1.0, "t1": 1.1, "rows": 2, "kind": "denoise",
              "prefill_tokens": 0, "ctx_tokens": 100, "positions": 8,
              "tokens_out": 2, "commit_rows": 0, "expert_pairs": 32,
              "experts_touched": 12, "expert_load_max": 5}
    return {**record, **over}


def test_readers_on_hand_made_ticks(tiny):
    _small, fields, _ = tiny
    ticks = [_tick(), _tick(t0=1.2, t1=1.3, tokens_out=0, commit_rows=2),
             _tick(t0=1.4, t1=1.5, rows=0, kind="plain", prefill_tokens=24,
                   tokens_out=0)]
    ctx = {"ticks": ticks, "traced": (0.0, 2.0), "fields": fields,
           "costs": costs_sdar}
    assert readers_sdar.tokens_per_row_pass(ctx) == 2 / 4
    assert readers_sdar.tokens_per_row_pass({**ctx, "ticks": []}) is None
    assert readers_sdar.experts_roofline(ctx, pattern="^gmm") is None
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    layers = fields["n_layers"]
    trace = {"op_seconds": {"gmm": 0.5, "paged_verify": 0.25,
                            "fusion": 1.0},
             "op_counts": {"gmm": 3 * layers * 3,
                           "paged_verify": layers * 2}, "busy_s": 2.0}
    full = {**ctx, "trace": trace, "peak": peak}
    calls = [costs_sdar.moe_experts_call(fields, 32, 12)] * 2 + [
        costs_sdar.moe_experts_call(
            fields, 24 * fields["top_k"] * layers,
            layers * fields["n_experts"])]
    least = sum(max(c["flops"] / 1e9, c["bytes"] / 1e6) for c in calls)
    assert readers_sdar.experts_roofline(
        full, pattern="^gmm") == pytest.approx(
            100.0 * least / 0.5)
    assert readers.op_share(full, pattern="^gmm") == 25.0
    chunk = costs_sdar.paged_chunk_call(fields, 4, 92, 2)
    assert readers_sdar.chunk_roofline_ticks(
        full, pattern="^paged_verify") == pytest.approx(
            100.0 * layers * 2 * max(chunk["flops"] / 1e9,
                                     chunk["bytes"] / 1e6) / 0.25)
    # a log and a trace that do not describe the same interval: nothing
    trace["op_counts"]["gmm"] = 3 * layers * 9
    assert readers_sdar.experts_roofline(
        full, pattern="^gmm") is None


# -- the cell's rehearsal, end to end ------------------------------------------
def test_rehearsal_exits_3_with_a_whole_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MLT_ATTN_INTERPRET="1")
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "sdar-serve-chat", "--seed", "2147483903",
         "--seconds", "4", "--trace", "1", "--rehearse", "1"],
        env=env, cwd=cells.ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3 and done.stdout == ""
    line = json.loads(next(
        text for text in reversed(done.stderr.splitlines())
        if text.startswith('{"correct"')))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and list(line)[-1] == "compared"
    assert set(line["compared"]) == {
        "denoise_logit_gap_max", "denoise_choice_gap_max",
        "denoise_choice_gap_mean", "denoise_confidence_gap_median",
        "denoise_schedule_faults", "compiles_in_window", "failed_requests"}
    assert line["compared"]["denoise_logit_gap_max"]["passes"] == 12
    # no chip: no time, no share of a peak; the tick log's own are there
    assert line["metrics"]["tokens_per_row_pass.sdar"]["value"] \
        == pytest.approx(0.8, abs=0.1)
    assert "step_mfu.sdar" not in line["metrics"]
    assert "moe_experts_roofline.sdar" not in line["metrics"]
