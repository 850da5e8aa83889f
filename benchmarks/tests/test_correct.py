"""What decides ``correct``, driven at rehearsal sizes on the CPU with the
harness's look for a chip skipped: a sound run comes out correct, the
control (the reference at int8 levels in the program's place) reads wider
than the program, and each fault of the timed path that a cell can have
comes out as not correct."""

import argparse
import json

import numpy as np
import pytest

from benchmarks.harness import cells, family_llama, reference, serve, train

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _args(seed, seconds=1.0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              rehearse=1)


@pytest.fixture(autouse=True)
def home(tmp_path, monkeypatch):
    monkeypatch.setenv("MLT_HOME", str(tmp_path / "mlt"))


# -- serve --------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """One window of the tiny serve cell; its requests are shared."""
    import os
    import tempfile

    os.environ["MLT_HOME"] = tempfile.mkdtemp()
    cell = cells.rehearsed(cells.load_cell("m7b-serve-chat"))
    serving = serve.ServeCell(cell)
    serving.build()
    serving.warm_buckets()
    result = serving.window(11, 2.0)
    _metrics, good, _bad = serve.end_to_end(
        result, cell["geometry"]["max_new_tokens"])
    fields = serving.fields
    serving.close()
    return cell, fields, good


def test_serve_sound_run_is_correct_and_the_control_reads_wider(served):
    cell, fields, good = served
    assert len(good) >= 8
    compared = serve.check(cell, fields, good, seed=11)
    assert compared["served_logit_gap_max"]["ok"], compared
    assert compared["served_logit_gap_max"]["served_tokens"] > 0
    weights = reference.make_weights(fields, 0, eager=True)
    sample = serve.sample_finished(good, 11, len(good))
    pad_to = family_llama.pad_length(cell)
    program = family_llama.served_gap(fields, weights, sample, pad_to)
    control = family_llama.served_gap(fields, weights, sample, pad_to,
                                      quant="int8")
    assert control["value"] > program["value"]


def test_serve_sample_is_seeded_and_holds_the_longest(served):
    _cell, _fields, good = served
    a = serve.sample_finished(good, 5, 3)
    b = serve.sample_finished(list(reversed(good)), 5, 3)
    assert [r["index"] for r in a] == [r["index"] for r in b]
    assert len(a[0]["prompt"]) == max(len(r["prompt"]) for r in good)
    assert [r["index"] for r in serve.sample_finished(good, 6, 3)] \
        != [r["index"] for r in a] or len(good) <= 3


def test_serve_altered_token_is_not_correct(served):
    """A token altered where it is produced: the last sampled request
    answers with the id next to the one the engine chose."""
    cell, fields, good = served
    sample = serve.sample_finished(good, 11, cell["check"]["sample_requests"])
    victim = sample[-1]["index"]
    broken = []
    for record in good:
        record = dict(record)
        if record["index"] == victim:
            tokens = list(record["tokens"])
            tokens[3] = (tokens[3] + 1) % fields["vocab_size"]
            record["tokens"] = tokens
        broken.append(record)
    compared = serve.check(cell, fields, broken, seed=11)
    assert not compared["served_logit_gap_max"]["ok"], compared


def test_serve_window_counts_all_that_was_sent_over_all_of_its_time():
    """The window opens as the first client starts, sends nothing once its
    seconds are up, waits for what was sent and closes after that wait;
    the rate is all of those tokens over all of that time."""
    import time
    import types

    cell = cells.rehearsed(cells.load_cell("m7b-serve-chat"))
    cell["traffic_data"]["ramp_seconds"] = 0.2
    serving = serve.ServeCell(cell)
    serving.engine = types.SimpleNamespace(stats={})
    out = cell["geometry"]["max_new_tokens"]

    def request(prompt):
        sent = time.perf_counter()
        time.sleep(0.15)
        return {"sent": sent, "done": time.perf_counter(), "prompt": prompt,
                "tokens": [1] * out, "timing": {"phases": {}, "wall_s": 0.15}}

    serving.request = request
    opened = []
    result = serving.window(7, 0.5, on_open=lambda: opened.append(
        time.perf_counter()))
    records = result["finished"]
    clients = cell["traffic_data"]["clients"]
    assert opened[0] <= result["opened"] <= min(r["sent"] for r in records)
    assert max(r["sent"] for r in records) < result["opened"] + 0.5
    assert max(r["done"] for r in records) > result["opened"] + 0.5
    assert result["closed"] >= max(r["done"] for r in records)
    assert sorted(r["index"] for r in records) == list(range(len(records)))
    assert len(records) >= 2 * clients and result["hung"] == 0
    starts = sorted(r["sent"] for r in records)[:clients]
    assert starts[-1] - starts[0] >= 0.1          # staggered over the ramp
    metrics, good, bad = serve.end_to_end(result, out)
    assert not bad and len(good) == len(records)
    assert metrics["serve_tokens_per_s"]["value"] == pytest.approx(
        out * len(records) / (result["closed"] - result["opened"]))


def test_serve_run_reports_a_broken_answer_as_not_correct(monkeypatch):
    """The rest of a run with the timed path broken underneath: the graph's
    answers lose a token, and the line says not correct and counts them."""
    cell = cells.rehearsed(cells.load_cell("m7b-serve-chat"))
    whole = serve.ServeCell.request

    def short(self, prompt):
        record = whole(self, prompt)
        record["tokens"] = record["tokens"][:-1]
        return record

    monkeypatch.setattr(serve.ServeCell, "request", short)
    line = json.loads(serve.run(cell, [], _args(3), DEVICE, 0.0))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert list(line)[-1] == "compared"


# -- train --------------------------------------------------------------------
def _train_line(monkeypatch, dispatch=None, seed=4):
    from mlrun_tpu.training.train import Trainer

    if dispatch is not None:
        monkeypatch.setattr(Trainer, "_dispatch", dispatch)
    cell = cells.rehearsed(cells.load_cell("nemo-train-lora"))
    return json.loads(train.run(cell, [], _args(seed, 0.5), DEVICE, 0.0))


def test_train_sound_run_is_correct(monkeypatch):
    line = _train_line(monkeypatch)
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(line)[-1] == "compared"


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    """A step that returns its state unchanged."""
    import jax

    from mlrun_tpu.training.train import Trainer

    def frozen(self, tokens, targets):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), self.state)
        _new, metrics = self.step_fn(self.state, tokens, targets)
        self.state = keep
        return metrics

    line = _train_line(monkeypatch, frozen)
    assert line["correct"] is False
    assert line["compared"]["lora_change_norm_gap_max"]["value"] \
        == pytest.approx(1.0)
    assert not line["compared"]["first_grad_norm_gap_max"]["ok"]


def test_train_half_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from mlrun_tpu.training.train import Trainer

    whole = Trainer._dispatch

    def half(self, tokens, targets):
        n = tokens.shape[0] // 2
        return whole(self, tokens[:n], targets[:n])

    line = _train_line(monkeypatch, half)
    assert line["correct"] is False, line["compared"]


def test_train_control_and_planted_fault_read_wider_than_the_program():
    cell = cells.rehearsed(cells.load_cell("nemo-train-lora"))
    fields = family_llama.llama_fields(cell["config_data"])
    ref = train.reference_readings(reference, fields, cell["traffic_data"], 9)
    control = train.reference_readings(
        reference, fields, cell["traffic_data"], 9, quant="int8")
    half = train.reference_readings(
        reference, fields, cell["traffic_data"], 9, drop_half_batch=True)
    same = train.compare(ref, ref)
    assert all(value == 0 for value, _w in same.values())
    low = train.compare(control, ref)
    assert low["first_grad_diff_norm_max"][0] > 0.02
    wrong = train.compare(half, ref)
    assert wrong["first_grad_diff_norm_max"][0] > 0.3
    assert wrong["loss_gap_max"][0] > 0.01
    # a leaf that does not move reads 1 by the measure
    still = dict(ref, change={k: 0.0 for k in ref["change"]})
    assert train.compare(still, ref)["lora_change_norm_gap_max"][0] \
        == pytest.approx(1.0)


def test_worst_leaf_is_measured_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9, "d": 0.0}
    program = {"a": 1.0, "b": 2.2, "c": 2e-9, "d": 0.0}
    gap, where = train.worst_norm_gap(program, ref)
    assert where == "b" and gap == pytest.approx(0.1)
    gap, where = train.worst_norm_gap(dict(program, d=0.5), ref)
    assert where == "d" and gap == pytest.approx(0.5)
    assert np.isfinite(train.worst_norm_gap(ref, ref)[0])
