"""``family: llama`` (the default): dense pre-norm decoders with grouped
query attention, rotary positions and a SwiGLU MLP, which the program runs
through ``models/llama.py``. The first instance of what a family module
gives; the one of another family stands beside it as
``family_<name>.py``, named by its configurations' ``family`` key.

What ``cells.py`` and the kinds read here: ``CONFIG_REQUIRED`` and
``CONFIG_KEYS`` (the model's own keys, beside the common ones of
``cells.py``), ``fields``, ``preset``, ``train_model``, ``reference``,
``costs``, and for a serve cell ``serve_check`` and ``serve_readings``.
"""

from __future__ import annotations

import time

import numpy as np

from . import costs, reference  # noqa: F401 - the family's, read by name
from .cells import CellError

CONFIG_REQUIRED = {"hidden_size", "intermediate_size", "num_attention_heads",
                   "num_key_value_heads", "head_dim", "num_hidden_layers",
                   "vocab_size", "rope_theta", "rms_norm_eps",
                   "tie_word_embeddings"}
CONFIG_KEYS = CONFIG_REQUIRED | {"max_position_embeddings", "hidden_act",
                                 "sliding_window"}


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


fields = llama_fields


def preset(fields: dict):
    """What a serve cell registers in ``MODEL_PRESETS`` under the
    configuration's name."""
    from mlrun_tpu.models.llama import LlamaConfig

    fields = dict(fields)
    return lambda **over: LlamaConfig(**{**fields, **over})


def train_model(fields: dict) -> dict:
    """What a train cell hands to ``train(model=...)``."""
    return dict(fields)


# -- correct, for a serve cell -------------------------------------------------
def pad_length(cell: dict) -> int:
    """The one length the reference pads every sampled request to."""
    return int(cell["traffic_data"]["prompt_tokens"]["max"]) \
        + int(cell["geometry"]["max_new_tokens"])


def served_gap(fields: dict, weights: dict, sample: list, pad_to: int,
               quant=None) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample. With ``quant`` set it is the
    control's reading instead: the gap of the token that the lower
    precision puts first, at the same positions."""
    widest, where, tokens_seen = 0.0, None, 0
    for record in sample:
        served = record["tokens"]
        if any(not 0 <= t < fields["vocab_size"] for t in served):
            return {"value": float("inf"), "tokens": tokens_seen,
                    "where": f"request {record['index']}: id out of range"}
        exact = np.asarray(reference.served_logits(
            fields, weights, record["prompt"], served, pad_to))
        if quant is None:
            chosen = served
        else:
            low = np.asarray(reference.served_logits(
                fields, weights, record["prompt"], served, pad_to,
                quant=quant))
            chosen = low.argmax(axis=-1)
        gaps = reference.gap_below_best(exact, chosen)
        tokens_seen += len(served)
        if float(gaps.max()) >= widest:
            widest = float(gaps.max())
            where = f"request {record['index']} token {int(gaps.argmax())}"
    return {"value": widest, "tokens": tokens_seen, "where": where}


def serve_check(cell: dict, fields: dict, sample: list) -> dict:
    """What is compared over the kind's sample of finished requests, each
    beside its limit. Weights are the server's recipe from key 0, made anew
    here."""
    if sample:
        reading = served_gap(fields,
                             reference.make_weights(fields, 0, eager=True),
                             sample, pad_length(cell))
    else:
        reading = {"value": float("inf"), "tokens": 0,
                   "where": "no request finished in the window"}
    limit = float(cell["check"]["limits"]["served_logit_gap_max"])
    return {"served_logit_gap_max": {
        "value": reading["value"], "limit": limit,
        "ok": bool(reading["value"] <= limit),
        "served_tokens": reading["tokens"], "requests": len(sample),
        "where": reading["where"]}}


def serve_readings(cell: dict, fields: dict, samples: list, controls: int):
    """For ``readings.py``: what ``serve_check`` compares over each sample
    in turn, and over the first ``controls`` the control's reading and that
    of a fault of the timed path."""
    weights = reference.make_weights(fields, 0, eager=True)
    pad_to = pad_length(cell)
    for i, sample in enumerate(samples):
        started = time.perf_counter()
        program = served_gap(fields, weights, sample, pad_to)
        took = time.perf_counter() - started
        entry = {"program_gap": program["value"], "tokens": program["tokens"],
                 "where": program["where"], "reference_s": took}
        if i < controls:
            control = served_gap(fields, weights, sample, pad_to,
                                 quant="int8")
            entry["control_int8_gap"] = control["value"]
            # a fault of the timed path: one served token altered where it
            # is produced (the id next to it)
            broken = [dict(r) for r in sample]
            broken[-1]["tokens"] = list(broken[-1]["tokens"])
            broken[-1]["tokens"][-1] = (broken[-1]["tokens"][-1] + 1) \
                % fields["vocab_size"]
            entry["altered_token_gap"] = served_gap(
                fields, weights, broken, pad_to)["value"]
        yield entry
