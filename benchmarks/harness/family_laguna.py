"""``family: laguna`` (``model_type: laguna``): pre-norm decoders whose
attention layers are of two kinds by ``layer_types``: full layers over the
whole context and sliding-window layers over their last ``sliding_window``
positions, each kind with its own query heads
(``num_attention_heads_per_layer``) and rope (``rope_parameters``: YaRN
over half a head for the full layers, plain over all of it for the window
layers); the leading ``dense`` MLP layers, then experts under sigmoid
routing with a routed scale beside a shared expert. The program runs them
through ``models/laguna.py`` (``LagunaConfig`` over
``models/llama.decoder_block``) and the paged engine's normal path: pages
for the full layers, a ring of the window's positions a window layer a
slot; only a serve cell makes sense of them here.

``layer_types``, ``mlp_layer_types`` and ``num_attention_heads_per_layer``
are cut with ``num_hidden_layers``: each has to be the published list's
first entries.

What a serve cell compares (``serve_check``): as ``family_xing4.py``, the
reference (one masked softmax over the whole sequence, the window's mask
written out) runs once over each sampled request's prompt + served tokens
and each served token's logit is held against the reference's best at its
position: the mean, the median and the share over 0.5 of the gaps are
compared where the cell's limits name them, the maximum is read (a router
makes single tokens heavy-tailed, PERF.md section 2). So chunked prefill
through the rings and every decode tick over them are held to the full
forward.
"""

from __future__ import annotations

import time

import numpy as np

from . import costs_laguna as costs  # noqa: F401 - the family's, by name
from . import reference_laguna as reference
from .cells import CellError

CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "tie_word_embeddings", "gating", "sliding_window", "rope_parameters",
    "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
    "moe_routed_scaling_factor", "moe_apply_router_weight_on_input",
    "partial_rotary_factor"}
CONFIG_KEYS = CONFIG_REQUIRED | {"attention_bias", "max_position_embeddings"}
CUT_WITH_DEPTH = ("layer_types", "mlp_layer_types",
                  "num_attention_heads_per_layer")


def fields(config: dict) -> dict:
    """The published keys under the names ``models/laguna.LagunaConfig``
    takes (dtype stays the dataclass's default, bfloat16)."""
    if config["torch_dtype"] != "bfloat16":
        raise CellError("only bfloat16 configurations run here")
    if config.get("attention_bias") or config["tie_word_embeddings"] \
            or not config["gating"] \
            or config["moe_apply_router_weight_on_input"]:
        raise CellError("attention bias, a tied head, ungated MLPs and the "
                        "router's weight on an expert's input are not run "
                        "here")
    depth = int(config["num_hidden_layers"])
    for key in CUT_WITH_DEPTH:
        cut = list(config[key])
        whole = list(config["published"].get(key, cut))
        if len(cut) != depth or whole[:depth] != cut:
            raise CellError(f"{key} has to be the published list's first "
                            f"{depth} entries")
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if full.get("rope_type") != "yarn" \
            or window.get("rope_type") != "default":
        raise CellError("the full layers' rope is YaRN's, the window "
                        "layers' the plain one")
    if float(full["partial_rotary_factor"]) \
            != float(config["partial_rotary_factor"]):
        raise CellError("partial_rotary_factor is the full layers'")
    return {
        "vocab_size": int(config["vocab_size"]),
        "n_layers": depth,
        "embed_dim": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layer_types": tuple(config["layer_types"]),
        "mlp_layer_types": tuple(config["mlp_layer_types"]),
        "heads_per_layer": tuple(
            int(h) for h in config["num_attention_heads_per_layer"]),
        "sliding_window": int(config["sliding_window"]),
        "mlp_dim": int(config["intermediate_size"]),
        "n_experts": int(config["num_experts"]),
        "experts_held": None,
        "top_k": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "shared_dim": int(config["shared_expert_intermediate_size"]),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "norm_topk": True,
        "norm_eps": float(config["rms_norm_eps"]),
        "rope_theta": float(full["rope_theta"]),
        "rope_factor": float(full["factor"]),
        "rope_original_max": int(full["original_max_position_embeddings"]),
        "rope_beta_fast": float(full["beta_fast"]),
        "rope_beta_slow": float(full["beta_slow"]),
        "rope_attention_factor": float(full["attention_factor"]),
        "rope_partial": float(full["partial_rotary_factor"]),
        "window_rope_theta": float(window["rope_theta"]),
        "window_rope_partial": float(window["partial_rotary_factor"]),
        "tie_embeddings": False,
    }


def preset(fields: dict):
    """What a serve cell registers in ``MODEL_PRESETS`` under the
    configuration's name."""
    from mlrun_tpu.models.laguna import LagunaConfig

    fields = dict(fields)
    return lambda **over: LagunaConfig(**{**fields, **over})


def train_model(fields: dict):
    raise CellError("the laguna family is served here, not trained: the "
                    "dropless expert layer has no backward")


# -- correct, for a serve cell ------------------------------------------------
READ = ("served_logit_gap_max", "served_logit_gap_mean",
        "served_logit_gap_p50", "served_logit_gap_p75",
        "served_logit_gap_p90", "served_logit_gap_over_half",
        "served_logit_gap_over_one")


def pad_length(cell: dict) -> int:
    """The one length the reference pads every sampled request to."""
    return int(cell["traffic_data"]["prompt_tokens"]["max"]) \
        + int(cell["geometry"]["max_new_tokens"])


class Judge:
    """The reference over one sample after another. It takes each
    request's embeddings as it meets it and holds the weights without the
    table (the table is gone after :meth:`embedded_all`), so that the
    reference fits beside nothing else on the chip."""

    def __init__(self, cell: dict, fields: dict):
        self.fields = fields
        self.pad_to = pad_length(cell)
        self.weights = reference.make_weights(fields, 0)

    def embedded_all(self, samples: list) -> list:
        """Each request's embeddings, sample by sample; then the table is
        let go."""
        out = [[reference.embedded(self.weights, reference.padded_ids(
            r["prompt"], r["tokens"], self.pad_to)) for r in sample]
            for sample in samples]
        self.weights = {k: v for k, v in self.weights.items()
                        if k != "embedding"}
        return out

    def gaps(self, sample: list, embeddings: list, quant=None,
             fault=None) -> dict:
        """The gaps by which the served tokens' logits lie below the
        reference's best, over the sample, as ``READ`` names them. With
        ``quant`` set it is the control's reading instead: the gaps of the
        tokens that the lower precision puts first, at the same positions.
        ``fault`` plants a fault in the reference."""
        gaps, where, widest = [], None, -1.0
        for record, embedded in zip(sample, embeddings):
            served = record["tokens"]
            if any(not 0 <= t < self.fields["vocab_size"] for t in served):
                return {**{key: float("inf") for key in READ},
                        "tokens": len(gaps),
                        "where": f"request {record['index']}: id out of "
                        f"range"}
            exact = np.asarray(reference.served_logits(
                self.fields, self.weights, record["prompt"], served,
                self.pad_to, fault=fault, embeddings=embedded))
            chosen = served
            if quant is not None:
                chosen = np.asarray(reference.served_logits(
                    self.fields, self.weights, record["prompt"], served,
                    self.pad_to, quant=quant,
                    embeddings=embedded)).argmax(axis=-1)
            row = reference.gap_below_best(exact, chosen)
            gaps.extend(row.tolist())
            if float(row.max()) >= widest:
                widest = float(row.max())
                where = (f"request {record['index']} token "
                         f"{int(row.argmax())}")
        if not gaps:
            return {**{key: float("inf") for key in READ}, "tokens": 0,
                    "where": "no request finished in the window"}
        gaps = np.asarray(gaps)
        return {"served_logit_gap_max": float(gaps.max()),
                "served_logit_gap_mean": float(gaps.mean()),
                **{f"served_logit_gap_p{q}": float(np.percentile(gaps, q))
                   for q in (50, 75, 90)},
                "served_logit_gap_over_half": float((gaps > 0.5).mean()),
                "served_logit_gap_over_one": float((gaps > 1.0).mean()),
                "tokens": len(gaps), "where": where}


def serve_check(cell: dict, fields: dict, sample: list) -> dict:
    """What is compared over the kind's sample of finished requests, each
    beside its limit (the numbers of ``READ`` that the cell's limits name).
    Weights are the server's recipe from key 0, made anew here."""
    started = time.perf_counter()
    if sample:
        judge = Judge(cell, fields)
        reading = judge.gaps(sample, judge.embedded_all([sample])[0])
    else:
        reading = {**{key: float("inf") for key in READ}, "tokens": 0,
                   "where": "no request finished in the window"}
    took = time.perf_counter() - started
    out = {}
    for key in READ:
        if key not in cell["check"]["limits"]:
            continue                # read for the readings, not compared
        limit = float(cell["check"]["limits"][key])
        out[key] = {"value": reading[key], "limit": limit,
                    "ok": bool(reading[key] <= limit),
                    "served_tokens": reading["tokens"],
                    "requests": len(sample), "reference_s": took}
    if "served_logit_gap_max" in out:
        out["served_logit_gap_max"]["where"] = reading["where"]
    return out


def altered_token(fields: dict, sample: list) -> list:
    """A fault of the timed path: one served token altered where it is
    produced (the id next to it)."""
    broken = [dict(r) for r in sample]
    broken[-1]["tokens"] = list(broken[-1]["tokens"])
    broken[-1]["tokens"][-1] = (broken[-1]["tokens"][-1] + 1) \
        % fields["vocab_size"]
    return broken


def serve_readings(cell: dict, fields: dict, samples: list, controls: int):
    """For ``readings.py``: what ``serve_check`` compares over each sample
    in turn, and over the first ``controls`` the control's reading (the
    reference at int8 levels in the program's place), a served token
    altered, and each fault that the reference can plant (``FAULTS``)."""
    judge = Judge(cell, fields)
    embedded = judge.embedded_all(samples)

    def read(sample, embeddings, prefix, **kw):
        got = judge.gaps(sample, embeddings, **kw)
        return {f"{prefix}_{key}": got[key] for key in READ}

    for i, (sample, embeddings) in enumerate(zip(samples, embedded)):
        started = time.perf_counter()
        program = judge.gaps(sample, embeddings)
        entry = {**{f"program_{key}": program[key] for key in READ},
                 "tokens": program["tokens"], "where": program["where"],
                 "reference_s": time.perf_counter() - started}
        if i < controls:
            entry.update(read(sample, embeddings, "control_int8",
                              quant="int8"))
            entry.update(read(altered_token(fields, sample), embeddings,
                              "altered_token"))
            for fault in reference.FAULTS:
                if fault is not None:
                    entry.update(read(sample, embeddings, fault,
                                      fault=fault))
        yield entry
