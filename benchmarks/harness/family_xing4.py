"""``family: xing4`` (``model_type: xing4_0``): pre-norm decoders with latent
attention (a low-rank query, one normalised latent and one rotated key a
token shared by the heads), YaRN rope, leading dense layers and then
experts under sigmoid routing beside a shared expert, and four residual
streams mixed by manifold-constrained hyper-connections. The program runs
them through ``models/xing4.py`` (``Xing4Config`` and the seams it hands
``models/llama.decoder_block``) and the paged engine's normal path; only a
serve cell makes sense of them here. The multi-token-prediction module the
published config counts (``num_nextn_predict_layers``) is not instantiated.

What a serve cell compares (``serve_check``): as ``family_llama.py``, the
reference runs once over each sampled request's prompt + served tokens and
each served token's logit is held against the reference's best at its
position. A router makes single tokens heavy-tailed: a rounding that moves
a token across the top-4 boundary of 64 sigmoid scores swaps an expert
whose gate is about a half, and at the published widths that happens to
about half of the tokens somewhere in six expert layers, so a sound
program's widest gap is as wide as a wrong token's (PERF.md section 2). What
is systematic shows in the middle of the distribution: the mean, the
quartiles and the share of tokens that lie more than 0.5 (or 1) below the
reference's best are read (``READ``), and a cell compares those that its
limits name.
"""

from __future__ import annotations

import time

import numpy as np

from . import costs_xing4 as costs  # noqa: F401 - the family's, read by name
from . import reference_xing4 as reference
from .cells import CellError

CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
    "vocab_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
    "scoring_func", "topk_method", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "rope_theta",
    "rope_scaling", "rms_norm_eps", "tie_word_embeddings", "hc_mult",
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max", "num_nextn_predict_layers"}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "attention_bias", "ep_size", "hidden_act", "max_position_embeddings",
    "moe_layer_freq"}


def fields(config: dict) -> dict:
    """The published keys under the names ``models/xing4.Xing4Config``
    takes (dtype stays the dataclass's default, bfloat16)."""
    if config["torch_dtype"] != "bfloat16":
        raise CellError("only bfloat16 configurations run here")
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["n_group"] != 1 or config["topk_group"] != 1:
        raise CellError("the router run here scores by sigmoid and chooses "
                        "by score plus bias over one group of experts")
    rope = config["rope_scaling"] or {}
    if rope.get("type") != "yarn":
        raise CellError("this family's rope is YaRN's")
    if config.get("attention_bias") or config.get("moe_layer_freq", 1) != 1 \
            or config.get("hidden_act", "silu") != "silu" \
            or config["tie_word_embeddings"]:
        raise CellError("attention bias, expert layers at a stride, another "
                        "activation and a tied head are not run here")
    clamp = float(config["mhc_h_res_clamp_max"])
    if float(config["mhc_h_res_clamp_min"]) != -clamp:
        raise CellError("the residual mix's clamp is symmetric here")
    nope, rot = int(config["qk_nope_head_dim"]), \
        int(config["qk_rope_head_dim"])
    return {
        "vocab_size": int(config["vocab_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "first_k_dense": int(config["first_k_dense_replace"]),
        "embed_dim": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": nope + rot,
        "q_lora_rank": int(config["q_lora_rank"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "nope_dim": nope, "rope_dim": rot,
        "v_dim": int(config["v_head_dim"]),
        "mlp_dim": int(config["intermediate_size"]),
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "n_shared_experts": int(config["n_shared_experts"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(rope["factor"]),
        "rope_original_max": int(
            rope["original_max_position_embeddings"]),
        "rope_beta_fast": float(rope["beta_fast"]),
        "rope_beta_slow": float(rope["beta_slow"]),
        "rope_mscale": float(rope["mscale"]),
        "rope_mscale_all_dim": float(rope["mscale_all_dim"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": False,
        "hc_mult": int(config["hc_mult"]),
        "hc_iters": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "hc_clamp": clamp,
    }


def preset(fields: dict):
    """What a serve cell registers in ``MODEL_PRESETS`` under the
    configuration's name."""
    from mlrun_tpu.models.xing4 import Xing4Config

    fields = dict(fields)
    return lambda **over: Xing4Config(**{**fields, **over})


def train_model(fields: dict):
    raise CellError("the xing4 family is served here, not trained: the "
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
        reference's best, over the sample: the widest, the mean and the
        90th percentile. With ``quant`` set it is the control's reading
        instead: the gaps of the tokens that the lower precision puts
        first, at the same positions. ``fault`` plants a fault in the
        reference."""
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
