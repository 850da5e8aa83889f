"""``family: nemotronh`` (``model_type: nemotron_h``): pre-norm layers of one
sub-layer each, of the kind ``hybrid_override_pattern`` names a layer
(``M`` a Mamba-2 state-space mixer, ``*`` attention without rotary
embedding, ``E`` experts of two products with ``relu(x)^2`` between them
under sigmoid routing beside a shared expert). The program runs them
through ``models/nemotron_h.py`` (``NemotronHConfig`` over
``models/llama.decoder_block``) and the paged engine's normal path, a
per-slot recurrent state beside the page pool; only a serve cell makes
sense of them here.

A configuration's ``n_routed_experts`` is what its chip holds, the
contiguous range from 0; the router's width is the published one
(``published.n_routed_experts``), and ``hybrid_override_pattern`` is cut
with ``num_hidden_layers``.

What a serve cell compares (``serve_check``): as ``family_xing4.py``, the
reference (which runs the state-space layer as the recurrence itself, one
token at a time) runs once over each sampled request's prompt + served
tokens and each served token's logit is held against the reference's best
at its position: the mean, the median and the share over 0.5 of the gaps
are compared where the cell's limits name them, the maximum is read (a
router makes single tokens heavy-tailed, PERF.md section 2). So a bucketed,
padded prefill into a slot's state and every in-place update of it are held
to the full forward.
"""

from __future__ import annotations

import time

import numpy as np

from . import costs_nemotronh as costs  # noqa: F401 - the family's, by name
from . import reference_nemotronh as reference
from .cells import CellError

CONFIG_REQUIRED = {
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "vocab_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
    "time_step_floor", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "n_group", "topk_group", "mlp_hidden_act",
    "mamba_hidden_act", "norm_eps", "tie_word_embeddings"}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "attention_bias", "expand", "intermediate_size", "layer_norm_epsilon",
    "mamba_proj_bias", "max_position_embeddings", "mlp_bias",
    "num_logits_to_keep", "partial_rotary_factor",
    "rescale_prenorm_residual", "residual_in_fp32", "rope_theta",
    "sliding_window", "use_bias", "use_conv_bias", "use_mamba_kernels"}


def fields(config: dict) -> dict:
    """The published keys under the names ``models/nemotron_h
    .NemotronHConfig`` takes (dtype stays the dataclass's default,
    bfloat16)."""
    if config["torch_dtype"] != "bfloat16":
        raise CellError("only bfloat16 configurations run here")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise CellError("the router run here chooses over one group of "
                        "experts")
    if config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or config["n_shared_experts"] != 1 \
            or config["tie_word_embeddings"]:
        raise CellError("experts of relu^2 beside one shared expert, a "
                        "silu state-space layer and an untied head are "
                        "what runs here")
    if any(config.get(key) for key in (
            "attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
            "sliding_window")) or not config.get("use_conv_bias", True):
        raise CellError("biases on products, a convolution without one "
                        "and a sliding window are not run here")
    depth = int(config["num_hidden_layers"])
    pattern = str(config["hybrid_override_pattern"])
    whole = str(config["published"].get("hybrid_override_pattern", pattern))
    if len(pattern) != depth or not whole.startswith(pattern):
        raise CellError(
            f"hybrid_override_pattern has to be the published pattern's "
            f"first {depth} characters")
    held = int(config["n_routed_experts"])
    width = int(config["published"].get("n_routed_experts", held))
    return {
        "vocab_size": int(config["vocab_size"]),
        "n_layers": depth, "pattern": pattern,
        "embed_dim": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_groups": int(config["n_groups"]),
        "ssm_state": int(config["ssm_state_size"]),
        "conv_kernel": int(config["conv_kernel"]),
        "chunk_size": int(config["chunk_size"]),
        "time_step_min": float(config["time_step_min"]),
        "time_step_max": float(config["time_step_max"]),
        "time_step_floor": float(config["time_step_floor"]),
        "n_experts": width,
        "experts_held": None if held == width else (0, held),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "shared_dim": int(config["moe_shared_expert_intermediate_size"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "norm_eps": float(config["norm_eps"]),
        "tie_embeddings": False,
    }


def preset(fields: dict):
    """What a serve cell registers in ``MODEL_PRESETS`` under the
    configuration's name."""
    from mlrun_tpu.models.nemotron_h import NemotronHConfig

    fields = dict(fields)
    return lambda **over: NemotronHConfig(**{**fields, **over})


def train_model(fields: dict):
    raise CellError("the nemotronh family is served here, not trained: the "
                    "chunked scan and the dropless expert layer have no "
                    "backward")


# -- correct, for a serve cell ------------------------------------------------
READ = ("served_logit_gap_max", "served_logit_gap_mean",
        "served_logit_gap_p50", "served_logit_gap_p75",
        "served_logit_gap_p90", "served_logit_gap_over_half",
        "served_logit_gap_over_one")


def pad_length(cell: dict) -> int:
    """The one length the reference pads every sampled request to."""
    return int(cell["traffic_data"]["prompt_tokens"]["max"]) \
        + int(cell["geometry"]["max_new_tokens"])


def _buckets(cell: dict) -> tuple:
    """The engine's prefill buckets under the cell's geometry (what
    ``pad_integrated`` fills a prompt's bucket up to)."""
    top = int(cell["geometry"]["max_len"])
    return tuple(b for b in (128, 512, 1024) if b <= top) or (top,)


def gaps(cell: dict, fields: dict, weights: dict, sample: list,
         **how) -> dict:
    """The gaps by which the served tokens' logits lie below the
    reference's best, over the sample, as ``READ`` names them. ``how``:
    ``quant`` or ``state_dtype`` make it a control's reading (the gaps of
    the tokens that the lower precision puts first, at the same
    positions); ``fault`` plants a fault in the reference."""
    pad_to = pad_length(cell)
    control = {k: v for k, v in how.items() if k != "fault"}
    found, where, widest = [], None, -1.0
    for record in sample:
        served = record["tokens"]
        if any(not 0 <= t < fields["vocab_size"] for t in served):
            return {**{key: float("inf") for key in READ},
                    "tokens": len(found),
                    "where": f"request {record['index']}: id out of range"}
        exact = np.asarray(reference.served_logits(
            fields, weights, record["prompt"], served, pad_to,
            fault=how.get("fault"), buckets=_buckets(cell)))
        chosen = served
        if control:
            chosen = np.asarray(reference.served_logits(
                fields, weights, record["prompt"], served, pad_to,
                **control)).argmax(axis=-1)
        row = reference.gap_below_best(exact, chosen)
        found.extend(row.tolist())
        if float(row.max()) >= widest:
            widest = float(row.max())
            where = f"request {record['index']} token {int(row.argmax())}"
    if not found:
        return {**{key: float("inf") for key in READ}, "tokens": 0,
                "where": "no request finished in the window"}
    found = np.asarray(found)
    return {"served_logit_gap_max": float(found.max()),
            "served_logit_gap_mean": float(found.mean()),
            **{f"served_logit_gap_p{q}": float(np.percentile(found, q))
               for q in (50, 75, 90)},
            "served_logit_gap_over_half": float((found > 0.5).mean()),
            "served_logit_gap_over_one": float((found > 1.0).mean()),
            "tokens": len(found), "where": where}


def serve_check(cell: dict, fields: dict, sample: list) -> dict:
    """What is compared over the kind's sample of finished requests, each
    beside its limit (the numbers of ``READ`` that the cell's limits name).
    Weights are the server's recipe from key 0, made anew here."""
    started = time.perf_counter()
    reading = gaps(cell, fields, reference.make_weights(fields, 0), sample)
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
    in turn, and over the first ``controls`` the controls' readings (the
    reference at int8 levels, and with its recurrent state kept in
    bfloat16, in the program's place), a served token altered, and each
    fault that the reference can plant (``FAULTS``)."""
    weights = reference.make_weights(fields, 0)

    def read(sample, prefix, **how):
        got = gaps(cell, fields, weights, sample, **how)
        return {f"{prefix}_{key}": got[key] for key in READ}

    for i, sample in enumerate(samples):
        started = time.perf_counter()
        program = gaps(cell, fields, weights, sample)
        entry = {**{f"program_{key}": program[key] for key in READ},
                 "tokens": program["tokens"], "where": program["where"],
                 "reference_s": time.perf_counter() - started}
        if i < controls:
            entry.update(read(sample, "control_int8", quant="int8"))
            entry.update(read(sample, "control_bf16_state",
                              state_dtype="bfloat16"))
            entry.update(read(altered_token(fields, sample),
                              "altered_token"))
            for fault in reference.FAULTS:
                if fault is not None:
                    entry.update(read(sample, fault, fault=fault))
        yield entry
