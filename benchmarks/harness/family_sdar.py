"""``family: sdar`` (``model_type: sdar_moe``): pre-norm decoders with
grouped-query attention, q/k norms, rotary positions, every layer a mixture
of experts with no shared expert, and generation by diffusion over blocks.
The program runs them through ``models/moe.py`` (``SdarConfig``, the
dropless ``moe_mlp``) and the paged engine's ``denoise`` tick; only a serve
cell makes sense of them here.

What a serve cell compares (``serve_check``): a step of this family yields
a block's worth of choices, not one token, so the comparison is of what each
pass chose. From each sampled request a few passes are drawn; the block's
state going into the pass is rebuilt from the answer's ``tokens`` and
``unmask_pass`` (the cell asks for it under ``request``), the reference runs
once over prompt + committed tokens + that state, and three numbers come
out: how far a token unmasked in that pass lies below the reference's best
logit at its position, how far the positions unmasked lie below the
reference's own picks in log-confidence, and whether every block of every
sampled request shows the passes the rule gives, exactly.
"""

from __future__ import annotations

import time

import numpy as np

from . import costs_sdar as costs  # noqa: F401 - the family's, read by name
from . import reference_sdar as reference
from .cells import CellError

CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_hidden_layers", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "vocab_size", "rope_theta", "rms_norm_eps",
    "tie_word_embeddings", "block_length", "mask_token_id"}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "attention_bias", "decoder_sparse_step", "hidden_act",
    "max_position_embeddings", "max_window_layers", "mlp_only_layers",
    "rope_scaling", "sliding_window", "use_sliding_window"}


def fields(config: dict) -> dict:
    """The published keys under the names ``models/moe.SdarConfig`` takes
    (dtype stays the dataclass's default, bfloat16)."""
    if config["torch_dtype"] != "bfloat16":
        raise CellError("only bfloat16 configurations run here")
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step",
                                                   1) != 1:
        raise CellError("every layer of this family is a mixture of "
                        "experts: no dense layers, no sparse step")
    if config.get("attention_bias") or config.get("use_sliding_window") \
            or config.get("rope_scaling"):
        raise CellError("attention bias, a sliding window and rope scaling "
                        "are not run here")
    return {
        "vocab_size": int(config["vocab_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "embed_dim": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "mlp_dim": int(config["intermediate_size"]),
        "n_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "block_length": int(config["block_length"]),
        "mask_token_id": int(config["mask_token_id"]),
    }


def preset(fields: dict):
    """What a serve cell registers in ``MODEL_PRESETS`` under the
    configuration's name."""
    from mlrun_tpu.models.moe import SdarConfig

    fields = dict(fields)
    return lambda **over: SdarConfig(**{**fields, **over})


def train_model(fields: dict):
    raise CellError("the sdar family is served here, not trained: the "
                    "masked-block training loss is not in the program")


# -- correct, for a serve cell -------------------------------------------------
# what ``denoise_gaps`` reads: the widest of each gap, and its mean over the
# sample's lanes
READ = ("logit_gap", "choice_gap", "logit_gap_mean", "choice_gap_mean",
        "confidence_gap_median")


def denoising_steps(cell: dict, fields: dict) -> int:
    return int((cell.get("server") or {}).get("denoising_steps")
               or fields["block_length"])


def pad_length(cell: dict, fields: dict) -> int:
    """The one length the reference pads every rebuilt sequence to."""
    size = fields["block_length"]
    longest = int(cell["traffic_data"]["prompt_tokens"]["max"]) \
        + int(cell["geometry"]["max_new_tokens"])
    return -(-longest // size) * size


def unmask_pass_of(record: dict, name: str = "unmask_pass"):
    passes = (record.get("body") or {}).get(name) or [None]
    return passes[0]


def checked_passes(cell: dict, fields: dict, record: dict) -> list:
    """The (block, pass) pairs of one request that are compared: drawn from
    what the seed gave the request (its index and prompt), never from what
    was served, the request's last block always among them. A block whose
    last lanes the answer's cut took away shows only its first pass (the
    state of a cut lane is not in the answer)."""
    size, steps = fields["block_length"], denoising_steps(cell, fields)
    prompt = record["prompt"]
    blocks = reference.blocks_of(len(prompt), len(record["tokens"]), size)
    if not blocks:
        return []
    rng = np.random.default_rng(
        [int(record["index"]), len(prompt), int(prompt[0]), 5])

    def passes_of(block):
        _base, first, lanes = block
        whole = first + lanes == size
        return range(len(reference.schedule(size - first, steps))
                     if whole else 1)

    last = blocks[-1]
    picks = [(last, int(rng.choice(list(passes_of(last)))))]
    others = [(b, at) for b in blocks[:-1] for at in passes_of(b)]
    count = int(cell["check"].get("passes_per_request", 4)) - 1
    order = rng.permutation(len(others))[:max(0, count)]
    return picks + [others[i] for i in sorted(order)]


def schedule_faults(cell: dict, fields: dict, record: dict) -> int:
    """Blocks of the request whose passes are not the rule's: exactly
    ``min(steps, m0)`` denoising passes with the rule's counts (for a block
    the cut shortened: no more than them)."""
    size, steps = fields["block_length"], denoising_steps(cell, fields)
    prompt, tokens = record["prompt"], record["tokens"]
    unmask_pass = unmask_pass_of(record)
    if unmask_pass is None or len(unmask_pass) != len(tokens):
        return len(reference.blocks_of(len(prompt), len(tokens), size)) or 1
    faults = 0
    for base, first, lanes in reference.blocks_of(len(prompt), len(tokens),
                                                  size):
        counts = reference.schedule(size - first, steps)
        start = base + first - len(prompt)
        passes = list(unmask_pass[start:start + lanes])
        if any(not 0 <= p < len(counts) for p in passes):
            faults += 1
            continue
        seen = [passes.count(s) for s in range(len(counts))]
        whole = first + lanes == size
        if (seen != counts) if whole else any(
                a > b for a, b in zip(seen, counts)):
            faults += 1
    return faults


def denoise_gaps(cell: dict, fields: dict, weights: dict, sample: list,
                 quant=None, fault=None) -> dict:
    """The two gaps over the checked passes of the sample. ``fault`` plants
    a fault in the reference. With ``quant`` set it is the control's
    reading instead: what the lower precision would unmask at the same
    states, held against the exact reference."""
    size, steps = fields["block_length"], denoising_steps(cell, fields)
    pad_to = pad_length(cell, fields)
    logit_gap = choice_gap = 0.0
    where, passes = {}, 0
    lanes = {"logit_gap": [], "choice_gap": [], "confidence_gap": []}
    for record in sample:
        prompt, tokens = record["prompt"], record["tokens"]
        unmask_pass = unmask_pass_of(record)
        said = unmask_pass_of(record, "unmask_confidence")
        if unmask_pass is None or len(unmask_pass) != len(tokens) \
                or said is None or len(said) != len(tokens) \
                or any(not 0 <= t < fields["vocab_size"] for t in tokens):
            return {"logit_gap": float("inf"), "choice_gap": float("inf"),
                    "logit_gap_mean": float("inf"),
                    "choice_gap_mean": float("inf"),
                    "confidence_gap_median": float("inf"),
                    "passes": passes, "lanes": len(lanes["logit_gap"]),
                    "where": {
                        key: f"request {record['index']}: no unmask_pass "
                        f"or unmask_confidence, or an id out of range"
                        for key in ("logit_gap", "choice_gap")}}
        sequence = list(prompt) + list(tokens)
        for block, at in checked_passes(cell, fields, record):
            base, first, _lanes = block
            committed, ids, masked, now = reference.block_state_at(
                prompt, tokens, unmask_pass, block, at, size)
            logits, _x0, confidence = reference.denoise_pass(
                fields, weights, committed, ids, masked, size, fault=fault,
                pad_to=pad_to)
            count = reference.schedule(size - first, steps)[at]
            would = reference.pick(confidence, masked, count)
            if quant is None:
                chosen = {lane: (sequence[base + lane],
                                 said[base + lane - len(prompt)])
                          for lane in now}
            else:
                _l, low_x0, low_confidence = reference.denoise_pass(
                    fields, weights, committed, ids, masked, size,
                    quant=quant, pad_to=pad_to)
                chosen = {lane: (int(low_x0[lane]),
                                 float(low_confidence[lane]))
                          for lane in reference.pick(low_confidence, masked,
                                                     count)}
            passes += 1
            floor = min(float(np.log(confidence[j])) for j in would)
            # log softmax(logits) of the block's lanes, in float64
            exact = logits.astype(np.float64)
            exact -= exact.max(axis=-1, keepdims=True)
            exact -= np.log(np.exp(exact).sum(axis=-1, keepdims=True))
            for lane, (token, confident) in chosen.items():
                gap = float(logits[lane].max() - logits[lane][token])
                behind = floor - float(np.log(confidence[lane]))
                lanes["logit_gap"].append(gap)
                lanes["choice_gap"].append(behind)
                lanes["confidence_gap"].append(abs(
                    float(np.log(max(confident, 1e-30)))
                    - float(exact[lane][token])))
                here = (f"request {record['index']} block at {base} pass "
                        f"{at} lane {lane}")
                if gap >= logit_gap:
                    logit_gap, where["logit_gap"] = gap, here
                if behind >= choice_gap:
                    choice_gap, where["choice_gap"] = behind, here
    means = {f"{key}_mean": (float(np.mean(lanes[key])) if lanes[key]
                             else float("inf"))
             for key in ("logit_gap", "choice_gap")}
    means["confidence_gap_median"] = float(np.median(
        lanes["confidence_gap"])) if lanes["confidence_gap"] \
        else float("inf")
    return {"logit_gap": logit_gap, "choice_gap": choice_gap, **means,
            "passes": passes, "lanes": len(lanes["logit_gap"]),
            "where": where}


def serve_check(cell: dict, fields: dict, sample: list) -> dict:
    """What is compared over the kind's sample of finished requests, each
    beside its limit. Weights are the server's recipe from key 0, made anew
    here."""
    limits = cell["check"]["limits"]
    if sample:
        reading = denoise_gaps(cell, fields,
                               reference.make_weights(fields, 0), sample)
        faults = sum(schedule_faults(cell, fields, r) for r in sample)
    else:
        reading = {key: float("inf") for key in READ}
        reading.update(passes=0, lanes=0, where={})
        faults = 1
    out = {}
    for key in READ:
        name = f"denoise_{key}_max" if key.endswith("_gap") \
            else f"denoise_{key}"
        if name not in limits:
            continue                # read for the readings, not compared
        limit = float(limits[name])
        out[name] = {"value": reading[key], "limit": limit,
                     "ok": bool(reading[key] <= limit),
                     "passes": reading["passes"], "lanes": reading["lanes"],
                     "requests": len(sample)}
        if key.endswith("_gap"):
            out[name]["where"] = reading["where"].get(
                key, "no request finished in the window")
    limit = int(limits.get("denoise_schedule_faults", 0))
    out["denoise_schedule_faults"] = {"value": faults, "limit": limit,
                                      "ok": faults <= limit}
    return out


def altered_token(cell: dict, fields: dict, sample: list) -> list:
    """A fault of the timed path: one served token altered where it is
    produced (the id next to it), at a lane that a checked pass unmasked."""
    broken = [dict(r) for r in sample]
    size = fields["block_length"]
    for record in broken:
        for block, at in checked_passes(cell, fields, record):
            _c, _i, _m, now = reference.block_state_at(
                record["prompt"], record["tokens"], unmask_pass_of(record),
                block, at, size)
            if now:
                at_token = block[0] + now[0] - len(record["prompt"])
                record["tokens"] = list(record["tokens"])
                record["tokens"][at_token] = \
                    (record["tokens"][at_token] + 1) % fields["vocab_size"]
                return broken
    return broken


def left_to_right(cell: dict, fields: dict, sample: list) -> list:
    """A fault of the timed path: the same tokens, said to have been
    unmasked from the left (the rule's counts a pass, lanes in order), as
    a scheduler that ignored the confidences would."""
    size, steps = fields["block_length"], denoising_steps(cell, fields)
    broken = []
    for record in sample:
        passes = []
        for _base, first, lanes in reference.blocks_of(
                len(record["prompt"]), len(record["tokens"]), size):
            order = [s for s, count in enumerate(
                reference.schedule(size - first, steps))
                for _ in range(count)]
            passes += order[:lanes]
        body = dict(record["body"], unmask_pass=[passes])
        broken.append(dict(record, body=body))
    return broken


def serve_readings(cell: dict, fields: dict, samples: list, controls: int):
    """For ``readings.py``: what ``serve_check`` compares over each sample
    in turn, and over the first ``controls`` the control's reading (the
    reference at int8 levels in the program's place) and the planted
    faults: a served token altered and left-to-right unmasking (in the
    sample), a causal mask inside the block and one expert's contribution
    dropped (in the reference)."""
    weights = reference.make_weights(fields, 0)

    def read(sample, prefix, **kw):
        got = denoise_gaps(cell, fields, weights, sample, **kw)
        return {f"{prefix}_{key}": got[key] for key in READ}

    for i, sample in enumerate(samples):
        started = time.perf_counter()
        program = denoise_gaps(cell, fields, weights, sample)
        entry = {**{f"program_{key}": program[key] for key in READ},
                 "schedule_faults": sum(schedule_faults(cell, fields, r)
                                        for r in sample),
                 "passes": program["passes"], "lanes": program["lanes"],
                 "where": program["where"],
                 "reference_s": time.perf_counter() - started}
        if i < controls:
            entry.update(read(sample, "control_int8", quant="int8"))
            entry.update(read(altered_token(cell, fields, sample),
                              "altered_token"))
            turned = left_to_right(cell, fields, sample)
            entry.update(read(turned, "left_to_right"))
            entry["left_to_right_schedule_faults"] = sum(
                schedule_faults(cell, fields, r) for r in turned)
            entry.update(read(sample, "causal_block", fault="causal_block"))
            entry.update(read(sample, "drop_expert", fault="drop_expert"))
        yield entry
