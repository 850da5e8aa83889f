"""``family: toy``: a sparse-expert decoder that commits a block of tokens
per step. It carries keys that the Llama family lacks and decides a cell's
``correct`` by its own comparison: what each step scored."""

from . import costs_toy as costs  # noqa: F401 - read by name
from . import reference_toy as reference

CONFIG_REQUIRED = {"hidden_size", "num_hidden_layers", "vocab_size",
                   "num_experts", "num_experts_per_tok",
                   "moe_intermediate_size", "norm_topk_prob", "block_length"}
CONFIG_KEYS = CONFIG_REQUIRED | {"rope_theta"}


def fields(config: dict) -> dict:
    return {"embed_dim": int(config["hidden_size"]),
            "n_layers": int(config["num_hidden_layers"]),
            "vocab_size": int(config["vocab_size"]),
            "n_experts": int(config["num_experts"]),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "expert_dim": int(config["moe_intermediate_size"]),
            "block": int(config["block_length"])}


def step_check(cell: dict, fields: dict, finished: list) -> dict:
    """The widest gap between what a step of the program scored and what
    the reference scores there, over every finished request."""
    widest = 0.0
    for record in finished:
        exact = reference.step_scores(fields, record["prompt"],
                                      len(record["scores"]))
        widest = max([widest] + [abs(a - b) for a, b in
                                 zip(record["scores"], exact)])
    limit = float(cell["check"]["limits"]["step_score_gap_max"])
    return {"step_score_gap_max": {"value": widest, "limit": limit,
                                   "ok": widest <= limit}}
