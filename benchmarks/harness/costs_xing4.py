"""Operations and bytes that the ``xing4`` family's algorithm needs, as
functions of shapes (``f``: the fields of ``family_xing4.fields``). As in
``costs.py`` this counts what has to be done, whatever implements it:
padding to a chunk or a lane, a second expansion of a cached prefix, dead
rows of a tick and products over experts a token was not routed to are left
out, and the expanded and the absorbed form of one attention read the same
roofline.
"""

from __future__ import annotations

BF16 = 2


def attention_params(f: dict) -> int:
    """Weights of one layer's attention products: the query's low-rank
    pair, the latent's down projection, its expansion into keys and values
    (or, absorbed, the folds through the same entries) and ``W_o``."""
    e, h = f["embed_dim"], f["n_heads"]
    return (e * f["q_lora_rank"]
            + f["q_lora_rank"] * h * (f["nope_dim"] + f["rope_dim"])
            + e * (f["kv_lora_rank"] + f["rope_dim"])
            + f["kv_lora_rank"] * h * (f["nope_dim"] + f["v_dim"])
            + h * f["v_dim"] * e)


def mixing_params(f: dict) -> int:
    """Weights of one layer's mixing products: two sub-layers, each three
    projections of the ``hc_mult`` x hidden state."""
    n = f["hc_mult"]
    return 2 * n * f["embed_dim"] * (2 * n + n * n)


def expert_params(f: dict) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * f["embed_dim"] * f["expert_dim"]


def expert_bytes(f: dict) -> int:
    """One expert's three matrices as stored (bfloat16)."""
    return expert_params(f) * BF16


def active_params(f: dict) -> int:
    """Matmul weights one token is multiplied with on its way through the
    layers: attention and mixing in every layer, the dense MLP in the
    leading layers, and in the others the router's full width, ``top_k``
    routed experts and the shared ones."""
    e = f["embed_dim"]
    dense, moe = f["first_k_dense"], f["n_layers"] - f["first_k_dense"]
    return (f["n_layers"] * (attention_params(f) + mixing_params(f))
            + dense * 3 * e * f["mlp_dim"]
            + moe * (e * f["n_experts"]
                     + (f["top_k"] + f["n_shared_experts"])
                     * expert_params(f)))


def head_params(f: dict) -> int:
    return f["embed_dim"] * f["vocab_size"]


def latent_row_bytes(f: dict) -> int:
    """What a token leaves in one layer's cache as the equations count it:
    the latent and the rotated key (the program pads the row to a whole
    number of lanes; the padding is not required work)."""
    return (f["kv_lora_rank"] + f["rope_dim"]) * BF16


def expanded_pair_flops(f: dict) -> int:
    """One query position against one key in the expanded form, one layer:
    q.k over nope + rope entries and p.v over the value's, all heads."""
    return 2 * f["n_heads"] * (f["nope_dim"] + f["rope_dim"] + f["v_dim"])


def absorbed_pair_flops(f: dict) -> int:
    """One query position against one cached row in the absorbed form, one
    layer: the score over latent + rope entries and the weighted sum over
    the latent's, all heads."""
    return 2 * f["n_heads"] * (2 * f["kv_lora_rank"] + f["rope_dim"])


def serve_request_flops(f: dict, prompt_tokens: int,
                        output_tokens: int) -> int:
    """Forward operations one request needs: every position that is fed
    (the prompt and all output tokens but the last) through the layers at 2
    operations an active weight; expanded attention over the prompt (each
    position against itself and what precedes it); absorbed attention for
    each decoded position over its context; the head at the positions that
    are read (one an output token)."""
    fed = prompt_tokens + max(0, output_tokens - 1)
    prompt_pairs = prompt_tokens * (prompt_tokens + 1) // 2
    decode_pairs = sum(range(prompt_tokens + 1, fed + 1))
    return (fed * 2 * active_params(f)
            + f["n_layers"] * (prompt_pairs * expanded_pair_flops(f)
                               + decode_pairs * absorbed_pair_flops(f))
            + output_tokens * 2 * head_params(f))


def mla_decode_call(f: dict, rows: float, tokens: float) -> dict:
    """One absorbed decode call (one layer, one tick): ``rows`` live rows
    attend ``tokens`` cached rows in all (each row its own context): the
    latent rows read once for all heads, the absorbed queries in, the
    weighted sums of latents out."""
    heads = f["n_heads"]
    width = 2 * f["kv_lora_rank"] + f["rope_dim"]
    return {"flops": float(absorbed_pair_flops(f)) * tokens,
            "bytes": float(latent_row_bytes(f)) * tokens
            + float(rows) * heads * width * BF16}


def mla_prefill_call(f: dict, tokens: float, pairs: float) -> dict:
    """One prompt chunk's attention in one layer, the expanded form's
    causal work: ``tokens`` query positions, ``pairs`` query-key pairs in
    all (each position against itself and what precedes it in its prompt).
    The latents of the context read once, the queries in, the heads'
    values out."""
    heads = f["n_heads"]
    # the chunk's last position attends the whole context: pairs = tokens x
    # start + tokens (tokens + 1) / 2
    start = max(0.0, (pairs - tokens * (tokens + 1) / 2.0) / max(tokens, 1))
    return {"flops": float(expanded_pair_flops(f)) * pairs,
            "bytes": float(latent_row_bytes(f)) * (start + tokens)
            + float(tokens) * heads * BF16
            * (f["nope_dim"] + f["rope_dim"] + f["v_dim"])}


def moe_experts_call(f: dict, pairs: float, touched: float) -> dict:
    """The routed experts' products of one dispatch (gate, up and down over
    the sorted pairs, all expert layers summed into ``pairs`` and
    ``touched``): two operations a weight a pair, and each expert that got
    a pair has its three matrices read once. The shared expert is a dense
    product and is not counted here."""
    return {"flops": 2.0 * expert_params(f) * pairs,
            "bytes": float(expert_bytes(f)) * touched}
