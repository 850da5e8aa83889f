"""Operations and bytes that the ``nemotronh`` family's algorithm needs, as
functions of shapes (``f``: the fields of ``family_nemotronh.fields``). As
in ``costs.py`` this counts what has to be done, whatever implements it:
padding to a bucket, a chunk or a lane, dead rows of a tick, products over
experts a token was not routed to and over experts that this chip does not
hold are left out.
"""

from __future__ import annotations

BF16, F32 = 2, 4
KINDS = {"M": "ssm", "*": "attn", "E": "mlp"}


def kind_layers(f: dict, kind: str) -> int:
    return sum(KINDS[c] == kind for c in f["pattern"])


def d_inner(f: dict) -> int:
    return f["ssm_heads"] * f["ssm_head_dim"]


def state_elements(f: dict) -> int:
    """Entries of one layer's recurrent state a sequence."""
    return d_inner(f) * f["ssm_state"]


def held_share(f: dict) -> float:
    """The share of the routed experts that this chip holds."""
    held = f.get("experts_held")
    return 1.0 if held is None else (held[1] - held[0]) / f["n_experts"]


def ssm_params(f: dict) -> int:
    """Weights of a state-space layer's two products."""
    e, di = f["embed_dim"], d_inner(f)
    return e * (2 * di + 2 * f["ssm_groups"] * f["ssm_state"]
                + f["ssm_heads"]) + di * e


def attention_params(f: dict) -> int:
    e = f["embed_dim"]
    return 2 * e * f["n_heads"] * f["head_dim"] \
        + 2 * e * f["n_kv_heads"] * f["head_dim"]


def expert_params(f: dict) -> int:
    """Weights of one routed expert: up and down."""
    return 2 * f["embed_dim"] * f["expert_dim"]


def expert_bytes(f: dict) -> int:
    """One expert's two matrices as stored (bfloat16)."""
    return expert_params(f) * BF16


def active_params(f: dict) -> float:
    """Matmul weights one token is multiplied with here on its way through
    the layers: a state-space layer's two products, an attention layer's
    four, and in an expert layer the router's full width, the shared expert
    and ``top_k`` routed experts scaled by the share of the experts that
    this chip holds (the others' pairs are computed on their chips)."""
    e = f["embed_dim"]
    return (kind_layers(f, "ssm") * ssm_params(f)
            + kind_layers(f, "attn") * attention_params(f)
            + kind_layers(f, "mlp") * (
                e * f["n_experts"] + 2 * e * f["shared_dim"]
                + f["top_k"] * held_share(f) * expert_params(f)))


def head_params(f: dict) -> int:
    return f["embed_dim"] * f["vocab_size"]


def recurrence_flops(f: dict) -> int:
    """One token through one layer's recurrence: the decay and the input's
    outer product into the state, and the read-out (six operations an entry
    of the state)."""
    return 6 * state_elements(f)


def attention_pair_flops(f: dict) -> int:
    """One query position against one key, one attention layer: q.k and
    p.v over all query heads."""
    return 4 * f["n_heads"] * f["head_dim"]


def serve_request_flops(f: dict, prompt_tokens: int,
                        output_tokens: int) -> float:
    """Forward operations one request needs here: every position that is
    fed (the prompt and all output tokens but the last) through the layers
    at 2 operations an active weight and through the state-space layers'
    recurrence; attention of each fed position over itself and what
    precedes it; the head at the positions that are read (one an output
    token)."""
    fed = prompt_tokens + max(0, output_tokens - 1)
    pairs = fed * (fed + 1) // 2
    return (fed * (2.0 * active_params(f)
                   + kind_layers(f, "ssm") * recurrence_flops(f))
            + kind_layers(f, "attn") * pairs * attention_pair_flops(f)
            + output_tokens * 2.0 * head_params(f))


def ssm_decode_call(f: dict, rows: float) -> dict:
    """One ``ssm_decode`` call (one state-space layer, one tick): each of
    ``rows`` live rows reads and writes its state (float32) and takes the
    token's x, B, C and step in and y out. The convolution's window (36,864
    B a row) is not the kernel's and is left out."""
    groups_n = f["ssm_groups"] * f["ssm_state"]
    per_row = (2 * state_elements(f) * F32
               + (d_inner(f) + 2 * groups_n) * BF16
               + f["ssm_heads"] * F32 + d_inner(f) * F32)
    return {"flops": float(recurrence_flops(f)) * rows,
            "bytes": float(per_row) * rows}


def ssd_prefill_call(f: dict, tokens: float, calls: float = 1.0) -> dict:
    """The chunked scan over ``tokens`` real prompt tokens of one layer in
    ``calls`` dispatches, at the published chunk: a token a head the causal
    half of its chunk's two in-chunk products (C B^T, a group's, and the
    weighted sum over x), the read-out of the carried state and its share
    of the chunk's state update; x, B, C and the step in, y out, and the
    state in and out once a call."""
    q, n, p = f["chunk_size"], f["ssm_state"], f["ssm_head_dim"]
    heads, groups = f["ssm_heads"], f["ssm_groups"]
    per_token = (groups * 2 * (q / 2.0) * n            # C B^T, causal half
                 + heads * 2 * (q / 2.0) * p           # (C B^T * L) x
                 + heads * 2 * 2 * n * p)              # C h, and x^T B
    per_token_bytes = ((d_inner(f) + 2 * groups * n) * BF16
                       + heads * F32 + d_inner(f) * F32)
    return {"flops": per_token * tokens,
            "bytes": per_token_bytes * tokens
            + 2.0 * state_elements(f) * F32 * calls}


def moe_experts_call(f: dict, pairs: float, touched: float) -> dict:
    """The routed experts' products of one dispatch (up and down over the
    sorted pairs, all expert layers summed into ``pairs`` and ``touched``):
    two operations a weight a pair, and each expert that got a pair has its
    two matrices read once. The shared expert is a dense product and is not
    counted here."""
    return {"flops": 2.0 * expert_params(f) * pairs,
            "bytes": float(expert_bytes(f)) * touched}


def paged_decode_call(f: dict, rows: float, context: float) -> dict:
    """One ``paged_decode`` call (one attention layer, one tick): each of
    ``rows`` live rows reads the keys and values of its ``context`` tokens
    and multiplies with them; q in, the output back."""
    heads, kvh, d = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    tokens = float(rows) * float(context)
    return {"flops": 4.0 * heads * d * tokens,
            "bytes": 2.0 * kvh * d * BF16 * tokens
            + 2.0 * rows * heads * d * BF16}
