"""Operations and bytes that the ``sdar`` family's algorithm needs, as
functions of shapes (``f``: the fields of ``family_sdar.fields``). As in
``costs.py`` this counts what has to be done, not what the program does:
padding to a bucket, dead rows of a pass, the head at positions that are not
masked and products over experts a token was not routed to are left out.
"""

from __future__ import annotations

from .reference_sdar import schedule

BF16 = 2


def attention_params(f: dict) -> int:
    """Weights of one layer's four attention products."""
    e = f["embed_dim"]
    q = f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    return e * q + 2 * e * kv + q * e


def expert_params(f: dict) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * f["embed_dim"] * f["expert_dim"]


def expert_bytes(f: dict) -> int:
    """One expert's three matrices as stored (bfloat16)."""
    return expert_params(f) * BF16


def router_params(f: dict) -> int:
    return f["embed_dim"] * f["n_experts"]


def layer_params(f: dict) -> int:
    """Matmul weights one layer holds: attention, router, every expert."""
    return attention_params(f) + router_params(f) \
        + f["n_experts"] * expert_params(f)


def active_layer_params(f: dict) -> int:
    """Matmul weights one token is multiplied with in one layer: attention,
    the router's full width, its ``top_k`` experts."""
    return attention_params(f) + router_params(f) \
        + f["top_k"] * expert_params(f)


def head_params(f: dict) -> int:
    return f["embed_dim"] * f["vocab_size"]


def attention_flops(f: dict, context: int) -> int:
    """One query position against ``context`` keys and values, all layers:
    q.k and p.v, two operations a multiply-add."""
    return 4 * f["n_layers"] * f["n_heads"] * f["head_dim"] * context


def serve_request_flops(f: dict, prompt_tokens: int, output_tokens: int,
                        steps: int | None = None) -> int:
    """Forward operations one request needs by the family's generation
    rule at block length ``B = f['block_length']`` and ``steps`` denoising
    steps a block (``None``: ``B``, the cells' setting), whatever
    implements it. The prompt's whole leading blocks once through the
    layers, each position against the positions up to its block's end, no
    head. Then block by block until prompt + output are covered, the last
    block whole: ``min(steps, m0)`` denoising passes and one commit pass,
    each over the block's ``B`` positions at 2 operations per active weight
    (attention, the router, ``top_k`` experts a token) and attention over
    the committed prefix and the block; the head only at the positions
    still masked going into a pass (none in the commit pass)."""
    size = int(f["block_length"])
    steps = size if steps is None else int(steps)
    layers = 2 * f["n_layers"] * active_layer_params(f)
    head = 2 * head_params(f)
    lead = prompt_tokens - prompt_tokens % size
    total = lead * layers + sum(
        attention_flops(f, (p // size + 1) * size) for p in range(lead))
    base, end = lead, prompt_tokens + output_tokens
    while base < end:
        masked = size - max(0, prompt_tokens - base)
        going_in = []
        for count in schedule(masked, steps):
            going_in.append(masked)
            masked -= count
        passes = len(going_in) + 1
        total += passes * size * (layers + attention_flops(f, base + size))
        total += head * sum(going_in)
        base += size
    return total


def moe_experts_call(f: dict, pairs: float, touched: float) -> dict:
    """The expert products of one pass or prefill (gate, up and down over
    the sorted pairs, all layers summed into ``pairs`` and ``touched``):
    two operations a weight a pair, and each expert that got a pair has its
    three matrices read once."""
    return {"flops": 2.0 * expert_params(f) * pairs,
            "bytes": float(expert_bytes(f)) * touched}


def paged_chunk_call(f: dict, block: int, prefix_tokens: float,
                     rows: float) -> dict:
    """One call of the prefix kernel (``_paged_chunk_call``; one layer, one
    pass): each live row's ``block`` query positions attend the row's
    committed prefix in its pages; ``prefix_tokens`` is that prefix summed
    over the ``rows`` live rows. Keys and values read once, q in, the
    partial output and its log-sum-exp back."""
    heads, kvh, d = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    return {"flops": 4.0 * heads * d * block * prefix_tokens,
            "bytes": 2.0 * kvh * d * BF16 * prefix_tokens
            + rows * block * heads * (d * (BF16 + 4) + 4)}
