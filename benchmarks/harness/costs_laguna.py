"""Operations and bytes that the ``laguna`` family's algorithm needs, as
functions of shapes (``f``: the fields of ``family_laguna.fields``). As in
``costs.py`` this counts what has to be done, whatever implements it:
padding to a bucket, a chunk or a lane, dead rows of a tick, a ring's
entries past a short context, the keys of a window layer's query that lie
outside its window, and products over experts a token was not routed to
are left out.
"""

from __future__ import annotations

BF16 = 2
LAYER_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def kind_layers(f: dict, kind: str) -> int:
    """Layers of ``kind``: ``full`` and ``window`` by ``layer_types``,
    ``mlp`` the expert layers (``sparse`` in ``mlp_layer_types``)."""
    if kind == "mlp":
        return sum(t == "sparse" for t in f["mlp_layer_types"])
    return sum(LAYER_KINDS[t] == kind for t in f["layer_types"])


def heads(f: dict, kind: str) -> int:
    """Query heads of a layer of ``kind``."""
    return next(h for h, t in zip(f["heads_per_layer"], f["layer_types"])
                if LAYER_KINDS[t] == kind)


def attention_params(f: dict, kind: str) -> int:
    """Weights of a layer's four attention products."""
    e, d = f["embed_dim"], f["head_dim"]
    return 2 * e * heads(f, kind) * d + 2 * e * f["n_kv_heads"] * d


def expert_params(f: dict) -> int:
    """Weights of one routed expert: gate, up and down."""
    return 3 * f["embed_dim"] * f["expert_dim"]


def expert_bytes(f: dict) -> int:
    """One expert's three matrices as stored (bfloat16)."""
    return expert_params(f) * BF16


def active_params(f: dict) -> float:
    """Matmul weights one token is multiplied with on its way through the
    layers: each layer's attention products by its kind, the dense MLP of
    the leading layers, and in an expert layer the router, the shared
    expert and ``top_k`` routed experts."""
    e = f["embed_dim"]
    dense = sum(t == "dense" for t in f["mlp_layer_types"])
    return (sum(kind_layers(f, k) * attention_params(f, k)
                for k in ("full", "window"))
            + dense * 3 * e * f["mlp_dim"]
            + kind_layers(f, "mlp") * (
                e * f["n_experts"] + 3 * e * f["shared_dim"]
                + f["top_k"] * expert_params(f)))


def head_params(f: dict) -> int:
    return f["embed_dim"] * f["vocab_size"]


def attention_pair_flops(f: dict, kind: str) -> int:
    """One query position against one key, one layer of ``kind``: q.k and
    p.v over all its query heads."""
    return 4 * heads(f, kind) * f["head_dim"]


def window_pairs(f: dict, start: int, tokens: int) -> int:
    """Query-key pairs inside the window of ``tokens`` positions from
    ``start``: the one at ``p`` sees ``min(p + 1, sliding_window)``."""
    width = f["sliding_window"]
    short = max(0, min(tokens, width - 1 - start))
    return short * start + short * (short + 1) // 2 \
        + (tokens - short) * width


def serve_request_flops(f: dict, prompt_tokens: int,
                        output_tokens: int) -> float:
    """Forward operations one request needs: every position that is fed
    (the prompt and all output tokens but the last) through the layers at 2
    operations an active weight; a full layer's attention of each fed
    position over itself and what precedes it, a window layer's over the
    window; the head at the positions that are read (one an output
    token)."""
    fed = prompt_tokens + max(0, output_tokens - 1)
    return (fed * 2.0 * active_params(f)
            + kind_layers(f, "full") * fed * (fed + 1) // 2
            * attention_pair_flops(f, "full")
            + kind_layers(f, "window") * window_pairs(f, 0, fed)
            * attention_pair_flops(f, "window")
            + output_tokens * 2.0 * head_params(f))


def window_decode_call(f: dict, rows: float, keys: float) -> dict:
    """One ``window_decode`` call (one window layer, one tick): ``keys``
    keys and values over the tick's rows (each row's own and what precedes
    it in the window: ``window_keys``), 4,096 B a key at the published
    widths, and q in and the output back a row; q.k and p.v over all query
    heads a key."""
    h, kvh, d = heads(f, "window"), f["n_kv_heads"], f["head_dim"]
    return {"flops": 4.0 * h * d * keys,
            "bytes": 2.0 * kvh * d * BF16 * keys
            + 2.0 * rows * h * d * BF16}


def window_prefill_call(f: dict, pairs: float, tokens: float,
                        calls: float = 1.0) -> dict:
    """The window layer's chunk attention over ``tokens`` real prompt
    tokens in ``calls`` dispatches: ``pairs`` query-key pairs inside the
    window (``window_prefill_pairs``) at q.k and p.v over all query heads;
    q in and the output back a token, and the keys and values of the
    chunk's tokens and of the window before each chunk read once."""
    h, kvh, d = heads(f, "window"), f["n_kv_heads"], f["head_dim"]
    keys = tokens + calls * f["sliding_window"]
    return {"flops": 4.0 * h * d * pairs,
            "bytes": 2.0 * tokens * h * d * BF16
            + 2.0 * kvh * d * BF16 * keys}


def moe_experts_call(f: dict, pairs: float, touched: float) -> dict:
    """The routed experts' products of one dispatch (gate, up and down over
    the sorted pairs, all expert layers summed into ``pairs`` and
    ``touched``): two operations a weight a pair, and each expert that got
    a pair has its three matrices read once. The shared expert is a dense
    product and is not counted here."""
    return {"flops": 2.0 * expert_params(f) * pairs,
            "bytes": float(expert_bytes(f)) * touched}


def paged_decode_call(f: dict, rows: float, context: float) -> dict:
    """One ``paged_decode`` call (one full layer, one tick): each of
    ``rows`` live rows reads the keys and values of its ``context`` tokens
    and multiplies with them; q in, the output back."""
    h, kvh, d = heads(f, "full"), f["n_kv_heads"], f["head_dim"]
    tokens = float(rows) * float(context)
    return {"flops": 4.0 * h * d * tokens,
            "bytes": 2.0 * kvh * d * BF16 * tokens
            + 2.0 * rows * h * d * BF16}
