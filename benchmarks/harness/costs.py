"""Operations and bytes that the algorithm needs, as functions of shapes.

Everything here counts what has to be done, not what the program does:
recomputation (remat), padding to a bucket or a page, and products that a
frozen weight never needs are left out. ``f`` is a configuration's fields
under the names of ``cells.llama_fields``.
"""

from __future__ import annotations

import json
import os

BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, not a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fp:
        table = json.load(fp)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(f: dict) -> int:
    """Weights of one layer's seven products."""
    e, m = f["embed_dim"], f["mlp_dim"]
    q = f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    return e * q + 2 * e * kv + q * e + 3 * e * m


def head_params(f: dict) -> int:
    return f["embed_dim"] * f["vocab_size"]


def matmul_params(f: dict) -> int:
    """Weights a token is multiplied with on its way to its logits (the
    embedding is a lookup)."""
    return f["n_layers"] * layer_matmul_params(f) + head_params(f)


def frozen_params(f: dict) -> int:
    """All weights held: embedding, layers with their norms, final norm,
    head."""
    head = 0 if f["tie_embeddings"] else head_params(f)
    return (f["vocab_size"] * f["embed_dim"] + f["n_layers"] * (
        layer_matmul_params(f) + 2 * f["embed_dim"]) + f["embed_dim"] + head)


def attention_flops(f: dict, context: int) -> int:
    """One query position against ``context`` keys and values, all layers:
    q.k and p.v, two operations a multiply-add."""
    return 4 * f["n_layers"] * f["n_heads"] * f["head_dim"] * context


def serve_request_flops(f: dict, prompt_tokens: int, output_tokens: int) -> int:
    """Forward operations one request needs: every prompt position through
    the layers (causal attention over what precedes it), the head once at
    the prompt's end, and each further output token through layers and
    head over its context. The last output token is sampled and never fed
    back."""
    layers = 2 * f["n_layers"] * layer_matmul_params(f)
    head = 2 * head_params(f)
    fed = prompt_tokens + max(0, output_tokens - 1)
    attn = sum(attention_flops(f, p + 1) for p in range(fed))
    return fed * layers + output_tokens * head + attn


def lora_train_flops_per_token(f: dict, seq_len: int) -> float:
    """Required operations per trained token for LoRA on a frozen base:
    the forward products (2 per weight) and the backward products for the
    activations' gradients (2 per weight); no gradient for a frozen weight,
    nothing recomputed. Causal attention: two products forward (q.k, p.v) and
    four backward (dv, dp, dq, dk; the scores' recomputation is left out),
    so three forwards, over
    the mean context (seq_len + 1) / 2. The adapters' own products
    (rank << width) are left out."""
    dense = 4.0 * matmul_params(f)
    context = (seq_len + 1) / 2.0
    attn = 3.0 * attention_flops(f, 1) * context
    return dense + attn


def paged_decode_call(f: dict, rows: float, context: float) -> dict:
    """One ``paged_decode`` call (one layer, one tick): each of ``rows``
    live rows reads the keys and values of its ``context`` tokens and
    multiplies with them; q in, the output back."""
    heads, kvh, d = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    tokens = float(rows) * float(context)
    return {"flops": 4.0 * heads * d * tokens,
            "bytes": 2.0 * kvh * d * BF16 * tokens
            + 2.0 * rows * heads * d * BF16}


def flash_call(f: dict, batch: int, seq_len: int, products: int,
               tensors: int) -> dict:
    """One causal flash-attention kernel call over [batch, heads, seq, d].
    ``products`` is the number of seq x seq x d products the kernel's
    algorithm needs over the lower triangle: 2 forward (q.k, p.v); 4 in the
    backward kernel for dk and dv (the scores again, dv, dp, dk); 3 in the
    one for dq (the scores again, dp, dq). ``tensors`` is how many
    [batch, heads, seq, d] arrays it reads or writes once."""
    heads, d = f["n_heads"], f["head_dim"]
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    return {"flops": 2.0 * d * pairs * products,
            "bytes": float(tensors * batch * heads * seq_len * d * BF16)}


def roofline_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which limit sets it."""
    by_flops = cost["flops"] / peak["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
