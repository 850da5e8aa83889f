"""The plain reference of the ``xing4`` family (``model_type: xing4_0``,
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B): the decoder's forward
pass in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision. No kernel, no cache, no batching, the expanded attention only,
the expert layer as a sum over experts, and nothing imported from
``mlrun_tpu``. ``tests/xing4_reference.py`` re-exports it for the program's
tests.

A token's residual state is ``X`` in R^{n x C} (n = ``hc_mult`` streams);
``X_0`` is the token's embedding in every stream. Each layer has two
sub-layers, attention then MLP, each wrapped alike with mixing parameters
of its own:

1. ``x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)`` times a learned scale;
   ``H_pre = sigmoid(a_pre x~ P_pre + b_pre)`` [n], ``H_post = 2
   sigmoid(a_post x~ P_post + b_post)`` [n], ``H_res = SK(clip(a_res
   mat(x~ P_res) + b_res, -30, 30))`` [n, n]: ``M = exp(.)``, then
   ``hc_iters`` times each column divided by its sum and each row by its
   (``+ hc_eps`` in each denominator).
2. ``u = H_pre X``; ``y = F(rmsnorm(u))``; ``X <- H_res X + H_post^T y``.
3. F = latent attention: ``c_q = rmsnorm(h W_dq)``; a head's ``[q_nope;
   q_rope] = c_q W_uq``; ``[c_kv; k_r] = h W_dkv``, ``c_kv = rmsnorm(c_kv)``;
   ``q_rope`` and ``k_r`` rotated under YaRN (``k_r`` shared by the heads);
   a head's ``[k_nope; v] = c_kv W_ukv``; scores ``q . [k_nope; k_r] x s``,
   ``s = (nope + rope)^-0.5 x m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
   1``; causal; softmax; ``concat(sum p v) W_o``.
4. YaRN: pair ``i`` of rope/2 has ``f_i = theta^(-2i/rope)``; the correction
   range ``[lo, hi]`` from ``beta_fast`` and ``beta_slow`` over the original
   positions (floor and ceiling of the published formula); a ramp ``r_i``
   from 0 at ``lo`` to 1 at ``hi``; frequency ``f_i ((1 - r_i) + r_i /
   factor)``; the tables carry ``m(mscale) / m(mscale_all_dim)``.
5. F = MLP. The first ``first_k_dense`` layers: ``W_down (silu(W_gate h) *
   W_up h)``. The others: ``s = sigmoid(h W_r)`` over all experts; chosen =
   top-k of ``s + b``; ``g = s[chosen]``, ``g <- g / (sum g + 1e-20) x
   routed_scale``; ``y = sum_i g_i E_i(h) + E_shared(h)``. No token is
   dropped; with ``held`` set, routed experts outside it add nothing.
6. ``h = sum over the streams of X_L``; ``rmsnorm(h)``; the untied head.

Departures from the published model, each also under ``assumed`` in
``benchmarks/configs/xing4.0-29b-a4b.json``: the weights are seeded random
(the program's recipe, ``make_weights``); the multi-token-prediction module
(``num_nextn_predict_layers``) is not instantiated; the rope pairs are
(first half, second half) of the rope entries (the published code
de-interleaves first: with seeded weights a fixed permutation of columns);
where ``hc_eps`` sits, the clip before the exponential, column before row,
the streams' start (copied) and end (summed) and the mixing norm's learned
scale are the reading of the mHC paper (arXiv:2512.24880) written above.

``quant="int8"`` is the control: every matmul's weights (per output channel)
and inputs (per row) rounded to int8 levels. ``fault`` plants one fault for
the readings that set the limits (``FAULTS``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = (None, "rope_unrotated", "no_mscale", "drop_shared", "no_bias",
          "res_identity", "no_sinkhorn")
SUBLAYERS = ("attn", "mlp")
# the leaves drawn from the key, in the order of the keys split from it
# (mlrun_tpu/models/xing4.py draws the same)
DRAWN = ("embedding", "w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "w_gate",
         "w_up", "w_down", "router", "router_bias", "experts_gate",
         "experts_up", "experts_down", "shared_gate", "shared_up",
         "shared_down", "lm_head") + tuple(
    f"hc_{sub}_{part}" for sub in SUBLAYERS
    for part in ("pre", "post", "res"))
ROUTER_BIAS_STD = 0.1
HC_RES_BIAS = 2.0


# -- weights, by the program's recipe (models/xing4.py init_params) -----------
@functools.partial(jax.jit, static_argnames=("fan_in", "shape"))
def _normal_leaf(key, fan_in: int, shape: tuple):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(jnp.bfloat16)


def make_weights(f: dict, seed: int, held=None) -> dict:
    """bfloat16 weights for the fields ``f`` from ``PRNGKey(seed)``: normal x
    fan_in^-0.5 from one key a drawn leaf, norm scales 1, the router in
    float32 (of bfloat16 values); the mixing gains 1, its read and write
    biases 0, the mix's bias ``HC_RES_BIAS`` x identity, the router's
    selection bias normal x ``ROUTER_BIAS_STD``."""
    keys = dict(zip(DRAWN, jax.random.split(jax.random.PRNGKey(seed),
                                            len(DRAWN))))
    e, h, n = f["embed_dim"], f["n_heads"], f["hc_mult"]
    L, Ld = f["n_layers"], f["first_k_dense"]
    Lm = L - Ld
    E, m, md = f["n_experts"], f["expert_dim"], f["mlp_dim"]
    rq, rkv = f["q_lora_rank"], f["kv_lora_rank"]
    dk = f["nope_dim"] + f["rope_dim"]
    shared = f["n_shared_experts"] * m
    wide = n * e
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)

    def drawn(name, fan_in, shape):
        return _normal_leaf(keys[name], fan_in, tuple(shape))

    layers = {
        "attn_norm_scale": ones((L, e)), "mlp_norm_scale": ones((L, e)),
        "w_dq": drawn("w_dq", e, (L, e, rq)),
        "q_norm_scale": ones((L, rq)),
        "w_uq": drawn("w_uq", rq, (L, rq, h * dk)),
        "w_dkv": drawn("w_dkv", e, (L, e, rkv + f["rope_dim"])),
        "kv_norm_scale": ones((L, rkv)),
        "w_ukv": drawn("w_ukv", rkv,
                       (L, rkv, h * (f["nope_dim"] + f["v_dim"]))),
        "wo": drawn("wo", h * f["v_dim"], (L, h * f["v_dim"], e)),
        "w_gate": drawn("w_gate", e, (Ld, e, md)),
        "w_up": drawn("w_up", e, (Ld, e, md)),
        "w_down": drawn("w_down", md, (Ld, md, e)),
        "router": drawn("router", e, (Lm, e, E)).astype(jnp.float32),
        "router_bias": jax.random.normal(
            keys["router_bias"], (Lm, E), jnp.float32) * ROUTER_BIAS_STD,
        "experts_gate": drawn("experts_gate", e, (Lm, E, e, m)),
        "experts_up": drawn("experts_up", e, (Lm, E, e, m)),
        "experts_down": drawn("experts_down", m, (Lm, E, m, e)),
        "shared_gate": drawn("shared_gate", e, (Lm, e, shared)),
        "shared_up": drawn("shared_up", e, (Lm, e, shared)),
        "shared_down": drawn("shared_down", shared, (Lm, shared, e)),
    }
    for sub in SUBLAYERS:
        layers[f"hc_{sub}_scale"] = ones((L, wide))
        for part, out in (("pre", n), ("post", n), ("res", n * n)):
            layers[f"hc_{sub}_{part}"] = drawn(f"hc_{sub}_{part}", wide,
                                               (L, wide, out))
        layers[f"hc_{sub}_gain"] = jnp.ones((L, 3), jnp.float32)
        layers[f"hc_{sub}_pre_bias"] = jnp.zeros((L, n), jnp.float32)
        layers[f"hc_{sub}_post_bias"] = jnp.zeros((L, n), jnp.float32)
        layers[f"hc_{sub}_res_bias"] = jnp.broadcast_to(
            HC_RES_BIAS * jnp.eye(n, dtype=jnp.float32), (L, n, n))
    if held is not None:
        for name in ("experts_gate", "experts_up", "experts_down"):
            layers[name] = layers[name][:, held[0]:held[1]]
    return {"embedding": drawn("embedding", e, (f["vocab_size"], e)),
            "layers": layers, "final_norm_scale": ones((e,)),
            "lm_head": drawn("lm_head", e, (e, f["vocab_size"]))}


# -- pieces -------------------------------------------------------------------
def _int8_levels(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, quant, spec="...i,io->...o", w_axis=0):
    """x float32 times w (bfloat16 values) in float32; ``w_axis`` is the
    weight's input axis (the control rounds along it)."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        x = _int8_levels(x, axis=-1)
        w = _int8_levels(w, axis=w_axis)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(f: dict):
    """The rope/2 pair frequencies under YaRN (step 4)."""
    dim, theta = f["rope_dim"], f["rope_theta"]

    def correction_dim(rotations):
        return dim * math.log(f["rope_original_max"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(f["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(f["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs * ((1.0 - ramp) + ramp / f["rope_factor"])


def _rope(f: dict, x, positions):
    """x [T, ..., rope]; rotate the (first half, second half) pairs."""
    angles = positions.astype(jnp.float32)[:, None] * yarn_frequencies(f)
    carried = yarn_mscale(f["rope_factor"], f["rope_mscale"]) \
        / yarn_mscale(f["rope_factor"], f["rope_mscale_all_dim"])
    cos, sin = jnp.cos(angles) * carried, jnp.sin(angles) * carried
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(f: dict, fault=None) -> float:
    scale = (f["nope_dim"] + f["rope_dim"]) ** -0.5
    if fault == "no_mscale":
        return scale
    return scale * yarn_mscale(f["rope_factor"],
                               f["rope_mscale_all_dim"]) ** 2


def sinkhorn(logits, iters: int, eps: float, clamp: float):
    """[..., n, n] -> doubly stochastic (step 1)."""
    m = jnp.exp(jnp.clip(logits, -clamp, clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def mixing(f: dict, x, lw, sub: str, quant=None, fault=None):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the state ``x``
    [T, n, C] for sub-layer ``sub`` (step 1)."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + f["hc_eps"])
    flat = flat * lw[f"hc_{sub}_scale"].astype(jnp.float32)
    gain = lw[f"hc_{sub}_gain"]
    pre = jax.nn.sigmoid(gain[0] * _mm(flat, lw[f"hc_{sub}_pre"], quant)
                         + lw[f"hc_{sub}_pre_bias"])
    post = 2.0 * jax.nn.sigmoid(
        gain[1] * _mm(flat, lw[f"hc_{sub}_post"], quant)
        + lw[f"hc_{sub}_post_bias"])
    res = gain[2] * _mm(flat, lw[f"hc_{sub}_res"], quant).reshape(t, n, n) \
        + lw[f"hc_{sub}_res_bias"]
    if fault == "res_identity":
        mix = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), (t, n, n))
    elif fault == "no_sinkhorn":
        mix = jnp.exp(jnp.clip(res, -f["hc_clamp"], f["hc_clamp"]))
    else:
        mix = sinkhorn(res, f["hc_iters"], f["hc_eps"], f["hc_clamp"])
    return pre, post, mix


def latent_attention(f: dict, h, lw, positions, quant=None, fault=None,
                     q_block: int = 512):
    """Step 3 over h [T, C], the expanded form, queries in blocks of
    ``q_block`` so that the scores of a long sequence fit."""
    t = h.shape[0]
    heads, nope, rope, vd = f["n_heads"], f["nope_dim"], f["rope_dim"], \
        f["v_dim"]
    eps, rank = f["norm_eps"], f["kv_lora_rank"]
    c_q = _rms_norm(_mm(h, lw["w_dq"], quant), lw["q_norm_scale"], eps)
    q = _mm(c_q, lw["w_uq"], quant).reshape(t, heads, nope + rope)
    down = _mm(h, lw["w_dkv"], quant)
    c_kv = _rms_norm(down[:, :rank], lw["kv_norm_scale"], eps)
    k_r = down[:, rank:]
    if fault != "rope_unrotated":
        k_r = _rope(f, k_r, positions)
    q = jnp.concatenate([q[..., :nope], _rope(f, q[..., nope:], positions)],
                        axis=-1)
    kv = _mm(c_kv, lw["w_ukv"], quant).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(f, fault)
    block = min(q_block, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, heads, nope + rope)
    starts = jnp.arange(qp.shape[0]) * block

    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        attn = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", attn, v, precision=HIGHEST)

    out = jax.lax.map(one_block, (qp, starts)).reshape(-1, heads * vd)[:t]
    return _mm(out, lw["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant=None):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant),
               w_down, quant)


def route(f: dict, h, lw, quant=None, fault=None):
    """(gates [T, k] float32, experts [T, k] int32): sigmoid scores, choice
    by score + bias, gates from the scores, renormalised and scaled."""
    scores = jax.nn.sigmoid(_mm(h, lw["router"], quant))
    biased = scores if fault == "no_bias" else scores + lw["router_bias"]
    _, experts = jax.lax.top_k(biased, f["top_k"])
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if f.get("norm_topk", True):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * f["routed_scale"], experts


def experts_mlp(f: dict, h, lw, quant=None, held=None, fault=None,
                shared: bool = True, moe_layer=0):
    """Step 5's expert layer over h [T, C], written as the sum over experts,
    one after another over all tokens with a gate of 0 where an expert was
    not chosen: no sort, no gather, one expert's weights in float32 at a
    time. ``lw['experts_*']`` are the stacks of every expert layer's
    experts, of which ``moe_layer``'s are read one at a time (a layer's
    slice of the stack would be a copy of all its experts); ``held = (lo,
    hi)``: the stacks hold that range of experts."""
    gates, experts = route(f, h, lw, quant, fault)
    lo, hi = (0, f["n_experts"]) if held is None else held

    def one_expert(total, index):
        gate = jnp.sum(jnp.where(experts == index, gates, 0.0), axis=-1)
        w_gate, w_up, w_down = (
            lw[name][moe_layer, index - lo]
            for name in ("experts_gate", "experts_up", "experts_down"))
        return total + gate[:, None] * swiglu(h, w_gate, w_up, w_down,
                                              quant), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                            jnp.arange(lo, hi))
    if shared and fault != "drop_shared":
        total = total + swiglu(h, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"], quant)
    return total


@functools.partial(jax.jit, static_argnames=("fields", "dense", "held",
                                             "quant", "fault"))
def _layer(fields, x, lw, positions, moe_layer, dense, held, quant, fault):
    """One layer over the state x [T, n, C]: attention, then the dense MLP
    (``dense``) or expert layer ``moe_layer`` of the experts' stacks, each
    read, computed and written back as step 2 says."""
    f = dict(fields)

    def sublayer(x, sub, compute):
        pre, post, mix = mixing(f, x, lw, sub, quant, fault)
        u = jnp.einsum("tn,tnc->tc", pre, x, precision=HIGHEST)
        y = compute(_rms_norm(u, lw[f"{sub}_norm_scale"], f["norm_eps"]))
        return jnp.einsum("tij,tjc->tic", mix, x, precision=HIGHEST) \
            + post[:, :, None] * y[:, None, :]

    x = sublayer(x, "attn", lambda h: latent_attention(
        f, h, lw, positions, quant, fault))
    if dense:
        return sublayer(x, "mlp", lambda h: swiglu(
            h, lw["w_gate"], lw["w_up"], lw["w_down"], quant))
    return sublayer(x, "mlp", lambda h: experts_mlp(
        f, h, lw, quant, held, fault, moe_layer=moe_layer))


def layer_weights(f: dict, weights: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves out of the stacked tree: the dense MLP's
    are stacked over the leading dense layers, the expert layer's over the
    layers after them; the experts' stacks stay whole."""
    first = f["first_k_dense"]
    out = {}
    for name, leaf in weights["layers"].items():
        if name in ("w_gate", "w_up", "w_down"):
            if layer < first:
                out[name] = leaf[layer]
        elif name.startswith("experts_"):
            if layer >= first:
                out[name] = leaf
        elif name.startswith(("shared_", "router")):
            if layer >= first:
                out[name] = leaf[layer - first]
        else:
            out[name] = leaf[layer]
    return out


@functools.partial(jax.jit, static_argnames=("fields", "rows", "quant"))
def _head(fields, weights, x, row_start, rows, quant):
    f = dict(fields)
    h = _rms_norm(jnp.sum(x, axis=1), weights["final_norm_scale"],
                  f["norm_eps"])
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, row_start, rows, axis=0)
    return _mm(h, weights["lm_head"], quant)


def embedded(weights: dict, ids):
    """The sequence's embeddings [T, C] (bfloat16 values), which a caller
    may take before it lets go of the table."""
    return weights["embedding"][jnp.asarray(ids, jnp.int32)]


def forward(f: dict, weights: dict, ids, held=None, quant=None, fault=None,
            rows=None, embeddings=None):
    """Logits [T, V] float32 at every position of the sequence ``ids`` [T],
    layer by layer (one layer's weights in float32 at a time). ``rows =
    (start, count)`` returns only those positions' logits; ``embeddings``
    [T, C]: the sequence's, where the caller took them already."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fields = tuple(sorted(f.items()))
    x = (embedded(weights, ids) if embeddings is None
         else embeddings).astype(jnp.float32)
    positions = jnp.arange(x.shape[0])
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], f["hc_mult"],
                                         x.shape[1]))
    first = f["first_k_dense"]
    for layer in range(f["n_layers"]):
        x = _layer(fields, x, layer_weights(f, weights, layer), positions,
                   jnp.int32(max(0, layer - first)), layer < first,
                   None if held is None else tuple(held), quant, fault)
    start, count = (0, None) if rows is None else rows
    return _head(fields, weights, x, jnp.asarray(start, jnp.int32), count,
                 quant)


# -- what a serve cell compares -----------------------------------------------
def padded_ids(prompt: list, served: list, pad_to: int) -> np.ndarray:
    ids = list(prompt) + list(served)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:len(ids)] = ids
    return tokens


def served_logits(f: dict, weights: dict, prompt: list, served: list,
                  pad_to: int, quant=None, fault=None, embeddings=None):
    """Logits [len(served), V] of the reference over ``prompt + served``
    (padded to ``pad_to``; the causal mask keeps the padding out): row ``i``
    is what it predicts for the position of ``served[i]``."""
    return forward(f, weights, padded_ids(prompt, served, pad_to),
                   quant=quant, fault=fault, embeddings=embeddings,
                   rows=(len(prompt) - 1, len(served)))


def gap_below_best(logits, tokens) -> np.ndarray:
    """For each row, the reference's best logit minus its logit of the
    row's token (0 where the token is the reference's own choice)."""
    logits = np.asarray(logits, np.float32)
    rows = np.arange(len(tokens))
    return logits.max(axis=-1) - logits[rows, np.asarray(tokens)]
