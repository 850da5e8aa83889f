"""The plain reference of the ``laguna`` family (``model_type: laguna``,
https://huggingface.co/poolside/Laguna-XS.2): the decoder's forward pass in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision. No
kernel, no cache, no batching: attention is one masked softmax over the
whole sequence with the window's mask written out, the expert layer a sum
over experts, and nothing is imported from ``mlrun_tpu``.
``tests/laguna_reference.py`` re-exports it for the program's tests.

``x`` is the residual, ``RMS`` RMSNorm at ``norm_eps``; no product has a
bias. Every layer is ``x <- x + Attn_l(RMS(x))``, then ``x <- x +
MLP_l(RMS(x))``; after the last, ``RMS`` and the untied head.

``Attn_l``: ``q = x W_q`` has ``H_l`` heads of ``head_dim`` (``H_l`` the
layer's ``heads_per_layer``, by its kind in ``layer_types``); ``k = x W_k``
and ``v = x W_v`` have ``n_kv_heads``. q and k are rotated by the layer
kind's rope, pairs ``(i, i + r / 2)`` within the leading ``r`` rotated
dims:

- ``full_attention``: ``r = rope_partial x head_dim``; pair ``i`` turns at
  ``f_i = theta^(-2i/r)`` under YaRN (a ramp ``g_i`` from 0 at
  ``floor(c(beta_fast))`` to 1 at ``ceil(c(beta_slow))``, ``c(b) = r
  ln(original_max / (2 pi b)) / (2 ln theta)``; frequency ``f_i ((1 - g_i)
  + g_i / factor)``), and cos and sin carry ``rope_attention_factor``;
- ``sliding_attention``: ``r = window_rope_partial x head_dim`` at
  ``window_rope_theta``, plain.

Scores ``q . k / sqrt(head_dim)``; query head ``h`` reads key/value head ``h
// (H_l / n_kv_heads)``; the mask is causal, and for a window layer also ``i
- j < sliding_window``; softmax over keys; ``concat(sum p v) W_o``.

``MLP_l``: the leading ``dense`` layers ``(silu(x W_g) * x W_u) W_d``;
``sparse`` ones: ``s = sigmoid(x W_r)`` over all experts, ``T = top_k(s)``,
``w_e = routed_scale s_e / sum_T s``, ``y = sum_T w_e E_e(x) + S(x)`` with
every expert ``E_e`` and the shared ``S`` a SwiGLU of their widths, the
weight on the output. No token is dropped; with ``experts_held`` set,
routed experts outside it add nothing.

Departures from the published model, each also under ``assumed`` in
``benchmarks/configs/laguna-xs.2.json``: the weights are seeded random (the
program's recipe, ``make_weights``); the router has no selection bias;
``gating`` is read as the gated (SwiGLU) form of every MLP and expert, and
no attention output gate is instantiated; the shared expert is ungated; no
q/k norm; the rotated dims are the leading ones.

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
FAULTS = (None, "window_off", "window_off_by_one", "yarn_on_window",
          "full_rotation", "no_attention_factor", "drop_shared",
          "softmax_scores")
FULL, WINDOW = "full", "window"
LAYER_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
# the leaves drawn from the key, in the order of the keys split from it
# (mlrun_tpu/models/laguna.py draws the same)
DRAWN = ("embedding", "lm_head", "wq", "wq@window", "wk", "wv", "wo",
         "wo@window", "w_gate", "w_up", "w_down", "router", "experts_gate",
         "experts_up", "experts_down", "shared_gate", "shared_up",
         "shared_down")


def kind(f: dict, layer: int) -> str:
    return LAYER_KINDS[f["layer_types"][layer]]


def kind_index(f: dict, layer: int) -> int:
    """Where the layer sits among the layers of its kind."""
    return sum(kind(f, i) == kind(f, layer) for i in range(layer))


def first_dense(f: dict) -> int:
    lead = 0
    while lead < f["n_layers"] and f["mlp_layer_types"][lead] == "dense":
        lead += 1
    return lead


# -- weights, by the program's recipe (models/laguna.py init_params) ----------
@functools.partial(jax.jit, static_argnames=("fan_in", "shape"))
def _normal_leaf(key, fan_in: int, shape: tuple):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=(
    "fan_in", "n_layers", "held", "shape"))
def _expert_stack(key, fan_in: int, n_layers: int, held: tuple,
                  shape: tuple):
    """The experts ``held = (lo, hi)`` of every expert layer, [layers, hi -
    lo, *shape]: each expert's matrix from a key of its own (the leaf's,
    folded with the layer and the expert)."""
    def layer(at):
        def expert(index):
            k = jax.random.fold_in(jax.random.fold_in(key, at), index)
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(jnp.bfloat16)

        return jax.vmap(expert)(jnp.arange(*held))

    return jax.lax.map(layer, jnp.arange(n_layers))


def make_weights(f: dict, seed: int) -> dict:
    """bfloat16 weights for the fields ``f`` from ``PRNGKey(seed)``: normal
    x fan_in^-0.5 from one key a drawn leaf, norm scales 1, the router in
    float32 (of bfloat16 values); an expert's matrices from keys of its own
    (``_expert_stack``), only those of ``experts_held`` (None: all). Each
    kind's query and output projections are stacked over its layers, the
    window kind's under ``<name>@window``."""
    keys = dict(zip(DRAWN, jax.random.split(jax.random.PRNGKey(seed),
                                            len(DRAWN))))
    e, d, L = f["embed_dim"], f["head_dim"], f["n_layers"]
    kv = f["n_kv_heads"] * d
    dense = first_dense(f)
    Le, E, m = L - dense, f["n_experts"], f["expert_dim"]
    md, sd = f["mlp_dim"], f["shared_dim"]
    held = tuple(f.get("experts_held") or (0, E))
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)

    def drawn(name, fan_in, shape):
        return _normal_leaf(keys[name], fan_in, tuple(shape))

    layers = {"attn_norm_scale": ones((L, e)), "mlp_norm_scale": ones((L, e)),
              "wk": drawn("wk", e, (L, e, kv)), "wv": drawn("wv", e, (L, e, kv))}
    for which, mark in ((FULL, ""), (WINDOW, "@window")):
        layers_of = [i for i in range(L) if kind(f, i) == which]
        if layers_of:
            q = f["heads_per_layer"][layers_of[0]] * d
            layers["wq" + mark] = drawn("wq" + mark, e, (len(layers_of), e, q))
            layers["wo" + mark] = drawn("wo" + mark, q, (len(layers_of), q, e))
    if dense:
        layers.update({"w_gate": drawn("w_gate", e, (dense, e, md)),
                       "w_up": drawn("w_up", e, (dense, e, md)),
                       "w_down": drawn("w_down", md, (dense, md, e))})
    if Le:
        layers.update({
            "router": drawn("router", e, (Le, e, E)).astype(jnp.float32),
            "experts_gate": _expert_stack(keys["experts_gate"], e, Le, held,
                                          (e, m)),
            "experts_up": _expert_stack(keys["experts_up"], e, Le, held,
                                        (e, m)),
            "experts_down": _expert_stack(keys["experts_down"], m, Le, held,
                                          (m, e)),
            "shared_gate": drawn("shared_gate", e, (Le, e, sd)),
            "shared_up": drawn("shared_up", e, (Le, e, sd)),
            "shared_down": drawn("shared_down", sd, (Le, sd, e))})
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


def yarn_frequencies(f: dict, dim: int):
    """The ``dim / 2`` pair frequencies of the full layers under YaRN."""
    theta = f["rope_theta"]

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


def rope_tables(f: dict, which: str, positions, fault=None):
    """(cos, sin) [T, r / 2] of a layer of kind ``which``, where ``r`` is
    the number of rotated dims: the full kind's under YaRN with the
    attention factor, the window kind's plain. Faults: ``yarn_on_window``
    gives the window layers the full kind's tables, ``full_rotation``
    rotates all of a full layer's dims, ``no_attention_factor`` leaves the
    factor out."""
    d = f["head_dim"]
    if which == WINDOW and fault == "yarn_on_window":
        which = FULL
    if which == WINDOW:
        dim = int(d * f["window_rope_partial"])
        freqs = 1.0 / (f["window_rope_theta"] ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        scale = 1.0
    else:
        dim = d if fault == "full_rotation" else int(d * f["rope_partial"])
        freqs = yarn_frequencies(f, dim)
        scale = 1.0 if fault == "no_attention_factor" \
            else f["rope_attention_factor"]
    angles = positions.astype(jnp.float32)[:, None] * freqs
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotated(x, cos, sin):
    """x [T, heads, d]: the leading ``2 * pairs`` dims rotated, pairs (i, i
    + pairs), the rest as they are."""
    half = cos.shape[-1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(f: dict, u, lw, which: str, heads: int, quant=None,
              fault=None, q_block: int = 256):
    """``Attn_l`` over u [T, C] for a layer of kind ``which`` with ``heads``
    query heads, queries in blocks of ``q_block`` against the whole
    sequence under the mask written out. Faults: ``window_off`` (a window
    layer sees everything before it), ``window_off_by_one`` (it sees one
    position more)."""
    t = u.shape[0]
    kvh, d = f["n_kv_heads"], f["head_dim"]
    positions = jnp.arange(t)
    cos, sin = rope_tables(f, which, positions, fault)
    q = _rotated(_mm(u, lw["wq"], quant).reshape(t, heads, d), cos, sin)
    k = _rotated(_mm(u, lw["wk"], quant).reshape(t, kvh, d), cos, sin)
    v = _mm(u, lw["wv"], quant).reshape(t, kvh, d)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    width = f["sliding_window"] + (fault == "window_off_by_one")
    windowed = which == WINDOW and fault != "window_off"
    block = min(q_block, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    starts = jnp.arange(qp.shape[0]) * block

    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) * d ** -0.5
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if windowed:
            seen = seen & (i - j < width)
        attn = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", attn, v, precision=HIGHEST)

    out = jax.lax.map(one_block, (qp, starts)).reshape(-1, heads * d)[:t]
    return _mm(out, lw["wo"], quant)


def swiglu(u, w_gate, w_up, w_down, quant=None):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant),
               w_down, quant)


def route(f: dict, u, lw, quant=None, fault=None):
    """(gates [T, k] float32, experts [T, k] int32): sigmoid scores (softmax
    under ``softmax_scores``), the top k, their scores over their sum times
    ``routed_scale``."""
    logits = _mm(u, lw["router"], quant)
    scores = jax.nn.softmax(logits, axis=-1) if fault == "softmax_scores" \
        else jax.nn.sigmoid(logits)
    gates, experts = jax.lax.top_k(scores, f["top_k"])
    if f.get("norm_topk", True):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * f["routed_scale"], experts


def experts_mlp(f: dict, u, lw, quant=None, held=None, fault=None,
                shared: bool = True, moe_layer=0):
    """The sparse ``MLP_l`` over u [T, C], written as the sum over experts,
    one after another over all tokens with a gate of 0 where an expert was
    not chosen: no sort, no gather, one expert's weights in float32 at a
    time. ``lw['experts_*']`` are the stacks of every expert layer's
    experts, of which ``moe_layer``'s are read one at a time; ``held = (lo,
    hi)``: the stacks hold that range of experts, and the others add
    nothing. ``shared`` False leaves the shared expert out (for the sum of
    several shares)."""
    gates, experts = route(f, u, lw, quant, fault)
    lo, hi = (0, f["n_experts"]) if held is None else held

    def one_expert(total, index):
        gate = jnp.sum(jnp.where(experts == index, gates, 0.0), axis=-1)
        w_gate, w_up, w_down = (
            lw[name][moe_layer, index - lo]
            for name in ("experts_gate", "experts_up", "experts_down"))
        return total + gate[:, None] * swiglu(u, w_gate, w_up, w_down,
                                              quant), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                            jnp.arange(lo, hi))
    if shared and fault != "drop_shared":
        total = total + swiglu(u, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"], quant)
    return total


@functools.partial(jax.jit, static_argnames=(
    "fields", "which", "heads", "dense", "held", "quant", "fault"))
def _layer(fields, x, lw, moe_layer, which, heads, dense, held, quant,
           fault):
    """One layer over x [T, C]: attention of its kind, then the dense MLP
    or expert layer ``moe_layer`` of the experts' stacks."""
    f = dict(fields)
    x = x + attention(f, _rms_norm(x, lw["attn_norm_scale"], f["norm_eps"]),
                      lw, which, heads, quant, fault)
    u = _rms_norm(x, lw["mlp_norm_scale"], f["norm_eps"])
    if dense:
        return x + swiglu(u, lw["w_gate"], lw["w_up"], lw["w_down"], quant)
    return x + experts_mlp(f, u, lw, quant, held, fault, moe_layer=moe_layer)


def layer_weights(f: dict, weights: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves out of the stacked tree, under the plain
    names: its kind's query and output projections, the dense MLP's (over
    the leading dense layers) or the expert layer's (over the layers after
    them; the experts' stacks stay whole), and its own of the rest."""
    dense, which = first_dense(f), kind(f, layer)
    out = {}
    for name, leaf in weights["layers"].items():
        base, _, mark = name.partition("@")
        if base in ("wq", "wo"):
            if (mark or FULL) == which:
                out[base] = leaf[kind_index(f, layer)]
        elif base in ("w_gate", "w_up", "w_down"):
            if layer < dense:
                out[base] = leaf[layer]
        elif base.startswith("experts_"):
            if layer >= dense:
                out[base] = leaf
        elif base.startswith(("shared_", "router")):
            if layer >= dense:
                out[base] = leaf[layer - dense]
        else:
            out[base] = leaf[layer]
    return out


@functools.partial(jax.jit, static_argnames=("fields", "rows", "quant"))
def _head(fields, weights, x, row_start, rows, quant):
    f = dict(fields)
    h = _rms_norm(x, weights["final_norm_scale"], f["norm_eps"])
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, row_start, rows, axis=0)
    return _mm(h, weights["lm_head"], quant)


def embedded(weights: dict, ids):
    """The sequence's embeddings [T, C] (bfloat16 values), which a caller
    may take before it lets go of the table."""
    return weights["embedding"][jnp.asarray(ids, jnp.int32)]


def _static(f: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in f.items()))


def forward(f: dict, weights: dict, ids, quant=None, fault=None, rows=None,
            embeddings=None):
    """Logits [T, V] float32 at every position of the sequence ``ids`` [T],
    layer by layer (one layer's weights in float32 at a time). ``rows =
    (start, count)`` returns only those positions' logits; ``embeddings``
    [T, C]: the sequence's, where the caller took them already; the
    weights hold the fields' ``experts_held``."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    held = f.get("experts_held")
    held = None if held is None else tuple(held)
    fields = _static(f)
    dense = first_dense(f)
    x = (embedded(weights, ids) if embeddings is None
         else embeddings).astype(jnp.float32)
    for layer in range(f["n_layers"]):
        x = _layer(fields, x, layer_weights(f, weights, layer),
                   jnp.int32(max(0, layer - dense)), kind(f, layer),
                   f["heads_per_layer"][layer], layer < dense, held, quant,
                   fault)
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
