"""The plain reference of the ``nemotronh`` family (``model_type:
nemotron_h``, https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16):
the decoder's forward pass in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision. No kernel, no cache, no batching, the
state-space layer as **the recurrence itself, one token at a time under
``lax.scan``** (never the chunked form, so that the program's chunked
prefill and its one-token update are both held to the definition), the
expert layer as a sum over experts, attention as a full causal product, and
nothing imported from ``mlrun_tpu``. ``tests/nemotron_h_reference.py``
re-exports it for the program's tests.

Every layer is one sub-layer alone, of the kind its character of ``pattern``
names: ``x <- x + mixer(rmsnorm(x, w, eps))``; after the last, the final
norm and the untied head.

``M``, Mamba-2 (H heads of P, G groups, state N, kernel K; d_inner = H P)::

    [z | xBC | dt] = u W_in
    xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-K+1+j})      depthwise, causal
    [x | B | C] = xBC           x: [H, P]   B, C: [G, N]; head h reads group h // (H / G)
    dt_t  = softplus(dt_t + dt_bias)
    h_t   = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t    A = -exp(A_log); h: [H, P, N]
    y_t   = h_t . C_t + D x_t
    y_t   = group_rmsnorm(y_t * silu(z_t), w_n, eps)      groups of d_inner / G
    out_t = y_t W_out

``E``: ``s = sigmoid(u W_r)``; chosen = top-k of ``s + b``; gates = ``s`` at
the chosen over their sum, times ``routed_scale``; an expert is ``relu(u
W_up)^2 W_down``; the shared expert the same at its own width; output =
routed + shared. No token is dropped; with ``held`` set, routed experts
outside it add nothing.

``*``: q, k, v by three products, no bias, **no rotary embedding**, causal
softmax at ``head_dim^-0.5`` over ``n_kv_heads`` key/value heads shared by
groups of query heads, ``W_o``.

Departures from the published model, each also under ``assumed`` in
``benchmarks/configs/nemotron-3-nano-30b-a3b.json``: the weights are seeded
random (the program's recipe, ``make_weights``); the recurrent state is
float32; no rotary embedding in the attention layers; the column order ``[z
| xBC | dt]`` and ``[x | B | C]``; ``dt`` is not clipped after its softplus.

``quant="int8"`` is the control: every matmul's weights (per output channel)
and inputs (per row) rounded to int8 levels. ``state_dtype="bfloat16"``
keeps the recurrent state rounded to bfloat16 after every token. ``fault``
plants one fault for the readings that set the limits (``FAULTS``);
``fault_at`` is the position the faults that happen at one token strike at
(the first decoded token).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = (None, "state_reset", "conv_dropped", "pad_integrated",
          "no_dt_bias", "no_skip", "ungrouped_norm", "silu_experts",
          "drop_shared", "no_bias")
KINDS = {"M": "ssm", "*": "attn", "E": "mlp"}
# the leaves drawn from the key, in the order of the keys split from it
# (mlrun_tpu/models/nemotron_h.py draws the same)
DRAWN = ("embedding", "lm_head", "ssm_in", "ssm_conv_w", "ssm_conv_b",
         "ssm_a_log", "ssm_dt_bias", "ssm_out", "wq", "wk", "wv", "wo",
         "router", "router_bias", "experts_up", "experts_down", "shared_up",
         "shared_down")
ROUTER_BIAS_STD = 0.1
CONV_BIAS_STD = 0.1


def kind_layers(f: dict, kind: str) -> int:
    return sum(KINDS[c] == kind for c in f["pattern"])


# -- weights, by the program's recipe (models/nemotron_h.py init_params) ------
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
    float32 (of bfloat16 values); ``A = -uniform[1, 16]``, ``dt_bias`` the
    inverse softplus of a log-uniform step in [time_step_min,
    time_step_max] floored at time_step_floor, ``D`` 1, convolution weights
    normal x kernel^-0.5 with bias normal x 0.1, the router's selection
    bias normal x 0.1; an expert's matrices from keys of its own
    (``_expert_stack``), and only those the fields' ``experts_held`` names
    are drawn (None: all)."""
    keys = dict(zip(DRAWN, jax.random.split(jax.random.PRNGKey(seed),
                                            len(DRAWN))))
    e, E, m = f["embed_dim"], f["n_experts"], f["expert_dim"]
    Ls, La, Le = (kind_layers(f, k) for k in ("ssm", "attn", "mlp"))
    h, kk = f["ssm_heads"], f["conv_kernel"]
    di = h * f["ssm_head_dim"]
    cd = di + 2 * f["ssm_groups"] * f["ssm_state"]
    q, kv = f["n_heads"] * f["head_dim"], f["n_kv_heads"] * f["head_dim"]
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)

    def drawn(name, fan_in, shape):
        return _normal_leaf(keys[name], fan_in, tuple(shape))

    held = tuple(f.get("experts_held") or (0, E))
    step = jnp.exp(jax.random.uniform(
        keys["ssm_dt_bias"], (Ls, h), jnp.float32,
        math.log(f["time_step_min"]), math.log(f["time_step_max"])))
    step = jnp.maximum(step, f["time_step_floor"])
    layers = {
        "ssm_norm_scale": ones((Ls, e)),
        "ssm_in": drawn("ssm_in", e, (Ls, e, di + cd + h)),
        "ssm_conv_w": drawn("ssm_conv_w", kk, (Ls, kk, cd)),
        "ssm_conv_b": (jax.random.normal(keys["ssm_conv_b"], (Ls, cd),
                                         jnp.float32)
                       * CONV_BIAS_STD).astype(jnp.bfloat16),
        "ssm_a_log": jnp.log(jax.random.uniform(
            keys["ssm_a_log"], (Ls, h), jnp.float32, 1.0, 16.0)),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((Ls, h), jnp.float32),
        "ssm_gate_norm_scale": ones((Ls, di)),
        "ssm_out": drawn("ssm_out", di, (Ls, di, e)),
        "attn_norm_scale": ones((La, e)),
        "wq": drawn("wq", e, (La, e, q)),
        "wk": drawn("wk", e, (La, e, kv)),
        "wv": drawn("wv", e, (La, e, kv)),
        "wo": drawn("wo", q, (La, q, e)),
        "mlp_norm_scale": ones((Le, e)),
        "router": drawn("router", e, (Le, e, E)).astype(jnp.float32),
        "router_bias": jax.random.normal(
            keys["router_bias"], (Le, E), jnp.float32) * ROUTER_BIAS_STD,
        "experts_up": _expert_stack(keys["experts_up"], e, Le, held,
                                    (e, m)),
        "experts_down": _expert_stack(keys["experts_down"], m, Le, held,
                                      (m, e)),
        "shared_up": drawn("shared_up", e, (Le, e, f["shared_dim"])),
        "shared_down": drawn("shared_down", f["shared_dim"],
                             (Le, f["shared_dim"], e)),
    }
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


def recurrence(x, dt, a, b, c, h0, state_dtype=None, reset_at=None):
    """The state-space layer's definition, one token at a time: x [T, H,
    P], dt [T, H] (after its softplus), a [H] (negative), b, c [T, G, N],
    h0 [H, P, N]; all float32. Returns (y [T, H, P] without the skip term,
    the state after the last token). ``state_dtype``: the state is rounded
    to it after every token (a control). ``reset_at``: the state is zeroed
    before that token (a planted fault)."""
    per_group = x.shape[1] // b.shape[1]

    def step(h, inputs):
        t, x_t, dt_t, b_t, c_t = inputs
        if reset_at is not None:
            h = jnp.where(t == reset_at, 0.0, h)
        b_t = jnp.repeat(b_t, per_group, axis=0)          # [H, N]
        c_t = jnp.repeat(c_t, per_group, axis=0)
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(jnp.float32)
        return h, jnp.einsum("hpn,hn->hp", h, c_t, precision=HIGHEST)

    h, y = jax.lax.scan(step, h0, (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, h


def mamba_mixer(f: dict, u, lw, quant=None, fault=None, state_dtype=None,
                fault_at=None):
    """The ``M`` sub-layer over u [T, C] from a zero state."""
    t = u.shape[0]
    heads, p = f["ssm_heads"], f["ssm_head_dim"]
    groups, n, taps = f["ssm_groups"], f["ssm_state"], f["conv_kernel"]
    di, gn = heads * p, groups * n
    cd = di + 2 * gn
    proj = _mm(u, lw["ssm_in"], quant)
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cd), jnp.float32), xbc])
    if fault == "conv_dropped" and fault_at is not None:
        # the window the token at fault_at reads is zeros
        rows = jnp.arange(t + taps - 1)
        padded = jnp.where(((rows >= fault_at) & (rows < fault_at + taps
                                                  - 1))[:, None],
                           0.0, padded)
    w = lw["ssm_conv_w"].astype(jnp.float32)
    conv = lw["ssm_conv_b"].astype(jnp.float32) + sum(
        padded[j:j + t] * w[j] for j in range(taps))
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(t, heads, p)
    b = conv[:, di:di + gn].reshape(t, groups, n)
    c = conv[:, di + gn:].reshape(t, groups, n)
    if fault != "no_dt_bias":
        dt = dt + lw["ssm_dt_bias"]
    dt = jax.nn.softplus(dt)
    a = -jnp.exp(lw["ssm_a_log"])
    y, _ = recurrence(
        x, dt, a, b, c, jnp.zeros((heads, p, n), jnp.float32), state_dtype,
        reset_at=fault_at if fault == "state_reset" else None)
    if fault != "no_skip":
        y = y + lw["ssm_d"][:, None] * x
    y = y.reshape(t, di) * jax.nn.silu(z)
    norm_groups = 1 if fault == "ungrouped_norm" else groups
    grouped = y.reshape(t, norm_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        + f["norm_eps"])
    y = grouped.reshape(t, di) * lw["ssm_gate_norm_scale"].astype(
        jnp.float32)
    return _mm(y, lw["ssm_out"], quant)


def attention(f: dict, u, lw, quant=None, hidden=(0, 0),
              q_block: int = 512):
    """The ``*`` sub-layer over u [T, C]: full causal softmax, no rotary
    embedding, queries in blocks so that a long sequence's scores fit.
    ``hidden = (start, count)``: positions no query sees as keys (a
    bucket's padding under ``pad_integrated``)."""
    t = u.shape[0]
    heads, kvh, d = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    q = _mm(u, lw["wq"], quant).reshape(t, heads, d)
    k = _mm(u, lw["wk"], quant).reshape(t, kvh, d)
    v = _mm(u, lw["wv"], quant).reshape(t, kvh, d)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    block = min(q_block, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    starts = jnp.arange(qp.shape[0]) * block

    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) * d ** -0.5
        keys = jnp.arange(t)[None, :]
        seen = ((start + jnp.arange(block))[:, None] >= keys) \
            & ((keys < hidden[0]) | (keys >= hidden[0] + hidden[1]))
        attn = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", attn, v, precision=HIGHEST)

    out = jax.lax.map(one_block, (qp, starts)).reshape(-1, heads * d)[:t]
    return _mm(out, lw["wo"], quant)


def relu2_mlp(u, w_up, w_down, quant=None, fault=None):
    hidden = _mm(u, w_up, quant)
    hidden = jax.nn.silu(hidden) if fault == "silu_experts" \
        else jnp.square(jax.nn.relu(hidden))
    return _mm(hidden, w_down, quant)


def route(f: dict, u, lw, quant=None, fault=None):
    """(gates [T, k] float32, experts [T, k] int32): sigmoid scores, choice
    by score + bias, gates from the scores, renormalised and scaled."""
    scores = jax.nn.sigmoid(_mm(u, lw["router"], quant))
    biased = scores if fault == "no_bias" else scores + lw["router_bias"]
    _, experts = jax.lax.top_k(biased, f["top_k"])
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if f.get("norm_topk", True):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * f["routed_scale"], experts


def experts_mlp(f: dict, u, lw, quant=None, held=None, fault=None,
                shared: bool = True, moe_layer=0):
    """The ``E`` sub-layer over u [T, C], written as the sum over experts,
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
        w_up, w_down = (lw[name][moe_layer, index - lo]
                        for name in ("experts_up", "experts_down"))
        return total + gate[:, None] * relu2_mlp(u, w_up, w_down, quant,
                                                 fault), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                            jnp.arange(lo, hi))
    if shared and fault != "drop_shared":
        total = total + relu2_mlp(u, lw["shared_up"], lw["shared_down"],
                                  quant, fault)
    return total


@functools.partial(jax.jit, static_argnames=(
    "fields", "kind", "held", "quant", "fault", "state_dtype"))
def _layer(fields, x, lw, moe_layer, fault_at, hidden, kind, held, quant,
           fault, state_dtype):
    """One layer over x [T, C]: its one sub-layer, read, computed and added
    back."""
    f = dict(fields)
    u = _rms_norm(x, lw[f"{kind}_norm_scale"], f["norm_eps"])
    if kind == "ssm":
        return x + mamba_mixer(f, u, lw, quant, fault, state_dtype,
                               fault_at)
    if kind == "attn":
        return x + attention(f, u, lw, quant, hidden)
    return x + experts_mlp(f, u, lw, quant, held, fault,
                           moe_layer=moe_layer)


def layer_weights(f: dict, weights: dict, layer: int):
    """(kind, index within the kind, the layer's leaves): each kind's
    leaves are stacked over that kind's layers; the experts' stacks stay
    whole."""
    kind = KINDS[f["pattern"][layer]]
    at = sum(KINDS[c] == kind for c in f["pattern"][:layer])
    own = {"ssm": ("ssm_",), "attn": ("attn_", "wq", "wk", "wv", "wo"),
           "mlp": ("mlp_", "router", "experts_", "shared_")}[kind]
    return kind, at, {
        name: (leaf if name.startswith("experts_") else leaf[at])
        for name, leaf in weights["layers"].items() if name.startswith(own)}


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


def forward(f: dict, weights: dict, ids, quant=None, fault=None, rows=None,
            embeddings=None, state_dtype=None, fault_at=None,
            hidden=(0, 0)):
    """Logits [T, V] float32 at every position of the sequence ``ids`` [T],
    layer by layer (one layer's weights in float32 at a time). ``rows =
    (start, count)`` returns only those positions' logits; ``embeddings``
    [T, C]: the sequence's, where the caller took them already; the
    weights hold the fields' ``experts_held``; ``hidden = (start, count)``:
    positions that attention does not see, while the state-space layers
    integrate them."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    held = f.get("experts_held")
    held = None if held is None else tuple(held)
    fields = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in f.items()))
    x = (embedded(weights, ids) if embeddings is None
         else embeddings).astype(jnp.float32)
    for layer in range(f["n_layers"]):
        kind, at, lw = layer_weights(f, weights, layer)
        x = _layer(fields, x, lw, jnp.int32(at),
                   jnp.int32(-1 if fault_at is None else fault_at),
                   jnp.asarray(hidden, jnp.int32), kind, held, quant, fault,
                   state_dtype)
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
                  pad_to: int, quant=None, fault=None, embeddings=None,
                  state_dtype=None, buckets=(128, 512, 1024)):
    """Logits [len(served), V] of the reference over ``prompt + served``
    (padded to ``pad_to``; every layer is causal, so the padding behind
    them changes nothing): row ``i`` is what it predicts for the position
    of ``served[i]``. The faults that happen at one token strike at the
    first decoded one (position ``len(prompt)``). ``pad_integrated`` is
    what a program does that hands on the state of a prefill bucket's
    end: the prompt's bucket (the least of ``buckets`` that holds it) is
    filled up with id 0 between the prompt and the served tokens, the
    state-space layers integrate the filling and attention does not see
    it (``embeddings`` are then made anew, of twice the length)."""
    real = len(prompt)
    if fault != "pad_integrated":
        return forward(f, weights, padded_ids(prompt, served, pad_to),
                       quant=quant, fault=fault, embeddings=embeddings,
                       state_dtype=state_dtype, fault_at=real,
                       rows=(real - 1, len(served)))
    fill = min((b for b in buckets if b >= real), default=real) - real
    ids = padded_ids(list(prompt) + [0] * fill, served, 2 * pad_to)
    logits = forward(f, weights, ids, quant=quant, fault=fault,
                     state_dtype=state_dtype, hidden=(real, fill),
                     rows=(real - 1, fill + len(served)))
    # the first token comes from the prompt's own last position, the others
    # from behind the filling
    return jnp.concatenate([logits[:1], logits[fill + 1:]])


def gap_below_best(logits, tokens) -> np.ndarray:
    """For each row, the reference's best logit minus its logit of the
    row's token (0 where the token is the reference's own choice)."""
    logits = np.asarray(logits, np.float32)
    rows = np.arange(len(tokens))
    return logits.max(axis=-1) - logits[rows, np.asarray(tokens)]
