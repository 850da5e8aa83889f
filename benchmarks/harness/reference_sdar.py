"""The plain reference of the ``sdar`` family (``model_type: sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): the decoder's forward pass
under the block mask and generation by diffusion over blocks, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision. No
kernel, no cache, no batching, no sort of the token-expert pairs (the
expert layer is the sum over experts as written in step 4, every expert
over all tokens with a gate of 0 where it was not chosen), and nothing
imported from ``mlrun_tpu``. ``tests/sdar_reference.py`` re-exports
it for the program's tests.

For a sequence ``t`` of length ``T`` and block length ``B``, ``x = E[t]``
(masked positions hold the mask id's embedding), and for each layer:

1. ``h = rmsnorm(x, w_attn)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``.
2. ``q = rmsnorm(q, w_qn)``, ``k = rmsnorm(k, w_kn)`` over each head's
   entries, before the rotation; RoPE over the whole head, rotate-half.
3. Scores ``q k^T / sqrt(d)``, each kv head shared by its group of query
   heads; position ``i`` sees ``j`` iff ``j // B <= i // B``; softmax in
   float32; ``x = x + attn Wo``.
4. ``h2 = rmsnorm(x, w_mlp)``; ``p = softmax(h2 Wr)`` over all experts;
   ``(g, e) = top_k(p)``, ``g = g / sum(g)``; ``y = sum_i g_i Wdown[e_i]
   (silu(Wgate[e_i] h2) * Wup[e_i] h2)``; ``x = x + y``. No token is
   dropped; with ``held`` set, experts outside it add nothing.

Then ``rmsnorm(x, w_final)`` and the untied head, logits in float32.

Departures from the published model: the weights are seeded random (the
program's recipe, ``make_weights``); ``block_length``, ``mask_token_id``,
the unmasking rule (``low_confidence_static``: a block's masked positions
split evenly over the steps, the most confident unmasked first) and
``denoising_steps`` are not keys of ``config.json`` (the catalog's
``not_given``: block length, noise schedule) and follow the family's
published generation script as remembered, no network here; generation is
greedy.

``quant="int8"`` is the control: every matmul's weights (per output
channel) and inputs (per row) rounded to int8 levels. ``fault`` plants one
of two faults for the readings that set the limits: ``"causal_block"`` (a
causal mask inside the block) and ``"drop_expert"`` (each token's least
weighted expert contributes nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = (None, "causal_block", "drop_expert")


# -- weights, by the program's recipe (models/moe.py init_params) -------------
@functools.partial(jax.jit, static_argnames=("fan_in", "shape"))
def _normal_leaf(key, fan_in: int, shape: tuple):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(jnp.bfloat16)


def make_weights(f: dict, seed: int, held=None) -> dict:
    """bfloat16 weights for the fields ``f`` from ``PRNGKey(seed)``: normal x
    fan_in^-0.5 from ten keys split off it, norm scales 1, the router kept
    in float32 (of bfloat16 values). With ``held = (lo, hi)`` the experts'
    leaves are that slice of the whole draw."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    e, L, E = f["embed_dim"], f["n_layers"], f["n_experts"]
    h = f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    m, v, d = f["expert_dim"], f["vocab_size"], f["head_dim"]
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layers = {
        "attn_norm_scale": ones((L, e)), "mlp_norm_scale": ones((L, e)),
        "q_norm_scale": ones((L, d)), "k_norm_scale": ones((L, d)),
        "wq": _normal_leaf(keys[1], e, (L, e, h)),
        "wk": _normal_leaf(keys[2], e, (L, e, kv)),
        "wv": _normal_leaf(keys[3], e, (L, e, kv)),
        "wo": _normal_leaf(keys[4], h, (L, h, e)),
        "router": _normal_leaf(keys[5], e, (L, e, E)).astype(jnp.float32),
        "experts_gate": _normal_leaf(keys[6], e, (L, E, e, m)),
        "experts_up": _normal_leaf(keys[7], e, (L, E, e, m)),
        "experts_down": _normal_leaf(keys[8], m, (L, E, m, e)),
    }
    if held is not None:
        for name in ("experts_gate", "experts_up", "experts_down"):
            layers[name] = layers[name][:, held[0]:held[1]]
    return {"embedding": _normal_leaf(keys[0], e, (v, e)), "layers": layers,
            "final_norm_scale": ones((e,)),
            "lm_head": _normal_leaf(keys[9], e, (e, v))}


# -- the decoder ---------------------------------------------------------------
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


def _rope(x, positions, theta):
    """x [T, H, D]; rotate the (first half, second half) pairs."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs      # [T, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_mask(length: int, block_length: int, causal_inside: bool = False):
    """[T, T] bool: row ``i`` sees column ``j`` iff ``j // B <= i // B``
    (with ``causal_inside``, the planted fault: iff ``j <= i``)."""
    pos = jnp.arange(length)
    if causal_inside:
        return pos[None, :] <= pos[:, None]
    return (pos[None, :] // block_length) <= (pos[:, None] // block_length)


def route(f: dict, h2, router, quant=None):
    """(gates [T, k] float32, experts [T, k] int32) of every token over the
    router's full width: softmax, top-k, renormalised."""
    probs = jax.nn.softmax(_mm(h2, router, quant), axis=-1)
    gates, experts = jax.lax.top_k(probs, f["top_k"])
    if f.get("norm_topk", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts


def experts_mlp(f: dict, h2, lw, quant=None, held=None, fault=None):
    """The expert layer over ``h2`` [T, M]: ``y = sum_e g_e(t) Wdown[e]
    (silu(Wgate[e] h2) * Wup[e] h2)`` with ``g_e(t)`` the token's
    renormalised gate for expert ``e`` where ``e`` is one of its top-k, and
    0 elsewhere. Written as that sum, one expert after another over all
    tokens: no sort, no gather, and one expert's weights in float32 at a
    time, so that the published widths fit. ``held = (lo, hi)``:
    ``lw['experts_*']`` hold that range and experts outside it add
    nothing."""
    gates, experts = route(f, h2, lw["router"], quant)
    if fault == "drop_expert":
        gates = gates.at[:, -1].set(0.0)        # the least weighted one
    lo, hi = (0, f["n_experts"]) if held is None else held

    def one_expert(total, args):
        index, w_gate, w_up, w_down = args
        gate = jnp.sum(jnp.where(experts == index, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(_mm(h2, w_gate, quant)) * _mm(h2, w_up, quant)
        return total + gate[:, None] * _mm(hidden, w_down, quant), None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h2),
        (jnp.arange(lo, hi), lw["experts_gate"], lw["experts_up"],
         lw["experts_down"]))
    return total


@functools.partial(jax.jit, static_argnames=(
    "fields", "block_length", "held", "quant", "fault", "row_count"))
def _forward(fields, weights, ids, masked, row_start, block_length, held,
             quant, fault, row_count):
    f = dict(fields)
    t = ids.shape[0]
    eps, heads, kvh, d = f["norm_eps"], f["n_heads"], f["n_kv_heads"], \
        f["head_dim"]
    tokens = jnp.where(masked, f["mask_token_id"], ids)
    x = weights["embedding"][tokens].astype(jnp.float32)
    positions = jnp.arange(t)
    mask = block_mask(t, block_length, fault == "causal_block")
    for layer in range(f["n_layers"]):
        lw = jax.tree_util.tree_map(lambda a: a[layer], weights["layers"])
        h = _rms_norm(x, lw["attn_norm_scale"], eps)
        q = _mm(h, lw["wq"], quant).reshape(t, heads, d)
        k = _mm(h, lw["wk"], quant).reshape(t, kvh, d)
        v = _mm(h, lw["wv"], quant).reshape(t, kvh, d)
        q = _rope(_rms_norm(q, lw["q_norm_scale"], eps), positions,
                  f["rope_theta"])
        k = _rope(_rms_norm(k, lw["k_norm_scale"], eps), positions,
                  f["rope_theta"])
        k = jnp.repeat(k, heads // kvh, axis=1)
        v = jnp.repeat(v, heads // kvh, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            * (d ** -0.5)
        attn = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hqk,khd->qhd", attn, v, precision=HIGHEST)
        x = x + _mm(out.reshape(t, heads * d), lw["wo"], quant)
        h2 = _rms_norm(x, lw["mlp_norm_scale"], eps)
        x = x + experts_mlp(f, h2, lw, quant, held, fault)
    x = _rms_norm(x, weights["final_norm_scale"], eps)
    if row_count is not None:                   # only these positions' logits
        x = jax.lax.dynamic_slice_in_dim(x, row_start, row_count, axis=0)
    return _mm(x, weights["lm_head"], quant)


def forward(fields: dict, weights: dict, ids, masked, block_length=None,
            held=None, quant=None, fault=None, rows=None):
    """Logits [T, V] float32 at every position of the sequence ``ids``
    [T] whose ``masked`` [T] positions hold the mask id. ``rows = (start,
    count)`` returns only those positions' logits (the whole sequence still
    runs through every layer)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    block_length = int(block_length or fields["block_length"])
    start, count = (0, None) if rows is None else rows
    return _forward(tuple(sorted(fields.items())), weights,
                    jnp.asarray(ids, jnp.int32), jnp.asarray(masked, bool),
                    jnp.asarray(start, jnp.int32), block_length,
                    None if held is None else tuple(held), quant, fault,
                    count)


# -- generation by diffusion over blocks --------------------------------------
def schedule(m0: int, steps: int) -> list:
    """How many positions each denoising pass of a block unmasks, for a
    block opened with ``m0`` masked positions: an even split over ``steps``
    with the remainder on the first passes; ``min(steps, m0)`` passes."""
    counts = [m0 // steps + (1 if s < m0 % steps else 0)
              for s in range(steps)]
    return [c for c in counts if c > 0]


def pick(confidence, masked, count: int) -> list:
    """The ``count`` still-masked positions of highest confidence (equal
    confidences by position)."""
    still = [j for j, m in enumerate(masked) if m]
    still.sort(key=lambda j: (-float(confidence[j]), j))
    return still[:count]


def denoise_pass(fields, weights, committed, block_ids, block_masked,
                 block_length=None, held=None, quant=None, fault=None,
                 pad_to=None):
    """One pass over the block that follows the ``committed`` ids: (logits
    [B, V], ``x0`` [B], confidence [B]) at the block's positions, from one
    forward over committed + block. ``pad_to`` pads the sequence with
    further masked blocks (unseen by the block) to one compiled length."""
    block_length = int(block_length or fields["block_length"])
    base = len(committed)
    if base % block_length or len(block_ids) != block_length:
        raise ValueError("a block starts at a multiple of its length")
    ids = list(committed) + list(block_ids)
    masked = [False] * base + [bool(m) for m in block_masked]
    if pad_to is not None and pad_to > len(ids):
        masked += [True] * (pad_to - len(ids))
        ids += [0] * (pad_to - len(ids))
    logits = forward(fields, weights, ids, masked, block_length, held,
                     quant, fault, rows=(base, block_length))
    probs = jax.nn.softmax(logits, axis=-1)
    x0 = jnp.argmax(logits, axis=-1)
    confidence = jnp.take_along_axis(probs, x0[:, None], axis=-1)[:, 0]
    return np.asarray(logits), np.asarray(x0), np.asarray(confidence)


def generate(fields, weights, prompt, max_new: int, steps: int,
             block_length=None, held=None):
    """Greedy generation by the family's rule, end to end (small sizes):
    returns (tokens [max_new], unmask_pass [max_new], passes), ``passes``
    one record per pass in order: ``base``, ``ids`` and ``masked`` going in,
    ``logits`` [B, V] and ``confidence`` [B] at the block, the positions it
    ``unmasked`` (none for a commit pass)."""
    size = int(block_length or fields["block_length"])
    prompt = [int(t) for t in prompt]
    lead = len(prompt) - len(prompt) % size
    committed, known = prompt[:lead], prompt[lead:]
    out, out_pass, passes = [], [], []
    while len(out) < max_new:
        ids = known + [0] * (size - len(known))
        masked = [False] * len(known) + [True] * (size - len(known))
        by_pass = [-1] * len(known) + [0] * (size - len(known))
        for s, count in enumerate(schedule(sum(masked), steps)):
            logits, x0, confidence = denoise_pass(
                fields, weights, committed, ids, masked, size, held)
            chosen = pick(confidence, masked, count)
            passes.append({"base": len(committed), "ids": list(ids),
                           "masked": list(masked), "logits": logits,
                           "confidence": confidence, "unmasked": chosen})
            for j in chosen:
                ids[j], masked[j], by_pass[j] = int(x0[j]), False, s
        passes.append({"base": len(committed), "ids": list(ids),
                       "masked": list(masked), "logits": None,
                       "unmasked": []})      # the commit pass
        out += ids[len(known):]
        out_pass += by_pass[len(known):]
        committed, known = committed + ids, []
    return out[:max_new], out_pass[:max_new], passes


# -- a served answer, taken apart ---------------------------------------------
def blocks_of(prompt_len: int, generated: int, block_length: int) -> list:
    """The blocks a request of ``prompt_len`` prompt tokens and ``generated``
    returned tokens went through, as (base, first generated lane, lanes
    returned): the last block is denoised whole and the answer cut, so its
    returned lanes may be fewer than it has."""
    out = []
    base = prompt_len - prompt_len % block_length
    end = prompt_len + generated
    while base < end:
        first = max(0, prompt_len - base)
        out.append((base, first, min(block_length, end - base) - first))
        base += block_length
    return out


def block_state_at(prompt, tokens, unmask_pass, block, at_pass: int,
                   block_length: int):
    """What went into pass ``at_pass`` of one block of a served request,
    rebuilt from its answer: (committed ids before the block, block ids,
    block masked, the lanes that this pass unmasked). ``block`` is an entry
    of :func:`blocks_of`. A lane past the answer's cut is unknown and is
    taken as still masked (only a request's last block has such lanes)."""
    base, first, lanes = block
    sequence = list(prompt) + list(tokens)
    ids, masked, now = [], [], []
    for j in range(block_length):
        position = base + j
        if j < first:                               # the prompt's tail
            ids.append(int(sequence[position]))
            masked.append(False)
            continue
        known = j < first + lanes
        at = unmask_pass[position - len(prompt)] if known else None
        if known and at < at_pass:
            ids.append(int(sequence[position]))
            masked.append(False)
        else:
            ids.append(0)
            masked.append(True)
            if known and at == at_pass:
                now.append(j)
    return sequence[:base], ids, masked, now
