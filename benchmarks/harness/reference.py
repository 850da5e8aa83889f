"""The plain reference: the decoder's forward pass, its loss, the LoRA
gradients and the AdamW update in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision. No kernel, no cache, no batching tricks, and
nothing imported from ``mlrun_tpu``.

It makes its own weights. The program seeds its weights itself (the server
with key 0, the trainer with the run's seed), so the only way to hold the
same model is to follow the same public recipe from the same key:
``normal(key_i, shape) * fan_in**-0.5`` cast to bfloat16, keys split as the
model file documents. The bfloat16 values are the model; the reference
computes over them in float32.

``quant="int8"`` is the control: the same mathematics with every matmul's
weights (per output channel) and inputs (per row) rounded to int8 levels,
the step below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LORA_TARGETS = ("wq", "wk", "wv", "wo")


# -- weights, by the program's documented recipe ------------------------------
def _leaf_specs(f: dict):
    e, L, m, v = f["embed_dim"], f["n_layers"], f["mlp_dim"], f["vocab_size"]
    h = f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    # (path, key index into split(key, 8) or "head", fan_in, shape)
    specs = [
        (("embedding",), 0, e, (v, e)),
        (("layers", "wq"), 1, e, (L, e, h)),
        (("layers", "wk"), 2, e, (L, e, kv)),
        (("layers", "wv"), 3, e, (L, e, kv)),
        (("layers", "wo"), 4, h, (L, h, e)),
        (("layers", "w_gate"), 5, e, (L, e, m)),
        (("layers", "w_up"), 6, e, (L, e, m)),
        (("layers", "w_down"), 7, m, (L, m, e)),
    ]
    if not f["tie_embeddings"]:
        specs.append((("lm_head",), "head", e, (e, v)))
    return specs


def _normal_leaf_eager(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(jnp.bfloat16)


_normal_leaf = jax.jit(_normal_leaf_eager, static_argnames=("shape", "fan_in"))


def make_weights(f: dict, seed: int, eager: bool = False) -> dict:
    """bfloat16 weights for the fields ``f`` from ``PRNGKey(seed)``.

    The trainer makes its weights under ``jit`` and the server op by op;
    the product's rounding to bfloat16 differs between the two in about one
    weight in 10^5 (by one unit in the last place), so ``eager`` follows
    the server."""
    normal_leaf = _normal_leaf_eager if eager else _normal_leaf
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 8)
    out = {"layers": {
        "attn_norm_scale": jnp.ones((f["n_layers"], f["embed_dim"]),
                                    jnp.bfloat16),
        "mlp_norm_scale": jnp.ones((f["n_layers"], f["embed_dim"]),
                                   jnp.bfloat16)},
        "final_norm_scale": jnp.ones((f["embed_dim"],), jnp.bfloat16)}
    for path, which, fan_in, shape in _leaf_specs(f):
        k = jax.random.fold_in(key, 99) if which == "head" else keys[which]
        leaf = normal_leaf(k, shape, fan_in)
        if len(path) == 1:
            out[path[0]] = leaf
        else:
            out[path[0]][path[1]] = leaf
    return out


def make_lora(f: dict, seed: int, rank: int, alpha: float) -> dict:
    """LoRA factors by the program's recipe: A ~ normal * in**-0.5 from
    ``fold_in(key, i)``, B = 0, a per-layer scaling alpha / rank."""
    key = jax.random.PRNGKey(seed)
    e = f["embed_dim"]
    h = f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    dims = {"wq": (e, h), "wk": (e, kv), "wv": (e, kv), "wo": (h, e)}
    L = f["n_layers"]
    lora = {}
    for i, target in enumerate(LORA_TARGETS):
        d_in, d_out = dims[target]
        lora[target] = {
            "lora_a": jax.random.normal(jax.random.fold_in(key, i),
                                        (L, d_in, rank), jnp.float32)
            * (d_in ** -0.5),
            "lora_b": jnp.zeros((L, rank, d_out), jnp.float32),
            "scaling": jnp.full((L,), alpha / rank, jnp.float32),
        }
    return lora


# -- the decoder ---------------------------------------------------------------
def _int8_levels(x, axis):
    """Round to 255 symmetric levels along ``axis`` (straight-through for
    the gradient, so the control can also be differentiated)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """x [..., in] float32 times w [in, out] (bfloat16 values) in float32."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        x = _int8_levels(x, axis=-1)
        w = _int8_levels(w, axis=0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [B, S, H, D]; rotate the (first half, second half) pairs."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs     # [S, D/2]
    cos, sin = jnp.cos(angles)[None, :, None, :], \
        jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(f, quant, x, w, lora):
    """One decoder layer on x [B, S, E] float32; ``w`` and ``lora`` are one
    layer's leaves."""
    b, s, _ = x.shape
    heads, kvh, d = f["n_heads"], f["n_kv_heads"], f["head_dim"]

    def proj(inp, name):
        out = _mm(inp, w[name], quant)
        if lora is not None and name in lora:
            la = lora[name]
            delta = jnp.einsum("...i,ir->...r", inp, la["lora_a"],
                               precision=HIGHEST)
            delta = jnp.einsum("...r,ro->...o", delta, la["lora_b"],
                               precision=HIGHEST)
            out = out + la["scaling"] * delta
        return out

    hid = _rms_norm(x, w["attn_norm_scale"], f["norm_eps"])
    positions = jnp.arange(s)
    q = _rope(proj(hid, "wq").reshape(b, s, heads, d), positions,
              f["rope_theta"])
    k = _rope(proj(hid, "wk").reshape(b, s, kvh, d), positions,
              f["rope_theta"])
    v = proj(hid, "wv").reshape(b, s, kvh, d)
    group = heads // kvh
    q = q.reshape(b, s, kvh, group, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HIGHEST) \
        / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)
    x = x + proj(attn.reshape(b, s, heads * d), "wo")
    hid = _rms_norm(x, w["mlp_norm_scale"], f["norm_eps"])
    gate, up = _mm(hid, w["w_gate"], quant), _mm(hid, w["w_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["w_down"], quant)


def hidden(f: dict, weights: dict, tokens, lora=None, quant=None):
    """tokens [B, S] -> final-norm hidden [B, S, E] float32. Layers run
    under ``lax.scan`` with each body rematerialised, so float32 copies of
    one layer's weights are all that live at a time."""
    x = weights["embedding"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def body(x, scanned):
        w, la = scanned
        return _layer(f, quant, x, w, la), None

    x, _ = jax.lax.scan(body, x, (weights["layers"], lora))
    return _rms_norm(x, weights["final_norm_scale"], f["norm_eps"])


def _head(weights):
    head = weights.get("lm_head")
    return weights["embedding"].T if head is None else head


# -- serving: how far below the reference's best does a token lie -------------
@functools.partial(jax.jit, static_argnames=("f", "rows", "quant"))
def _logits_rows(weights, tokens, start, *, f, rows, quant):
    x = hidden(dict(f), weights, tokens, quant=quant)
    picked = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1)
    return _mm(picked, _head(weights), quant)[0]          # [rows, V]


def served_logits(f: dict, weights: dict, prompt: list, served: list,
                  pad_to: int, quant=None):
    """Logits [len(served), V] of the reference over ``prompt + served``:
    row ``i`` is what it predicts for the position of ``served[i]``."""
    ids = list(prompt) + list(served)
    tokens = np.zeros((1, pad_to), np.int32)
    tokens[0, :len(ids)] = ids
    return _logits_rows(weights, jnp.asarray(tokens),
                        jnp.int32(len(prompt) - 1),
                        f=tuple(sorted(f.items())), rows=len(served),
                        quant=quant)


def gap_below_best(logits, tokens) -> np.ndarray:
    """For each row, the reference's best logit minus its logit of the
    row's token (0 where the token is the reference's own choice)."""
    logits = np.asarray(logits, np.float32)
    rows = np.arange(len(tokens))
    return logits.max(axis=-1) - logits[rows, np.asarray(tokens)]


# -- training: loss, LoRA gradients, AdamW ------------------------------------
@functools.partial(jax.jit, static_argnames=("f", "quant", "chunk"))
def _row_loss_and_grads(weights, lora, tokens, targets, *, f, quant, chunk):
    """Summed next-token loss of one block of rows and its gradient for the
    LoRA leaves. The head and the softmax run ``chunk`` positions at a
    time, rematerialised, so the [S, V] logits never live whole."""
    f = dict(f)

    def total(lora_):
        x = hidden(f, weights, tokens, lora=lora_, quant=quant)
        b, s, e = x.shape
        xc = x.reshape(b, s // chunk, chunk, e).transpose(1, 0, 2, 3)
        tc = targets.reshape(b, s // chunk, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def piece(carry, scanned):
            xs, ts = scanned
            logits = _mm(xs, _head(weights), quant)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, ts[..., None], axis=-1)
            return carry + jnp.sum(nll), None

        out, _ = jax.lax.scan(piece, jnp.zeros((), jnp.float32), (xc, tc))
        return out

    return jax.value_and_grad(total)(lora)


def loss_and_grads(f: dict, weights: dict, lora: dict, tokens, targets,
                   quant=None, rows_per_block: int = 1):
    """Mean loss over every token of the batch and its LoRA gradient, in
    blocks of rows so that the float32 pass fits beside the weights."""
    tokens, targets = np.asarray(tokens), np.asarray(targets)
    seq = tokens.shape[1]
    chunk = math.gcd(seq, 512)
    loss_sum, grad_sum = 0.0, None
    for lo in range(0, tokens.shape[0], rows_per_block):
        loss, grads = _row_loss_and_grads(
            weights, lora, jnp.asarray(tokens[lo:lo + rows_per_block]),
            jnp.asarray(targets[lo:lo + rows_per_block]),
            f=tuple(sorted(f.items())), quant=quant, chunk=chunk)
        loss_sum = loss_sum + loss
        grad_sum = grads if grad_sum is None else jax.tree_util.tree_map(
            jnp.add, grad_sum, grads)
    count = float(tokens.size)
    return loss_sum / count, jax.tree_util.tree_map(
        lambda g: g / count, grad_sum)


def learning_rate(count: int, peak: float, warmup: int, total: int) -> float:
    """Linear warm-up from 0 over ``warmup`` updates, then a cosine to 0
    at ``max(total, warmup + 1)``; ``count`` is the updates made so far."""
    decay_steps = max(total, warmup + 1)
    if count < warmup:
        return peak * count / warmup
    frac = min(1.0, (count - warmup) / (decay_steps - warmup))
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def adamw_step(params, grads, mu, nu, count: int, lr: float, b1=0.9,
               b2=0.95, eps=1e-8, weight_decay=0.0):
    """One AdamW update; ``count`` is the updates made before this one."""
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda n, g: b2 * n + (1 - b2) * jnp.square(g), nu, grads)
    c1, c2 = 1 - b1 ** (count + 1), 1 - b2 ** (count + 1)
    params = tm(lambda p, m, n: p - lr * (
        (m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p),
        params, mu, nu)
    return params, mu, nu


def train_steps(f: dict, weights: dict, lora: dict, batches, *, peak_lr,
                total_steps, warmup_steps=10, grad_clip=1.0, b1=0.9,
                b2=0.95, quant=None, drop_half_batch=False):
    """Follow the trainer through ``len(batches)`` steps. Returns the
    losses, the first gradient as the optimizer's moments hold it (after
    the clip), and the LoRA leaves at the end.

    ``drop_half_batch`` plants a fault for the tests and readings: the
    second half of every batch is left out and the mean taken over the
    rest."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, lora)
    mu, nu = zeros, zeros
    losses, first_grads = [], None
    for count, (tokens, targets) in enumerate(batches):
        if drop_half_batch:
            half = max(1, len(tokens) // 2)
            tokens, targets = tokens[:half], targets[:half]
        loss, grads = loss_and_grads(f, weights, lora, tokens, targets,
                                     quant=quant)
        grads = clip_by_global_norm(grads, grad_clip)
        if first_grads is None:
            first_grads = grads
        lr = learning_rate(count, peak_lr, warmup_steps, total_steps)
        lora, mu, nu = adamw_step(lora, grads, mu, nu, count, lr, b1, b2)
        losses.append(float(loss))
    return losses, first_grads, lora
