"""Mixture-of-Experts llama variant: two expert layers over one weight
layout (experts stacked ``[L, E, ...]``).

**The trainer's** (``_moe_mlp``, with ``forward`` / ``loss_fn`` here): a
GShard-style capacity dispatch. Completes the parallelism inventory
(SURVEY.md §2.4 reserved the expert axis): the experts are sharded over an
``expert`` mesh axis (parallel/sharding rules below); dispatch/combine are
the TPU-idiomatic one-hot einsums (static capacity; no dynamic shapes), so
XLA lays the token shuffle onto all-to-alls across the expert axis. It
drops the tokens past an expert's capacity and carries the aux loss.

**The server's** (``moe_mlp``): dropless. It is told which experts it holds,
routes over the router's full width, sorts the token-expert pairs by
expert, runs one grouped matrix product per projection over the experts
held and combines with the gates; what absent experts would add is left
out. The serving programs reach it through ``models/llama.layer_mlp``
(docs/serving.md "Block-diffusion decoding"). ``SdarConfig`` is the family
it serves: q/k norms, every layer experts, generation by diffusion over
blocks. The two layers meet in a later PR (moving the trainer needs the
exchange across the ``expert`` axis, ROADMAP S6).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.norms import rms_norm
from ..ops.rotary import rope_table
from .llama import (
    EXPERT_SERVING_LEAVES,
    LlamaConfig,
    decoder_block,
    embed,
    lm_head,
    trainer_proj,
)

Params = dict

# sharding rules for the expert-stacked tensors (prepended by users of
# make_moe_rules): experts sharded over 'expert', their matrices over
# fsdp/tensor like the dense ones
MOE_RULES = [
    (r".*experts_gate.*", (None, "expert", "fsdp", "tensor")),
    (r".*experts_up.*", (None, "expert", "fsdp", "tensor")),
    (r".*experts_down.*", (None, "expert", "tensor", "fsdp")),
    (r".*router.*", (None, "fsdp", None)),
]


def make_moe_rules():
    from ..parallel.sharding import DEFAULT_RULES

    return MOE_RULES + list(DEFAULT_RULES)


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    @property
    def expert_width(self) -> int:
        """Hidden width of one expert (``mlp_dim`` unless the family keeps
        a dense width apart from it)."""
        return self.mlp_dim

    def param_count(self) -> int:
        embed = self.vocab_size * self.embed_dim
        attn = (self.embed_dim * self.qkv_dim
                + 2 * self.embed_dim * self.kv_dim
                + self.qkv_dim * self.embed_dim)
        if self.qk_norm:
            attn += 2 * self.head_dim
        moe = (self.n_experts * 3 * self.embed_dim * self.expert_width
               + self.embed_dim * self.n_experts)
        per_layer = attn + moe + 2 * self.embed_dim
        head = 0 if self.tie_embeddings else self.vocab_size * self.embed_dim
        return embed + self.n_layers * per_layer + self.embed_dim + head

    def flops_per_token(self, seq_len: int) -> float:
        """MFU must count ACTIVE params only: each token touches top_k
        experts, not all n_experts (dense flops_per_token would inflate
        the denominator and understate MFU)."""
        attn = (self.embed_dim * self.qkv_dim
                + 2 * self.embed_dim * self.kv_dim
                + self.qkv_dim * self.embed_dim)
        active_moe = (self.top_k * 3 * self.embed_dim * self.expert_width
                      + self.embed_dim * self.n_experts)
        matmul = (self.n_layers * (attn + active_moe)
                  + self.vocab_size * self.embed_dim)
        attn_flops = 2 * self.n_layers * seq_len * self.qkv_dim
        return 6.0 * matmul + 6.0 * attn_flops


@dataclasses.dataclass(frozen=True)
class SdarConfig(MoEConfig):
    """The SDAR family (``model_type: sdar_moe``): q/k norms, every layer a
    mixture of ``n_experts`` experts of width ``expert_dim`` with ``top_k``
    a token and no shared expert, generation by diffusion over blocks of
    ``block_length`` positions (``LlamaConfig.block_length``; masked
    positions embed ``mask_token_id``). Served only, by the paged engine
    through ``moe_mlp``; ``mlp_dim`` is the family's unused dense width."""

    qk_norm: bool = True
    block_length: int = 4
    mask_token_id: int = 151669
    expert_dim: int = 768
    # gates of the chosen experts renormalised to sum to 1
    norm_topk: bool = True
    # the contiguous range (lo, hi) of experts whose weights are held
    # here; None: all of them
    experts_held: Optional[tuple] = None
    remat: bool = False

    @property
    def expert_width(self) -> int:
        return self.expert_dim


def tiny_sdar(**overrides) -> SdarConfig:
    return dataclasses.replace(SdarConfig(
        vocab_size=512, n_layers=2, embed_dim=64, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=128, n_experts=8, top_k=2, expert_dim=32,
        block_length=4, mask_token_id=511, rope_theta=1e6, norm_eps=1e-6,
        tie_embeddings=False), **overrides)


def tiny_moe(**overrides) -> MoEConfig:
    return dataclasses.replace(MoEConfig(
        vocab_size=512, n_layers=2, embed_dim=128, n_heads=4, n_kv_heads=2,
        head_dim=32, mlp_dim=128, n_experts=4, top_k=2,
        tie_embeddings=True, remat=False), **overrides)


def mixtral_8x7b_like(**overrides) -> MoEConfig:
    return dataclasses.replace(MoEConfig(
        vocab_size=32000, n_layers=32, embed_dim=4096, n_heads=32,
        n_kv_heads=8, head_dim=128, mlp_dim=14336, n_experts=8, top_k=2,
        rope_theta=1e6), **overrides)


@functools.partial(jax.jit, static_argnames=("fan_in", "shape", "dtype"))
def _normal_leaf(k, fan_in: int, shape: tuple, dtype):
    # under jit: the float32 draw of a stack of experts never exists whole
    return (jax.random.normal(k, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def init_params(config: MoEConfig, key: jax.Array) -> Params:
    """The recipe of models/llama.py (normal x fan_in^-0.5, norm scales 1)
    over ten keys split from ``key``; with ``experts_held`` set the experts'
    leaves are the held slice of the whole draw, so an expert's weights do
    not depend on which share holds it."""
    keys = jax.random.split(key, 10)
    dtype = config.dtype
    e, h, kv, m = (config.embed_dim, config.qkv_dim, config.kv_dim,
                   config.expert_width)
    L, E = config.n_layers, config.n_experts

    def norm_init(fan_in, shape, k):
        return _normal_leaf(k, fan_in, tuple(shape), jnp.dtype(dtype))

    params: Params = {
        "embedding": norm_init(e, (config.vocab_size, e), keys[0]),
        "layers": {
            "attn_norm_scale": jnp.ones((L, e), dtype),
            "wq": norm_init(e, (L, e, h), keys[1]),
            "wk": norm_init(e, (L, e, kv), keys[2]),
            "wv": norm_init(e, (L, e, kv), keys[3]),
            "wo": norm_init(h, (L, h, e), keys[4]),
            "mlp_norm_scale": jnp.ones((L, e), dtype),
            "router": norm_init(e, (L, e, E), keys[5]).astype(jnp.float32),
            "experts_gate": norm_init(e, (L, E, e, m), keys[6]),
            "experts_up": norm_init(e, (L, E, e, m), keys[7]),
            "experts_down": norm_init(m, (L, E, m, e), keys[8]),
        },
        "final_norm_scale": jnp.ones((e,), dtype),
    }
    if config.qk_norm:
        params["layers"]["q_norm_scale"] = jnp.ones((L, config.head_dim),
                                                    dtype)
        params["layers"]["k_norm_scale"] = jnp.ones((L, config.head_dim),
                                                    dtype)
    held = getattr(config, "experts_held", None)
    if held is not None:
        for name in ("experts_gate", "experts_up", "experts_down"):
            params["layers"][name] = \
                params["layers"][name][:, held[0]:held[1]]
    if not config.tie_embeddings:
        params["lm_head"] = norm_init(
            e, (e, config.vocab_size), keys[9])
    return params


def _grouped(lhs, rhs, group_sizes, layer=None, out_major=False):
    """``lhs[rows of group g] @ rhs[g]`` for every group, rows sorted by
    group: [P, K] x [G, K, N] -> [P, N] float32, by the library's megablox
    ``gmm`` kernel (its operations read ``gmm`` in a device trace). Rows
    past the groups' sum come out undefined; the caller weights them with 0.
    ``out_major``: ``rhs`` is stored [G, N, K] (:func:`_expert_product`).

    With ``layer`` given, ``rhs`` is the stack of all layers' groups [L, G,
    K, N] and the product runs over layer ``layer``'s: the kernel is handed
    the stack as stored, viewed as L x G groups of which only the layer's
    have rows (it visits no empty group). A sliced layer would be an
    operand of its own, a copy of every expert's weights before each
    product (what a pool layer was to the paged kernels, PERF.md PR 28).

    Kept after one timing on the chip (PERF.md, PR 30): the three products
    of a layer at this family's widths take 1.73 ms at 1,024 pairs over 128
    experts and 2.29 ms at 8,192 with these tiles, ``jax.lax.ragged_dot``
    4.83 and 5.44 ms."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from ..ops.attention import interpret_default

    if layer is not None:
        n_layers, groups = rhs.shape[:2]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * groups,), jnp.int32), group_sizes,
            (layer * groups,))
        rhs = rhs.reshape(n_layers * groups, *rhs.shape[2:])
    rows, k = lhs.shape
    n = rhs.shape[-2 if out_major else -1]
    tile_rows = min(128, -(-rows // 8) * 8)
    pad = (-rows) % tile_rows
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tile_k = min(k, 2048)
    # a tile of an expert's matrix stays within 4 MiB (two of them are in
    # flight): widths such as 2688 x 1856 would else overrun the kernel's
    # fast memory (AOT compile, PR 37)
    tile_n = min(n, 2048, (2048 * 1024 // tile_k) // 128 * 128)
    out = gmm(lhs, rhs, group_sizes, jnp.float32,
              (tile_rows, tile_k, tile_n), transpose_rhs=out_major,
              interpret=interpret_default())
    return out[:rows]


def _expert_product(rows, lp, name: str, load, layer):
    """The sorted ``rows`` through the held experts' matrices ``name`` as
    ``lp`` holds them: the logical stack ``lp[name]`` [.., E, K, N], or the
    same matrices out-major under ``EXPERT_SERVING_LEAVES[name]`` [.., E,
    N, K] (what a serving engine keeps where N is no whole number of
    lanes). None where the experts have no such matrix."""
    if name in lp:
        return _grouped(rows, lp[name], load, layer)
    relaid = EXPERT_SERVING_LEAVES[name]
    if relaid in lp:
        return _grouped(rows, lp[relaid], load, layer, out_major=True)
    return None


def moe_mlp(config: MoEConfig, x, lp, held=None, live=None, layer=None):
    """The served, dropless expert layer: x [B, S, M] -> (y [B, S, M],
    load int32 [experts held]).

    Routes every token over the router's full width (softmax in float32,
    top-k, gates renormalised under ``norm_topk``; under ``config.scoring
    == "sigmoid"`` sigmoid scores, top-k of score + ``lp["router_bias"]``
    (of the score alone where the layer has no such leaf),
    gates from the scores, renormalised and times ``routed_scale``), adds
    the shared expert where ``lp`` has one (:func:`shared_expert`), keeps the
    token-expert pairs whose expert lies in ``held`` (a contiguous range
    ``(lo, hi)``; ``None``: all) and whose token is ``live`` ([B, S] bool;
    ``None``: all), sorts them by expert, runs one grouped product per
    projection over the held experts' weights (``lp["experts_*"]``
    [hi - lo, ...]) and adds each pair's output, times its gate, to its
    token. An expert is a SwiGLU of three products where ``lp`` holds
    ``experts_gate`` (under that name or its serving one), else two
    products with ``relu(.)^2`` between them (:func:`_activated`). No token
    is dropped; what an absent expert would add is left out and nothing
    stands in for it. ``load`` is the pairs each held expert got. With ``layer`` given, ``lp["experts_*"]`` are the stacks
    of every layer's held experts ([L, hi - lo, ...], as the parameters
    store them) and the products run over that layer's (:func:`_grouped`). The routing runs under the named scope
    ``layer/moe/route``, the products under ``layer/moe/experts``."""
    b, s, m = x.shape
    E, k = config.n_experts, config.top_k
    lo, hi = (0, E) if held is None else held
    n_held = hi - lo
    given = lp["experts_down"].shape[0 if layer is None else 1]
    if given != n_held:
        raise ValueError(
            f"moe_mlp holds experts [{lo}, {hi}) but was given {given} "
            f"experts' weights")
    t = b * s
    xt = x.reshape(t, m)
    with jax.named_scope("layer/moe/route"):
        logits = jnp.einsum("tm,me->te", xt.astype(jnp.float32),
                            lp["router"].astype(jnp.float32))
        if getattr(config, "scoring", "softmax") == "sigmoid":
            # choice by score plus the per-expert selection bias where the
            # layer has one, gates from the scores without it, renormalised
            # and scaled
            scores = jax.nn.sigmoid(logits)
            biased = scores + lp["router_bias"] if "router_bias" in lp \
                else scores
            _, chosen = jax.lax.top_k(biased, k)
            gates = jnp.take_along_axis(scores, chosen, axis=-1)
            if getattr(config, "norm_topk", True):
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + 1e-20)
            gates = gates * config.routed_scale
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, chosen = jax.lax.top_k(probs, k)          # [T, k]
            if getattr(config, "norm_topk", True):
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        expert = chosen.reshape(t * k)
        kept = (expert >= lo) & (expert < hi)
        if live is not None:
            kept = kept & jnp.repeat(live.reshape(t), k)
        # pairs that are not kept sort behind the last group
        group = jnp.where(kept, expert - lo, n_held)
        order = jnp.argsort(group)                           # stable
        load = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(
            1)[:n_held]
        rows = xt[order // k]                                # [T * k, M]
    with jax.named_scope("layer/moe/experts"):
        gate_h = _expert_product(rows, lp, "experts_gate", load, layer)
        up_h = _expert_product(rows, lp, "experts_up", load, layer)
        hidden = _activated(gate_h, up_h).astype(x.dtype)
        out = _grouped(hidden, lp["experts_down"], load, layer)  # [T*k, M]
    with jax.named_scope("layer/moe/route"):
        weight = jnp.where(kept, gates.reshape(t * k), 0.0)
        pairs = jnp.where(kept[:, None], out[jnp.argsort(order)], 0.0)
        y = jnp.sum((pairs * weight[:, None]).reshape(t, k, m), axis=1)
    y = y.astype(x.dtype).reshape(b, s, m)
    if "shared_up" in lp:
        y = y + shared_expert(x, lp)
    return y, load


def _activated(gate, up):
    """An expert's hidden activation out of its first products (float32):
    SwiGLU, ``silu(gate) * up``, where the expert has a gate matrix;
    ``relu(up)^2`` where it is two products with nothing beside them
    (``gate`` None). What the layer's leaves hold decides, never a
    switch."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


def shared_expert(x, lp):
    """The expert every token goes through beside its routed ones (``lp``
    carries ``shared_up`` / ``shared_down`` and, for a SwiGLU,
    ``shared_gate``), over x [B, S, M], whatever experts are held here.
    Runs under the named scope ``layer/moe/shared``."""
    with jax.named_scope("layer/moe/shared"):
        gate = jnp.einsum("bse,eh->bsh", x, lp["shared_gate"],
                          preferred_element_type=jnp.float32) \
            if "shared_gate" in lp else None
        up = jnp.einsum("bse,eh->bsh", x, lp["shared_up"],
                        preferred_element_type=jnp.float32)
        hidden = _activated(gate, up).astype(x.dtype)
        return jnp.einsum("bsh,he->bse", hidden, lp["shared_down"],
                          preferred_element_type=jnp.float32).astype(x.dtype)


def _moe_mlp(config: MoEConfig, x, lp):
    """GShard top-k dispatch: x [B, S, M] -> [B, S, M] + aux loss scalar.

    The trainer's layer: static capacity, tokens past it dropped, the aux
    loss, the ``expert`` mesh axis. The server runs :func:`moe_mlp`
    (dropless, told which experts it holds); the two meet in a later PR
    (ROADMAP S6)."""
    b, s, m = x.shape
    E, k = config.n_experts, config.top_k
    capacity = max(1, int(config.capacity_factor * s * k / E))

    router_logits = jnp.einsum(
        "bsm,me->bse", x.astype(jnp.float32), lp["router"])
    probs = jax.nn.softmax(router_logits, axis=-1)          # [B,S,E]

    # aux load-balancing loss (Switch): E * sum(fraction_tokens * mean_prob)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux_loss = E * jnp.sum(frac_tokens * mean_probs)

    # top-k selection with renormalized gates
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # [B,S,k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    dispatch = jnp.zeros((b, s, E, capacity), jnp.float32)
    combine = jnp.zeros((b, s, E, capacity), jnp.float32)
    # running token count per expert, updated per choice rank
    counts = jnp.zeros((b, E), jnp.int32)
    for choice in range(k):
        idx = expert_idx[:, :, choice]                      # [B,S]
        gate = gate_vals[:, :, choice]                      # [B,S]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)    # [B,S,E]
        # position_in_expert = tokens of same expert before me (+ carried)
        pos = jnp.cumsum(onehot, axis=1) - onehot + counts[:, None, :]
        counts = counts + jnp.sum(onehot, axis=1)
        my_pos = jnp.sum(pos * onehot, axis=-1)             # [B,S]
        keep = my_pos < capacity
        cap_onehot = jax.nn.one_hot(my_pos, capacity,
                                    dtype=jnp.float32)      # [B,S,C]
        mask = (onehot.astype(jnp.float32)[:, :, :, None]
                * cap_onehot[:, :, None, :]
                * keep.astype(jnp.float32)[:, :, None, None])
        dispatch = dispatch + mask
        combine = combine + mask * gate[:, :, None, None]

    # dispatch tokens to expert buffers: [E, B, C, M]
    expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch,
                           x.astype(jnp.float32)).astype(x.dtype)
    gate_h = jnp.einsum("ebcm,emh->ebch", expert_in, lp["experts_gate"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    up_h = jnp.einsum("ebcm,emh->ebch", expert_in, lp["experts_up"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
    expert_out = jnp.einsum(
        "ebch,ehm->ebcm", jax.nn.silu(gate_h) * up_h, lp["experts_down"],
        preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bsec,ebcm->bsm", combine,
                     expert_out.astype(jnp.float32)).astype(x.dtype)
    return out, aux_loss


def _layer_body(config: MoEConfig, x, lp, cos, sin):
    """One decoder layer of the trainer: the block of models/llama.py over
    the capacity-dispatch expert layer. Returns (x, aux loss)."""
    return decoder_block(
        config, lp, x, cos, sin, proj=trainer_proj(None, x.dtype),
        attend=lambda q, k, v: attention(q, k, v, causal=True,
                                         impl=config.attention_impl),
        mlp=lambda h2: _moe_mlp(config, h2, lp))


def hidden_states(config: MoEConfig, params: Params, tokens: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (final hidden [B, S, E], aux_loss scalar)."""
    b, s = tokens.shape
    x = embed(config, params, tokens)
    cos, sin = rope_table(jnp.arange(s), config.head_dim, config.rope_theta)

    body = functools.partial(_layer_body, config)
    if config.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_fn(carry, lp):
        out, aux = body(carry, lp, cos, sin)
        return out, aux

    x, aux_losses = jax.lax.scan(scan_fn, x, params["layers"])
    x = rms_norm(x, params["final_norm_scale"], config.norm_eps)
    return x, jnp.mean(aux_losses)


def forward(config: MoEConfig, params: Params, tokens: jax.Array
            ) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (logits [B, S, V] f32, aux_loss scalar)."""
    x, aux = hidden_states(config, params, tokens)
    logits = jnp.einsum("bse,ev->bsv", x, lm_head(params),
                        preferred_element_type=jnp.float32)
    return logits, aux


def loss_fn(config: MoEConfig, params: Params, tokens, targets,
            mask=None, loss_chunk: int = 0) -> tuple[jax.Array, dict]:
    """CE + router aux loss. ``loss_chunk > 0`` runs the lm head through
    llama's chunked CE so the full [B, S, vocab] logits never materialize
    (same memory bound as the dense trainer's loss_chunk)."""
    if loss_chunk:
        from .llama import chunked_ce

        x, aux_loss = hidden_states(config, params, tokens)
        ce, accuracy, _ = chunked_ce(x, lm_head(params), targets, mask=mask,
                                     chunk=loss_chunk)
        loss = ce + config.router_aux_weight * aux_loss
        return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux_loss,
                      "accuracy": accuracy}
    logits, aux_loss = forward(config, params, tokens)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / total
    loss = ce + config.router_aux_weight * aux_loss
    # same metric surface as the chunked path, so callbacks monitoring
    # "accuracy" behave identically for loss_chunk=0 and loss_chunk>0
    accuracy = jnp.sum(
        (jnp.argmax(logits, axis=-1) == targets) * mask) / total
    return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux_loss,
                  "accuracy": accuracy}


def param_shapes(config: MoEConfig) -> Params:
    """Shape/dtype tree without allocating (trainer sharding setup)."""
    return jax.eval_shape(
        functools.partial(init_params, config), jax.random.PRNGKey(0))
