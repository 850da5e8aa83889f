"""Llama-family transformer — pure functional JAX, TPU-first.

Design choices (not a port — the reference has no model code at all):
- parameters are a flat pytree of **stacked** per-layer arrays
  ``[n_layers, ...]`` so the decoder is a single ``lax.scan`` over layers:
  one compiled layer body (fast XLA compile), natural pjit sharding along
  the non-layer dims (see parallel/sharding.py DEFAULT_RULES).
- bf16 activations/weights by default; f32 for norms' accumulation, softmax,
  and the final logits matmul (preferred_element_type).
- GQA attention via ops.attention (pallas flash on TPU), RoPE, SwiGLU.
- ``jax.checkpoint`` (remat) around each layer body for long-context training.

Presets cover the Llama-3 family; ``llama3_8b`` is the benchmark target
(BASELINE.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.norms import rms_norm
from ..ops.rotary import apply_rope, rope_table

Params = dict


class BlockSeams(NamedTuple):
    """Where a family enters :func:`decoder_block`, as functions of the
    config (``LlamaConfig.seams``; models/xing4.py has the other set).

    ``qkv(config, lp, h, cos, sin, proj) -> (q, k, v)``: what ``attend``
    is handed, out of the normed input. ``read(config, x, lp, sub) -> (u,
    mix)`` and ``write(config, x, mix, y) -> x``: what sub-layer ``sub``
    (``"attn"``, ``"mlp"``) reads of the residual state and how its output
    goes back (``x`` and ``x + y`` here). ``enter(config, x)`` and
    ``leave(config, x)``: the embeddings into the residual state before the
    first layer, and the state out of it after the last."""

    qkv: Callable
    read: Callable
    write: Callable
    enter: Callable
    leave: Callable


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    # a token leaves per-head keys and values in the cache (False), or one
    # latent row all heads share (True: models/xing4.py); a class's, never
    # an instance's
    latent_cache = False
    # a sequence keeps a recurrent state of constant size a layer beside its
    # rows in the cache (True: models/nemotron_h.py, ``state_rows()``); a
    # class's, never an instance's
    recurrent_state = False

    vocab_size: int = 128256
    n_layers: int = 32
    embed_dim: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    remat: bool = True
    # remat policy under remat=True: "nothing" recomputes the whole layer
    # (min memory); "save_attn" keeps attention outputs and recomputes only
    # the MLP half (≈E·S·B extra bytes/layer for noticeably less backward
    # FLOPs); "dots" saves every matmul output (max memory, min recompute)
    remat_policy: str = "nothing"
    attention_impl: str = "auto"
    # q and k normalised per head (one learned scale of head_dim each)
    # before the rotation; off for the Llama and Mistral families
    qk_norm: bool = False
    # attention mask by blocks of positions aligned to multiples of this
    # length: position i sees j iff j // block_length <= i // block_length.
    # 1 is the causal mask; > 1 is a model that generates by diffusion over
    # blocks (models/moe.py SdarConfig, docs/serving.md "Block-diffusion
    # decoding"), which only the paged engine serves
    block_length: int = 1

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def seams(self) -> BlockSeams:
        return LLAMA_SEAMS

    def cache_rows(self) -> dict:
        """What a token leaves behind in a layer's cache: each buffer's
        name and the shape of one token's row."""
        row = (self.n_kv_heads, self.head_dim)
        return {"k": row, "v": row}

    @property
    def cache_layers(self) -> int:
        """The layers whose tokens leave rows in the cache."""
        return self.n_layers

    def state_rows(self) -> dict:
        """What a sequence keeps a layer beside its rows, whatever its
        length: each buffer's name with its shape and dtype (none here)."""
        return {}

    @property
    def state_layers(self) -> int:
        """The layers that keep ``state_rows()``."""
        return 0

    def sublayers(self, layer) -> tuple:
        """The sub-layers of layer ``layer`` (None: any) as
        :func:`decoder_block` runs them, in order."""
        return ("attn", "mlp")

    def leaf_index(self, name: str, layer: int):
        """Where layer ``layer`` sits in the stack of leaf ``name``, or
        None where the layer has no such leaf (:func:`layer_slice`): here
        every leaf is stacked over all layers."""
        return layer

    def rope(self, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The cos/sin tables of ``positions`` as this family rotates."""
        return rope_table(positions, self.head_dim, self.rope_theta)

    def param_count(self) -> int:
        embed = self.vocab_size * self.embed_dim
        per_layer = (
            self.embed_dim * self.qkv_dim          # wq
            + 2 * self.embed_dim * self.kv_dim     # wk, wv
            + self.qkv_dim * self.embed_dim        # wo
            + 3 * self.embed_dim * self.mlp_dim    # gate, up, down
            + 2 * self.embed_dim                   # norms
        )
        head = 0 if self.tie_embeddings else self.vocab_size * self.embed_dim
        return embed + self.n_layers * per_layer + self.embed_dim + head

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token (fwd+bwd ≈ 6·N_matmul + attention term)."""
        matmul_params = self.param_count() - self.vocab_size * self.embed_dim \
            * (1 if self.tie_embeddings else 2) - self.embed_dim \
            - 2 * self.embed_dim * self.n_layers
        # embedding lookup is free; lm_head matmul counts
        matmul_params += self.vocab_size * self.embed_dim
        attn = 2 * self.n_layers * seq_len * self.qkv_dim  # qk^T + pv per token
        return 6.0 * matmul_params + 6.0 * attn


# -- presets ---------------------------------------------------------------

def llama3_8b(**overrides) -> LlamaConfig:
    return dataclasses.replace(LlamaConfig(), **overrides)


def llama3_70b(**overrides) -> LlamaConfig:
    return dataclasses.replace(LlamaConfig(
        n_layers=80, embed_dim=8192, n_heads=64, n_kv_heads=8,
        mlp_dim=28672), **overrides)


def llama3_1b(**overrides) -> LlamaConfig:
    """~1.2B config (llama3.2-1B-like) — fits one v5e chip for benching."""
    return dataclasses.replace(LlamaConfig(
        vocab_size=128256, n_layers=16, embed_dim=2048, n_heads=32,
        n_kv_heads=8, head_dim=64, mlp_dim=8192, tie_embeddings=True),
        **overrides)


def tiny_llama(**overrides) -> LlamaConfig:
    """Tiny config for tests / dryruns."""
    return dataclasses.replace(LlamaConfig(
        vocab_size=512, n_layers=2, embed_dim=128, n_heads=4, n_kv_heads=2,
        head_dim=32, mlp_dim=256, tie_embeddings=True, remat=False),
        **overrides)


# -- init -------------------------------------------------------------------

def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """Initialize the stacked-parameter pytree."""
    keys = jax.random.split(key, 8)
    dtype = config.dtype
    e, h, kv, m, L = (config.embed_dim, config.qkv_dim, config.kv_dim,
                      config.mlp_dim, config.n_layers)

    def norm_init(fan_in, shape, k):
        scale = fan_in ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params: Params = {
        "embedding": norm_init(e, (config.vocab_size, e), keys[0]),
        "layers": {
            "attn_norm_scale": jnp.ones((L, e), dtype),
            "wq": norm_init(e, (L, e, h), keys[1]),
            "wk": norm_init(e, (L, e, kv), keys[2]),
            "wv": norm_init(e, (L, e, kv), keys[3]),
            "wo": norm_init(h, (L, h, e), keys[4]),
            "mlp_norm_scale": jnp.ones((L, e), dtype),
            "w_gate": norm_init(e, (L, e, m), keys[5]),
            "w_up": norm_init(e, (L, e, m), keys[6]),
            "w_down": norm_init(m, (L, m, e), keys[7]),
        },
        "final_norm_scale": jnp.ones((e,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = norm_init(
            e, (e, config.vocab_size), jax.random.fold_in(key, 99))
    return params


def param_shapes(config: LlamaConfig) -> Params:
    """Shape/dtype skeleton without allocating (for eval_shape / sharding)."""
    return jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0)))


def init_permutation_params(config: LlamaConfig, perm, scale: float = 50.0,
                            seed: int = 0) -> Params:
    """Deterministic "permutation-following" params: the greedy next
    token after ``t`` is the unique ``v`` with ``perm[v] == t``. All
    transformer weights are zero (the residual passes the embedding
    through untouched) and the untied head is ``scale * E[perm]^T``, so
    ``logits[v] = scale * <x, E[perm[v]]>`` peaks where ``perm[v]``
    matches the current token with gaps of O(scale) — orders of
    magnitude above jit-vs-eager float noise, which keeps argmax stable
    across differently-shaped compiled forwards. The speculative
    decoding tests and ``bench_serve.py --spec`` need exactly this
    knob (draft quality = how much of the draft's permutation agrees
    with the target's — :func:`permutation_pair`); one definition here,
    not one per caller. Requires ``tie_embeddings=False``."""
    if config.tie_embeddings:
        raise ValueError("permutation params need an untied lm_head "
                         "(tie_embeddings=False)")
    params = init_params(config, jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(jnp.zeros_like, params)
    emb = jax.random.normal(jax.random.PRNGKey(seed + 1),
                            (config.vocab_size, config.embed_dim),
                            jnp.float32)
    emb = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
    params["embedding"] = emb.astype(config.dtype)
    # norms must stay identity-ish: rms_norm scales are multiplicative
    params["layers"]["attn_norm_scale"] = jnp.ones_like(
        params["layers"]["attn_norm_scale"])
    params["layers"]["mlp_norm_scale"] = jnp.ones_like(
        params["layers"]["mlp_norm_scale"])
    params["final_norm_scale"] = jnp.ones_like(params["final_norm_scale"])
    params["lm_head"] = (scale * emb[jnp.asarray(perm)].T).astype(
        config.dtype)
    return params


def permutation_pair(vocab_size: int, overlap: float, seed: int = 0):
    """A target permutation plus a draft permutation agreeing on
    ``overlap`` of tokens — the controlled acceptance-rate dial for
    :func:`init_permutation_params` model pairs (overlap 1.0 → every
    draft proposal accepted; 0.0-ish → near-zero acceptance).

    The target is one full-length cycle and the disagreements are
    spaced evenly along it. A greedy stream walks exactly one cycle of
    the permutation, so with random disagreement placement a row's
    EFFECTIVE acceptance would be the luck of its cycle (some rows
    near 1.0, others near 0 at the same ``overlap``) — evenly spaced
    corruption on a single cycle makes ``overlap`` a uniform per-row
    dial instead."""
    import numpy as np

    rng = np.random.default_rng(seed)
    order = rng.permutation(vocab_size)           # cycle walk order
    target = np.empty(vocab_size, dtype=order.dtype)
    target[order] = np.roll(order, -1)            # single n-cycle
    draft = target.copy()
    n_diff = int(round(vocab_size * (1 - overlap)))
    n_diff -= n_diff % 2                          # swaps corrupt in pairs
    if n_diff >= 2:
        pos = np.linspace(0, vocab_size, n_diff,
                          endpoint=False).astype(np.int64)
        a, b = order[pos[0::2]], order[pos[1::2]]
        draft[a], draft[b] = target[b], target[a]
    return target, draft


# -- forward ----------------------------------------------------------------

def _remat_policy(name: str):
    """Map a LlamaConfig.remat_policy name onto a jax checkpoint policy."""
    if name == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if name == "save_attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    raise ValueError(
        f"unknown remat_policy '{name}' (nothing | save_attn | dots)")


def trainer_proj(lora: Optional[dict], dtype):
    """The trainer's projection ``proj(h_in, w, key)``: f32 product cast to
    ``dtype``, then the layer's LoRA delta (``lora[key]``, where present)
    added in ``dtype``. The serving form (serving/llm.py ``_serving_proj``)
    adds its delta in f32 before the cast: the two round in a different
    order, so they stay two."""
    def proj(h_in, w, key=None, out_major=False):
        if out_major:
            raise ValueError(
                f"'{key}' is in a serving engine's layout "
                f"({SERVING_LEAVES.get(key, key)}, [heads, head_dim, E]); "
                f"the trainer takes the logical tree")
        out = jnp.einsum("bse,eh->bsh", h_in, w,
                         preferred_element_type=jnp.float32).astype(dtype)
        if lora is not None and key in lora:
            a, bb, scaling = (lora[key]["lora_a"], lora[key]["lora_b"],
                              lora[key]["scaling"])
            delta = jnp.einsum("bse,er->bsr", h_in, a.astype(dtype))
            delta = jnp.einsum("bsr,rh->bsh", delta, bb.astype(dtype))
            out = (out + scaling.astype(dtype) * delta).astype(dtype)
        return out

    return proj


# the attention's input projections, and the names under which a serving
# engine holds them out-major and split into heads, [L, heads, head_dim, E]:
# the layout their products contract over (serving/llm.py ``serving_tree``).
# Which layout a tree holds is read from these names and from nothing else.
SERVING_LEAVES = {"wq": "wq_t", "wk": "wk_t", "wv": "wv_t"}
# an expert's input matrices, and the names under which a serving engine
# holds them out-major, [L, experts, width, E], where the expert's width is
# no whole number of 128 lanes (serving/llm.py ``relay_layers``): the device
# keeps a buffer whose minor dimension is not one with the other dimension
# minor, and a kernel that wants it row-major gets a copy of all of it
# before every call
EXPERT_SERVING_LEAVES = {"experts_gate": "experts_gate_t",
                         "experts_up": "experts_up_t"}


def _in_proj(proj, lp, h, key: str):
    """``h`` through the layer's projection ``key`` as ``lp`` holds it: the
    logical leaf ``lp[key]`` [E, H], or the same matrix stored [heads,
    head_dim, E] under ``SERVING_LEAVES[key]``, which ``proj`` is told
    (``out_major``) and answers split into heads."""
    if key in lp:
        return proj(h, lp[key], key)
    return proj(h, lp[SERVING_LEAVES[key]], key, out_major=True)


def llama_qkv(config: LlamaConfig, lp, h, cos, sin, proj):
    """q, k and v of the Llama family out of the normed input ``h``: three
    projections, the q/k norm where the config has one, the rotation."""
    b, s, _ = h.shape
    q = _in_proj(proj, lp, h, "wq").reshape(b, s, config.n_heads,
                                            config.head_dim)
    k = _in_proj(proj, lp, h, "wk").reshape(b, s, config.n_kv_heads,
                                            config.head_dim)
    v = _in_proj(proj, lp, h, "wv").reshape(b, s, config.n_kv_heads,
                                            config.head_dim)
    q, k = qk_normed(config, q, k, lp)
    if cos is None:             # a family that rotates nothing
        return q, k, v
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


LLAMA_SEAMS = BlockSeams(
    qkv=llama_qkv,
    read=lambda config, x, lp, sub: (x, None),
    write=lambda config, x, mix, y: x + y,
    enter=lambda config, x: x,
    leave=lambda config, x: x)


def decoder_block(config: LlamaConfig, lp, x, cos, sin, *, proj, attend,
                  mlp=None, ssm=None, live=None, layer=None):
    """The decoder layer, written once: a sequence of sub-layers
    (``config.sublayers(layer)``: attention then MLP for the dense, the
    block-diffusion and the latent families; one a layer, of the kind the
    config's pattern gives, for models/nemotron_h.py). x: the residual
    state ([B, S, E]; a family with several residual streams carries [B,
    S, n, E]); ``lp`` the layer's parameters. Each sub-layer reads the
    state (``seams.read``: the state itself here), norms what it read
    (``<sub>_norm_scale``), computes, and writes back (``seams.write``: an
    addition here). ``attn``: q/k/v by ``seams.qkv`` (:func:`llama_qkv`:
    projections through ``proj(h_in, w, key)``, :func:`trainer_proj` or
    serving/llm.py ``_serving_proj``, q/k norm, rope), ``attend(q, k, v) ->
    [B, S, Hq, D]`` (or with the heads merged already, [B, S, Hq * D]),
    ``wo``. ``mlp``: :func:`layer_mlp` with ``live`` and ``layer``, unless
    ``mlp`` is given: ``mlp(h2) -> (out, extra)``. ``ssm``: the caller's
    ``ssm(lp, h, proj) -> out``. ``config.seams`` are the family's
    (:class:`BlockSeams`).

    ``attend`` is all a caller says about its cache: the closure writes
    what the token leaves behind where that caller keeps it and reads the
    attention back (none, dense rows, pages), keeping what it wrote for
    its own return. ``ssm`` likewise is all it says about a recurrent
    state: the closure runs the mixer from the state the sequence kept and
    keeps the new one.

    Returns ``(x, extra)``: ``extra`` is what the MLP returned beside its
    output (expert load, aux loss, ``None``)."""
    b, s = x.shape[:2]
    seams = config.seams
    extra = None
    for sub in config.sublayers(layer):
        # the named scopes are metadata a profile groups operations by
        # (embed, layer/attn, layer/mlp, head, loss): no instruction is
        # renamed
        with jax.named_scope(f"layer/{sub}"):
            u, mix = seams.read(config, x, lp, sub)
            h = rms_norm(u, lp[f"{sub}_norm_scale"], config.norm_eps)
            if sub == "attn":
                q, k, v = seams.qkv(config, lp, h, cos, sin, proj)
                attn = attend(q, k, v).reshape(b, s, config.qkv_dim)
                out = proj(attn, lp["wo"], "wo")
            elif sub == "ssm":
                out = ssm(lp, h, proj)
            elif mlp is not None:
                out, extra = mlp(h)
            else:
                out, extra = layer_mlp(config, h, lp, proj, live=live,
                                       layer=layer)
            x = seams.write(config, x, mix, out)
    return x, extra


def _layer_body(config: LlamaConfig, x, layer_params, cos, sin,
                lora: Optional[dict] = None, attention_fn=None):
    """One decoder layer of the trainer. x: [B, S, E]. ``attention_fn``
    overrides the attention dispatcher (context-parallel paths pass
    ring/ulysses)."""
    from jax.ad_checkpoint import checkpoint_name

    def attend(q, k, v):
        if attention_fn is not None:
            attn = attention_fn(q, k, v)
        else:
            attn = attention(q, k, v, causal=True,
                             impl=config.attention_impl)
        # named for the "save_attn" remat policy: backward keeps the
        # attention output and recomputes only the MLP half (named as the
        # block's wo takes it, heads merged: what is saved is what is read)
        return checkpoint_name(
            attn.reshape(*attn.shape[:2], config.qkv_dim), "attn_out")

    return decoder_block(config, layer_params, x, cos, sin,
                         proj=trainer_proj(lora, x.dtype), attend=attend)[0]


def qk_normed(config: LlamaConfig, q, k, lp):
    """q and k as the rotation takes them: under ``config.qk_norm`` each
    head's vector is RMS-normalised with the layer's learned scale
    (``q_norm_scale`` / ``k_norm_scale`` [head_dim]) first."""
    if not config.qk_norm:
        return q, k
    return (rms_norm(q, lp["q_norm_scale"], config.norm_eps),
            rms_norm(k, lp["k_norm_scale"], config.norm_eps))


# a layer's MLP leaves: the dense SwiGLU's, and the expert layer's that are
# stacked over the expert layers alone where dense layers lead
DENSE_MLP_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LAYER_LEAVES = ("router", "shared_", "experts_")


def dense_then_experts(first_k_dense: int, name: str, layer: int):
    """``leaf_index`` of a tree whose first ``first_k_dense`` layers run
    the dense MLP and the others experts: the dense MLP's leaves are
    stacked over the leading layers, the expert layer's (router, shared
    expert, experts) over the layers after them, every other leaf over
    all."""
    if name in DENSE_MLP_LEAVES and first_k_dense:
        return layer if layer < first_k_dense else None
    if name.startswith(EXPERT_LAYER_LEAVES) and first_k_dense:
        return layer - first_k_dense if layer >= first_k_dense else None
    return layer


def layer_slice(layers: Params, layer: int, first_k_dense: int = 0,
                index_of=None) -> Params:
    """Layer ``layer``'s parameters out of the stacked tree, for the
    serving programs' Python loop over layers. Stacks of experts
    (``experts_*``) stay whole: their grouped products reach a layer's
    experts through the group sizes (models/moe.py ``_grouped``), where a
    sliced stack would be copied before every product.

    A leaf may be stacked over some of the layers only: ``index_of(name,
    layer)`` (a config's ``leaf_index``) says where the layer sits in the
    leaf's stack, or None: the layer has no such leaf and gets none, which
    is how :func:`decoder_block` and :func:`layer_mlp` tell a layer's kind.
    Absent, it is :func:`dense_then_experts` of ``first_k_dense``."""
    if index_of is None:
        index_of = functools.partial(dense_then_experts, first_k_dense)
    out = {}
    for name, leaf in layers.items():
        at = index_of(name, layer)
        if at is not None:
            out[name] = leaf if name.startswith("experts_") else leaf[at]
    return out


def layer_mlp(config: LlamaConfig, h2, lp, proj, live=None, layer=None):
    """The layer's MLP over the normed input ``h2`` [B, S, E], as
    :func:`decoder_block` calls it: the dense SwiGLU through the caller's
    ``proj`` (which adds a tenant's LoRA delta), or, where the layer's
    parameters carry experts, the dropless expert layer of models/moe.py
    over the experts the config holds. ``live`` [B, S] bool leaves dead
    rows out of the expert routing; ``layer`` is the layer's index where
    ``lp`` came from :func:`layer_slice` (its experts are still stacked).
    Returns ``(out, load)``: ``load`` is
    the pairs each held expert got (int32 [experts held]), ``None`` for
    the dense MLP."""
    if "experts_down" in lp:
        from .moe import moe_mlp

        if layer is not None:
            # the experts' stacks hold the expert layers alone
            layer = config.leaf_index("experts_down", layer)
        return moe_mlp(config, h2, lp,
                       held=getattr(config, "experts_held", None), live=live,
                       layer=layer)
    gate = proj(h2, lp["w_gate"], "w_gate")
    up = proj(h2, lp["w_up"], "w_up")
    return proj(jax.nn.silu(gate) * up, lp["w_down"], "w_down"), None


def embed(config: LlamaConfig, params: Params, tokens: jax.Array,
          act_spec=None):
    """tokens [B, S] -> their embeddings [B, S, E] in the model's dtype.
    ``act_spec``: the activations' sharding, where the table is sharded
    (:func:`forward`)."""
    with jax.named_scope("embed"):
        if act_spec is not None:
            x = params["embedding"].at[tokens].get(out_sharding=act_spec)
        else:
            x = params["embedding"][tokens]
        return x.astype(config.dtype)


def lm_head(params: Params) -> jax.Array:
    """The head's matrix [E, vocab]: the tree's own (untied) or the
    embedding's transpose (tied)."""
    head = params.get("lm_head")
    return params["embedding"].T if head is None else head


def head_logits(config: LlamaConfig, params: Params, x: jax.Array):
    """x [B, S, E], a serving program's last layer's output -> logits
    [B, S, vocab] f32: final norm, :func:`lm_head`, f32 product."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm_scale"], config.norm_eps)
        return jnp.einsum("bse,ev->bsv", x, lm_head(params),
                          preferred_element_type=jnp.float32)


def forward(config: LlamaConfig, params: Params, tokens: jax.Array,
            positions: jax.Array | None = None,
            lora: Optional[Params] = None,
            act_spec=None) -> jax.Array:
    """tokens [B, S] -> logits [B, S, vocab] (f32).

    ``act_spec`` is an optional PartitionSpec for [batch, seq, embed]
    activations — required under jit when the embedding table is sharded
    (the gather's output sharding is ambiguous otherwise).
    """
    x = hidden_states(config, params, tokens, positions=positions,
                      lora=lora, act_spec=act_spec)
    with jax.named_scope("head"):
        logits = jnp.einsum("bse,ev->bsv", x, lm_head(params),
                            preferred_element_type=jnp.float32)
    return logits


def hidden_states(config: LlamaConfig, params: Params, tokens: jax.Array,
                  positions: jax.Array | None = None,
                  lora: Optional[Params] = None,
                  act_spec=None) -> jax.Array:
    """tokens [B, S] -> final-norm hidden [B, S, E] (no lm head)."""
    b, s = tokens.shape
    x = embed(config, params, tokens, act_spec)
    if positions is None:
        positions = jnp.arange(s)
    cos, sin = rope_table(positions, config.head_dim, config.rope_theta)

    body = functools.partial(_layer_body, config)
    if config.remat:
        body = jax.checkpoint(
            body, policy=_remat_policy(config.remat_policy),
            static_argnums=())

    if lora is not None:
        def scan_fn(carry, scanned):
            layer_params, layer_lora = scanned
            return body(carry, layer_params, cos, sin, layer_lora), None

        x, _ = jax.lax.scan(scan_fn, x, (params["layers"], lora))
    else:
        def scan_fn(carry, layer_params):
            return body(carry, layer_params, cos, sin, None), None

        x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    return rms_norm(x, params["final_norm_scale"], config.norm_eps)


def chunked_loss(config: LlamaConfig, params: Params, tokens: jax.Array,
                 targets: jax.Array, mask: jax.Array | None = None,
                 lora: Optional[Params] = None, chunk: int = 512,
                 act_spec=None) -> tuple[jax.Array, dict]:
    """Cross-entropy without materializing [B, S, vocab] logits.

    The lm-head matmul + softmax run per sequence chunk under
    ``jax.checkpoint`` (recomputed in backward), so peak memory for the loss
    drops from O(B·S·V) to O(B·chunk·V) — the difference between fitting
    batch 8 and batch 32 at vocab 128k on a 16GB chip.
    """
    x = hidden_states(config, params, tokens, lora=lora, act_spec=act_spec)
    # head and loss are one chunked region here: the logits never exist
    with jax.named_scope("loss"):
        loss, accuracy, total = chunked_ce(x, lm_head(params), targets,
                                           mask=mask, chunk=chunk)
    return loss, {"loss": loss, "accuracy": accuracy, "tokens": total}


def chunked_ce(x: jax.Array, head: jax.Array, targets: jax.Array,
               mask: jax.Array | None = None, chunk: int = 512
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Cross-entropy from hidden states without materializing the full
    [B, S, vocab] logits — shared by every model family with a dense
    lm head (llama here, models/moe.py's MoE). Returns
    (mean_nll, accuracy, token_count)."""
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    b, s, e = x.shape
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        # pad to a chunk multiple (mask=0 on pad) so the O(B·chunk·V) bound
        # holds for any sequence length
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))

    xc = x.reshape(b, n_chunks, chunk, e).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_stats(x_chunk, t_chunk, m_chunk):
        logits = jnp.einsum("bce,ev->bcv", x_chunk, head,
                            preferred_element_type=jnp.float32)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            log_probs, t_chunk[..., None], axis=-1)[..., 0]
        correct = (jnp.argmax(logits, axis=-1) == t_chunk)
        return (jnp.sum(nll * m_chunk),
                jnp.sum(correct * m_chunk), jnp.sum(m_chunk))

    def scan_body(carry, xs):
        loss_sum, correct_sum, count = carry
        l, c, n = chunk_stats(*xs)
        return (loss_sum + l, correct_sum + c, count + n), None

    (loss_sum, correct_sum, count), _ = jax.lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32)), (xc, tc, mc))
    total = jnp.maximum(count, 1.0)
    return loss_sum / total, correct_sum / total, total


def loss_fn(config: LlamaConfig, params: Params, tokens: jax.Array,
            targets: jax.Array, mask: jax.Array | None = None,
            lora: Optional[Params] = None,
            act_spec=None, loss_chunk: int = 0) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; returns (loss, metrics).

    ``loss_chunk > 0`` uses the memory-efficient chunked head (see
    chunked_loss)."""
    if loss_chunk:
        return chunked_loss(config, params, tokens, targets, mask=mask,
                            lora=lora, chunk=loss_chunk, act_spec=act_spec)
    logits = forward(config, params, tokens, lora=lora, act_spec=act_spec)
    with jax.named_scope("loss"):
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        if act_spec is not None:
            from jax.sharding import NamedSharding as _NS
            from jax.sharding import PartitionSpec as _P

            spec = act_spec.spec if isinstance(act_spec, _NS) else act_spec
            gather_spec = _P(*(tuple(spec)[:2] + (None,)))
            if isinstance(act_spec, _NS):
                gather_spec = _NS(act_spec.mesh, gather_spec)
            nll = -jnp.take_along_axis(
                log_probs, targets[..., None], axis=-1,
                out_sharding=gather_spec)[..., 0]
        else:
            nll = -jnp.take_along_axis(
                log_probs, targets[..., None], axis=-1)[..., 0]
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        mask = mask.astype(jnp.float32)
        total = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll * mask) / total
        accuracy = jnp.sum(
            (jnp.argmax(logits, axis=-1) == targets) * mask) / total
    return loss, {"loss": loss, "accuracy": accuracy,
                  "tokens": total}
