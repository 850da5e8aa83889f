"""The Laguna family (``model_type: laguna``): attention layers of two kinds
in one model, full layers that attend to the whole context and window
layers that attend to their last ``sliding_window`` positions, each kind
with its own number of query heads over the same key/value heads and its
own rotary embedding (YaRN over the leading half of a head's dims for the
full layers, plain rope over all of them for the window layers); leading
dense layers, then experts under sigmoid routing with a routed scale beside
a shared expert. Served only, by the paged engine (docs/serving.md "Window
layers and the window cache").

Nothing here is a second decoder block: ``models/llama.decoder_block`` runs
every layer. The query and output projections of the two kinds differ in
width, so each kind's are stacked over its layers alone, the window kind's
under the mark ``@window`` (``wq@window``: ``llama.KIND_MARK``), and a
layer's slice holds its own kind's under the plain names
(``LagunaConfig.leaf_index``). The rope of a layer is its kind's
(``layer_rope``), and its cache is its kind's (``cache_index``): the full
layers' keys and values go to the page pool, the window layers' to a ring
of constant size a slot (``state_rows()``, serving/window.py). The expert
layer is ``models/moe.moe_mlp`` over what the layer's leaves hold: no
selection bias, a shared expert of SwiGLU.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.rotary import rope_table
from .llama import FULL, KIND_MARK, WINDOW, dense_then_experts
from .moe import MoEConfig, _normal_leaf
from .nemotron_h import _expert_stack
from .xing4 import yarn_frequencies

Params = dict
# ``layer_types`` as published, and the kind each names
LAYER_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
# the leaves whose width is the kind's number of query heads: stacked by
# kind, the window kind's under ``<name>@window``
KIND_LEAVES = ("wq", "wq_t", "wo")


@dataclasses.dataclass(frozen=True)
class LagunaConfig(MoEConfig):
    """``n_heads`` is the full layers' number of query heads
    (``num_attention_heads``); ``heads_per_layer`` each layer's, as
    published (``num_attention_heads_per_layer``). ``mlp_dim`` is the
    leading dense layers' width, ``expert_dim`` an expert's and
    ``shared_dim`` the shared expert's. The full layers rotate the first
    ``rope_partial`` of a head's dims under YaRN, and their tables carry
    ``rope_attention_factor``; the window layers rotate ``window_rope_
    partial`` of them at ``window_rope_theta``."""

    window_cache = True

    n_layers: int = 7
    layer_types: tuple = ("full_attention", "sliding_attention",
                          "sliding_attention", "sliding_attention",
                          "full_attention", "sliding_attention",
                          "sliding_attention")
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 6
    heads_per_layer: tuple = (48, 64, 64, 64, 48, 64, 64)
    sliding_window: int = 512
    expert_dim: int = 512
    shared_dim: int = 512
    scoring: str = "sigmoid"
    routed_scale: float = 2.5
    norm_topk: bool = True
    experts_held: Optional[tuple] = None
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 64.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4158883083359672
    rope_partial: float = 0.5
    window_rope_theta: float = 10000.0
    window_rope_partial: float = 1.0
    norm_eps: float = 1e-6
    remat: bool = False

    def __post_init__(self):
        if not (len(self.layer_types) == len(self.mlp_layer_types)
                == len(self.heads_per_layer) == self.n_layers):
            raise ValueError(
                f"layer_types, mlp_layer_types and heads_per_layer give one "
                f"entry for each of {self.n_layers} layers")
        if set(self.layer_types) - set(LAYER_KINDS) \
                or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"layer kinds are {sorted(LAYER_KINDS)}, MLP "
                             f"kinds dense and sparse")
        dense = self.first_k_dense
        if "dense" in self.mlp_layer_types[dense:]:
            raise ValueError("dense MLP layers lead the expert layers")
        for kind in (FULL, WINDOW):
            heads = {h for h, t in zip(self.heads_per_layer,
                                       self.layer_types)
                     if LAYER_KINDS[t] == kind}
            if len(heads) > 1 or heads and min(heads) % self.n_kv_heads:
                raise ValueError(f"the {kind} layers have one number of "
                                 f"query heads, a multiple of n_kv_heads")
        if self.kind_layers(FULL) and self.heads(FULL) != self.n_heads:
            raise ValueError("n_heads is the full layers' query heads")

    @property
    def expert_width(self) -> int:
        return self.expert_dim

    @property
    def first_k_dense(self) -> int:
        lead = 0
        while lead < self.n_layers and self.mlp_layer_types[lead] == "dense":
            lead += 1
        return lead

    def kind(self, layer: int) -> str:
        return LAYER_KINDS[self.layer_types[layer]]

    def kind_layers(self, kind: str) -> int:
        return sum(self.kind(layer) == kind for layer in range(self.n_layers))

    def heads(self, kind: str) -> int:
        """Query heads of a layer of ``kind``."""
        return next(h for layer, h in enumerate(self.heads_per_layer)
                    if self.kind(layer) == kind)

    def _index(self, kind: str, layer: int):
        """Where layer ``layer`` sits among the layers of ``kind``, or
        None: it is of the other kind."""
        if self.kind(layer) != kind:
            return None
        return sum(self.kind(i) == kind for i in range(layer))

    # -- where the block finds a layer's leaves, rope and cache --------------
    @property
    def cache_layers(self) -> int:
        """The full layers: their keys and values go to the page pool."""
        return self.kind_layers(FULL)

    @property
    def state_layers(self) -> int:
        """The window layers: each keeps a ring a slot (``state_rows``)."""
        return self.kind_layers(WINDOW)

    def state_rows(self) -> dict:
        """What a sequence keeps of a window layer, whatever its length:
        the keys and values of its last ``sliding_window`` positions, the
        row of position ``p`` at ``p mod sliding_window``."""
        row = ((self.sliding_window, self.n_kv_heads, self.head_dim),
               self.dtype)
        return {"window_k": row, "window_v": row}

    def leaf_index(self, name: str, layer: int):
        """A query or output projection is stacked over its kind's layers
        (the window kind's under ``<name>@window``), the dense MLP over the
        leading dense layers, the expert layer over the layers after them;
        every other leaf over all."""
        base, _, kind = name.partition(KIND_MARK)
        if base in KIND_LEAVES:
            return self._index(kind or FULL, layer)
        return dense_then_experts(self.first_k_dense, base, layer)

    def cache_index(self, layer: int):
        """``(kind, index within the kind)``: the pool's layer of a full
        layer, the ring of a window layer (serving/window.py)."""
        kind = self.kind(layer)
        return kind, self._index(kind, layer)

    def rope(self, positions):
        """The tables of both kinds, ``({kind: cos}, {kind: sin})``: the
        full layers' under YaRN over their rotated dims, carrying the
        attention factor; the window layers' plain."""
        rotated = int(self.head_dim * self.rope_partial)
        freqs = yarn_frequencies(rotated, self.rope_theta, self.rope_factor,
                                 self.rope_original_max, self.rope_beta_fast,
                                 self.rope_beta_slow)
        angles = positions.astype(jnp.float32)[..., None] * freqs
        scale = self.rope_attention_factor
        cos_w, sin_w = rope_table(
            positions, int(self.head_dim * self.window_rope_partial),
            self.window_rope_theta)
        return ({FULL: jnp.cos(angles) * scale, WINDOW: cos_w},
                {FULL: jnp.sin(angles) * scale, WINDOW: sin_w})

    def layer_rope(self, cos, sin, layer: int):
        kind = self.kind(layer)
        return cos[kind], sin[kind]

    # -- counts ---------------------------------------------------------------
    def attention_params(self, kind: str) -> int:
        e, q = self.embed_dim, self.heads(kind) * self.head_dim
        return 2 * e * q + 2 * e * self.kv_dim

    def expert_params(self) -> int:
        return 3 * self.embed_dim * self.expert_dim

    def expert_layer_params(self, experts: int) -> int:
        e = self.embed_dim
        return (experts * self.expert_params() + 3 * e * self.shared_dim
                + e * self.n_experts)

    def _attention_total(self) -> int:
        return sum(self.attention_params(self.kind(layer))
                   for layer in range(self.n_layers))

    def param_count(self) -> int:
        held = self.n_experts if self.experts_held is None \
            else self.experts_held[1] - self.experts_held[0]
        e, dense = self.embed_dim, self.first_k_dense
        return (2 * self.vocab_size * e + e + 2 * e * self.n_layers
                + self._attention_total()
                + dense * 3 * e * self.mlp_dim
                + (self.n_layers - dense) * self.expert_layer_params(held))

    def flops_per_token(self, seq_len: int) -> float:
        """Training convention of the other configs (6 x active matmul
        weights + 6 x attention): a window layer attends to at most
        ``sliding_window`` positions."""
        e, dense = self.embed_dim, self.first_k_dense
        active = (self._attention_total() + dense * 3 * e * self.mlp_dim
                  + (self.n_layers - dense) * (
                      e * self.n_experts + 3 * e * self.shared_dim
                      + self.top_k * self.expert_params())
                  + self.vocab_size * e)
        attn = sum(2 * min(seq_len, self.sliding_window
                           if self.kind(layer) == WINDOW else seq_len)
                   * self.heads_per_layer[layer] * self.head_dim
                   for layer in range(self.n_layers))
        return 6.0 * active + 6.0 * attn


def tiny_laguna(**overrides) -> LagunaConfig:
    """Two full and three window layers over a window of 16 positions."""
    return dataclasses.replace(LagunaConfig(
        vocab_size=512, n_layers=5, embed_dim=64, n_heads=6, n_kv_heads=2,
        head_dim=16, mlp_dim=128, n_experts=8, top_k=2, expert_dim=32,
        shared_dim=32, sliding_window=16,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention",
                     "full_attention"),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        heads_per_layer=(6, 8, 8, 8, 6), rope_theta=500000.0,
        rope_factor=4.0, rope_original_max=64, rope_beta_fast=32.0,
        tie_embeddings=False), **overrides)


def laguna_xs2(**overrides) -> LagunaConfig:
    """The published configuration (40 layers, 33.4 B parameters)."""
    types = ("full_attention",) + ("sliding_attention",) * 3
    return dataclasses.replace(LagunaConfig(
        vocab_size=100352, n_layers=40, embed_dim=2048, n_heads=48,
        n_kv_heads=8, head_dim=128, mlp_dim=8192, n_experts=256, top_k=8,
        layer_types=types * 10, mlp_layer_types=("dense",) + ("sparse",) * 39,
        heads_per_layer=(48, 64, 64, 64) * 10, rope_theta=500000.0,
        tie_embeddings=False), **overrides)


# -- weights ------------------------------------------------------------------
# the leaves drawn from the key, in the order of the keys split from it
# (benchmarks/harness/reference_laguna.py draws the same)
DRAWN = ("embedding", "lm_head", "wq", "wq@window", "wk", "wv", "wo",
         "wo@window", "w_gate", "w_up", "w_down", "router", "experts_gate",
         "experts_up", "experts_down", "shared_gate", "shared_up",
         "shared_down")


def init_params(config: LagunaConfig, key: jax.Array) -> Params:
    """The recipe of models/llama.py (normal x fan_in^-0.5, norm scales 1)
    over one key a drawn leaf; an expert's matrices from a key of its own,
    drawn a layer at a time in the model's dtype (``nemotron_h
    ._expert_stack``: a float32 copy of a stack is never whole). Each
    kind's query and output projections are stacked over that kind's
    layers."""
    keys = dict(zip(DRAWN, jax.random.split(key, len(DRAWN))))
    dtype = jnp.dtype(config.dtype)
    e, kv, d = config.embed_dim, config.kv_dim, config.head_dim
    L, dense = config.n_layers, config.first_k_dense
    Le, E, m = L - dense, config.n_experts, config.expert_dim
    md, sd = config.mlp_dim, config.shared_dim
    held = tuple(config.experts_held or (0, E))

    def drawn(name, fan_in, shape):
        return _normal_leaf(keys[name], fan_in, tuple(shape), dtype)

    layers = {"attn_norm_scale": jnp.ones((L, e), dtype),
              "mlp_norm_scale": jnp.ones((L, e), dtype),
              "wk": drawn("wk", e, (L, e, kv)),
              "wv": drawn("wv", e, (L, e, kv))}
    for kind, mark in ((FULL, ""), (WINDOW, KIND_MARK + WINDOW)):
        count = config.kind_layers(kind)
        if count:
            q = config.heads(kind) * d
            layers["wq" + mark] = drawn("wq" + mark, e, (count, e, q))
            layers["wo" + mark] = drawn("wo" + mark, q, (count, q, e))
    if dense:
        layers.update({"w_gate": drawn("w_gate", e, (dense, e, md)),
                       "w_up": drawn("w_up", e, (dense, e, md)),
                       "w_down": drawn("w_down", md, (dense, md, e))})
    if Le:
        layers.update({
            "router": drawn("router", e, (Le, e, E)).astype(jnp.float32),
            "experts_gate": _expert_stack(keys["experts_gate"], e, Le, held,
                                          (e, m), dtype),
            "experts_up": _expert_stack(keys["experts_up"], e, Le, held,
                                        (e, m), dtype),
            "experts_down": _expert_stack(keys["experts_down"], m, Le, held,
                                          (m, e), dtype),
            "shared_gate": drawn("shared_gate", e, (Le, e, sd)),
            "shared_up": drawn("shared_up", e, (Le, e, sd)),
            "shared_down": drawn("shared_down", sd, (Le, sd, e))})
    return {"embedding": drawn("embedding", e, (config.vocab_size, e)),
            "layers": layers,
            "final_norm_scale": jnp.ones((e,), dtype),
            "lm_head": drawn("lm_head", e, (e, config.vocab_size))}
