"""The Xing4.0 family (``model_type: xing4_0``): latent attention, sigmoid
routing with a shared expert behind leading dense layers, YaRN rope, and
manifold-constrained hyper-connections (mHC, arXiv:2512.24880) in place of
the residual addition. Served only, by the paged engine (docs/serving.md
"Latent attention and the latent page pool", "Mixed residual streams",
"Sigmoid routing with a shared expert").

Nothing here is a second decoder block: the family enters
``models/llama.decoder_block`` through its seams (``XING4_SEAMS``): what q,
k and v are (a low-rank query with its norm; the normalised latent ``c_kv``
and the one rotated key ``k_r`` all heads share, which is also what a token
leaves in the cache), and how a sub-layer reads and writes the residual
state (a weighted sum of ``hc_mult`` streams in, a doubly stochastic mix
and a weighted write back). The expert layer is ``models/moe.moe_mlp`` under
this config's ``scoring``, ``routed_scale`` and shared expert; the first
``first_k_dense`` layers run the dense SwiGLU (``llama.layer_mlp`` by layer
index). The multi-token-prediction module of the published model is not
instantiated (benchmarks/configs/xing4.0-29b-a4b.json, ``assumed``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.rotary import apply_rope
from .llama import BlockSeams, dense_then_experts
from .moe import MoEConfig, _normal_leaf

Params = dict
HIGHEST = jax.lax.Precision.HIGHEST
# the sub-layers of a block, each with mixing parameters of its own
SUBLAYERS = ("attn", "mlp")


@dataclasses.dataclass(frozen=True)
class Xing4Config(MoEConfig):
    """``head_dim`` is the query/key width of a head (``nope_dim`` +
    ``rope_dim``), ``v_dim`` the value width; ``mlp_dim`` the dense layers'
    width and ``expert_dim`` an expert's. ``n_kv_heads`` is unused: every
    head shares one latent row."""

    latent_cache = True

    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    head_dim: int = 192
    expert_dim: int = 1024
    n_shared_experts: int = 1
    first_k_dense: int = 2
    # router: sigmoid scores, choice by score + bias, gates from the scores
    # renormalised and scaled
    scoring: str = "sigmoid"
    routed_scale: float = 2.0
    norm_topk: bool = True
    experts_held: Optional[tuple] = None
    # YaRN
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # mHC: streams, Sinkhorn-Knopp iterations, the guards
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    remat: bool = False

    def __post_init__(self):
        if self.head_dim != self.nope_dim + self.rope_dim:
            raise ValueError(
                f"head_dim {self.head_dim} is nope_dim {self.nope_dim} + "
                f"rope_dim {self.rope_dim}")

    @property
    def expert_width(self) -> int:
        return self.expert_dim

    @property
    def qkv_dim(self) -> int:
        """What ``wo`` takes: the heads' values."""
        return self.n_heads * self.v_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        """``head_dim^-0.5`` times YaRN's ``m^2``."""
        return self.head_dim ** -0.5 * _yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def seams(self) -> BlockSeams:
        return XING4_SEAMS

    @property
    def latent_dim(self) -> int:
        """Width of the row a token leaves behind: the latent, then the
        rope key, then zeros up to a multiple of 128 lanes. A row of 576
        is no whole number of lanes: the device then keeps the buffer with
        the page's positions minor and copies all of it into the kernel's
        row-major order and back, every tick (AOT compile, PR 34; a buffer
        of the key's 64 entries alone likewise)."""
        return -(-(self.kv_lora_rank + self.rope_dim) // 128) * 128

    def cache_rows(self) -> dict:
        """What a token leaves behind a layer: one row all heads share."""
        return {"ckr": (self.latent_dim,)}

    def leaf_index(self, name: str, layer: int):
        return dense_then_experts(self.first_k_dense, name, layer)

    def rope(self, positions):
        return yarn_table(
            positions, self.rope_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max, self.rope_beta_fast,
            self.rope_beta_slow, self.rope_mscale,
            self.rope_mscale_all_dim)

    # -- counts (the /metrics MFU gauge reads them) --------------------------
    def attention_params(self) -> int:
        e, h = self.embed_dim, self.n_heads
        return (e * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * h * self.head_dim
                + e * (self.kv_lora_rank + self.rope_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * h * (self.nope_dim + self.v_dim)
                + h * self.v_dim * e)

    def mixing_params(self) -> int:
        """Both sub-layers' mixing parameters of one layer."""
        n = self.hc_mult
        width = n * self.embed_dim
        return 2 * (width + width * (2 * n + n * n) + 3 + 2 * n + n * n)

    def expert_params(self) -> int:
        return 3 * self.embed_dim * self.expert_dim

    def _layer_common(self) -> int:
        return self.attention_params() + self.mixing_params() \
            + 2 * self.embed_dim

    def param_count(self) -> int:
        e = self.embed_dim
        dense = self._layer_common() + 3 * e * self.mlp_dim
        expert = (self._layer_common() + e * self.n_experts
                  + self.n_experts
                  + (self.n_experts + self.n_shared_experts)
                  * self.expert_params())
        return (2 * self.vocab_size * e + e
                + self.first_k_dense * dense + self.n_moe_layers * expert)

    def flops_per_token(self, seq_len: int) -> float:
        """Training convention of the other configs (6 x active matmul
        weights + 6 x attention): active are the attention products, the
        router, ``top_k`` routed and the shared experts, or the dense MLP,
        and the head."""
        e = self.embed_dim
        attn = self.attention_params() - self.q_lora_rank \
            - self.kv_lora_rank
        active = (self.n_layers * attn
                  + self.first_k_dense * 3 * e * self.mlp_dim
                  + self.n_moe_layers * (
                      e * self.n_experts
                      + (self.top_k + self.n_shared_experts)
                      * self.expert_params())
                  + self.vocab_size * e)
        attn_flops = self.n_layers * seq_len * self.n_heads \
            * (self.head_dim + self.v_dim)
        return 6.0 * active + 6.0 * attn_flops


def tiny_xing4(**overrides) -> Xing4Config:
    return dataclasses.replace(Xing4Config(
        vocab_size=512, n_layers=3, first_k_dense=1, embed_dim=64,
        n_heads=4, n_kv_heads=4, q_lora_rank=32, kv_lora_rank=16,
        nope_dim=16, rope_dim=8, v_dim=16, head_dim=24, mlp_dim=128,
        n_experts=8, top_k=2, expert_dim=32, n_shared_experts=1,
        rope_theta=10000.0, rope_factor=4.0, rope_original_max=64,
        norm_eps=1e-6, tie_embeddings=False), **overrides)


def xing4_29b_a4b(**overrides) -> Xing4Config:
    """The published configuration (40 layers, 29.5 B parameters)."""
    return dataclasses.replace(Xing4Config(
        vocab_size=131072, n_layers=40, first_k_dense=2, embed_dim=3584,
        n_heads=32, n_kv_heads=32, mlp_dim=9216, n_experts=64, top_k=4,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False),
        **overrides)


# -- YaRN ---------------------------------------------------------------------
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float, beta_slow: float):
    """The ``dim / 2`` pair frequencies: extrapolated (``theta^(-2i/dim)``)
    below the correction range, interpolated (divided by ``factor``) above
    it, a linear ramp between."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freqs = 1.0 / (theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs * ((1.0 - ramp) + ramp / factor)


@functools.partial(jax.jit, static_argnames=(
    "dim", "theta", "factor", "original_max", "beta_fast", "beta_slow",
    "mscale", "mscale_all_dim"))
def yarn_table(positions, dim: int, theta: float, factor: float,
               original_max: int, beta_fast: float, beta_slow: float,
               mscale: float = 1.0, mscale_all_dim: float = 1.0):
    """cos/sin tables [..., dim / 2] under YaRN; they carry
    ``m(mscale) / m(mscale_all_dim)`` (1 where the two agree)."""
    freqs = yarn_frequencies(dim, theta, factor, original_max, beta_fast,
                             beta_slow)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    carried = _yarn_mscale(factor, mscale) \
        / _yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(angles) * carried, jnp.sin(angles) * carried


# -- weights ------------------------------------------------------------------
# the leaves drawn from the key, in the order of the keys split from it
# (benchmarks/harness/reference_xing4.py draws the same)
DRAWN = ("embedding", "w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "w_gate",
         "w_up", "w_down", "router", "router_bias", "experts_gate",
         "experts_up", "experts_down", "shared_gate", "shared_up",
         "shared_down", "lm_head") + tuple(
    f"hc_{sub}_{part}" for sub in SUBLAYERS
    for part in ("pre", "post", "res"))
# standard deviation of the router's selection bias: against sigmoid scores
# that spread by about 0.2 it moves a good share of the tokens' choices
ROUTER_BIAS_STD = 0.1
# what the residual mix leans on before the Sinkhorn normalisation: twice
# the identity, so that H_res is neither the identity nor far from mixing
HC_RES_BIAS = 2.0


def init_params(config: Xing4Config, key: jax.Array) -> Params:
    """The recipe of models/llama.py (normal x fan_in^-0.5, norm scales 1)
    over one key a drawn leaf. The mixing parameters act: projections by
    the recipe over the ``hc_mult`` x hidden inputs, the three gains 1, the
    read and write biases 0, the mix's bias ``HC_RES_BIAS`` x identity; the
    router's selection bias is normal x ``ROUTER_BIAS_STD``."""
    keys = dict(zip(DRAWN, jax.random.split(key, len(DRAWN))))
    dtype = jnp.dtype(config.dtype)
    e, h, n = config.embed_dim, config.n_heads, config.hc_mult
    L, Ld, Lm = config.n_layers, config.first_k_dense, config.n_moe_layers
    E, m, md = config.n_experts, config.expert_dim, config.mlp_dim
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    wide = n * e

    def drawn(name, fan_in, shape, to=dtype):
        return _normal_leaf(keys[name], fan_in, tuple(shape), to)

    layers = {
        "attn_norm_scale": jnp.ones((L, e), dtype),
        "mlp_norm_scale": jnp.ones((L, e), dtype),
        "w_dq": drawn("w_dq", e, (L, e, rq)),
        "q_norm_scale": jnp.ones((L, rq), dtype),
        "w_uq": drawn("w_uq", rq, (L, rq, h * config.head_dim)),
        "w_dkv": drawn("w_dkv", e, (L, e, rkv + config.rope_dim)),
        "kv_norm_scale": jnp.ones((L, rkv), dtype),
        "w_ukv": drawn("w_ukv", rkv,
                       (L, rkv, h * (config.nope_dim + config.v_dim))),
        "wo": drawn("wo", h * config.v_dim, (L, h * config.v_dim, e)),
        "w_gate": drawn("w_gate", e, (Ld, e, md)),
        "w_up": drawn("w_up", e, (Ld, e, md)),
        "w_down": drawn("w_down", md, (Ld, md, e)),
        "router": drawn("router", e, (Lm, e, E)).astype(jnp.float32),
        "router_bias": (jax.random.normal(
            keys["router_bias"], (Lm, E), jnp.float32) * ROUTER_BIAS_STD),
        "experts_gate": drawn("experts_gate", e, (Lm, E, e, m)),
        "experts_up": drawn("experts_up", e, (Lm, E, e, m)),
        "experts_down": drawn("experts_down", m, (Lm, E, m, e)),
        "shared_gate": drawn("shared_gate", e,
                             (Lm, e, config.n_shared_experts * m)),
        "shared_up": drawn("shared_up", e,
                           (Lm, e, config.n_shared_experts * m)),
        "shared_down": drawn("shared_down", config.n_shared_experts * m,
                             (Lm, config.n_shared_experts * m, e)),
    }
    for sub in SUBLAYERS:
        layers[f"hc_{sub}_scale"] = jnp.ones((L, wide), dtype)
        for part, out in (("pre", n), ("post", n), ("res", n * n)):
            layers[f"hc_{sub}_{part}"] = drawn(
                f"hc_{sub}_{part}", wide, (L, wide, out))
        layers[f"hc_{sub}_gain"] = jnp.ones((L, 3), jnp.float32)
        layers[f"hc_{sub}_pre_bias"] = jnp.zeros((L, n), jnp.float32)
        layers[f"hc_{sub}_post_bias"] = jnp.zeros((L, n), jnp.float32)
        layers[f"hc_{sub}_res_bias"] = jnp.broadcast_to(
            HC_RES_BIAS * jnp.eye(n, dtype=jnp.float32), (L, n, n))
    held = config.experts_held
    if held is not None:
        for name in ("experts_gate", "experts_up", "experts_down"):
            layers[name] = layers[name][:, held[0]:held[1]]
    return {"embedding": drawn("embedding", e, (config.vocab_size, e)),
            "layers": layers,
            "final_norm_scale": jnp.ones((e,), dtype),
            "lm_head": drawn("lm_head", e, (e, config.vocab_size))}


# -- the residual path: manifold-constrained hyper-connections ----------------
def sinkhorn(logits, iters: int, eps: float, clamp: float):
    """[..., n, n] -> doubly stochastic: ``exp`` of the clipped entries,
    then ``iters`` times each column divided by its sum and each row by
    its. Run over the n x n entries as arrays of their own (tokens minor),
    the sums as additions: an iteration is then elementwise over one
    lane-dense shape and one fused operation of a loop of ``iters``, where
    reductions over a 4-wide minor axis are two small kernels each."""
    n = logits.shape[-1]
    lead = logits.shape[:-2]
    m = jnp.exp(jnp.clip(logits, -clamp, clamp)).reshape(-1, n * n)
    cells = tuple(m[:, at] for at in range(n * n))     # row-major entries

    def iteration(_, cells):
        cols = [functools.reduce(jnp.add, cells[j::n]) + eps
                for j in range(n)]
        cells = [cell / cols[at % n] for at, cell in enumerate(cells)]
        rows = [functools.reduce(jnp.add, cells[i * n:(i + 1) * n]) + eps
                for i in range(n)]
        return tuple(cell / rows[at // n] for at, cell in enumerate(cells))

    cells = jax.lax.fori_loop(0, iters, iteration, cells, unroll=5)
    return jnp.stack(cells, axis=-1).reshape(*lead, n, n)


def mixing_coefficients(config: Xing4Config, x, lp, sub: str):
    """(H_pre [.., n], H_post [.., n], H_res [.., n, n]) float32 of the
    residual state ``x`` [.., n, C] for sub-layer ``sub``: the state
    normalised over all its ``n`` x C entries, three projections with their
    gains and biases, then a sigmoid, twice a sigmoid and Sinkhorn-Knopp."""
    n = config.hc_mult
    lead = x.shape[:-2]
    flat = x.astype(jnp.float32).reshape(*lead, n * x.shape[-1])
    flat = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + config.hc_eps)
    flat = flat * lp[f"hc_{sub}_scale"].astype(jnp.float32)
    gain = lp[f"hc_{sub}_gain"]

    def projected(part):
        return jnp.einsum("...w,wo->...o", flat,
                          lp[f"hc_{sub}_{part}"].astype(jnp.float32),
                          precision=HIGHEST)

    pre = jax.nn.sigmoid(gain[0] * projected("pre")
                         + lp[f"hc_{sub}_pre_bias"])
    post = 2.0 * jax.nn.sigmoid(gain[1] * projected("post")
                                + lp[f"hc_{sub}_post_bias"])
    res = gain[2] * projected("res").reshape(*lead, n, n) \
        + lp[f"hc_{sub}_res_bias"]
    return pre, post, sinkhorn(res, config.hc_iters, config.hc_eps,
                               config.hc_clamp)


def hc_read(config: Xing4Config, x, lp, sub: str):
    """What sub-layer ``sub`` reads of the state ``x`` [B, S, n, C]: the
    streams summed under ``H_pre`` ([B, S, C]), and the write-back's
    coefficients for :func:`hc_write`."""
    with jax.named_scope("layer/hc"):
        pre, post, res = mixing_coefficients(config, x, lp, sub)
        u = jnp.einsum("bsn,bsnc->bsc", pre, x.astype(jnp.float32))
        return u.astype(x.dtype), (post, res)


def hc_write(config: Xing4Config, x, mix, y):
    """``X <- H_res X + H_post^T y``: the streams mixed among themselves,
    and the sub-layer's output ``y`` [B, S, C] added to each under its
    weight."""
    with jax.named_scope("layer/hc"):
        post, res = mix
        mixed = jnp.einsum("bsij,bsjc->bsic", res, x.astype(jnp.float32))
        return (mixed + post[..., None]
                * y.astype(jnp.float32)[:, :, None, :]).astype(x.dtype)


def hc_enter(config: Xing4Config, x):
    """The token's embedding copied into every stream."""
    return jnp.broadcast_to(x[:, :, None, :],
                            (*x.shape[:2], config.hc_mult, x.shape[-1]))


def hc_leave(config: Xing4Config, x):
    """The streams summed (in float32) before the final norm."""
    return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


# -- latent attention: what q, k and v are ------------------------------------
def latent_qkv(config: Xing4Config, lp, h, cos, sin, proj):
    """h [B, S, E] -> (q [B, S, H, nope + rope] with its rope entries
    rotated, the token's cache row [B, S, latent_dim]: ``c_kv``
    normalised, then ``k_r`` rotated, then zeros, and ``None``): the query
    through its low-rank pair and norm, and what the token leaves in the
    cache, which
    ``attend`` expands (prefill) or absorbs the query into (decode). The
    values are a part of that row, so nothing stands in ``v``'s place."""
    b, s, _ = h.shape
    with jax.named_scope("layer/attn/latent"):
        c_q = rms_norm(proj(h, lp["w_dq"], "w_dq"), lp["q_norm_scale"],
                       config.norm_eps)
        q = proj(c_q, lp["w_uq"], "w_uq").reshape(
            b, s, config.n_heads, config.head_dim)
        down = proj(h, lp["w_dkv"], "w_dkv")
        c_kv = rms_norm(down[..., :config.kv_lora_rank],
                        lp["kv_norm_scale"], config.norm_eps)
        k_r = apply_rope(down[..., None, config.kv_lora_rank:], cos,
                         sin)[:, :, 0]
        q = jnp.concatenate(
            [q[..., :config.nope_dim],
             apply_rope(q[..., config.nope_dim:], cos, sin)], axis=-1)
        row = jnp.concatenate([c_kv, k_r], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, config.latent_dim - row.shape[-1])))
    return q, row, None


def up_projections(config: Xing4Config, w_ukv):
    """``W_uk`` and ``W_uv`` [kv_lora_rank, H, nope | v] out of a layer's
    ``w_ukv`` (a head's key entries, then its value entries)."""
    w = w_ukv.reshape(config.kv_lora_rank, config.n_heads,
                      config.nope_dim + config.v_dim)
    return w[..., :config.nope_dim], w[..., config.nope_dim:]


def expand_latents(config: Xing4Config, w_ukv, rows):
    """The expanded form: keys [.., T, H, nope + rope] (the rotated key
    shared by the heads) and values [.., T, H, v] of cache ``rows`` [.., T,
    latent_dim]."""
    c_kv = rows[..., :config.kv_lora_rank]
    k_r = rows[..., config.kv_lora_rank:config.kv_lora_rank
               + config.rope_dim]
    with jax.named_scope("layer/attn/latent"):
        kv = jnp.einsum("...tc,chd->...thd", c_kv, w_ukv.reshape(
            config.kv_lora_rank, config.n_heads,
            config.nope_dim + config.v_dim),
            preferred_element_type=jnp.float32).astype(c_kv.dtype)
        k_rope = jnp.broadcast_to(
            k_r[..., None, :], (*kv.shape[:-1], config.rope_dim))
        return (jnp.concatenate([kv[..., :config.nope_dim], k_rope], -1),
                kv[..., config.nope_dim:])


def absorb_query(config: Xing4Config, w_ukv, q):
    """The absorbed form's query: q [.., H, nope + rope] -> [.., H,
    latent_dim]: ``q_nope W_uk^T``, then ``q_rope``, then zeros, laid out
    as a cache row is, so that a dot with one is the expanded form's
    score."""
    with jax.named_scope("layer/attn/absorb"):
        w_uk, _ = up_projections(config, w_ukv)
        q_lat = jnp.einsum("...hd,chd->...hc", q[..., :config.nope_dim],
                           w_uk)
        row = jnp.concatenate([q_lat, q[..., config.nope_dim:]], axis=-1)
        return jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                       + [(0, config.latent_dim - row.shape[-1])])


def unfold_values(config: Xing4Config, w_ukv, o_lat, dtype):
    """The absorbed form's output: ``o_lat`` [.., H, kv_lora_rank] (the
    softmax's weighted sum of latents) through ``W_uv`` -> [.., H, v]."""
    with jax.named_scope("layer/attn/absorb"):
        _, w_uv = up_projections(config, w_ukv)
        return jnp.einsum("...hc,chd->...hd", o_lat.astype(dtype), w_uv)


XING4_SEAMS = BlockSeams(qkv=latent_qkv, read=hc_read, write=hc_write,
                         enter=hc_enter, leave=hc_leave)


def param_shapes(config: Xing4Config) -> Params:
    return jax.eval_shape(
        functools.partial(init_params, config), jax.random.PRNGKey(0))
