"""The Nemotron-H family (``model_type: nemotron_h``): layers of one
sub-layer each, of a kind that ``pattern`` gives a layer (``M`` a Mamba-2
state-space mixer, ``*`` attention without rotary embedding over few
key/value heads, ``E`` experts of two products with ``relu(x)^2`` between
them under sigmoid routing beside a shared expert). Served only, by the
paged engine (docs/serving.md "State-space layers and the per-slot state").

Nothing here is a second decoder block: ``models/llama.decoder_block`` runs
``config.sublayers(layer)``, here one kind a layer, and the stacked tree
holds each kind's leaves over that kind's layers alone
(``NemotronHConfig.leaf_index``). A state-space layer leaves no rows in the
page pool; what a sequence keeps of it is a state of constant size
(``state_rows()``): ``ssm`` [heads, head_dim, state] float32 and the
convolution's last ``conv_kernel - 1`` inputs. The mixer has two forms over
the same numbers: :func:`mamba_chunk` (a chunk of a prompt, a state in and
out, ``ops/ssm.ssd_prefill``) and :func:`mamba_step` (one token a row, the
rows' states in place, ``ops/ssm.ssm_decode``). The expert layer is
``models/moe.moe_mlp`` over what the layer's leaves hold.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.ssm import ssd_prefill, ssm_decode
from .moe import MoEConfig, _normal_leaf

Params = dict
# a pattern's characters, and the sub-layer each names
KINDS = {"M": "ssm", "*": "attn", "E": "mlp"}
ATTN_LEAVES = ("attn_norm_scale", "wq", "wk", "wv", "wo", "wq_t", "wk_t",
               "wv_t")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MoEConfig):
    """``n_heads`` x ``head_dim`` queries over ``n_kv_heads`` keys and
    values in the attention layers; ``ssm_heads`` x ``ssm_head_dim`` the
    state-space layers' inner width (not a multiple of the hidden size by
    rule); ``expert_dim`` an expert's width, ``shared_dim`` the shared
    expert's. ``mlp_dim`` is unused."""

    recurrent_state = True

    n_layers: int = 5
    pattern: str = "ME*ME"
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    expert_dim: int = 1856
    shared_dim: int = 3712
    scoring: str = "sigmoid"
    routed_scale: float = 2.5
    norm_topk: bool = True
    experts_held: Optional[tuple] = None
    norm_eps: float = 1e-5
    remat: bool = False

    def __post_init__(self):
        if len(self.pattern) != self.n_layers \
                or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r} has to name one of "
                f"{sorted(KINDS)} for each of {self.n_layers} layers")

    @property
    def expert_width(self) -> int:
        return self.expert_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, then B, then C."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def kind_layers(self, kind: str) -> int:
        return sum(KINDS[c] == kind for c in self.pattern)

    @property
    def cache_layers(self) -> int:
        return self.kind_layers("attn")

    @property
    def state_layers(self) -> int:
        return self.kind_layers("ssm")

    def sublayers(self, layer) -> tuple:
        return (KINDS[self.pattern[layer]],)

    def leaf_index(self, name: str, layer: int):
        """Where layer ``layer`` sits in leaf ``name``'s stack (the layers
        of the leaf's kind, in order), or None: the layer is of another
        kind."""
        kind = "ssm" if name.startswith("ssm_") else \
            "attn" if name in ATTN_LEAVES else "mlp"
        if KINDS[self.pattern[layer]] != kind:
            return None
        return sum(KINDS[c] == kind for c in self.pattern[:layer])

    def state_rows(self) -> dict:
        """What a sequence keeps of a state-space layer, whatever its
        length: each buffer's name with its shape and dtype."""
        return {"ssm": ((self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                        jnp.float32),
                "conv": ((self.conv_kernel - 1, self.conv_dim), self.dtype)}

    def rope(self, positions):
        """The attention layers rotate nothing (the state-space layers
        carry position): no tables."""
        return None, None

    # -- counts ---------------------------------------------------------------
    def ssm_params(self) -> int:
        e, di, h = self.embed_dim, self.d_inner, self.ssm_heads
        return (e * (2 * di + 2 * self.ssm_groups * self.ssm_state + h)
                + self.conv_dim * (self.conv_kernel + 1) + 3 * h + di
                + di * e + e)

    def attention_params(self) -> int:
        e = self.embed_dim
        return 2 * e * self.qkv_dim + 2 * e * self.kv_dim + e

    def expert_params(self) -> int:
        return 2 * self.embed_dim * self.expert_dim

    def expert_layer_params(self, experts: int) -> int:
        e = self.embed_dim
        return (experts * self.expert_params() + 2 * e * self.shared_dim
                + e * self.n_experts + self.n_experts + e)

    def param_count(self) -> int:
        held = self.n_experts if self.experts_held is None \
            else self.experts_held[1] - self.experts_held[0]
        return (2 * self.vocab_size * self.embed_dim + self.embed_dim
                + self.kind_layers("ssm") * self.ssm_params()
                + self.kind_layers("attn") * self.attention_params()
                + self.kind_layers("mlp") * self.expert_layer_params(held))

    def flops_per_token(self, seq_len: int) -> float:
        """Training convention of the other configs (6 x active matmul
        weights + 6 x attention and the recurrence)."""
        e = self.embed_dim
        active = (self.kind_layers("ssm") * (
            e * (2 * self.d_inner + 2 * self.ssm_groups * self.ssm_state
                 + self.ssm_heads) + self.d_inner * e)
            + self.kind_layers("attn") * (self.attention_params() - e)
            + self.kind_layers("mlp") * (
                e * self.n_experts + self.top_k * self.expert_params()
                + 2 * e * self.shared_dim)
            + self.vocab_size * e)
        mixing = 2 * self.kind_layers("attn") * seq_len * self.qkv_dim \
            + 2 * self.kind_layers("ssm") * self.d_inner * self.ssm_state
        return 6.0 * active + 6.0 * mixing


def tiny_nemotron_h(**overrides) -> NemotronHConfig:
    return dataclasses.replace(NemotronHConfig(
        vocab_size=512, n_layers=5, pattern="ME*ME", embed_dim=64,
        n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=32, ssm_heads=4,
        ssm_head_dim=16, ssm_groups=2, ssm_state=16, chunk_size=16,
        n_experts=8, top_k=2, expert_dim=32, shared_dim=64,
        tie_embeddings=False), **overrides)


def nemotron_3_nano_30b_a3b(**overrides) -> NemotronHConfig:
    """The published configuration (52 layers, 31.6 B parameters)."""
    return dataclasses.replace(NemotronHConfig(
        vocab_size=131072, n_layers=52, embed_dim=2688, n_heads=32,
        n_kv_heads=2, head_dim=128, mlp_dim=1856, n_experts=128, top_k=6,
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        rope_theta=10000.0, tie_embeddings=False), **overrides)


# -- weights ------------------------------------------------------------------
# the leaves drawn from the key, in the order of the keys split from it
# (benchmarks/harness/reference_nemotronh.py draws the same)
DRAWN = ("embedding", "lm_head", "ssm_in", "ssm_conv_w", "ssm_conv_b",
         "ssm_a_log", "ssm_dt_bias", "ssm_out", "wq", "wk", "wv", "wo",
         "router", "router_bias", "experts_up", "experts_down", "shared_up",
         "shared_down")
ROUTER_BIAS_STD = 0.1
CONV_BIAS_STD = 0.1


@functools.partial(jax.jit, static_argnames=(
    "fan_in", "n_layers", "held", "shape", "dtype"))
def _expert_stack(key, fan_in: int, n_layers: int, held: tuple,
                  shape: tuple, dtype):
    """The experts ``held = (lo, hi)`` of every expert layer, [layers, hi -
    lo, *shape]: each expert's matrix from a key of its own (the leaf's,
    folded with the layer and the expert), so that an expert's weights do
    not depend on which share holds it, and a share never draws the
    others' (all 128 of a layer at the published widths are 1.3 GB a
    leaf). A layer at a time: the float32 draw never exists whole."""
    def layer(at):
        def expert(index):
            k = jax.random.fold_in(jax.random.fold_in(key, at), index)
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)

        return jax.vmap(expert)(jnp.arange(*held))

    return jax.lax.map(layer, jnp.arange(n_layers))


def init_params(config: NemotronHConfig, key: jax.Array) -> Params:
    """The recipe of models/llama.py (normal x fan_in^-0.5, norm scales 1)
    over one key a drawn leaf, and for the state-space layers a draw under
    which every parameter acts: ``A = -uniform[1, 16]``, ``dt_bias`` the
    inverse softplus of a log-uniform step in [``time_step_min``,
    ``time_step_max``] floored at ``time_step_floor``, ``D`` 1, convolution
    weights normal x kernel^-0.5 with bias normal x 0.1; the router's
    selection bias normal x 0.1. Each kind's leaves are stacked over that
    kind's layers."""
    keys = dict(zip(DRAWN, jax.random.split(key, len(DRAWN))))
    dtype = jnp.dtype(config.dtype)
    e, E, m = config.embed_dim, config.n_experts, config.expert_dim
    Ls, La, Le = (config.kind_layers(k) for k in ("ssm", "attn", "mlp"))
    h, di, cd, kk = (config.ssm_heads, config.d_inner, config.conv_dim,
                     config.conv_kernel)

    def drawn(name, fan_in, shape, to=dtype):
        return _normal_leaf(keys[name], fan_in, tuple(shape), to)

    held = tuple(config.experts_held or (0, E))
    step = jnp.exp(jax.random.uniform(
        keys["ssm_dt_bias"], (Ls, h), jnp.float32,
        math.log(config.time_step_min), math.log(config.time_step_max)))
    step = jnp.maximum(step, config.time_step_floor)
    layers = {
        "ssm_norm_scale": jnp.ones((Ls, e), dtype),
        "ssm_in": drawn("ssm_in", e, (Ls, e, di + cd + h)),
        "ssm_conv_w": drawn("ssm_conv_w", kk, (Ls, kk, cd)),
        "ssm_conv_b": (jax.random.normal(keys["ssm_conv_b"], (Ls, cd),
                                         jnp.float32)
                       * CONV_BIAS_STD).astype(dtype),
        "ssm_a_log": jnp.log(jax.random.uniform(
            keys["ssm_a_log"], (Ls, h), jnp.float32, 1.0, 16.0)),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((Ls, h), jnp.float32),
        "ssm_gate_norm_scale": jnp.ones((Ls, di), dtype),
        "ssm_out": drawn("ssm_out", di, (Ls, di, e)),
        "attn_norm_scale": jnp.ones((La, e), dtype),
        "wq": drawn("wq", e, (La, e, config.qkv_dim)),
        "wk": drawn("wk", e, (La, e, config.kv_dim)),
        "wv": drawn("wv", e, (La, e, config.kv_dim)),
        "wo": drawn("wo", config.qkv_dim, (La, config.qkv_dim, e)),
        "mlp_norm_scale": jnp.ones((Le, e), dtype),
        "router": drawn("router", e, (Le, e, E)).astype(jnp.float32),
        "router_bias": jax.random.normal(
            keys["router_bias"], (Le, E), jnp.float32) * ROUTER_BIAS_STD,
        "experts_up": _expert_stack(keys["experts_up"], e, Le, held,
                                    (e, m), dtype),
        "experts_down": _expert_stack(keys["experts_down"], m, Le, held,
                                      (m, e), dtype),
        "shared_up": drawn("shared_up", e, (Le, e, config.shared_dim)),
        "shared_down": drawn("shared_down", config.shared_dim,
                             (Le, config.shared_dim, e)),
    }
    return {"embedding": drawn("embedding", e, (config.vocab_size, e)),
            "layers": layers,
            "final_norm_scale": jnp.ones((e,), dtype),
            "lm_head": drawn("lm_head", e, (e, config.vocab_size))}


# -- the state-space mixer ----------------------------------------------------
def _projected(config: NemotronHConfig, lp, h, proj):
    """h [B, S, E] -> (z [B, S, d_inner], xBC [B, S, conv_dim], dt [B, S,
    H] float32 before its softplus): the one input product's columns."""
    with jax.named_scope("layer/ssm/proj"):
        out = proj(h, lp["ssm_in"], "ssm_in")
        di, cd = config.d_inner, config.conv_dim
        return (out[..., :di], out[..., di:di + cd],
                out[..., di + cd:].astype(jnp.float32)
                + lp["ssm_dt_bias"])


def _convolved(config: NemotronHConfig, lp, taps: list):
    """The depthwise causal convolution with its bias and silu over
    ``taps``: the ``conv_kernel`` inputs of every output position, oldest
    first, each [.., conv_dim]. Returns (x [.., H, P], B and C [.., G,
    N])."""
    w = lp["ssm_conv_w"].astype(jnp.float32)
    out = lp["ssm_conv_b"].astype(jnp.float32) + sum(
        tap.astype(jnp.float32) * w[j] for j, tap in enumerate(taps))
    out = jax.nn.silu(out).astype(taps[0].dtype)
    di, gn = config.d_inner, config.ssm_groups * config.ssm_state
    lead = out.shape[:-1]
    return (out[..., :di].reshape(*lead, config.ssm_heads,
                                  config.ssm_head_dim),
            out[..., di:di + gn].reshape(*lead, config.ssm_groups,
                                         config.ssm_state),
            out[..., di + gn:].reshape(*lead, config.ssm_groups,
                                       config.ssm_state))


def _gated_out(config: NemotronHConfig, lp, y, x, z, proj):
    """The skip term, the gate, the norm over groups of ``d_inner /
    ssm_groups`` channels and the output product. y [B, S, H, P] float32,
    x the scan's input, z [B, S, d_inner]."""
    with jax.named_scope("layer/ssm/gate"):
        b, s = z.shape[:2]
        y = y + lp["ssm_d"][:, None] * x.astype(jnp.float32)
        y = y.reshape(b, s, config.d_inner) \
            * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(b, s, config.ssm_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
            + config.norm_eps)
        y = grouped.reshape(b, s, config.d_inner) \
            * lp["ssm_gate_norm_scale"].astype(jnp.float32)
        return proj(y.astype(z.dtype), lp["ssm_out"], "ssm_out")


def mamba_chunk(config: NemotronHConfig, lp, h, state, window, n_real,
                proj):
    """A chunk of one prompt through the mixer: h [1, S, E]; ``state`` [H,
    P, N] float32 and ``window`` [conv_kernel - 1, conv_dim], what the
    sequence kept before the chunk; ``n_real`` (traced) how many of the S
    tokens are the prompt's own. A token past them has ``dt`` 0 and the
    new window is read at the prompt's end, so what comes back is what the
    sequence keeps after its last real token, whatever the bucket's
    padding. Returns (out [1, S, E], state, window)."""
    s = h.shape[1]
    taps = config.conv_kernel
    z, xbc, dt = _projected(config, lp, h, proj)
    with jax.named_scope("layer/ssm/conv"):
        padded = jnp.concatenate([window.astype(xbc.dtype), xbc[0]])
        x, b, c = _convolved(config, lp,
                             [padded[j:j + s] for j in range(taps)])
        window = jax.lax.dynamic_slice_in_dim(padded, n_real, taps - 1)
    with jax.named_scope("layer/ssm/scan"):
        dt = jnp.where(jnp.arange(s)[:, None] < n_real,
                       jax.nn.softplus(dt[0]), 0.0)
        y, state = ssd_prefill(x, dt, -jnp.exp(lp["ssm_a_log"]), b, c,
                               state, chunk=config.chunk_size)
    return _gated_out(config, lp, y[None], x[None], z, proj), state, window


def mamba_step(config: NemotronHConfig, lp, h, states, windows, index, live,
               proj):
    """One token a row through the mixer: h [rows, 1, E]; ``states`` [L,
    rows, H, P, N] and ``windows`` [L, rows, conv_kernel - 1, conv_dim]
    the stacks of every state-space layer's, of which layer ``index`` is
    read and written; ``live`` [rows] bool: a dead row's state and window
    stay as they are. Returns (out [rows, 1, E], states, windows)."""
    z, xbc, dt = _projected(config, lp, h, proj)
    with jax.named_scope("layer/ssm/conv"):
        window = windows[index]
        taps = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
        x, b, c = _convolved(config, lp,
                             [taps[:, j] for j in range(taps.shape[1])])
        windows = windows.at[index].set(
            jnp.where(live[:, None, None], taps[:, 1:], window))
    with jax.named_scope("layer/ssm/scan"):
        dt = jnp.where(live[:, None], jax.nn.softplus(dt[:, 0]), 0.0)
        states, y = ssm_decode(states, index, x,
                               dt, -jnp.exp(lp["ssm_a_log"]), b, c)
    return (_gated_out(config, lp, y[:, None], x[:, None], z, proj), states,
            windows)
