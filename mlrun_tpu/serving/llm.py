"""TPU LLM inference engine: XLA-compiled prefill + decode with a KV cache.

This is the serving-side counterpart of models/llama.py, built for the
<200ms p50 TTFT target (BASELINE.md): weight-resident params, compile-cache
warmup at load, prefill bucketed to power-of-two lengths (bounded compile
count), decode as a jitted single-token step with donated cache. The
reference has no model inference engine at all — its V2ModelServer calls
user predict() (mlrun/serving/v2_serving.py); here predict() runs this
engine on TPU.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import (
    EXPERT_SERVING_LEAVES,
    SERVING_LEAVES,
    LlamaConfig,
    Params,
    decoder_block,
    embed,
    head_logits,
    layer_slice,
    llama_qkv,
)
from ..ops.attention import causal_bound
from ..utils import logger


def init_kv_cache(config: LlamaConfig, batch: int, max_len: int,
                  dtype=None, kv_dtype: str = "native") -> dict:
    """KV cache pytree: one buffer [layers, batch, max_len, *row] for each
    of ``config.cache_rows()`` (per-head keys and values, or for a latent
    family one latent and one rotated key a token) over the layers that
    leave rows (``config.cache_layers``), one buffer [layers, batch,
    *shape] for each of ``config.state_rows()`` (a recurrent family's
    state, over its ``state_layers``), and ``pos``.
    ``kv_dtype="int8"`` stores k/v per-vector symmetric
    int8 (scale over head_dim, kept f32 per [layer, batch, pos, kv_head]) —
    half the HBM residency of bf16, so twice the slots x context per chip.
    Dequantization happens at attention time; see _quantize_kv."""
    if kv_dtype not in ("native", "int8"):
        raise ValueError(
            f"unknown kv_dtype '{kv_dtype}' (native | int8)")
    refuse_layout(config, "an int8 cache", kv_dtype == "int8")
    dtype = dtype or config.dtype
    lead = (config.cache_layers, batch, max_len)
    rest = {**init_slot_state(config, batch),
            "pos": jnp.zeros((batch,), jnp.int32)}
    if kv_dtype == "int8":
        shape = lead + config.cache_rows()["k"]
        scale_shape = shape[:-1]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.float32),
            "v_scale": jnp.zeros(scale_shape, jnp.float32),
            **rest,
        }
    return {**{name: jnp.zeros(lead + row, dtype)
               for name, row in config.cache_rows().items()}, **rest}


def init_slot_state(config: LlamaConfig, slots: int) -> dict:
    """What ``slots`` sequences keep beside their rows: one buffer
    [state_layers, slots, *shape] for each of ``config.state_rows()``;
    empty for a family without a recurrent state."""
    return {name: jnp.zeros((config.state_layers, slots) + shape, dtype)
            for name, (shape, dtype) in config.state_rows().items()}


class LatentCacheError(ValueError):
    """A family whose cache holds one latent row a token
    (``config.latent_cache``, docs/serving.md "Latent attention and the
    latent page pool") was asked for something that layout does not carry
    yet: an int8 cache, the host KV tier, a KV handoff, speculation, an
    engine other than the paged one."""


class RecurrentStateError(ValueError):
    """A family that keeps a recurrent state a slot
    (``config.recurrent_state``, docs/serving.md "State-space layers and
    the per-slot state") was asked for something a state cannot give: a
    prefix hit skips tokens the state needs and a rejected draft token
    cannot be taken back out of it, so prefix reuse, speculation, a KV
    handoff and the host tier are refused, and with them an int8 cache,
    per-tenant adapters and every engine but the paged one."""


def refuse_layout(config, what: str, asked: bool = True):
    """Raise the family's typed error where ``asked`` is for something
    that what it keeps a sequence by does not carry."""
    if asked and config.latent_cache:
        raise LatentCacheError(
            f"{type(config).__name__} keeps a latent cache, which does "
            f"not carry {what} yet (docs/serving.md \"Latent attention "
            f"and the latent page pool\")")
    if asked and config.recurrent_state:
        raise RecurrentStateError(
            f"{type(config).__name__} keeps a recurrent state a slot, "
            f"which does not carry {what} (docs/serving.md \"State-space "
            f"layers and the per-slot state\")")


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., D] -> (int8 values, f32 scale over the last dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(scale[..., None], 1e-8)).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _cached_attention(config, q, k_cache, v_cache, q_positions, cache_len):
    """q: [B, S, H, D]; caches: [B, M, HKV, D]. Causal over positions (by
    blocks of ``config.block_length``: ops.attention.causal_bound)."""
    n_rep = config.n_heads // config.n_kv_heads
    b, m = k_cache.shape[0], k_cache.shape[1]
    if n_rep > 1:
        k_cache = jnp.repeat(k_cache, n_rep, axis=2)
        v_cache = jnp.repeat(v_cache, n_rep, axis=2)
    scale = config.head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(m)[None, :]  # [1, M]
    bound = causal_bound(q_positions, config.block_length)
    mask = (k_pos[None] <= bound[:, :, None])  # [B, S, M]
    logits = jnp.where(mask[:, None], logits, -2.0**30)
    weights = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v_cache)


def _cached_attention_lse(config, q, k_cache, v_cache, q_positions, k_lo):
    """Bounded dense cached attention returning (o, lse): like
    :func:`_cached_attention` but kv rows below ``k_lo`` are masked out
    — on a paged prefix-cache hit those positions live in shared pool
    pages and are attended by the paged prefill kernel; the two partial
    softmax states are then LSE-merged
    (ops/paged_attention.merge_softmax_states). o is [B, S, H, D] f32,
    lse [B, H, S] f32 (the flash kernels' lse layout). This is the
    s == 1 form of the hit path (a one-token chunk) — a 1-row flash
    instance gains nothing and is a shape class TPU lowering never
    otherwise sees."""
    n_rep = config.n_heads // config.n_kv_heads
    m = k_cache.shape[1]
    if n_rep > 1:
        k_cache = jnp.repeat(k_cache, n_rep, axis=2)
        v_cache = jnp.repeat(v_cache, n_rep, axis=2)
    scale = config.head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(m)[None, :]  # [1, M]
    bound = causal_bound(q_positions, config.block_length)
    mask = (k_pos[None] <= bound[:, :, None]) \
        & (k_pos[None] >= k_lo)     # [B, S, M]
    logits = jnp.where(mask[:, None], logits, -2.0**30)
    m_max = jnp.max(logits, axis=-1)                      # [B, H, S]
    weight = jnp.exp(logits - m_max[..., None])
    denom = jnp.maximum(jnp.sum(weight, axis=-1), 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", weight / denom[..., None],
                   v_cache.astype(jnp.float32))
    return o, m_max + jnp.log(denom)


def _lora_delta(h_in, lora_target, layer, adapter_ids):
    """Per-row low-rank delta for one projection: each batch row gathers
    its OWN (A, B, scaling) from the stacked adapter bank
    (serving/adapters.py AdapterBank) by its adapter slot index. Rows on
    slot 0 (base model / padding) hit all-zero factors — a zero delta —
    so every tenant mix runs the same compiled program. Accumulated in
    f32 like the base einsum, so adding the delta pre-cast matches
    ``merge_lora``-merged weights to accumulation-order rounding."""
    a = lora_target["lora_a"][adapter_ids, layer]       # [B, in, r]
    bb = lora_target["lora_b"][adapter_ids, layer]      # [B, r, out]
    scaling = lora_target["scaling"][adapter_ids, layer]  # [B]
    delta = jnp.einsum("bse,ber->bsr", h_in, a,
                       preferred_element_type=jnp.float32)
    delta = jnp.einsum("bsr,brh->bsh", delta, bb,
                       preferred_element_type=jnp.float32)
    return delta * scaling[:, None, None]


def _serving_proj(lora, adapter_ids, layer: int, dtype):
    """The serving programs' projection ``proj(h_in, w, key)`` for layer
    ``layer``: f32 product, plus each row's LoRA delta out of the adapter
    bank (``lora[key]``, where present) in f32, then the cast to ``dtype``
    (models/llama.py ``trainer_proj`` casts first: they stay two).
    ``out_major``: ``w`` is stored [heads, head_dim, E]
    (:func:`serving_tree`) and the output comes split into heads, [B, S,
    heads, head_dim]: the same product over another order of storage."""
    def proj(h_in, w, key=None, out_major=False):
        out = jnp.einsum("bse,nde->bsnd" if out_major else "bse,eh->bsh",
                         h_in, w, preferred_element_type=jnp.float32)
        if lora is not None and key is not None and key in lora:
            out = out + _lora_delta(h_in, lora[key], layer,
                                    adapter_ids).reshape(out.shape)
        return out.astype(dtype)

    return proj


def relay_layers(config: LlamaConfig, layers: dict) -> dict:
    """``layers`` (a tree's stacked layers) with the attention's input
    projections in the serving layout, **in place**: for a family whose
    q/k/v are models/llama.py ``llama_qkv``'s, each of ``wq``, ``wk``,
    ``wv`` [L, E, H] is stored anew out-major and split into heads, [L,
    heads, head_dim, E], under its name in ``SERVING_LEAVES``, and the
    logical leaf is let go before the next is touched, so at no moment
    both copies of more than one leaf are held (by this dict: a caller
    that keeps the leaves elsewhere keeps them).

    Why (AOT compile, tests/test_tpu_compile.py): a serving program's
    product ``"bse,eh->bsh"`` with the reshape into heads behind it is
    laid out with the contraction minor where the logical leaf has it
    major, so every run of the program slices the layer's leaf out of the
    stack and transposes it before the product, 50 MB a layer at
    Mistral-7B's widths. ``"bse,nde->bsnd"`` over the relaid leaf slices
    the layer inside the product, as ``wo`` and the MLP's are; held [L,
    H, E] without the heads the transposition goes and the slice stays a
    copy of its own. A leaf already relaid, and a family whose seam reads
    other leaves, are left as they are.

    An expert's input matrices (``experts_gate``, ``experts_up`` [L,
    experts, E, width]) whose width is no whole number of 128 lanes are
    stored anew out-major, [L, experts, width, E], under their names in
    ``EXPERT_SERVING_LEAVES``: the device keeps such a buffer with E minor
    whatever the program says, and the grouped kernel, which takes its
    operands row-major, got a copy of the whole stack before every call
    (1.76 GB eleven times a tick at 2688 x 1856; AOT compile, PR 37).
    Out-major the same bytes are row-major, and the kernel reads them
    transposed."""
    if config.seams.qkv is llama_qkv:
        for name, relaid in SERVING_LEAVES.items():
            if name in layers:
                w = layers.pop(name)
                layers[relaid] = jax.block_until_ready(
                    jnp.swapaxes(w, 1, 2).reshape(
                        w.shape[0], -1, config.head_dim, w.shape[1]))
    for name, relaid in EXPERT_SERVING_LEAVES.items():
        # (a width under one lane is padded to a lane whichever way)
        if name in layers and layers[name].shape[-1] > 128 \
                and layers[name].shape[-1] % 128:
            layers[relaid] = jax.block_until_ready(
                jnp.swapaxes(layers.pop(name), -1, -2))
    return layers


def serving_tree(config: LlamaConfig, params: Params) -> Params:
    """The tree a serving engine holds, out of the one it is given: the
    logical tree's leaves with ``wq``, ``wk``, ``wv`` relaid
    (:func:`relay_layers`, on a copy of the two dicts: the caller's tree
    is not changed, and its logical leaves live as long as the caller
    keeps them). A tree already in the serving layout, or of a family
    with nothing to relay, is returned as it is."""
    layers = relay_layers(config, dict(params["layers"]))
    if layers.keys() == params["layers"].keys():
        return params
    return {**params, "layers": layers}


def relaid_bytes(params: Params) -> int:
    """Bytes of the leaves ``params`` holds in the serving layout."""
    relaid = {*SERVING_LEAVES.values(), *EXPERT_SERVING_LEAVES.values()}
    return sum(int(leaf.nbytes) for name, leaf in params["layers"].items()
               if name in relaid)


def _serving_layers(config: LlamaConfig, params: Params, x, cos, sin,
                    attend, lora=None, adapter_ids=None, live=None,
                    ssm=None):
    """The decoder's layers as every serving program runs them: a Python
    loop (compiled once per program; exposes per-layer cache updates
    without scan-carry gymnastics) of models/llama.py ``decoder_block``
    over ``layer_slice`` and :func:`_serving_proj`. ``attend(layer, q, k,
    v) -> [B, S, Hq, D]`` is the caller's cache: it writes K and V and
    reads the attention; ``layer`` counts the layers that leave rows.
    ``ssm(layer, lp, h, proj) -> out`` is the caller's recurrent state
    (models/nemotron_h.py): ``layer`` counts the layers that keep one.
    Returns ``(x, loads)``: ``loads`` the expert layers' loads, empty for
    dense MLPs."""
    loads = []
    at = config.leaf_index
    x = config.seams.enter(config, x)
    for layer in range(config.n_layers):
        x, load = decoder_block(
            config, layer_slice(params["layers"], layer, index_of=at), x,
            cos, sin, proj=_serving_proj(lora, adapter_ids, layer, x.dtype),
            attend=functools.partial(attend, at("wo", layer)),
            ssm=ssm and functools.partial(ssm, at("ssm_in", layer)),
            live=live, layer=layer)
        if load is not None:
            loads.append(load)
    return config.seams.leave(config, x), loads


def expert_counters(loads: list):
    """int32 [3] of the expert layers' ``loads`` of one dispatch: pairs
    routed and experts that got a pair, both summed over layers, and the
    most pairs one expert got in one layer."""
    stacked = jnp.stack(loads)                   # [L, experts held]
    return jnp.stack([jnp.sum(stacked), jnp.sum(stacked > 0),
                      jnp.max(stacked)]).astype(jnp.int32)


def latent_prefill_attend(config, params, cache: dict, new: dict, start,
                          attn_impl: str):
    """``attend`` of a prompt chunk for a latent family
    (:func:`_forward_with_cache`): the chunk's cache rows (latent, then
    rotated key) into the admission's dense rows at ``start``, then the
    expanded form over the rows up to the chunk's end
    (ops/mla_attention.py ``expanded_cached_attention``: the ``mla_flash``
    kernel under ``attn_impl="flash"``, plain products otherwise)."""
    from ..models.xing4 import expand_latents
    from ..ops.mla_attention import expanded_cached_attention

    def attend(layer, q, rows, _):
        new["ckr"].append(jax.lax.dynamic_update_slice(
            cache["ckr"][layer], rows.astype(cache["ckr"].dtype),
            (0, start, 0)))
        w_ukv = params["layers"]["w_ukv"][layer]
        out = expanded_cached_attention(
            q[0], new["ckr"][-1][0], start,
            functools.partial(expand_latents, config, w_ukv),
            v_dim=config.v_dim, scale=config.softmax_scale,
            impl="flash" if attn_impl == "flash" and q.shape[1] > 1
            else "dense")
        return out[None].astype(q.dtype)

    return attend


def state_prefill_mixer(config, cache: dict, new: dict, n_real):
    """``ssm`` of a prompt chunk for a family with a recurrent state
    (:func:`_forward_with_cache`, batch 1): the mixer runs from what the
    admission's cache kept before the chunk (zeros, or the chunk before
    it) and the cache gets what the sequence keeps after the chunk's
    ``n_real``-th token, the last of the prompt's own: attention masks a
    bucket's padding out, a recurrence would integrate it."""
    from ..models.nemotron_h import mamba_chunk

    def ssm(layer, lp, h, proj):
        out, state, window = mamba_chunk(
            config, lp, h, cache["ssm"][layer, 0], cache["conv"][layer, 0],
            n_real, proj)
        new["ssm"].append(state[None])
        new["conv"].append(window[None])
        return out

    return ssm


def _stacked_cache(new: dict, pos) -> dict:
    """The new cache out of the per-layer lists a closure filled."""
    return {**{name: jnp.stack(rows) for name, rows in new.items()},
            "pos": pos}


def _kv_rows(store: dict, k, v) -> dict:
    """K and V as ``store`` (a dense cache or a page pool) keeps them:
    cast to its dtype, or on an int8 store quantised per vector with
    their scales beside them."""
    if "k_scale" in store:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k.astype(store["k"].dtype), "v": v.astype(store["v"].dtype)}


def _dense_kv_write(config, cache: dict, new: dict, layer: int, k, v, write):
    """Layer ``layer``'s K and V into a dense cache, as every dense
    ``attend`` closure does it: each buffer of the layer rewritten by
    ``write(buffer, rows)`` (where the rows go is the caller's) and
    appended to ``new[name]`` (a list a buffer, which the caller stacks:
    :func:`_stacked_cache`). Returns the layer's (k, v) as attention reads
    them."""
    for name, row in _kv_rows(cache, k, v).items():
        new[name].append(write(cache[name][layer], row))
    if "k_scale" in cache:
        return (_dequantize_kv(new["k"][-1], new["k_scale"][-1],
                               config.dtype),
                _dequantize_kv(new["v"][-1], new["v_scale"][-1],
                               config.dtype))
    return new["k"][-1], new["v"][-1]


def _forward_with_cache(config: LlamaConfig, params: Params,
                        tokens: jax.Array, cache: dict,
                        lora: Optional[Params] = None,
                        adapter_ids: Optional[jax.Array] = None,
                        prefix_kv: Optional[dict] = None,
                        all_logits: bool = False,
                        attn_impl: str = "dense",
                        page_size: int = 0,
                        logits_at: Optional[jax.Array] = None,
                        with_loads: bool = False):
    """Run tokens starting at cache['pos']; returns (logits, new_cache):
    the logits of ONE dispatched position, [B, vocab] — the last one, or
    the one ``logits_at`` names (a traced int32 index into the ``S``
    dispatched tokens: a bucket-padded prompt's last REAL position,
    ``take - 1``, so one compiled program a bucket serves every prompt
    length in it and the first token comes from the dispatch that
    prefilled it; causal masking keeps the padding to its right from
    touching it).
    A family with a recurrent state keeps it in the cache too
    (:func:`state_prefill_mixer`): what comes back is the state after that
    last real position, so a padded bucket does not move it, and the next
    chunk of a chunked prefill starts from it.
    With ``with_loads`` (an engine's prefill program of an expert model
    served token by token) a third output follows: the dispatch's
    :func:`expert_counters`.
    ``all_logits=True`` returns [B, S, vocab] logits for every input
    position instead (speculative verification needs the target's
    distribution after each proposed token — serving/speculative.py).

    ``lora``/``adapter_ids`` enable batched multi-tenant LoRA
    (docs/serving.md "Multi-tenant LoRA"): ``lora`` is the stacked
    adapter bank (``{target: {lora_a: [S, L, in, r], ...}}``) and
    ``adapter_ids`` [B] selects each row's bank slot (0 = base model).

    ``attn_impl="flash"`` runs the attention over the cache through the
    offset-aware flash kernel (ops.attention.flash_attention_cached,
    interpret mode off-TPU) instead of the dense masked softmax — the
    engines' prefill hot path (docs/serving.md "Attention kernels").

    ``prefix_kv`` is the paged engine's prefix-hit form (batch=1): a
    dict of the pool as stored, all layers — ``{"k": [L, P+1, ps, Hkv, D],
    "v": ..., "page_ids": [pages_per_slot] int32, "base": int32
    scalar[, "k_scale"/"v_scale": [L, P+1, ps, Hkv] f32 on int8
    pools]}``. Cache rows below ``base`` are zeros — the cached prefix
    KV is attended IN PLACE through the page ids by the multi-row paged
    prefill kernel and LSE-merged with the local attention over the
    suffix rows, so a prefix hit never gathers the cached KV densely
    (``page_size`` must then be the pool's static page size)."""
    b, s = tokens.shape
    new = {name: [] for name in cache if name != "pos"}
    max_len = cache[next(iter(new))].shape[2]
    start = cache["pos"]  # [B]
    positions = start[:, None] + jnp.arange(s)[None, :]  # [B, S]
    x = embed(config, params, tokens)
    # rope per batch row (positions differ per row only after mixed prefill;
    # keep a single table using row 0 — engine keeps pos uniform per batch)
    cos, sin = config.rope(positions[0])

    def write(buffer, rows):
        # k,v into the cache at start..start+s (uniform start)
        return jax.lax.dynamic_update_slice(
            buffer, rows, (0, start[0]) + (0,) * (rows.ndim - 2))

    def attend(layer, q, k, v):
        k_attn, v_attn = _dense_kv_write(config, cache, new, layer, k, v,
                                         write)
        n_rep = config.n_heads // config.n_kv_heads
        if prefix_kv is not None:
            # paged prefix-hit suffix prefill: local rows (>= base) via
            # bounded flash (s > 1) or the bounded dense form (a
            # one-token chunk), the cached prefix via the
            # multi-row paged prefill kernel reading pool pages in
            # place — partial softmax states LSE-merged
            # (docs/serving.md "Attention kernels")
            from ..ops.attention import (
                _flash_fwd_v2_cached_bounded,
                _repeat_kv,
            )
            from ..ops.paged_attention import (
                merge_softmax_states,
                paged_prefix_part,
            )

            base = prefix_kv["base"]
            if attn_impl == "flash" and s > 1:
                o_loc, lse_loc = _flash_fwd_v2_cached_bounded(
                    q, _repeat_kv(k_attn, n_rep),
                    _repeat_kv(v_attn, n_rep), start[0], base,
                    block_length=config.block_length)
            else:
                o_loc, lse_loc = _cached_attention_lse(
                    config, q, k_attn, v_attn, positions, base)
            o_pre, lse_pre = paged_prefix_part(
                q, prefix_kv["k"], prefix_kv["v"], layer,
                prefix_kv["page_ids"], base, page_size=page_size,
                k_scale=prefix_kv.get("k_scale"),
                v_scale=prefix_kv.get("v_scale"))
            return merge_softmax_states(o_pre, lse_pre, o_loc,
                                        lse_loc).astype(q.dtype)
        if attn_impl == "flash" and s > 1:
            from ..ops.attention import _repeat_kv, flash_attention_cached

            # positions are uniform per batch row on the prefill path
            # (mixed-start batches never reach here — see rope note
            # above).
            # 1-token dispatches (decode steps, a one-token chunk) stay
            # dense: a block_q=1 kernel instance gains nothing and is a
            # shape class TPU lowering never otherwise sees
            return flash_attention_cached(
                q, _repeat_kv(k_attn, n_rep), _repeat_kv(v_attn, n_rep),
                start[0], block_length=config.block_length)
        return _cached_attention(config, q, k_attn, v_attn, positions,
                                 max_len)

    ssm = None
    if config.latent_cache:
        # batch 1: the paged engine's admission (the dense engines refuse
        # the family)
        attend = latent_prefill_attend(config, params, cache, new,
                                       start[0], attn_impl)
    elif config.recurrent_state:
        ssm = state_prefill_mixer(config, cache, new,
                                  s if logits_at is None else logits_at + 1)
    x, loads = _serving_layers(config, params, x, cos, sin, attend, lora,
                               adapter_ids, ssm=ssm)
    if not all_logits:
        # one row through the final norm and the head: the position the
        # caller names, else the last dispatched one
        with jax.named_scope("head"):
            x = x[:, -1:] if logits_at is None else \
                jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
    logits = head_logits(config, params, x)
    out = ((logits if all_logits else logits[:, 0]),
           _stacked_cache(new, cache["pos"] + s))
    if with_loads:
        # an expert model served token by token reports its experts' load
        # a dispatch (a block model's pass does: _verify_rowwise_paged)
        with jax.named_scope("head"):
            out += (expert_counters(loads),)
    return out


class LLMEngine:
    """Compiled prefill/decode around a Llama param tree."""

    def __init__(self, config: LlamaConfig, params: Params,
                 max_len: int = 2048, batch: int = 1,
                 prefill_buckets: tuple = (128, 512, 1024),
                 temperature: float = 0.0, kv_dtype: str = "native",
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 attention_impl: str | None = None,
                 adapters=None, max_live_adapters: int | None = None):
        from ..config import mlconf
        from ..ops.attention import resolve_prefill_impl

        if getattr(config, "block_length", 1) > 1:
            from .llm_batch import BlockDecodingError

            raise BlockDecodingError(
                f"LLMEngine decodes one token a step; a model with "
                f"block_length {config.block_length} needs the paged "
                f"engine (continuous_batching=True, paged=True)")
        refuse_layout(config, "LLMEngine's dense rows (the paged engine "
                      "serves it: continuous_batching=True, paged=True)")
        self.config = config
        # wq, wk, wv in the layout their products contract over
        self.params = serving_tree(config, params)
        self.weights_relaid_bytes = relaid_bytes(self.params)
        self.max_len = max_len
        self.batch = batch
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.kv_dtype = kv_dtype
        self._rng = jax.random.PRNGKey(seed)
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= max_len) or (max_len,)
        if attention_impl is None:
            attention_impl = str(
                mlconf.serving.llm.get("attention_impl", "auto"))
        self.attention_impl = attention_impl
        # flash prefill; decode stays dense — a 1-token q gains nothing
        # from blockwise streaming and the masked softmax is one fused op
        self.prefill_impl = resolve_prefill_impl(attention_impl)
        # multi-tenant LoRA (docs/serving.md "Multi-tenant LoRA"):
        # named adapters resolved per request/row through the registry
        from .adapters import AdapterRegistry

        if adapters is None:
            self._adapters = None
        elif isinstance(adapters, AdapterRegistry):
            self._adapters = adapters
        else:
            self._adapters = AdapterRegistry(config, sources=adapters,
                                             max_live=max_live_adapters)

        self._prefill = jax.jit(
            functools.partial(_forward_with_cache, config,
                              attn_impl=self.prefill_impl))
        self._decode = jax.jit(
            functools.partial(_forward_with_cache, config),
            donate_argnums=(2,))

        # fused greedy decode: N tokens per dispatch via lax.scan
        def decode_n(params, first_token, cache, n, lora=None,
                     adapter_ids=None):
            def body(carry, _):
                token, cache_in = carry
                logits, cache_out = _forward_with_cache(
                    config, params, token, cache_in, lora=lora,
                    adapter_ids=adapter_ids)
                next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (next_token[:, None], cache_out), next_token

            (_, cache), tokens = jax.lax.scan(
                body, (first_token, cache), None, length=n)
            return tokens, cache  # tokens: [n, B]

        self._decode_n = jax.jit(decode_n, static_argnums=(3,),
                                 donate_argnums=(2,))
        self.decode_chunk = 32

    def _lora_kwargs(self, slots=None) -> dict:
        """jit kwargs threading the adapter bank + per-row slot indices
        into the forward; empty (and compile-identical to the
        pre-adapter programs) when no registry is attached. ``slots`` is
        one bank slot per batch row (int or [batch] array); default all
        rows on the base slot 0."""
        if self._adapters is None:
            return {}
        if slots is None:
            ids = np.zeros((self.batch,), np.int32)
        else:
            ids = np.broadcast_to(
                np.asarray(slots, np.int32), (self.batch,)).copy()
        return {"lora": self._adapters.bank.tensors,
                "adapter_ids": jnp.asarray(ids)}

    def warmup(self):
        """Compile every prefill bucket + the decode step ahead of traffic."""
        started = time.perf_counter()
        kw = self._lora_kwargs()
        for bucket in self.prefill_buckets:
            cache = init_kv_cache(self.config, self.batch, self.max_len,
                              kv_dtype=self.kv_dtype)
            tokens = jnp.zeros((self.batch, bucket), jnp.int32)
            logits, cache = self._prefill(self.params, tokens, cache,
                                          logits_at=np.int32(bucket - 1),
                                          **kw)
            step_tok = jnp.zeros((self.batch, 1), jnp.int32)
            logits, cache = self._decode(self.params, step_tok, cache, **kw)
            step_tok = jnp.zeros((self.batch, 1), jnp.int32)
            tokens_out, cache = self._decode_n(self.params, step_tok, cache,
                                               self.decode_chunk, **kw)
            jax.block_until_ready((logits, tokens_out))
        logger.info("llm engine warm", buckets=list(self.prefill_buckets),
                    warmup_s=round(time.perf_counter() - started, 2))

    def _bucket_for(self, length: int) -> int:
        for bucket in self.prefill_buckets:
            if length <= bucket:
                return bucket
        return self.max_len

    def generate(self, prompt_tokens, max_new_tokens: int = 64,
                 eos_id: int | None = None,
                 adapter: str = "",
                 request_key=None) -> tuple[list[int], dict]:
        """Greedy/temperature generation for a single prompt (batch=1 row
        replicated); returns (tokens, timing stats). ``adapter`` names a
        registry adapter applied to every row (404s typed when
        unknown); a tenant id with canary-loop state resolves to its
        effective versioned id first (serving/canary.py)."""
        prompt = np.asarray(prompt_tokens, dtype=np.int32).reshape(1, -1)
        prompt_len = prompt.shape[1]
        if prompt_len + max_new_tokens > self.max_len:
            from .resilience import PromptTooLongError

            raise PromptTooLongError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens} exceeds max_len {self.max_len}")
        split_tenant = split_side = ""
        if adapter:
            from .canary import get_canary_router, split_key_for

            router = get_canary_router()
            if router is not None:
                resolved, side = router.resolve(
                    adapter, split_key_for(prompt_tokens, request_key))
                if side:
                    split_tenant, split_side = adapter, side
                adapter = resolved
        if adapter and self._adapters is None:
            from .adapters import UnknownAdapterError

            raise UnknownAdapterError(
                f"engine has no adapter registry (adapter='{adapter}')")
        bucket = self._bucket_for(prompt_len)
        padded = np.zeros((self.batch, bucket), np.int32)
        padded[:, :prompt_len] = prompt

        t0 = time.perf_counter()
        kw = {}
        if self._adapters is not None:
            self._adapters.pin(adapter)
        try:
            if self._adapters is not None:
                slot = self._adapters.ensure_loaded(adapter)
                kw = self._lora_kwargs(slot)
            out_tokens, ttft, t1 = self._generate_inner(
                prompt_len, padded, max_new_tokens, eos_id, t0, kw)
        finally:
            if self._adapters is not None:
                self._adapters.unpin(adapter)
        decode_time = time.perf_counter() - t1
        stats = {
            "ttft_s": ttft,
            "decode_tokens_per_sec": (len(out_tokens) - 1) / decode_time
            if decode_time > 0 and len(out_tokens) > 1 else 0.0,
            "prompt_len": prompt_len,
            "generated": len(out_tokens),
        }
        if split_side:
            # metered on SUCCESS only (a typed rejection above never
            # reaches here) — the split-fraction telemetry counts
            # served requests
            from ..obs import CANARY_REQUESTS

            CANARY_REQUESTS.inc(adapter=split_tenant, side=split_side)
        from .samples import emit_sample, sampling_enabled

        if sampling_enabled():
            emit_sample(adapter=adapter, tokens=list(out_tokens),
                        prompt_len=prompt_len, generated=len(out_tokens),
                        ttft_s=ttft,
                        total_s=time.perf_counter() - t0,
                        logit_margin=float("nan"),
                        engine=type(self).__name__, replica="")
        return out_tokens, stats

    # -- adapter source lifecycle (docs/continuous_tuning.md) ----------------
    def add_adapter_source(self, name: str, source):
        if self._adapters is None:
            raise ValueError(
                "engine has no adapter registry (build it with "
                "adapters=... to hot-load canaries)")
        self._adapters.add_source(name, source)

    def retire_adapter(self, name: str, keep_source: bool = False):
        if self._adapters is not None:
            self._adapters.retire(name, keep_source=keep_source)

    def _prefill_prompt(self, padded, prompt_len, cache, kw):
        """One dispatch of the bucket-padded prompt rows: the logits of
        the last REAL position (the program returns the position it is
        told), the cache rewound from the bucket's end to the prompt's —
        decoding overwrites the padding's rows from there."""
        logits, cache = self._prefill(
            self.params, jnp.asarray(padded), cache,
            logits_at=np.int32(prompt_len - 1), **kw)
        cache["pos"] = jnp.full((self.batch,), prompt_len, jnp.int32)
        return logits, cache

    def _generate_inner(self, prompt_len, padded, max_new_tokens, eos_id,
                        t0, kw):
        cache = init_kv_cache(self.config, self.batch, self.max_len,
                              kv_dtype=self.kv_dtype)
        logits, cache = self._prefill_prompt(padded, prompt_len, cache, kw)
        next_token = self._sample(logits)
        jax.block_until_ready(next_token)
        ttft = time.perf_counter() - t0

        out_tokens = [int(np.asarray(next_token)[0])]
        t1 = time.perf_counter()
        remaining = max_new_tokens - 1
        if self.temperature and self.temperature > 0:
            # sampled decode: per-token loop (carry randomness on host)
            for _ in range(remaining):
                if eos_id is not None and out_tokens[-1] == eos_id:
                    break
                step = jnp.full((self.batch, 1), out_tokens[-1], jnp.int32)
                logits, cache = self._decode(self.params, step, cache, **kw)
                next_token = self._sample(logits)
                out_tokens.append(int(np.asarray(next_token)[0]))
        else:
            # greedy: fused multi-token scan per dispatch. Always run the
            # full compiled chunk (ONE program, compiled at warmup) and
            # truncate host-side — a variable tail would recompile per
            # distinct length on the serving path.
            while remaining > 0:
                if eos_id is not None and out_tokens[-1] == eos_id:
                    break
                if prompt_len + len(out_tokens) + self.decode_chunk \
                        > self.max_len:
                    break  # cache capacity: full chunk wouldn't fit
                step = jnp.full((self.batch, 1), out_tokens[-1], jnp.int32)
                tokens, cache = self._decode_n(self.params, step, cache,
                                               self.decode_chunk, **kw)
                chunk = np.asarray(tokens)[:, 0].tolist()[:remaining]
                if eos_id is not None and eos_id in chunk:
                    chunk = chunk[: chunk.index(eos_id) + 1]
                out_tokens.extend(int(t) for t in chunk)
                remaining -= len(chunk)
        return out_tokens, ttft, t1

    def generate_batch(self, prompts: list, max_new_tokens: int = 64,
                       eos_id: int | None = None,
                       adapters: list | None = None) -> tuple[list, dict]:
        """Batched greedy generation for EQUAL-LENGTH prompts (one fused
        decode scan serves the whole batch). Mixed lengths fall back to a
        per-prompt loop — exact per-row positions/pad masking in the cache
        is R2 work.

        ``adapters`` gives one registry adapter name per prompt ("" =
        base): each batch row applies its OWN low-rank delta inside the
        shared dispatch (docs/serving.md "Multi-tenant LoRA"); padding
        rows ride the base slot.

        Engine must be built with batch >= len(prompts).
        """
        n = len(prompts)
        if n == 0:
            return [], {"ttft_s": 0.0, "decode_tokens_per_sec": 0.0,
                        "batch": 0}
        if n > self.batch:
            raise ValueError(
                f"{n} prompts exceed engine batch size {self.batch}")
        if adapters is not None and len(adapters) != n:
            raise ValueError(
                f"adapters has {len(adapters)} entries for {n} prompts")
        row_adapters = list(adapters or [""] * n)
        if any(row_adapters) and self._adapters is None:
            from .adapters import UnknownAdapterError

            raise UnknownAdapterError(
                "engine has no adapter registry "
                f"(adapters={sorted(set(filter(None, row_adapters)))})")
        lengths = {len(p) for p in prompts}
        # sampled decoding carries host-side randomness — use the per-prompt
        # path so semantics match generate() exactly
        if len(lengths) > 1 or (self.temperature and self.temperature > 0):
            outs = []
            started = time.perf_counter()
            first_ttft = None
            for prompt, row_adapter in zip(prompts, row_adapters):
                tokens, stats = self.generate(prompt, max_new_tokens,
                                              eos_id, adapter=row_adapter)
                outs.append(tokens)
                first_ttft = first_ttft if first_ttft is not None \
                    else stats["ttft_s"]
            wall = time.perf_counter() - started
            generated = sum(len(o) for o in outs)
            return outs, {
                "ttft_s": first_ttft or 0.0,
                # true aggregate: total tokens over total wall time
                "decode_tokens_per_sec": generated / wall if wall > 0
                else 0.0,
                "batch": n,
            }

        prompt_len = lengths.pop()
        bucket = self._bucket_for(prompt_len)
        padded = np.zeros((self.batch, bucket), np.int32)
        for i, prompt in enumerate(prompts):
            padded[i, :prompt_len] = prompt

        t0 = time.perf_counter()
        kw = {}
        pinned = []
        try:
            if self._adapters is not None:
                # pin every row's adapter for the whole batched dispatch;
                # padding rows (>= n) stay on the base slot 0
                slots = np.zeros((self.batch,), np.int32)
                for i, row_adapter in enumerate(row_adapters):
                    self._adapters.pin(row_adapter)
                    pinned.append(row_adapter)
                    slots[i] = self._adapters.ensure_loaded(row_adapter)
                kw = self._lora_kwargs(slots)
            out, ttft, t1, generated = self._generate_batch_inner(
                n, prompt_len, padded, max_new_tokens, eos_id, t0, kw)
        finally:
            if self._adapters is not None:
                for row_adapter in pinned:
                    self._adapters.unpin(row_adapter)
        decode_time = time.perf_counter() - t1
        stats = {
            "ttft_s": ttft,
            "decode_tokens_per_sec": generated / decode_time
            if decode_time > 0 and generated else 0.0,
            "batch": n,
        }
        return out, stats

    def _generate_batch_inner(self, n, prompt_len, padded, max_new_tokens,
                              eos_id, t0, kw):
        cache = init_kv_cache(self.config, self.batch, self.max_len,
                              kv_dtype=self.kv_dtype)
        logits, cache = self._prefill_prompt(padded, prompt_len, cache, kw)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [[int(t)] for t in np.asarray(next_token)[:n]]
        ttft = time.perf_counter() - t0

        t1 = time.perf_counter()
        remaining = max_new_tokens - 1
        generated_so_far = 1
        step = next_token[:, None]
        while remaining > 0:
            if eos_id is not None and all(
                    o and o[-1] == eos_id for o in out[:n]):
                break  # every row finished — skip further decode dispatches
            # same capacity guard as generate(): pos starts at prompt_len
            if prompt_len + generated_so_far + self.decode_chunk \
                    > self.max_len:
                break
            tokens, cache = self._decode_n(self.params, step, cache,
                                           self.decode_chunk, **kw)
            chunk = np.asarray(tokens)  # [chunk, B]
            take = min(self.decode_chunk, remaining)
            for i in range(n):
                row = chunk[:take, i].tolist()
                if eos_id is not None and eos_id in row:
                    row = row[: row.index(eos_id) + 1]
                if not out[i] or (eos_id is None
                                  or out[i][-1] != eos_id):
                    out[i].extend(int(t) for t in row)
            step = tokens[-1][:, None]
            remaining -= take
            generated_so_far += self.decode_chunk  # cache rows consumed
        generated = sum(len(o) for o in out) - n
        return out, ttft, t1, generated

    def _sample(self, logits):
        if self.temperature and self.temperature > 0:
            from .sampling import sample_logits

            b = logits.shape[0]
            self._rng, sub = jax.random.split(self._rng)
            return sample_logits(
                logits, sub,
                jnp.full((b,), self.temperature, jnp.float32),
                jnp.full((b,), self.top_k, jnp.int32),
                jnp.full((b,), self.top_p, jnp.float32))
        return jnp.argmax(logits, axis=-1)


class LLMModelServer:
    """Serving-graph step: tokenization on host, generation on TPU.

    class args: model_preset|model_path, tokenizer, max_len, warmup...
    """

    def __new__(cls, *args, **kwargs):
        from .v2_serving import V2ModelServer

        class _Server(V2ModelServer):
            def __init__(self, *a, model_preset: str = "tiny",
                         tokenizer: str | None = None, max_len: int = 1024,
                         max_new_tokens: int = 64, hf_model: str | None = None,
                         temperature: float = 0.0, warmup: bool = True,
                         continuous_batching: bool = False, slots: int = 4,
                         kv_dtype: str = "native", top_k: int = 0,
                         top_p: float = 1.0, paged: bool = False,
                         page_size: int = 128,
                         n_pages: int | None = None,
                         max_queue_size: int = 0, max_wait: float = 0.0,
                         degradation: dict | None = None,
                         prefill_chunk: int | None = None,
                         prefix_cache: bool | None = None,
                         attention_impl: str | None = None,
                         replicas: int = 0,
                         prefill_replicas: int = 0,
                         routing: str | None = None,
                         adapters: dict | None = None,
                         max_live_adapters: int | None = None,
                         adapter_rate: float | None = None,
                         adapter_burst: float | None = None,
                         request_ledger: bool | None = None,
                         speculative: dict | bool | None = None,
                         denoising_steps: int | None = None,
                         remasking: str = "low_confidence_static", **kw):
                super().__init__(*a, **kw)
                self.model_preset = model_preset
                self.tokenizer_id = tokenizer
                self.max_len = max_len
                self.max_new_tokens = max_new_tokens
                self.hf_model = hf_model
                self.temperature = temperature
                self._warmup = warmup
                self.continuous_batching = continuous_batching
                self.slots = slots
                self.kv_dtype = kv_dtype
                self.top_k = top_k
                self.top_p = top_p
                self.paged = paged
                self.page_size = page_size
                self.n_pages = n_pages
                # overload knobs forwarded to the batching engines
                # (docs/serving_resilience.md)
                self.max_queue_size = max_queue_size
                self.max_wait = max_wait
                self.degradation = degradation
                # prefill/prefix-cache knobs (docs/serving.md "Prefill &
                # prefix cache"); None = mlconf.serving.llm defaults
                self.prefill_chunk = prefill_chunk
                self.prefix_cache = prefix_cache
                # attention kernel dispatch (docs/serving.md "Attention
                # kernels"): auto | flash | kernel | reference
                self.attention_impl = attention_impl
                # engine fleet (docs/serving.md "Engine fleet"):
                # replicas >= 2 builds an EngineFleet instead of one
                # engine; prefill_replicas > 0 additionally splits
                # prefill and decode into separate pools with KV handoff
                self.replicas = replicas
                self.prefill_replicas = prefill_replicas
                self.routing = routing
                # multi-tenant LoRA (docs/serving.md "Multi-tenant
                # LoRA"): named adapter sources (tree | artifact path |
                # callable), device working-set bound, and the
                # per-tenant admission token bucket
                self.adapters = adapters
                self.max_live_adapters = max_live_adapters
                self.adapter_rate = adapter_rate
                self.adapter_burst = adapter_burst
                # per-request phase ledger (docs/observability.md
                # "Request attribution"); None = mlconf default (on)
                self.request_ledger = request_ledger
                # in-engine speculative decoding (docs/serving.md
                # "Speculative decoding"): True / {"k": ..., "draft":
                # preset} enables a resident draft model; None = the
                # mlconf.serving.llm.speculative defaults decide
                self.speculative = speculative
                # block-diffusion decoding (docs/serving.md
                # "Block-diffusion decoding"), for a model with
                # block_length > 1: denoising passes a block (None: the
                # block length) and the unmasking rule; the paged engine
                # checks both against the model
                self.denoising_steps = denoising_steps
                self.remasking = remasking
                self._tokenizer = None
                self.engine = None
                # predict→postprocess handover for the opt-in "timing"
                # field: thread-local, because concurrent requests share
                # this server instance and do_event runs the whole
                # pre/predict/post chain on one thread — an instance
                # attribute would hand one request's timing to another
                import threading as _threading

                self._timing_out = _threading.local()

            def load(self):
                from ..frameworks.jax.auto_trainer import MODEL_PRESETS
                from ..models import init_params

                if self.hf_model:
                    from ..frameworks.huggingface import (
                        load_hf_weights_into_llama,
                    )

                    config, params = load_hf_weights_into_llama(self.hf_model)
                else:
                    config = MODEL_PRESETS[self.model_preset]()
                    params = init_params(config, jax.random.PRNGKey(0))
                # this tree is the server's alone, so it goes into the
                # serving layout in place, a leaf at a time: the engines
                # (a fleet's replicas share it) then take it as it is,
                # and the device never holds both copies of all three
                relay_layers(config, params["layers"])
                if self.tokenizer_id:
                    from transformers import AutoTokenizer

                    self._tokenizer = AutoTokenizer.from_pretrained(
                        self.tokenizer_id)
                # resolve the speculative class arg to the engines'
                # draft-carrying dict: True / {"draft": preset} builds
                # the named draft preset resident alongside the target
                # (seeded differently — a real deployment loads trained
                # draft weights the same way)
                spec_conf = None
                if self.continuous_batching:
                    from ..config import mlconf

                    node = mlconf.serving.llm.get("speculative")
                    spec_conf = dict(node.to_dict()) if node is not None \
                        else {}
                    spec_arg = self.speculative
                    if isinstance(spec_arg, bool):
                        spec_arg = {"enabled": spec_arg}
                    if isinstance(spec_arg, dict):
                        spec_conf.update(spec_arg)
                        spec_conf.setdefault("enabled", True)
                    if (spec_conf.get("enabled")
                            and spec_conf.get("draft")
                            and "draft_config" not in spec_conf):
                        draft_config = MODEL_PRESETS[spec_conf["draft"]]()
                        spec_conf["draft_config"] = draft_config
                        spec_conf["draft_params"] = init_params(
                            draft_config, jax.random.PRNGKey(1))
                        relay_layers(draft_config,
                                     spec_conf["draft_params"]["layers"])
                    if not (spec_conf.get("enabled")
                            and spec_conf.get("draft_config") is not None):
                        spec_conf = None
                if self.continuous_batching:
                    # slot-based scheduler: concurrent requests interleave
                    # on one decode batch; per-request sampling settings
                    # ride the shared dispatch (serving/sampling.py)
                    def build_engine(role="unified"):
                        if self.paged:
                            # paged KV pool: oversubscribable long-prompt
                            # serving (serving/paged.py)
                            from .paged import PagedContinuousBatchingEngine

                            return PagedContinuousBatchingEngine(
                                config, params, max_len=self.max_len,
                                slots=self.slots, kv_dtype=self.kv_dtype,
                                page_size=self.page_size,
                                n_pages=self.n_pages,
                                max_queue_size=self.max_queue_size,
                                max_wait=self.max_wait,
                                degradation=self.degradation,
                                prefill_chunk=self.prefill_chunk,
                                prefix_cache=self.prefix_cache,
                                attention_impl=self.attention_impl,
                                adapters=self.adapters,
                                max_live_adapters=self.max_live_adapters,
                                adapter_rate=self.adapter_rate,
                                adapter_burst=self.adapter_burst,
                                request_ledger=self.request_ledger,
                                speculative=spec_conf,
                                denoising_steps=self.denoising_steps,
                                remasking=self.remasking)
                        from .llm_batch import ContinuousBatchingEngine

                        return ContinuousBatchingEngine(
                            config, params, max_len=self.max_len,
                            slots=self.slots, kv_dtype=self.kv_dtype,
                            max_queue_size=self.max_queue_size,
                            max_wait=self.max_wait,
                            degradation=self.degradation,
                            prefill_chunk=self.prefill_chunk,
                            attention_impl=self.attention_impl,
                            adapters=self.adapters,
                            max_live_adapters=self.max_live_adapters,
                            adapter_rate=self.adapter_rate,
                            adapter_burst=self.adapter_burst,
                            request_ledger=self.request_ledger,
                            speculative=spec_conf)

                    if self.replicas >= 2 or self.prefill_replicas:
                        # replica fleet: prefix-affinity routing across
                        # N engines, optional prefill/decode pools with
                        # KV handoff (docs/serving.md "Engine fleet")
                        from .fleet import EngineFleet

                        self.engine = EngineFleet(
                            build_engine,
                            replicas=max(1, self.replicas),
                            prefill_replicas=self.prefill_replicas,
                            routing=self.routing)
                    else:
                        self.engine = build_engine()
                    if self._warmup:
                        self.engine.warmup()
                    self.engine.start()
                else:
                    if self.paged:
                        raise ValueError(
                            "paged=True needs continuous_batching=True "
                            "(the paged pool backs the slot scheduler)")
                    self.engine = LLMEngine(
                        config, params, max_len=self.max_len,
                        temperature=self.temperature,
                        top_k=self.top_k, top_p=self.top_p,
                        kv_dtype=self.kv_dtype,
                        attention_impl=self.attention_impl,
                        adapters=self.adapters,
                        max_live_adapters=self.max_live_adapters)
                    if self._warmup:
                        self.engine.warmup()
                self.model = self.engine

            def predict(self, request):
                inputs = request["inputs"]
                # v2 body tenant id: {"inputs": [...], "adapter": "t1"}
                # threads through submit()/generate() to the batched
                # multi-LoRA decode (docs/serving.md "Multi-tenant
                # LoRA"); unknown names 404 typed, capacity/fairness 429.
                # An optional "request_key" (session/user id) pins the
                # canary hash split's side for this client
                # (docs/continuous_tuning.md) — absent, the prompt
                # tokens decide deterministically.
                adapter = request.get("adapter", "") or ""
                request_key = request.get("request_key") or None
                # opt-in per-request forensics: {"timing": true} in the
                # v2 body returns each input's phase-ledger breakdown
                # (obs/reqledger.py) in the response envelope — the
                # debug field behind "where did this request's time go".
                # Clear the handover slot up front: a predict() that
                # raised after filling it must not leak one request's
                # timing (trace ids included) onto this thread's next
                # request.
                self._timing_out.value = None
                want_timing = bool(request.get("timing"))
                # {"return_unmask_pass": true}: for a block-diffusion
                # model, the pass within its block that unmasked each
                # returned position and the confidence it had in that
                # pass, one list each per output
                want_passes = bool(request.get("return_unmask_pass"))
                id_lists = []
                for item in inputs:
                    if isinstance(item, str):
                        if self._tokenizer is None:
                            raise ValueError(
                                "string inputs need a tokenizer= class arg")
                        id_lists.append(self._tokenizer(item)["input_ids"])
                    else:
                        id_lists.append(list(item))

                if self.continuous_batching:
                    # submit everything, then collect — requests share the
                    # decode batch instead of running serially. Bounded
                    # wait: a dead scheduler fails the futures rather than
                    # wedging the worker.
                    futures = [self.engine.submit(
                        ids, max_new_tokens=self.max_new_tokens,
                        temperature=self.temperature,
                        top_k=self.top_k, top_p=self.top_p,
                        adapter=adapter, request_key=request_key)
                        for ids in id_lists]
                    results = [f.result(timeout=600) for f in futures]
                    if results:
                        self.set_metric(
                            "ttft_s",
                            min(s["ttft_s"] for _, s in results))
                        generated = sum(s["generated"] for _, s in results)
                        wall = max(s["total_s"] for _, s in results)
                        if wall > 0:
                            self.set_metric("decode_tps", generated / wall)
                    engine_stats = self.engine.stats
                    for key in ("ttft_p50_s", "ttft_p95_s", "itl_p50_s",
                                "itl_p95_s", "prefix_hit_rate",
                                "prefix_cached_tokens", "prefix_evictions",
                                "prefill_chunks"):
                        if key in engine_stats:
                            self.set_metric(key, engine_stats[key])
                    extras = {}
                    if want_timing:
                        extras["timing"] = [s.get("timing")
                                            for _, s in results]
                    if want_passes:
                        for name in ("unmask_pass", "unmask_confidence"):
                            extras[name] = [s.get(name) for _, s in results]
                    self._timing_out.value = extras or None
                    out_tokens = [tokens for tokens, _ in results]
                else:
                    out_tokens = []
                    for ids in id_lists:
                        tokens, stats = self.engine.generate(
                            ids, max_new_tokens=self.max_new_tokens,
                            adapter=adapter, request_key=request_key)
                        self.set_metric("ttft_s", stats["ttft_s"])
                        self.set_metric("decode_tps",
                                        stats["decode_tokens_per_sec"])
                        out_tokens.append(tokens)

                outputs = []
                for item, tokens in zip(inputs, out_tokens):
                    if self._tokenizer is not None and isinstance(item, str):
                        outputs.append(self._tokenizer.decode(tokens))
                    else:
                        outputs.append(tokens)
                return outputs

            def postprocess(self, response):
                # the opt-in "timing" debug field rides the v2 envelope
                # next to "outputs" (one entry per input, aligned):
                # phase-attributed wall + trace id, straight from the
                # engine's request ledger
                extras = getattr(self._timing_out, "value", None) or {}
                self._timing_out.value = None
                for name, values in extras.items():
                    if any(v is not None for v in values):
                        response[name] = values
                return response

        return _Server(*args, **kwargs)
