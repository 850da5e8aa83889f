"""Ask the chip's compiler, without the chip: the Pallas kernels of the
serving path lowered and compiled for a described ``v5e:2x2`` topology at
the head shapes of ``llama3-1b`` and ``llama3-8b`` with
``interpret=False`` (the `on-chip-measurement` guide, section 2, third
rehearsal). Interpret mode accepts block shapes, VMEM footprints and
slices the TPU lowering refuses; these cases are what stands between a
passing tier-1 and a first decode tick that raises on the chip.

The kernels take the page pool as it is stored (``[L, P+1, page_size,
Hkv, D]``) and a traced layer index. ``test_pool_programs_copy_no_layer``
compiles the serving programs around them and reads the optimised HLO:
a kernel handed ``pool[layer]`` makes XLA copy a whole pool layer before
every call (an operand of a kernel is a buffer of its own), which no
result or interpret-mode test can see.

A compile that passes is not a chip run: nothing here executes, and
nothing here says anything about results or times (``chip_smoke.py``
phase ``kernels`` checks results on the chip).

The topology is described inside the module-scoped fixture below and
nowhere else — only the xdist worker that is handed this file loads the
TPU library, and it compiles in its own process.
"""

import dataclasses
import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mlrun_tpu.models.llama import LlamaConfig, init_params
from mlrun_tpu.ops import paged_attention as pattn
from mlrun_tpu.serving import llm, paged

# ``mlrun_tpu.ops.attention`` the attribute is the dispatcher function
attn = importlib.import_module("mlrun_tpu.ops.attention")

# the chip_smoke.py serving shape: 16 slots x 16 pages/slot, 512 pool
# pages + the scratch page, page_size 128
SLOTS, PAGES_PER_SLOT, N_PAGES, PAGE_SIZE = 16, 16, 512, 128
N_HEADS, N_KV_HEADS = 32, 8
HEAD_DIMS = {"llama3-1b": 64, "llama3-8b": 128}
PREFILL_CHUNK = 512      # a prefill bucket; 1 is a one-token chunk
VERIFY_ROWS = 5          # speculative k + 1
POOL_LAYERS = 3          # a wrong layer index or a per-layer copy shows
KV_DTYPES = {"bf16": "native", "int8": "int8"}   # the engine's names


@pytest.fixture(scope="module")
def on_chip():
    """``on_chip(dims, dtype)``: a shape placed on one described v5e chip
    — what ``jit(...).lower`` takes where no device holds an array."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip — keep these out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                   sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _pool(on_chip, head_dim, kv_dtype):
    """(pool, layer, scales-kwargs) shapes: the pool as the engine stores
    it and the traced layer index."""
    dims = (POOL_LAYERS, N_PAGES + 1, PAGE_SIZE, N_KV_HEADS, head_dim)
    layer = on_chip((), jnp.int32)
    if kv_dtype == "int8":
        scale = on_chip(dims[:-1], jnp.float32)
        return on_chip(dims, jnp.int8), layer, {"k_scale": scale,
                                                "v_scale": scale}
    return on_chip(dims, jnp.bfloat16), layer, {}


def _placer(on_chip):
    """``place(tree)``: the shapes of a tree's leaves on the described
    chip."""
    return lambda tree: jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype), tree)


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_decode_compiles(on_chip, model, kv_dtype):
    d = HEAD_DIMS[model]
    pool, layer, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((SLOTS, N_HEADS, d), jnp.bfloat16)
    table = on_chip((SLOTS, PAGES_PER_SLOT), jnp.int32)
    pos = on_chip((SLOTS,), jnp.int32)

    def decode(q, k, v, layer, table, pos, **scales):
        return pattn._paged_decode_call(q, k, v, layer, table, pos,
                                        PAGE_SIZE, interpret=False,
                                        **scales)

    _compile(decode, q, pool, pool, layer, table, pos, **scales)


@pytest.mark.parametrize("chunk", [PREFILL_CHUNK, 1])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_prefill_compiles(on_chip, model, kv_dtype, chunk):
    """The prefix kernel under a bucket's query rows and under one: no
    engine dispatches a one-token prefill after a padded one any more,
    but the kernel takes a one-row query whoever hands it one (a
    ``prefill_chunk`` of 1)."""
    d = HEAD_DIMS[model]
    pool, layer, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((1, chunk, N_HEADS, d), jnp.bfloat16)
    ids = on_chip((PAGES_PER_SLOT,), jnp.int32)
    base = on_chip((), jnp.int32)

    def prefill(q, k, v, layer, ids, base, **scales):
        return pattn.paged_prefix_part(q, k, v, layer, ids, base,
                                       page_size=PAGE_SIZE,
                                       interpret=False, **scales)

    _compile(prefill, q, pool, pool, layer, ids, base, **scales)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_verify_compiles(on_chip, model, kv_dtype):
    d = HEAD_DIMS[model]
    pool, layer, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((SLOTS, VERIFY_ROWS, N_HEADS, d), jnp.bfloat16)
    chunk_kv = on_chip((SLOTS, VERIFY_ROWS, N_KV_HEADS, d), jnp.bfloat16)
    table = on_chip((SLOTS, PAGES_PER_SLOT), jnp.int32)
    base = on_chip((SLOTS,), jnp.int32)

    def verify(q, ck, cv, k, v, layer, table, base, **scales):
        return pattn.paged_verify_attention(
            q, ck, cv, k, v, layer, table, base, page_size=PAGE_SIZE,
            impl="kernel", interpret=False, **scales)

    _compile(verify, q, chunk_kv, chunk_kv, pool, pool, layer, table,
             base, **scales)


@pytest.mark.parametrize("form", ["self", "cached", "bounded"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_flash_v2_compiles(on_chip, model, form):
    """The engines' prefill flash at a 512-token bucket: the training /
    self form over the chunk, the cached form over a 2048-row KV cache,
    and the bounded form the paged prefix-hit path merges with."""
    d = HEAD_DIMS[model]
    sk = PREFILL_CHUNK if form == "self" else PAGES_PER_SLOT * PAGE_SIZE
    q = on_chip((1, PREFILL_CHUNK, N_HEADS, d), jnp.bfloat16)
    kv = on_chip((1, sk, N_HEADS, d), jnp.bfloat16)
    scalar = on_chip((), jnp.int32)
    if form == "self":
        _compile(lambda q, k, v: attn._flash_fwd_v2(
            q, k, v, interpret=False), q, kv, kv)
    elif form == "cached":
        _compile(lambda q, k, v, off: attn._flash_fwd_v2_cached(
            q, k, v, off, interpret=False), q, kv, kv, scalar)
    else:
        _compile(lambda q, k, v, off, lo: attn._flash_fwd_v2_cached_bounded(
            q, k, v, off, lo, interpret=False), q, kv, kv, scalar, scalar)


@pytest.mark.parametrize("hidden", [2048, 4096])
def test_rms_norm_pallas_compiles(on_chip, hidden):
    """Exported, called by no model or engine yet (ROADMAP D3); kept
    compiling so it stays an option."""
    from mlrun_tpu.ops.norms import rms_norm_pallas

    x = on_chip((8, 2048, hidden), jnp.bfloat16)
    scale = on_chip((hidden,), jnp.float32)
    _compile(lambda x, scale: rms_norm_pallas(x, scale), x, scale)


# -- the serving programs around the kernels ---------------------------------

def _pool_layer_results(hlo: str, head_dim: int):
    """Opcodes of the instructions whose result is one layer of the page
    pool — its pages ``[P+1, page_size, Hkv, D]`` or its int8 scales
    ``[P+1, page_size, Hkv]``, with or without a leading 1."""
    dims = f"{N_PAGES + 1},{PAGE_SIZE},{N_KV_HEADS}"
    shape = rf"\w+\[(?:1,)?{dims}(?:,{head_dim})?\]"
    result = re.compile(rf"^\s*(?:ROOT )?\S+ = {shape}\S* ([\w-]+)\(")
    return {m.group(1) for line in hlo.splitlines()
            if (m := result.match(line))}


@pytest.fixture
def program_shapes(on_chip, monkeypatch):
    """``program_shapes(head_dim, kv_dtype)``: a narrow model at the
    real head shapes, its page pool, and ``place`` for further operands.
    The programs' kernel calls ask ``interpret_default()``, which sees
    this sandbox's CPU: answered here as the chip would."""
    monkeypatch.setattr(pattn, "interpret_default", lambda: False)
    monkeypatch.setattr(attn, "interpret_default", lambda: False)

    place = _placer(on_chip)

    def shapes(head_dim, kv_dtype):
        config = LlamaConfig(
            vocab_size=1024, n_layers=POOL_LAYERS, embed_dim=512,
            n_heads=N_HEADS, n_kv_heads=N_KV_HEADS, head_dim=head_dim,
            mlp_dim=1024)
        # the tree as an engine holds it (serving/llm.py serving_tree)
        params = place(jax.eval_shape(lambda: llm.serving_tree(
            config, init_params(config, jax.random.PRNGKey(0)))))
        pool = place(jax.eval_shape(lambda: paged.init_paged_pool(
            config, N_PAGES + 1, PAGE_SIZE, KV_DTYPES[kv_dtype])))
        return config, params, pool, place

    return shapes


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("head_dim", [128, 64])
@pytest.mark.parametrize("program", ["decode", "verify", "prefill_hit"])
def test_pool_programs_copy_no_layer(program_shapes, on_chip, program,
                                     head_dim, kv_dtype):
    """The decode, speculative-verify and prefix-hit prefill programs
    hand their kernels the pool as stored: in the optimised HLO nothing
    but a ``bitcast`` yields an array the shape of a pool layer, and at
    head_dim 128 on a bf16 pool the program's temporaries stay under
    one layer (a copy per kernel call needs two: K and V).

    Known and left alone (PERF.md section 7): at head_dim 64 the pool,
    and on int8 pools the scales, arrive in a page_size-minor device
    layout and are copied whole into row-major and back around the
    program; the scales' copy is made of layer-sized ``slice-done``
    pieces that a ``ConcatBitcast`` joins."""
    config, params, pool, place = program_shapes(head_dim, kv_dtype)
    table = on_chip((SLOTS, PAGES_PER_SLOT), jnp.int32)
    pos = on_chip((SLOTS,), jnp.int32)
    kwargs, donate = {}, (2,)      # the engine donates the pool
    if program == "decode":
        fn = functools.partial(paged._decode_rowwise_paged, config,
                               PAGE_SIZE, "kernel")
        args = (params, on_chip((SLOTS, 1), jnp.int32), pool, table, pos)
        # as the engine dispatches it: a row's input token is the host's
        # or the last tick's, still on the device
        kwargs = {"prev_token": on_chip((SLOTS,), jnp.int32),
                  "from_prev": on_chip((SLOTS,), jnp.bool_)}
    elif program == "verify":
        fn = functools.partial(paged._verify_rowwise_paged, config,
                               PAGE_SIZE, "kernel")
        args = (params, on_chip((SLOTS, VERIFY_ROWS), jnp.int32), pool,
                table, pos)
    else:
        fn = functools.partial(llm._forward_with_cache, config,
                               attn_impl="flash", page_size=PAGE_SIZE)
        cache = place(jax.eval_shape(lambda: llm.init_kv_cache(
            config, 1, PAGES_PER_SLOT * PAGE_SIZE,
            kv_dtype=KV_DTYPES[kv_dtype])))
        args = (params, on_chip((1, PREFILL_CHUNK), jnp.int32), cache)
        # as the engine dispatches it: the position whose logits come
        # back (a padded prompt's last real one) is a traced index
        kwargs = {"prefix_kv": dict(
            pool, page_ids=on_chip((PAGES_PER_SLOT,), jnp.int32),
            base=on_chip((), jnp.int32)),
            "logits_at": on_chip((), jnp.int32)}
        donate = ()                # a hit only reads the pool
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *args, **kwargs).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= POOL_LAYERS
    allowed = {"bitcast", "slice-done"} if kv_dtype == "int8" \
        else {"bitcast"}
    assert _pool_layer_results(hlo, head_dim) <= allowed
    if head_dim == 128 and kv_dtype == "bf16":
        layer_bytes = (N_PAGES + 1) * PAGE_SIZE * N_KV_HEADS * head_dim * 2
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


# -- wq, wk, wv as the engine holds them --------------------------------------
# the benchmark cell m7b-serve-chat's model as it runs there
# (benchmarks/configs/: 16 layers; at two the compiler fetches both layers'
# leaves ahead into its fast memory, which 16 do not fit): 32 slots x 16
# pages of 128
M7B_SLOTS, M7B_LAYERS, M7B_HIDDEN, M7B_MLP, M7B_VOCAB, M7B_HEAD_DIM = \
    32, 16, 4096, 14336, 32768, 128
# the dimensions, 1s dropped, under which a layer's wq [4096, 4096] or
# wk / wv [4096, 1024] shows in the HLO, in either order, heads split or not
_QKV_DIMS = {dims for h in (N_HEADS, N_KV_HEADS) for dims in (
    (M7B_HIDDEN, h * M7B_HEAD_DIM), (h * M7B_HEAD_DIM, M7B_HIDDEN),
    (M7B_HIDDEN, h, M7B_HEAD_DIM), (h, M7B_HEAD_DIM, M7B_HIDDEN))}


def _qkv_sized_results(hlo: str):
    """Opcodes of the instructions of the entry computation whose result,
    or an element of whose tuple, has the elements of one layer's ``wq``,
    ``wk`` or ``wv``. What is fused into a product (a ``slice``, a
    ``bitcast``) sits in that fusion's own computation and yields no
    buffer."""
    found, entry = set(), False
    line_re = re.compile(r"^\s*(?:ROOT )?\S+ = (.*?) ([a-z][a-z0-9-]*)\(")
    array_re = re.compile(r"bf16\[([\d,]+)\]")
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif line.startswith("}"):
            entry = False
        elif entry and (m := line_re.match(line)):
            if any(tuple(int(d) for d in dims.split(",") if d != "1")
                   in _QKV_DIMS for dims in array_re.findall(m.group(1))):
                found.add(m.group(2))
    return found


@pytest.fixture
def m7b_shapes(on_chip, monkeypatch):
    monkeypatch.setattr(pattn, "interpret_default", lambda: False)
    monkeypatch.setattr(attn, "interpret_default", lambda: False)
    config = LlamaConfig(
        vocab_size=M7B_VOCAB, n_layers=M7B_LAYERS, embed_dim=M7B_HIDDEN,
        n_heads=N_HEADS, n_kv_heads=N_KV_HEADS, head_dim=M7B_HEAD_DIM,
        mlp_dim=M7B_MLP, rope_theta=1e6)

    place = _placer(on_chip)

    def shapes(tree):
        def made():
            params = init_params(config, jax.random.PRNGKey(0))
            return params if tree == "logical" \
                else llm.serving_tree(config, params)

        return config, place(jax.eval_shape(made)), place

    return shapes


@pytest.mark.parametrize("tree", ["engine", "logical"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_qkv_weights_read_as_stored(m7b_shapes, on_chip, program, tree):
    """``jit_mlt_decode`` and the cold prefill at bucket 512 of
    ``m7b-serve-chat``: on the engine's tree (``wq``, ``wk``, ``wv`` held
    [L, heads, head_dim, E], serving/llm.py ``serving_tree``) no
    instruction of the program yields an array with the elements of a
    layer's leaf but a ``bitcast``: the layer is sliced inside its
    product. The control compiles the same program on the logical tree,
    whose product wants the contraction minor where the leaf has it major,
    and finds the slice and the ``copy`` a layer a leaf that every run
    would pay (0.8 GB): the test sees what it guards."""
    config, params, place = m7b_shapes(tree)
    if program == "decode":
        pool = place(jax.eval_shape(lambda: paged.init_paged_pool(
            config, N_PAGES + 1, PAGE_SIZE)))
        compiled = jax.jit(functools.partial(
            paged._decode_rowwise_paged, config, PAGE_SIZE, "kernel"),
            donate_argnums=(2,)).lower(
                params, on_chip((M7B_SLOTS, 1), jnp.int32), pool,
                on_chip((M7B_SLOTS, PAGES_PER_SLOT), jnp.int32),
                on_chip((M7B_SLOTS,), jnp.int32),
                prev_token=on_chip((M7B_SLOTS,), jnp.int32),
                from_prev=on_chip((M7B_SLOTS,), jnp.bool_)).compile()
    else:
        cache = place(jax.eval_shape(lambda: llm.init_kv_cache(
            config, 1, PAGES_PER_SLOT * PAGE_SIZE)))
        compiled = jax.jit(functools.partial(
            llm._forward_with_cache, config, attn_impl="flash")).lower(
                params, on_chip((1, PREFILL_CHUNK), jnp.int32), cache,
                logits_at=on_chip((), jnp.int32)).compile()
    opcodes = _qkv_sized_results(compiled.as_text())
    if tree == "logical":
        assert {"fusion", "copy"} <= opcodes, opcodes
    else:
        assert opcodes <= {"bitcast"}, opcodes


# -- the block-diffusion family: experts, q/k norms, the block mask ----------
# the benchmark cell sdar-serve-chat's shapes (benchmarks/workloads/), the
# model cut to two layers (a copy of a layer's experts shows from two on): 32 slots x 16 pages/slot of 128, a block of 4
SDAR_SLOTS, SDAR_BLOCK = 32, 4


@pytest.fixture
def sdar_shapes(on_chip, monkeypatch):
    from mlrun_tpu.models.moe import SdarConfig
    from mlrun_tpu.models.moe import init_params as init_moe

    monkeypatch.setattr(pattn, "interpret_default", lambda: False)
    monkeypatch.setattr(attn, "interpret_default", lambda: False)
    config = SdarConfig(
        vocab_size=151936, n_layers=2, embed_dim=2048, n_heads=32,
        n_kv_heads=4, head_dim=128, mlp_dim=6144, n_experts=128, top_k=8,
        expert_dim=768, block_length=SDAR_BLOCK, rope_theta=1e6,
        norm_eps=1e-6)

    place = _placer(on_chip)

    params = place(jax.eval_shape(lambda: llm.serving_tree(
        config, init_moe(config, jax.random.PRNGKey(0)))))
    return config, params, place


def test_denoise_program_compiles(sdar_shapes, on_chip):
    """``jit_mlt_denoise`` as the tick dispatches it: the verify program
    with a mask bitmap, the schedule's counts and the block state of the
    pass in flight, a block of 4 a slot, q/k norms, the prefix kernel and
    the grouped expert products (three a layer, on the device under a name
    a trace shows)."""
    config, params, place = sdar_shapes
    pool = place(jax.eval_shape(lambda: paged.init_paged_pool(
        config, N_PAGES + 1, PAGE_SIZE)))
    fn = functools.partial(paged._verify_rowwise_paged, config, PAGE_SIZE,
                           "kernel")
    lanes = (SDAR_SLOTS, SDAR_BLOCK)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, on_chip(lanes, jnp.int32), pool,
        on_chip((SDAR_SLOTS, PAGES_PER_SLOT), jnp.int32),
        on_chip((SDAR_SLOTS,), jnp.int32),
        masked=on_chip(lanes, jnp.bool_),
        count=on_chip((SDAR_SLOTS,), jnp.int32),
        prev_ids=on_chip(lanes, jnp.int32),
        prev_masked=on_chip(lanes, jnp.bool_),
        from_prev=on_chip((SDAR_SLOTS,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 4      # prefix kernel + products
    assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 6
    assert re.search(r"paged_verify", hlo)
    # the experts' stacks reach the products as stored: no copy of them
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    # packed: x0, confidence and the choice of every lane, three counters;
    # beside it the block state the next pass rides on, left on the device
    packed = 3 * SDAR_SLOTS * SDAR_BLOCK + 3
    assert f"s32[{packed}]" in hlo
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, o.dtype) for o in out[-2:]] == [
        (lanes, jnp.int32), (lanes, jnp.bool_)]


@pytest.mark.parametrize("bucket", [128, 1024])
def test_block_masked_prefill_compiles(sdar_shapes, on_chip, bucket):
    """The prefill program of the same model: ``flash_v2`` under the block
    mask (block_length 4), experts over a bucket's positions."""
    config, params, place = sdar_shapes
    fn = functools.partial(llm._forward_with_cache, config,
                           attn_impl="flash", page_size=PAGE_SIZE)
    cache = place(jax.eval_shape(lambda: llm.init_kv_cache(
        config, 1, PAGES_PER_SLOT * PAGE_SIZE)))
    compiled = jax.jit(fn).lower(
        params, on_chip((1, bucket), jnp.int32), cache,
        logits_at=on_chip((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "flash_v2" in hlo and "tpu_custom_call" in hlo
    assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 6


# -- the latent-attention family: xing4-serve-longdoc's programs ------------
# the benchmark cell's geometry (benchmarks/workloads/xing4-serve-longdoc.json)
# at the published widths, seven layers: 32 slots x 64 pages/slot of 128,
# 2,048 pool pages + the scratch page, chunks of 1,024
X4_SLOTS, X4_PAGES_PER_SLOT, X4_PAGES, X4_CHUNK = 32, 64, 2048, 1024


@pytest.fixture
def xing4_shapes(on_chip, monkeypatch):
    from mlrun_tpu.models import xing4
    from mlrun_tpu.ops import mla_attention as mla

    for module in (pattn, attn, mla):
        monkeypatch.setattr(module, "interpret_default", lambda: False)
    config = xing4.xing4_29b_a4b(n_layers=7, first_k_dense=1)

    place = _placer(on_chip)

    params = place(xing4.param_shapes(config))
    pool = place(jax.eval_shape(lambda: paged.init_paged_pool(
        config, X4_PAGES + 1, PAGE_SIZE)))
    return config, params, pool, place


def test_mla_flash_compiles(on_chip):
    """``mla_flash`` at the published head widths (keys of 192, values of
    128, 32 heads), a chunk of 1,024 against a block of 1,024."""
    from mlrun_tpu.ops import mla_attention as mla

    q = on_chip((X4_CHUNK, 32, 192), jnp.bfloat16)
    v = on_chip((X4_CHUNK, 32, 128), jnp.bfloat16)
    at = on_chip((), jnp.int32)
    _compile(functools.partial(mla.mla_flash, scale=0.14468,
                               interpret=False), q, q, v, at, at)


def test_mla_paged_decode_compiles(xing4_shapes, on_chip):
    """``mla_paged_decode`` over the cell's latent pool: one row of 512 +
    64 a token for 32 heads."""
    from mlrun_tpu.ops import mla_attention as mla

    config, _params, pool, _place = xing4_shapes
    _compile(functools.partial(mla.mla_paged_decode, page_size=PAGE_SIZE,
                               rank=512, scale=config.softmax_scale,
                               interpret=False),
             on_chip((X4_SLOTS, 32, 640), jnp.bfloat16), pool["ckr"],
             on_chip((), jnp.int32),
             on_chip((X4_SLOTS, X4_PAGES_PER_SLOT), jnp.int32),
             on_chip((X4_SLOTS,), jnp.int32))


def test_xing4_decode_program_compiles(xing4_shapes, on_chip):
    """``jit_mlt_decode`` of the cell: the absorbed kernel a layer, the
    grouped expert products of six expert layers, the pool updated in
    place, tokens and the experts' counters in one vector."""
    config, params, pool, _place = xing4_shapes
    fn = functools.partial(paged._decode_rowwise_paged, config, PAGE_SIZE,
                           "kernel", with_loads=True)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, on_chip((X4_SLOTS, 1), jnp.int32), pool,
        on_chip((X4_SLOTS, X4_PAGES_PER_SLOT), jnp.int32),
        on_chip((X4_SLOTS,), jnp.int32),
        prev_token=on_chip((X4_SLOTS,), jnp.int32),
        from_prev=on_chip((X4_SLOTS,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert "mla_paged_decode" in hlo
    assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 18
    assert f"s32[{X4_SLOTS + 3}]" in hlo
    memory = compiled.memory_analysis()
    # weights and pool as stored, and no copy of an expert stack or of a
    # pool layer among the temporaries
    assert memory.temp_size_in_bytes < 512 << 20
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15 << 30


def test_xing4_prefill_chunk_compiles(xing4_shapes, on_chip):
    """``jit_mlt_prefill`` of the cell: a chunk of 1,024 against the
    admission's 8,192 latent rows, the expanded kernel in the loop over
    blocks, the experts' counters as a third output."""
    config, params, _pool, place = xing4_shapes
    fn = functools.partial(llm._forward_with_cache, config,
                           attn_impl="flash", page_size=PAGE_SIZE,
                           with_loads=True)
    cache = place(jax.eval_shape(lambda: llm.init_kv_cache(
        config, 1, X4_PAGES_PER_SLOT * PAGE_SIZE)))
    compiled = jax.jit(fn).lower(
        params, on_chip((1, X4_CHUNK), jnp.int32), cache,
        logits_at=on_chip((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "mla_flash" in hlo and "tpu_custom_call" in hlo
    assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 18
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 14 << 30


def test_xing4_insert_program_compiles(xing4_shapes, on_chip):
    """``jit_mlt_insert``: an admission's latent rows into the pool."""
    config, _params, pool, place = xing4_shapes
    small = place(jax.eval_shape(lambda: llm.init_kv_cache(
        config, 1, X4_PAGES_PER_SLOT * PAGE_SIZE)))
    fn = functools.partial(paged.insert_prompt_pages, page_size=PAGE_SIZE)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        pool, small, on_chip((X4_PAGES_PER_SLOT,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# -- the state-space family: nemotron-serve-chat128's programs --------------
# the benchmark cell's geometry (benchmarks/workloads/
# nemotron-serve-chat128.json) at the published widths and the cell's own
# depth (26 layers: 12 state-space, 11 expert, 3 attention; 16 of 128 experts
# held; a vocabulary of 16,384): 128 slots x 10 pages/slot of 128, 1,280 pool
# pages + the scratch page
NH_SLOTS, NH_PAGES_PER_SLOT, NH_PAGES = 128, 10, 1280
NH_HEADS, NH_HEAD_DIM, NH_GROUPS, NH_STATE = 64, 64, 8, 128


@pytest.fixture
def nemotron_shapes(on_chip, monkeypatch):
    from mlrun_tpu.models import nemotron_h
    from mlrun_tpu.ops import ssm

    for module in (pattn, attn, ssm):
        monkeypatch.setattr(module, "interpret_default", lambda: False)
    whole = nemotron_h.nemotron_3_nano_30b_a3b()
    config = dataclasses.replace(
        whole, n_layers=26, pattern=whole.pattern[:26],
        experts_held=(0, 16), vocab_size=16384)

    place = _placer(on_chip)

    params = place(jax.eval_shape(lambda: llm.serving_tree(
        config, nemotron_h.init_params(config, jax.random.PRNGKey(0)))))
    pool = place(jax.eval_shape(lambda: paged.init_paged_pool(
        config, NH_PAGES + 1, PAGE_SIZE, slots=NH_SLOTS)))
    return config, params, pool, place


@pytest.mark.parametrize("bucket", [128, 512, 1024])
def test_ssd_prefill_compiles(on_chip, bucket):
    """``ssd_prefill`` at the published widths (64 heads of 64 in 8 groups,
    a state of 128, chunks of 128) over each prefill bucket."""
    from mlrun_tpu.ops import ssm

    bc = on_chip((bucket, NH_GROUPS, NH_STATE), jnp.bfloat16)
    _compile(functools.partial(ssm.ssd_prefill, chunk=128, interpret=False),
             on_chip((bucket, NH_HEADS, NH_HEAD_DIM), jnp.bfloat16),
             on_chip((bucket, NH_HEADS), jnp.float32),
             on_chip((NH_HEADS,), jnp.float32), bc, bc,
             on_chip((NH_HEADS, NH_HEAD_DIM, NH_STATE), jnp.float32))


def test_ssm_decode_compiles(nemotron_shapes, on_chip):
    """``ssm_decode`` over the cell's stack of states (12 layers x 128
    rows x 2 MB), read and written in place: the program's temporaries
    stay far under one layer's states."""
    from mlrun_tpu.ops import ssm

    _config, _params, pool, _place = nemotron_shapes
    bc = on_chip((NH_SLOTS, NH_GROUPS, NH_STATE), jnp.bfloat16)
    compiled = jax.jit(
        functools.partial(ssm.ssm_decode, interpret=False),
        donate_argnums=(0,)).lower(
            pool[paged.STATE]["ssm"], on_chip((), jnp.int32),
            on_chip((NH_SLOTS, NH_HEADS, NH_HEAD_DIM), jnp.bfloat16),
            on_chip((NH_SLOTS, NH_HEADS), jnp.float32),
            on_chip((NH_HEADS,), jnp.float32), bc, bc).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("kernel", ["paged_decode", "flash_v2"])
def test_attention_kernels_at_a_group_of_sixteen(on_chip, kernel):
    """32 query heads over 2 key/value heads of 128: ``paged_decode`` over
    the cell's pool and the cached flash prefill at bucket 1,024."""
    if kernel == "paged_decode":
        pool = on_chip((3, NH_PAGES + 1, PAGE_SIZE, 2, 128), jnp.bfloat16)
        _compile(
            lambda q, k, v, layer, table, pos: pattn._paged_decode_call(
                q, k, v, layer, table, pos, PAGE_SIZE, interpret=False),
            on_chip((NH_SLOTS, 32, 128), jnp.bfloat16), pool, pool,
            on_chip((), jnp.int32),
            on_chip((NH_SLOTS, NH_PAGES_PER_SLOT), jnp.int32),
            on_chip((NH_SLOTS,), jnp.int32))
    else:
        q = on_chip((1, 1024, 32, 128), jnp.bfloat16)
        kv = on_chip((1, NH_PAGES_PER_SLOT * PAGE_SIZE, 2, 128),
                     jnp.bfloat16)
        _compile(
            lambda q, k, v, start: attn._flash_fwd_v2_cached(
                q, attn._repeat_kv(k, 16), attn._repeat_kv(v, 16), start,
                interpret=False), q, kv, kv, on_chip((), jnp.int32))


def _sized_results(hlo: str, elements: int) -> set:
    """Opcodes of the instructions, anywhere in the program, whose result
    (or an element of whose tuple) has ``elements`` entries."""
    found = set()
    line_re = re.compile(r"^\s*(?:ROOT )?\S+ = (.*?) ([a-z][a-z0-9-]*)\(")
    array_re = re.compile(r"(?:bf16|f32)\[([\d,]+)\]")
    for line in hlo.splitlines():
        if m := line_re.match(line):
            for dims in array_re.findall(m.group(1)):
                size = 1
                for d in dims.split(","):
                    size *= int(d)
                if size == elements:
                    found.add(m.group(2))
    return found


@pytest.mark.parametrize("program", ["decode", "insert"])
def test_state_programs_copy_no_layer(nemotron_shapes, on_chip, program):
    """``jit_mlt_decode`` and ``jit_mlt_insert`` of the cell at its own
    depth hand the state on as stored: in the optimised HLO nothing but a
    ``bitcast`` yields an array the size of a layer's states (128 rows x 2
    MB) or of a pool layer (the stack and the pool are written in place:
    what updates them yields the whole of them), and the temporaries stay
    under one layer's states, so nothing copies the whole either. A kernel
    handed ``state[layer]`` would make XLA copy a layer's states before
    every call: 128 x 2 MB x 12 a tick, the cell's largest stream twice."""
    config, params, pool, place = nemotron_shapes
    state_layer = NH_SLOTS * NH_HEADS * NH_HEAD_DIM * NH_STATE
    pool_layer = (NH_PAGES + 1) * PAGE_SIZE * 2 * 128
    if program == "decode":
        compiled = jax.jit(functools.partial(
            paged._decode_rowwise_paged, config, PAGE_SIZE, "kernel",
            with_loads=True), donate_argnums=(2,)).lower(
                params, on_chip((NH_SLOTS, 1), jnp.int32), pool,
                on_chip((NH_SLOTS, NH_PAGES_PER_SLOT), jnp.int32),
                on_chip((NH_SLOTS,), jnp.int32),
                prev_token=on_chip((NH_SLOTS,), jnp.int32),
                from_prev=on_chip((NH_SLOTS,), jnp.bool_)).compile()
        hlo = compiled.as_text()
        assert len(re.findall(r"%ssm_decode[\w.-]* = ", hlo)) == 12
        assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 22
        assert len(re.findall(r"%paged_decode[\w.-]* = ", hlo)) == 3
    else:
        small = place(jax.eval_shape(lambda: llm.init_kv_cache(
            config, 1, NH_PAGES_PER_SLOT * PAGE_SIZE)))
        compiled = jax.jit(functools.partial(
            paged.insert_prompt_pages, page_size=PAGE_SIZE),
            donate_argnums=(0,)).lower(
                pool, small, on_chip((NH_PAGES_PER_SLOT,), jnp.int32),
                slot=on_chip((), jnp.int32)).compile()
        hlo = compiled.as_text()
    for size in (state_layer, pool_layer):
        assert _sized_results(hlo, size) <= {"bitcast"}, \
            (size, _sized_results(hlo, size))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < state_layer * 4
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15 << 30


@pytest.mark.parametrize("bucket", [128, 512, 1024])
def test_nemotron_prefill_compiles(nemotron_shapes, on_chip, bucket):
    """``jit_mlt_prefill`` of the cell at each bucket: the chunked scan a
    state-space layer, the cached flash kernel an attention layer, the
    grouped expert products, the state after the prompt's last real token
    in the admission's cache."""
    config, params, _pool, place = nemotron_shapes
    cache = place(jax.eval_shape(lambda: llm.init_kv_cache(
        config, 1, NH_PAGES_PER_SLOT * PAGE_SIZE)))
    compiled = jax.jit(functools.partial(
        llm._forward_with_cache, config, attn_impl="flash",
        page_size=PAGE_SIZE, with_loads=True)).lower(
            params, on_chip((1, bucket), jnp.int32), cache,
            logits_at=on_chip((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%ssd_prefill[\w.-]* = ", hlo)) == 12
    assert len(re.findall(r"%gmm[\w.-]* = ", hlo)) == 22
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 10 << 30
