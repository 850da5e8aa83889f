"""Ask the chip's compiler, without the chip: the Pallas kernels of the
serving path lowered and compiled for a described ``v5e:2x2`` topology at
the head shapes of ``llama3-1b`` and ``llama3-8b`` with
``interpret=False`` (the `on-chip-measurement` guide, section 2, third
rehearsal). Interpret mode accepts block shapes, VMEM footprints and
slices the TPU lowering refuses; these cases are what stands between a
passing tier-1 and a first decode tick that raises on the chip.

A compile that passes is not a chip run: nothing here executes, and
nothing here says anything about results or times (``chip_smoke.py``
phase ``kernels`` checks results on the chip).

The topology is described inside the module-scoped fixture below and
nowhere else — only the xdist worker that is handed this file loads the
TPU library, and it compiles in its own process.
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mlrun_tpu.ops import paged_attention as pattn

# ``mlrun_tpu.ops.attention`` the attribute is the dispatcher function
attn = importlib.import_module("mlrun_tpu.ops.attention")

# the chip_smoke.py serving shape: 16 slots x 16 pages/slot, 512 pool
# pages + the scratch page, page_size 128
SLOTS, PAGES_PER_SLOT, N_PAGES, PAGE_SIZE = 16, 16, 512, 128
N_HEADS, N_KV_HEADS = 32, 8
HEAD_DIMS = {"llama3-1b": 64, "llama3-8b": 128}
PREFILL_CHUNK = 512      # a prefill bucket; 1 is the last-token replay
VERIFY_ROWS = 5          # speculative k + 1


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
    """(pages, scales-kwargs) shapes of one pool layer."""
    dims = (N_PAGES + 1, PAGE_SIZE, N_KV_HEADS, head_dim)
    if kv_dtype == "int8":
        scale = on_chip(dims[:-1], jnp.float32)
        return on_chip(dims, jnp.int8), {"k_scale": scale,
                                         "v_scale": scale}
    return on_chip(dims, jnp.bfloat16), {}


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_decode_compiles(on_chip, model, kv_dtype):
    d = HEAD_DIMS[model]
    pages, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((SLOTS, N_HEADS, d), jnp.bfloat16)
    table = on_chip((SLOTS, PAGES_PER_SLOT), jnp.int32)
    pos = on_chip((SLOTS,), jnp.int32)

    def decode(q, k, v, table, pos, **scales):
        return pattn._paged_decode_call(q, k, v, table, pos, PAGE_SIZE,
                                        interpret=False, **scales)

    _compile(decode, q, pages, pages, table, pos, **scales)


@pytest.mark.parametrize("chunk", [PREFILL_CHUNK, 1])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_prefill_compiles(on_chip, model, kv_dtype, chunk):
    d = HEAD_DIMS[model]
    pages, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((1, chunk, N_HEADS, d), jnp.bfloat16)
    ids = on_chip((PAGES_PER_SLOT,), jnp.int32)
    base = on_chip((), jnp.int32)

    def prefill(q, k, v, ids, base, **scales):
        return pattn.paged_prefix_part(q, k, v, ids, base,
                                       page_size=PAGE_SIZE,
                                       interpret=False, **scales)

    _compile(prefill, q, pages, pages, ids, base, **scales)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(HEAD_DIMS))
def test_paged_verify_compiles(on_chip, model, kv_dtype):
    d = HEAD_DIMS[model]
    pages, scales = _pool(on_chip, d, kv_dtype)
    q = on_chip((SLOTS, VERIFY_ROWS, N_HEADS, d), jnp.bfloat16)
    chunk_kv = on_chip((SLOTS, VERIFY_ROWS, N_KV_HEADS, d), jnp.bfloat16)
    table = on_chip((SLOTS, PAGES_PER_SLOT), jnp.int32)
    base = on_chip((SLOTS,), jnp.int32)

    def verify(q, ck, cv, k, v, table, base, **scales):
        return pattn.paged_verify_attention(
            q, ck, cv, k, v, table, base, page_size=PAGE_SIZE,
            impl="kernel", interpret=False, **scales)

    _compile(verify, q, chunk_kv, chunk_kv, pages, pages, table, base,
             **scales)


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
