"""TPU ops tests: attention kernels, ring attention, norms, rope.

Run on CPU (pallas interpret mode); kernel-vs-reference exactness is the
contract (reference has no analog — new TPU capability)."""

import jax
import jax.numpy as jnp
import pytest

from mlrun_tpu.ops import attention_reference, rms_norm
from mlrun_tpu.ops.attention import (
    _flash_fwd_v2,
    _flash_mlt_bwd,
    _flash_mlt_fwd,
    _repeat_kv,
)


@pytest.fixture(scope="module")
def qkv():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 2, 64))
    return q, k, v


def test_flash_kernel_matches_reference(qkv):
    q, k, v = qkv
    ref = attention_reference(q, k, v, causal=True)
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)
    o, _ = _flash_fwd_v2(q, kk, vv, causal=True, interpret=True,
                         block_q=128, block_k=128)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


def test_flash_kernel_noncausal(qkv):
    q, k, v = qkv
    ref = attention_reference(q, k, v, causal=False)
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)
    o, _ = _flash_fwd_v2(q, kk, vv, causal=False, interpret=True,
                         block_q=128, block_k=128)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


def test_flash_backward_matches_autodiff(qkv):
    q, k, v = qkv
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    o, res = _flash_mlt_fwd(q, kk, vv, True)
    dq, dk, dv = _flash_mlt_bwd(True, res, 2 * o)
    gq, gk, gv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kk, vv)
    for got, want in ((dq, gq), (dk, gk), (dv, gv)):
        assert float(jnp.max(jnp.abs(got - want))) < 2e-3


def test_ring_attention_matches_reference(qkv):
    from mlrun_tpu.ops.ring_attention import make_ring_attention
    from mlrun_tpu.parallel.mesh import make_mesh

    q, k, v = qkv
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)
    ref = attention_reference(q, kk, vv, causal=True)
    mesh = make_mesh({"seq": 4})
    ring = make_ring_attention(mesh, seq_axis="seq")
    out = ring(q, kk, vv)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_gqa_repeat():
    k = jnp.arange(2 * 4 * 2 * 3).reshape(2, 4, 2, 3).astype(jnp.float32)
    r = _repeat_kv(k, 3)
    assert r.shape == (2, 4, 6, 3)
    assert jnp.allclose(r[:, :, 0], r[:, :, 1])


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
    scale = jnp.ones((128,))
    out = rms_norm(x, scale)
    rms = jnp.sqrt(jnp.mean(out.astype(jnp.float32) ** 2, axis=-1))
    assert jnp.allclose(rms, 1.0, atol=1e-3)


def test_rope_rotation_preserves_norm():
    from mlrun_tpu.ops import apply_rope_qk

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 2, 32))
    q2, k2 = apply_rope_qk(q, k, jnp.arange(16))
    assert jnp.allclose(jnp.linalg.norm(q2, axis=-1),
                        jnp.linalg.norm(q, axis=-1), atol=1e-4)
    # relative property: shifting both positions equally keeps q.k dots
    q3, k3 = apply_rope_qk(q, k, jnp.arange(16) + 7)
    dots2 = jnp.einsum("bshd,bshd->bsh", q2, k2)
    dots3 = jnp.einsum("bshd,bshd->bsh", q3, k3)
    assert jnp.allclose(dots2, dots3, atol=1e-3)


def test_ulysses_attention_matches_reference(qkv):
    """All-to-all sequence parallelism — exact vs reference, both masks."""
    from mlrun_tpu.ops.ulysses import make_ulysses_attention
    from mlrun_tpu.parallel.mesh import make_mesh

    q, k, v = qkv
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)
    mesh = make_mesh({"seq": 4})
    for causal in (True, False):
        ref = attention_reference(q, kk, vv, causal=causal)
        out = make_ulysses_attention(mesh, "seq", causal=causal)(q, kk, vv)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_ulysses_rejects_indivisible_heads(qkv):
    import pytest as _pytest

    from mlrun_tpu.ops.ulysses import make_ulysses_attention
    from mlrun_tpu.parallel.mesh import make_mesh

    q, k, v = qkv  # 4 q heads
    mesh = make_mesh({"seq": 4})
    bad_q = q[:, :, :3]  # 3 heads not divisible by 4
    with _pytest.raises(Exception, match="divisible"):
        make_ulysses_attention(mesh, "seq")(bad_q, bad_q, bad_q)


def test_flash_v2_grid_kernel(qkv):
    """Grid-pipelined kernel: multiple k blocks, odd lengths, both masks."""
    from mlrun_tpu.ops.attention import _flash_fwd_v2

    q, k, v = qkv
    kk, vv = _repeat_kv(k, 2), _repeat_kv(v, 2)
    for causal in (True, False):
        ref = attention_reference(q, kk, vv, causal=causal)
        o, _ = _flash_fwd_v2(q, kk, vv, causal=causal, block_q=128,
                             block_k=64, interpret=True)
        assert float(jnp.max(jnp.abs(o - ref))) < 2e-5
