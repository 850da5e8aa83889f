"""MoE + expert-parallelism tests (CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mlrun_tpu.models.moe import (
    forward,
    init_params,
    loss_fn,
    make_moe_rules,
    tiny_moe,
)
from mlrun_tpu.parallel.mesh import make_mesh
from mlrun_tpu.parallel.sharding import batch_sharding, tree_shardings


@pytest.fixture(scope="module")
def cfg():
    return tiny_moe(attention_impl="reference")


def test_forward_shapes_and_aux(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    logits, aux = forward(cfg, params, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    # balanced-ish routing at init: aux loss near 1.0 (perfect balance = 1)
    assert 0.5 < float(aux) < 4.0


def test_param_count(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert actual == cfg.param_count()


def test_expert_capacity_drops_gracefully(cfg):
    """With tiny capacity most tokens get dropped but forward stays finite
    (residual path carries them)."""
    import dataclasses

    small = dataclasses.replace(cfg, capacity_factor=0.1)
    params = init_params(small, jax.random.PRNGKey(0))
    logits, _ = forward(small, params, jnp.zeros((2, 16), jnp.int32))
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_moe_trains_sharded_with_expert_axis(cfg):
    """Expert-parallel mesh: experts sharded over 'expert', loss decreases."""
    mesh = make_mesh({"expert": 2, "fsdp": 2})
    rules = make_moe_rules()
    params = init_params(cfg, jax.random.PRNGKey(0))
    shardings = tree_shardings(params, mesh, rules)
    # expert tensors actually sharded on the expert axis
    assert "expert" in str(shardings["layers"]["experts_gate"].spec)
    params = jax.tree_util.tree_map(jax.device_put, params, shardings)

    optimizer = optax.adam(1e-2)
    opt_state = jax.tree_util.tree_map(
        jax.device_put, optimizer.init(params),
        tree_shardings(jax.eval_shape(optimizer.init, params), mesh, rules))
    data_sh = batch_sharding(mesh)

    @jax.jit
    def step(params, opt_state, tokens, targets):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets), has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32), data_sh)
    targets = jax.device_put(
        rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32), data_sh)
    first = last = None
    for _ in range(10):
        params, opt_state, metrics = step(params, opt_state, tokens, targets)
        loss = float(metrics["ce_loss"])
        first = first if first is not None else loss
        last = loss
    assert last < first, (first, last)


def test_loss_metric_surface_chunk_parity(cfg):
    """`accuracy` is present and equal in BOTH loss paths so callbacks
    monitoring it behave identically for loss_chunk=0 and >0 (ISSUE
    satellite)."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (2, 16), dtype=np.int32)
    targets = rng.integers(0, cfg.vocab_size, (2, 16), dtype=np.int32)
    _, plain = loss_fn(cfg, params, tokens, targets, loss_chunk=0)
    _, chunked = loss_fn(cfg, params, tokens, targets, loss_chunk=8)
    assert "accuracy" in plain and "accuracy" in chunked
    assert abs(float(plain["accuracy"]) - float(chunked["accuracy"])) < 1e-5
    assert abs(float(plain["ce_loss"]) - float(chunked["ce_loss"])) < 1e-4


# -- the served, dropless expert layer (models/moe.py moe_mlp) ----------------
import dataclasses  # noqa: E402

from . import sdar_reference as ref  # noqa: E402

from mlrun_tpu.models.moe import _moe_mlp, moe_mlp, tiny_sdar  # noqa: E402


def _sdar_layer(seed=0, **overrides):
    cfg = tiny_sdar(dtype=jnp.float32, **overrides)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    return cfg, lp


def _skewed(cfg, lp, tokens=24, seed=1):
    """Inputs and a router under which expert 0 takes a pair of every
    token and expert 3 none."""
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (2, tokens // 2, cfg.embed_dim), jnp.float32)
    u = jnp.ones((cfg.embed_dim,)) / cfg.embed_dim ** 0.5
    x = x + 4.0 * u
    router = lp["router"].at[:, 0].set(3.0 * u).at[:, 3].set(-3.0 * u)
    return x, dict(lp, router=router)


@pytest.mark.parametrize("routing", ["even", "skewed"])
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_dropless_layer_matches_reference(routing, top_k):
    """Every token's experts applied one by one (the reference) against
    the sort and grouped product, also where one expert takes a pair of
    every token and one none."""
    cfg, lp = _sdar_layer(top_k=top_k)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.embed_dim),
                          jnp.float32)
    if routing == "skewed":
        x, lp = _skewed(cfg, lp)
    y, load = jax.jit(lambda x, lp: moe_mlp(cfg, x, lp))(x, lp)
    want = ref.experts_mlp(ref.fields_of(cfg), x.reshape(-1, cfg.embed_dim),
                           lp)
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), atol=2e-5)
    assert int(load.sum()) == 24 * top_k          # no token is dropped
    if routing == "skewed":
        assert int(load[0]) == 24 and int(load[3]) == 0


@pytest.mark.parametrize("routing", ["even", "skewed"])
def test_dropless_layer_matches_capacity_layer_when_nothing_drops(routing):
    """The trainer's capacity dispatch with room for every pair computes
    what the dropless layer computes."""
    cfg, lp = _sdar_layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.embed_dim),
                          jnp.float32)
    if routing == "skewed":
        x, lp = _skewed(cfg, lp)
    roomy = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts),
                                mlp_dim=cfg.expert_dim)
    want, _aux = _moe_mlp(roomy, x, lp)
    got, _load = moe_mlp(cfg, x, lp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _xing4_layer():
    """(config, an expert layer's parameters, the family's reference, the
    keyword that leaves the shared expert out of the reference's part)."""
    from mlrun_tpu.models import init_params as any_init
    from mlrun_tpu.models import tiny_xing4
    from mlrun_tpu.models.llama import layer_slice

    from . import xing4_reference

    cfg = tiny_xing4(dtype=jnp.float32)
    params = any_init(cfg, jax.random.PRNGKey(0))
    lp = layer_slice(params["layers"], cfg.first_k_dense, cfg.first_k_dense)
    # the stack of one expert layer's experts, as a layer's own
    lp = {name: (value[0] if name.startswith("experts_") else value)
          for name, value in lp.items()}
    return cfg, lp, xing4_reference


@pytest.mark.parametrize("routing", ["even", "skewed"])
@pytest.mark.parametrize("shares", [2, 4, 8])
@pytest.mark.parametrize("family", ["sdar", "xing4"])
def test_expert_shares_add_up_to_the_whole_layer(family, shares, routing):
    """The share test: with ``held`` set to each share of the experts in
    turn, the partial results add up to the whole layer's, in the program
    and in the reference alike, and the loads to the whole load. What every
    share computes alike (``xing4``: the shared expert) is counted once."""
    from mlrun_tpu.models.moe import shared_expert

    if family == "sdar":
        (cfg, lp), reference = _sdar_layer(), ref
    else:
        cfg, lp, reference = _xing4_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.embed_dim),
                          jnp.float32)
    if routing == "skewed":
        x, lp = _skewed(cfg, lp)
    whole, load = moe_mlp(cfg, x, lp)
    fields = reference.fields_of(cfg)
    flat = x.reshape(-1, cfg.embed_dim)
    alike = shared_expert(x, lp) if "shared_gate" in lp else 0.0
    routed_only = {"shared": False} if "shared_gate" in lp else {}
    width = cfg.n_experts // shares
    total, total_ref, loads = alike, jnp.reshape(alike, (-1,) + (
        (cfg.embed_dim,) if "shared_gate" in lp else ())), []
    for lo in range(0, cfg.n_experts, width):
        held = (lo, lo + width)
        part = {name: (value[lo:lo + width]
                       if name.startswith("experts_") else value)
                for name, value in lp.items()}
        y, part_load = moe_mlp(cfg, x, part, held=held)
        total = total + (y - alike)
        loads.append(part_load)
        if family == "xing4":
            part = {name: (value[None] if name.startswith("experts_")
                           else value) for name, value in part.items()}
        total_ref = total_ref + reference.experts_mlp(
            fields, flat, part, held=held, **routed_only)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(total_ref).reshape(whole.shape),
                               np.asarray(whole), atol=2e-5)
    assert np.array_equal(np.concatenate(loads), np.asarray(load))


def test_dropless_layer_leaves_dead_rows_out():
    cfg, lp = _sdar_layer()
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 4, cfg.embed_dim),
                          jnp.float32)
    live = jnp.asarray([[True] * 4, [False] * 4, [True] * 4])
    y, load = moe_mlp(cfg, x, lp, live=live)
    whole, _ = moe_mlp(cfg, x, lp)
    assert int(load.sum()) == 8 * cfg.top_k
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(whole[0]),
                               atol=2e-5)
    assert not np.asarray(y[1]).any()


def test_layer_of_a_stack_of_experts():
    """The serving programs hand the layer the stacks of every layer's
    experts and the layer's index: the same result as from its slice."""
    cfg = tiny_sdar(dtype=jnp.float32)
    layers = init_params(cfg, jax.random.PRNGKey(0))["layers"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 6, cfg.embed_dim),
                          jnp.float32)
    for layer in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], layers)
        want, want_load = moe_mlp(cfg, x, lp)
        stacked = {name: (value if name.startswith("experts_")
                          else value[layer]) for name, value in layers.items()}
        got, load = jax.jit(lambda x, lp: moe_mlp(cfg, x, lp, layer=layer))(
            x, stacked)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        assert np.array_equal(np.asarray(load), np.asarray(want_load))


def test_held_experts_need_their_weights():
    cfg, lp = _sdar_layer()
    x = jnp.zeros((1, 4, cfg.embed_dim), jnp.float32)
    with pytest.raises(ValueError, match="holds experts"):
        moe_mlp(cfg, x, lp, held=(0, 2))


def test_init_params_is_the_configs_own():
    """``models.init_params`` makes what the config's module defines: the
    experts and q/k norm scales of an SDAR config, the held share's slice
    of the same draw, and the dense leaves of a Llama config."""
    from mlrun_tpu.models import init_params as any_init, tiny_llama

    cfg = tiny_sdar()
    whole = any_init(cfg, jax.random.PRNGKey(0))["layers"]
    assert whole["experts_gate"].shape == (2, 8, 64, 32)
    assert whole["q_norm_scale"].shape == (2, 16)
    share = any_init(dataclasses.replace(cfg, experts_held=(2, 4)),
                     jax.random.PRNGKey(0))["layers"]
    assert np.array_equal(np.asarray(share["experts_down"], np.float32),
                          np.asarray(whole["experts_down"][:, 2:4],
                                     np.float32))
    dense = any_init(tiny_llama(), jax.random.PRNGKey(0))["layers"]
    assert "w_gate" in dense and "experts_gate" not in dense
    actual = sum(x.size for x in jax.tree_util.tree_leaves(
        any_init(cfg, jax.random.PRNGKey(0))))
    assert actual == cfg.param_count()
