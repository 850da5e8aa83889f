"""The plain reference against the program's model at a tiny size on the
CPU: the same seeded weights by the same recipe, the same logits, the same
loss and LoRA gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells, reference

FIELDS = dict(vocab_size=512, n_layers=2, embed_dim=160, n_heads=4,
              n_kv_heads=2, head_dim=32, mlp_dim=256, rope_theta=1e6,
              norm_eps=1e-5, tie_embeddings=False)


def _program_config(**over):
    from mlrun_tpu.models.llama import LlamaConfig

    return LlamaConfig(**{**FIELDS, "remat": False,
                          "attention_impl": "reference", **over})


@pytest.mark.parametrize("seed", [0, 2147483999])
@pytest.mark.parametrize("eager", [True, False])
def test_weights_follow_the_programs_recipe(seed, eager):
    from mlrun_tpu.models import init_params

    key = jax.random.PRNGKey(seed)
    theirs = init_params(_program_config(), key) if eager else jax.jit(
        lambda k: init_params(_program_config(), k))(key)
    ours = reference.make_weights(FIELDS, seed, eager=eager)
    for (path, a), (_p, b) in zip(
            jax.tree_util.tree_leaves_with_path(theirs),
            jax.tree_util.tree_leaves_with_path(ours)):
        assert a.dtype == b.dtype == jnp.bfloat16, path
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), path


def test_lora_follows_the_programs_recipe():
    from mlrun_tpu.models.lora import init_lora

    theirs = init_lora(_program_config(), jax.random.PRNGKey(5), 4, 32.0)
    ours = reference.make_lora(FIELDS, 5, 4, 32.0)
    assert jax.tree_util.tree_structure(theirs) \
        == jax.tree_util.tree_structure(ours)
    for a, b in zip(jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_logits_agree_with_the_programs_forward():
    from mlrun_tpu.models.llama import forward

    weights = reference.make_weights(FIELDS, 3)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 21).tolist()
    served = rng.integers(1, 512, 6).tolist()
    ours = np.asarray(reference.served_logits(FIELDS, weights, prompt,
                                              served, pad_to=40))
    tokens = jnp.asarray([prompt + served])
    theirs = np.asarray(forward(_program_config(dtype=jnp.float32),
                                jax.tree_util.tree_map(
                                    lambda w: w.astype(jnp.float32), weights),
                                tokens))[0, len(prompt) - 1:-1]
    assert ours.shape == theirs.shape == (6, 512)
    assert np.abs(ours - theirs).max() < 2e-4
    # padding behind the sequence changes nothing before it
    wider = np.asarray(reference.served_logits(FIELDS, weights, prompt,
                                               served, pad_to=64))
    assert np.abs(ours - wider).max() < 1e-5
    gaps = reference.gap_below_best(ours, ours.argmax(-1))
    assert (gaps == 0).all()


def test_loss_and_lora_gradients_agree_with_the_programs():
    from mlrun_tpu.models.llama import loss_fn
    from mlrun_tpu.models.lora import init_lora_nonzero

    config = _program_config(dtype=jnp.float32)
    weights = reference.make_weights(FIELDS, 1)
    params = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), weights)
    lora = init_lora_nonzero(config, jax.random.PRNGKey(2), rank=4)
    rng = np.random.default_rng(1)
    block = rng.integers(0, 512, (4, 65), dtype=np.int32)
    tokens, targets = block[:, :-1], block[:, 1:]

    def theirs(lora_):
        return loss_fn(config, params, jnp.asarray(tokens),
                       jnp.asarray(targets), lora=lora_)[0]

    with jax.default_matmul_precision("highest"):
        their_loss, their_grads = jax.value_and_grad(theirs)(lora)
    loss, grads = reference.loss_and_grads(FIELDS, weights, lora, tokens,
                                           targets)
    assert abs(float(loss) - float(their_loss)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(their_grads),
                            jax.tree_util.tree_leaves(grads)):
        scale = float(jnp.abs(a).max()) + 1e-12
        assert float(jnp.abs(a - b).max()) <= 2e-4 * scale + 1e-9, path


def test_adamw_follows_optax():
    import optax

    from mlrun_tpu.training.train import TrainConfig, make_optimizer

    optimizer = make_optimizer(TrainConfig(learning_rate=2e-4,
                                           total_steps=100000))
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    state = optimizer.init(params)
    ours = params
    mu = nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    theirs = params
    for count in range(4):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape) * 3, jnp.float32),
            params)
        updates, state = optimizer.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        clipped = reference.clip_by_global_norm(grads, 1.0)
        lr = reference.learning_rate(count, 2e-4, 10, 100000)
        ours, mu, nu = reference.adamw_step(ours, clipped, mu, nu, count, lr)
    for a, b in zip(jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours)):
        assert float(jnp.abs(a - b).max()) < 1e-7
    assert reference.learning_rate(0, 2e-4, 10, 100000) == 0.0
