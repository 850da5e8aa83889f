"""The plain reference of the ``sdar`` family
(benchmarks/harness/reference_sdar.py) against hand-built cases: the block
mask, q/k norms, the renormalised top-k gates, and generation by diffusion
over blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_sdar

from . import sdar_reference as ref


@pytest.fixture(scope="module")
def model():
    cfg = tiny_sdar()
    fields = ref.fields_of(cfg)
    return cfg, fields, ref.make_weights(fields, 0)


@pytest.mark.parametrize("block_length,want", [
    (1, [[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0],
         [1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 0], [1, 1, 1, 1, 1, 1]]),
    (2, [[1, 1, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [1, 1, 1, 1, 0, 0],
         [1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 1, 1]]),
    (4, [[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 0, 0],
         [1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 1, 1]]),
])
def test_block_mask(block_length, want):
    got = np.asarray(ref.block_mask(6, block_length)).astype(int)
    assert got.tolist() == want
    causal = np.asarray(ref.block_mask(6, block_length, causal_inside=True))
    assert causal.astype(int).tolist() == np.tril(np.ones((6, 6), int)
                                                  ).tolist()


def test_make_weights_is_the_programs_recipe(model):
    cfg, fields, weights = model
    params = init_params(cfg, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        other = weights
        for key in path:
            other = other[key.key]
        assert leaf.dtype == other.dtype and np.array_equal(
            np.asarray(leaf, np.float32), np.asarray(other, np.float32)), path
    held = ref.make_weights(fields, 0, held=(2, 6))["layers"]
    assert np.array_equal(
        np.asarray(held["experts_up"], np.float32),
        np.asarray(weights["layers"]["experts_up"][:, 2:6], np.float32))


def test_a_block_sees_itself_whole_and_nothing_after(model):
    _cfg, fields, weights = model
    ids = list(range(10, 22))
    none = [False] * 12
    base = np.asarray(ref.forward(fields, weights, ids, none))
    later = list(ids)
    later[9] = 400                          # a position of the third block
    got = np.asarray(ref.forward(fields, weights, later, none))
    assert np.array_equal(got[:8], base[:8])
    assert np.abs(got[8] - base[8]).max() > 1e-3    # same block, before it
    # with a causal mask inside the block (the planted fault) it would not
    got = np.asarray(ref.forward(fields, weights, later, none,
                                 fault="causal_block"))
    base_c = np.asarray(ref.forward(fields, weights, ids, none,
                                    fault="causal_block"))
    assert np.array_equal(got[:9], base_c[:9])
    # a masked position holds the mask id's embedding, whatever its id
    masked = [False] * 8 + [True] * 4
    a = np.asarray(ref.forward(fields, weights, ids, masked))
    b = np.asarray(ref.forward(fields, weights, ids[:8] + [5] * 4, masked))
    assert np.array_equal(a, b)
    rows = np.asarray(ref.forward(fields, weights, ids, masked, rows=(8, 4)))
    assert np.array_equal(rows, a[8:])


def test_qk_norm_is_per_head_and_before_the_rotation(model):
    _cfg, fields, weights = model
    x = jnp.asarray([[3.0, 4.0, 0.0, 0.0], [0.0, 0.0, 1.0, 0.0]])
    got = np.asarray(ref._rms_norm(x, jnp.asarray([1.0, 2.0, 1.0, 1.0]), 0.0))
    np.testing.assert_allclose(got[0], [3 / 2.5, 2 * 4 / 2.5, 0, 0], 1e-6)
    np.testing.assert_allclose(got[1], [0, 0, 2.0, 0], 1e-6)
    # a q projection three times as large changes nothing: each head's q
    # is normalised (up to eps) before rope and the scores
    ids, none = list(range(30, 38)), [False] * 8
    base = np.asarray(ref.forward(fields, weights, ids, none))
    layers = dict(weights["layers"],
                  wq=(weights["layers"]["wq"].astype(jnp.float32) * 4.0
                      ).astype(jnp.bfloat16))
    got = np.asarray(ref.forward(fields, dict(weights, layers=layers), ids,
                                 none))
    np.testing.assert_allclose(got, base, atol=2e-3)
    # but the learned scale does
    layers = dict(weights["layers"],
                  q_norm_scale=weights["layers"]["q_norm_scale"] * 4)
    got = np.asarray(ref.forward(fields, dict(weights, layers=layers), ids,
                                 none))
    assert np.abs(got - base).max() > 1e-2


def test_top_k_gates_are_renormalised(model):
    cfg, fields, weights = model
    lw = jax.tree_util.tree_map(lambda a: a[0], weights["layers"])
    h2 = jax.random.normal(jax.random.PRNGKey(5), (6, cfg.embed_dim))
    gates, experts = ref.route(fields, h2, lw["router"])
    probs = np.asarray(jax.nn.softmax(
        h2 @ lw["router"].astype(jnp.float32), axis=-1))
    for t in range(6):
        top = np.argsort(-probs[t])[:cfg.top_k]
        assert sorted(top.tolist()) == sorted(np.asarray(experts[t]).tolist())
        np.testing.assert_allclose(
            np.sort(np.asarray(gates[t])),
            np.sort(probs[t][top] / probs[t][top].sum()), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    plain, _ = ref.route(dict(fields, norm_topk=False), h2, lw["router"])
    assert float(np.asarray(plain).sum(-1).max()) < 1.0
    # experts that are all alike: the layer is that one expert, whatever
    # the routing, because the gates sum to one
    same = {name: (jnp.broadcast_to(value[:1], value.shape)
                   if name.startswith("experts_") else value)
            for name, value in lw.items()}
    got = ref.experts_mlp(fields, h2, same)
    w = {n: same[n][0].astype(jnp.float32) for n in same
         if n.startswith("experts_")}
    want = (jax.nn.silu(h2 @ w["experts_gate"]) * (h2 @ w["experts_up"])) \
        @ w["experts_down"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the planted fault drops the least weighted expert of every token
    dropped = ref.experts_mlp(fields, h2, same, fault="drop_expert")
    np.testing.assert_allclose(
        np.asarray(dropped),
        np.asarray(want) * (1.0 - np.asarray(gates)[:, -1:]), atol=1e-5)


@pytest.mark.parametrize("m0,steps,want", [
    (4, 4, [1, 1, 1, 1]), (4, 3, [2, 1, 1]), (4, 2, [2, 2]), (4, 1, [4]),
    (3, 4, [1, 1, 1]), (1, 4, [1]), (3, 2, [2, 1]), (0, 4, []),
])
def test_schedule(m0, steps, want):
    assert ref.schedule(m0, steps) == want


def test_pick_takes_the_most_confident_masked():
    confidence = [0.9, 0.2, 0.5, 0.5]
    assert ref.pick(confidence, [False, True, True, True], 2) == [2, 3]
    assert ref.pick(confidence, [True, True, True, True], 1) == [0]
    assert ref.pick(confidence, [False, True, False, False], 3) == [1]


def _echo_model(fields):
    """Zero layers' worth of mixing: every matrix of the layers zero, so a
    position's logits are its own embedding against the head, and a masked
    position always says the same token with the same confidence."""
    weights = ref.make_weights(fields, 3)
    layers = {name: (jnp.zeros_like(value) if name.startswith(("w", "exp"))
                     else value)
              for name, value in weights["layers"].items()}
    return dict(weights, layers=layers)


@pytest.mark.parametrize("steps,first,later", [
    (4, [0], [0, 1, 2, 3]), (2, [0], [0, 0, 1, 1]), (1, [0], [0, 0, 0, 0]),
])
def test_generate_follows_the_rule_on_a_hand_built_model(model, steps, first,
                                                         later):
    """Equal confidences everywhere: the rule unmasks from the left, the
    even split of a block over the steps shows in ``unmask_pass``, and a
    block of 4 at 4 steps costs five passes for four tokens."""
    _cfg, fields, _ = model
    weights = _echo_model(fields)
    said = int(np.argmax(np.asarray(ref.forward(
        fields, weights, [0], [True]))[0]))
    prompt = [7, 8, 9, 10, 11, 12, 13]              # P mod B = 3
    tokens, unmask_pass, passes = ref.generate(fields, weights, prompt, 9,
                                               steps)
    assert tokens == [said] * 9
    assert unmask_pass == first + later + later
    blocks = [[p for p in passes if p["base"] == base] for base in (4, 8, 12)]
    assert [len(b) for b in blocks] == [2, min(steps, 4) + 1,
                                        min(steps, 4) + 1]
    for block in blocks:
        assert block[-1]["logits"] is None and not any(block[-1]["masked"])
        assert block[0]["logits"].shape == (4, fields["vocab_size"])
    assert blocks[0][0]["ids"][:3] == [11, 12, 13]
    assert blocks[0][0]["masked"] == [False, False, False, True]


def test_generate_prefers_confidence_to_position(model):
    """On seeded weights the order inside a block follows the confidences
    of each pass, not the positions."""
    _cfg, fields, weights = model
    prompt = list(range(40, 48))
    tokens, unmask_pass, passes = ref.generate(fields, weights, prompt, 8, 4)
    assert len(tokens) == 8 and sorted(unmask_pass[:4]) == [0, 1, 2, 3]
    for record in passes:
        if record["logits"] is None:
            continue
        logits = record["logits"].astype(np.float64)
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        confidence = (probs / probs.sum(-1, keepdims=True)).max(-1)
        assert record["unmasked"] == ref.pick(confidence, record["masked"],
                                              len(record["unmasked"]))


def test_an_answer_taken_apart():
    assert ref.blocks_of(7, 9, 4) == [(4, 3, 1), (8, 0, 4), (12, 0, 4)]
    assert ref.blocks_of(8, 6, 4) == [(8, 0, 4), (12, 0, 2)]
    assert ref.blocks_of(2, 3, 4) == [(0, 2, 2), (4, 0, 1)]
    prompt, tokens = [1, 2, 3, 4, 5], [10, 11, 12, 13, 14, 15, 16]
    unmask_pass = [1, 0, 2, 3, 0, 1, 2]
    block = ref.blocks_of(5, 7, 4)[1]                # positions 8..11
    committed, ids, masked, now = ref.block_state_at(
        prompt, tokens, unmask_pass, block, 1, 4)
    assert committed == [1, 2, 3, 4, 5, 10, 11, 12]
    assert ids == [0, 14, 0, 0] and masked == [True, False, True, True]
    assert now == [2]
    first = ref.blocks_of(5, 7, 4)[0]                # the prompt's tail in it
    committed, ids, masked, now = ref.block_state_at(
        prompt, tokens, unmask_pass, first, 0, 4)
    assert committed == [1, 2, 3, 4] and ids == [5, 0, 0, 0]
    assert masked == [False, True, True, True] and now == [2]
    cut = ref.blocks_of(5, 6, 4)[1]                  # its last lane was cut
    _c, ids, masked, now = ref.block_state_at(
        prompt, tokens[:6], unmask_pass[:6], cut, 2, 4)
    assert masked == [True, False, False, True] and now == []
