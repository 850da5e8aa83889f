"""A block-diffusion model with experts and q/k norms through the paged
engine (docs/serving.md "Block-diffusion decoding"), against the plain
reference of its family at every pass: prefill under the block mask, then
denoising and commit passes of ``_denoise_tick``. Logits are compared, not
tokens: what the engine unmasked in a pass is held against the reference's
logits and confidences for the block state that went into that pass,
rebuilt from the answer's ``tokens`` and ``unmask_pass``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_llama, tiny_sdar
from mlrun_tpu.obs import get_tick_log
from mlrun_tpu.serving.llm import LLMEngine
from mlrun_tpu.serving.llm_batch import (
    BlockDecodingError,
    ContinuousBatchingEngine,
)
from mlrun_tpu.serving.paged import (
    PagedContinuousBatchingEngine,
    _verify_rowwise_paged,
    init_paged_pool,
)

from . import sdar_reference as ref

B, PAGE, PAD = 4, 16, 64
GAP = 2e-3          # float32 program against float32 reference


@pytest.fixture(scope="module")
def model():
    cfg = tiny_sdar(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, ref.fields_of(cfg)


def _engine(model, steps, **kw):
    cfg, params, _ = model
    kw.setdefault("attention_impl", "kernel")
    engine = PagedContinuousBatchingEngine(
        cfg, params, max_len=PAD, slots=3, page_size=PAGE, n_pages=12,
        prefill_buckets=(16, 32), denoising_steps=steps, **kw)
    engine.start()
    return engine


@pytest.fixture(scope="module")
def engines(model):
    """One started engine for each number of denoising steps asked for."""
    made = {}

    def get(steps):
        if steps not in made:
            made[steps] = _engine(model, steps, prefix_cache=False)
        return made[steps]

    yield get
    for engine in made.values():
        engine.stop()


def _check_against_reference(model, prompt, tokens, unmask_pass, steps,
                             said=None):
    """Every pass of every block of one answer: the schedule is the
    rule's, exactly; each token unmasked in a pass lies within ``GAP`` of
    the reference's best logit at its position; the positions unmasked are
    within ``GAP`` (in log-confidence) of the ones the reference picks."""
    _cfg, params, fields = model
    assert len(tokens) == len(unmask_pass)
    blocks = ref.blocks_of(len(prompt), len(tokens), B)
    for block in blocks:
        base, first, lanes = block
        m0 = B - first
        counts = ref.schedule(m0, steps)
        passes = unmask_pass[base + first - len(prompt):][:lanes]
        whole = first + lanes == B
        seen = [passes.count(s) for s in range(len(counts))]
        assert max(passes) < len(counts)
        assert seen == counts if whole else all(
            a <= b for a, b in zip(seen, counts))
        for at in range(len(counts)):
            committed, ids, masked, now = ref.block_state_at(
                prompt, tokens, unmask_pass, block, at, B)
            if not whole and at > 0:
                break            # a cut lane's state is not in the answer
            logits, _x0, confidence = ref.denoise_pass(
                fields, params, committed, ids, masked, B, pad_to=PAD)
            sequence = list(prompt) + list(tokens)
            for lane in now:
                token = sequence[base + lane]
                assert logits[lane].max() - logits[lane][token] < GAP
                if said is not None:    # the confidence the engine had
                    assert said[base + lane - len(prompt)] == pytest.approx(
                        confidence[lane], rel=5e-3)
            would = ref.pick(confidence, masked, counts[at])
            floor = min(np.log(confidence[j]) for j in would)
            for lane in now:
                assert floor - np.log(confidence[lane]) < GAP


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [8, 11, 19, 3])
def test_engine_against_reference_at_every_pass(model, engines, steps,
                                                prompt_len):
    """P mod B of 0 and 3, a prompt shorter than a block, and a request
    that crosses a page boundary (19 + 10 > 16 + ...), at 1, 2 and 4
    denoising steps."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, 510, prompt_len).tolist()
    tokens, stats = engines(steps).submit(
        prompt, max_new_tokens=10).result(timeout=300)
    assert len(tokens) == 10 and stats["generated"] == 10
    assert len(stats["unmask_confidence"]) == 10
    _check_against_reference(model, prompt, tokens, stats["unmask_pass"],
                             steps, said=stats["unmask_confidence"])
    want, want_pass, _ = ref.generate(model[2], model[1], prompt, 10, steps)
    assert tokens == want and stats["unmask_pass"] == want_pass


def test_mask_id_in_the_prompt_is_a_token(model, engines):
    """The mask state is the host's, never read off an id: a prompt may
    hold the mask id, also in the tail that opens the first block."""
    cfg = model[0]
    prompt = [5, cfg.mask_token_id, 9, 12, 40, 41, cfg.mask_token_id]
    tokens, stats = engines(4).submit(prompt, max_new_tokens=6).result(
        timeout=300)
    _check_against_reference(model, prompt, tokens, stats["unmask_pass"], 4)
    want, _, _ = ref.generate(model[2], model[1], prompt, 6, 4)
    assert tokens == want


def test_commit_and_denoising_rows_share_a_dispatch(model):
    """Rows admitted at different passes: some tick carries a row in its
    commit pass beside rows still denoising, and the tick log and the
    engine's stats say what a pass is."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 510, n).tolist() for n in (8, 15, 22, 7, 17)]
    engine = _engine(model, 2)
    try:
        futures = [engine.submit(p, max_new_tokens=9) for p in prompts]
        results = [f.result(timeout=300) for f in futures]
        stats = engine.stats
    finally:
        engine.stop()
    for prompt, (tokens, request) in zip(prompts, results):
        assert len(tokens) == 9
        _check_against_reference(model, prompt, tokens,
                                 request["unmask_pass"], 2)
    records = get_tick_log(engine._obs_name).records()
    assert all(t["kind"] == "denoise" for t in records)
    # an iteration dispatches a pass and commits the one before it: its
    # rows, positions, context and expert counters are those of the pass it
    # dispatched, its ``tokens_out`` what the pass dispatched before it
    # unmasked (an iteration that only reads the last pass has no rows)
    ticks = [t for t in records if t["rows"] > 0]
    assert ticks and any(0 < t["commit_rows"] < t["rows"] for t in ticks)
    cfg = model[0]
    for t in ticks:
        assert t["positions"] == t["rows"] * B
        assert t["expert_pairs"] == t["positions"] * cfg.top_k * cfg.n_layers
        assert 0 < t["experts_touched"] <= cfg.n_experts * cfg.n_layers
        assert 0 < t["expert_load_max"] <= t["positions"]
        assert t["ctx_tokens"] >= t["positions"]
    landed = None
    for t in records:
        room = 0 if landed is None \
            else (landed["rows"] - landed["commit_rows"]) * B
        assert t["tokens_out"] <= room
        if t["rows"]:
            landed = t
    row_passes = sum(t["rows"] for t in ticks)
    assert stats["denoise_passes"] + stats["commit_passes"] == row_passes
    assert stats["commit_passes"] == sum(t["commit_rows"] for t in ticks)
    assert stats["tokens_per_row_pass"] == pytest.approx(
        sum(t["tokens_out"] for t in records) / row_passes)
    assert stats["expert_load_max"] == max(t["expert_load_max"]
                                           for t in ticks)
    assert stats["expert_pairs"] == sum(t["expert_pairs"] for t in ticks)
    assert stats["experts_touched"] == sum(t["experts_touched"]
                                           for t in ticks)
    # every pass was dispatched over a pass in flight or read by a drain
    assert stats["lookahead_ticks"] == sum(t["lookahead"] for t in records)
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] == len(ticks)
    # 9 tokens of a prompt of P: the blocks cover P mod 4 + 9 positions, and
    # every position of theirs that the prompt does not give is unmasked once
    assert sum(t["tokens_out"] for t in records) == sum(
        -(-(len(p) % B + 9) // B) * B - len(p) % B for p in prompts)
    for _tokens, request in results:
        phases = request["timing"]["phases"]
        assert phases["decode_active"] > 0 and "prefill" in phases


def test_prefix_cache_hit_serves_the_same_answer(model):
    """Full pages of a block-causal prefill depend on nothing after them:
    a second request over the same 33-token prefix attends the cached
    pages in place and answers as the first did."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 510, 34).tolist()
    engine = _engine(model, 4, prefix_cache=True)
    try:
        first, _ = engine.submit(shared + [3, 4], max_new_tokens=8).result(
            timeout=300)
        again, stats = engine.submit(shared + [3, 4],
                                     max_new_tokens=8).result(timeout=300)
        other, other_stats = engine.submit(
            shared[:32] + [77, 78, 79], max_new_tokens=8).result(timeout=300)
        engine_stats = engine.stats
    finally:
        engine.stop()
    assert engine_stats["prefix_hits"] >= 2
    assert again == first
    _check_against_reference(model, shared + [3, 4], again,
                             stats["unmask_pass"], 4)
    _check_against_reference(model, shared[:32] + [77, 78, 79], other,
                             other_stats["unmask_pass"], 4)


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_pass_program_against_reference(model, impl):
    """The pass itself (``jit_mlt_denoise``: the verify program with a mask
    bitmap): ``x0`` and confidence of every lane of a live row against the
    reference's, a dead row left out of the expert counters, and the
    block's keys and values stored through the page table."""
    cfg, params, fields = model
    rng = np.random.default_rng(3)
    committed = rng.integers(0, 510, 20).tolist()
    pool = init_paged_pool(cfg, 5, PAGE)
    table = np.full((2, 4), -1, np.int32)
    table[0, :2] = [2, 0]
    nothing = dict(prev_ids=jnp.zeros((2, B), jnp.int32),
                   prev_masked=jnp.zeros((2, B), bool),
                   from_prev=jnp.zeros((2,), bool))
    step = jax.jit(lambda *a, **k: _verify_rowwise_paged(
        cfg, PAGE, impl, params, *a, **nothing, **k))
    pos = jnp.asarray([0, 0], jnp.int32)
    for base in range(0, 20, B):              # commit the leading blocks
        chunk = np.zeros((2, B), np.int32)
        chunk[0] = committed[base:base + B]
        _, pool, after, left = step(
            jnp.asarray(chunk), pool, jnp.asarray(table),
            pos.at[0].set(base), masked=jnp.zeros((2, B), bool),
            count=jnp.zeros((2,), jnp.int32))
        assert (np.asarray(after) == chunk).all() and not left.any()
    ids, masked = [41, 0, 42, 0], [False, True, False, True]
    chunk = np.zeros((2, B), np.int32)
    chunk[0] = ids
    packed, pool, after, left = step(
        jnp.asarray(chunk), pool, jnp.asarray(table), pos.at[0].set(20),
        masked=jnp.asarray([masked, [True] * B]),
        count=jnp.asarray([1, 0], jnp.int32))
    host = np.asarray(packed)
    x0 = host[:2 * B].reshape(2, B)
    confidence = host[2 * B:4 * B].view(np.float32).reshape(2, B)
    chosen = host[4 * B:6 * B].reshape(2, B)
    pairs, touched, load_max = host[6 * B:]
    # the pass unmasked the more confident of the row's two masked lanes,
    # and left the block state for the pass behind it on the device
    lane = 1 if confidence[0, 1] >= confidence[0, 3] else 3
    assert chosen.tolist() == [[int(j == lane) for j in range(B)], [0] * B]
    want = list(ids)
    want[lane] = x0[0, lane]
    assert np.asarray(after)[0].tolist() == want
    assert np.asarray(left).tolist() == [
        [m and j != lane for j, m in enumerate(masked)], [True] * B]
    logits, want_x0, want_conf = ref.denoise_pass(
        fields, params, committed, ids, masked, B, pad_to=PAD)
    for lane in range(B):
        assert logits[lane].max() - logits[lane][x0[0, lane]] < GAP
    np.testing.assert_allclose(confidence[0], want_conf, rtol=5e-3)
    assert pairs == B * cfg.top_k * cfg.n_layers      # the live row alone
    assert 0 < touched <= pairs and 0 < load_max <= B
    assert np.asarray(pool["k"][:, 0, 4:8]).any()     # page 0, lanes 20..23
    assert not np.asarray(pool["k"][:, 1]).any()      # an unmapped page


def test_block_length_one_takes_the_plain_program(model):
    """The same family with a block of one token is served by the plain
    decode program: experts and q/k norms through prefill and decode."""
    cfg = dataclasses.replace(model[0], block_length=1)
    params, fields = model[1], dict(model[2], block_length=1)
    prompt = np.random.default_rng(5).integers(0, 510, 9).tolist()
    engine = PagedContinuousBatchingEngine(
        cfg, params, max_len=PAD, slots=2, page_size=PAGE, n_pages=8,
        prefill_buckets=(16,), attention_impl="kernel")
    engine.start()
    try:
        tokens, stats = engine.submit(prompt, max_new_tokens=6).result(
            timeout=300)
    finally:
        engine.stop()
    assert len(tokens) == 6 and "unmask_pass" not in stats
    sequence = prompt + tokens
    logits = np.asarray(ref.forward(fields, params, sequence,
                                    [False] * len(sequence)))
    for i, token in enumerate(tokens):
        row = logits[len(prompt) - 1 + i]
        assert row.max() - row[token] < GAP
    kinds = {t["kind"] for t in get_tick_log(engine._obs_name).records()}
    assert kinds == {"plain"}


def test_typed_errors(model, engines):
    cfg, params, _ = model
    with pytest.raises(BlockDecodingError, match="paged engine"):
        LLMEngine(cfg, params, max_len=PAD)
    with pytest.raises(BlockDecodingError, match="paged engine"):
        ContinuousBatchingEngine(cfg, params, max_len=PAD, slots=2)
    draft = tiny_llama()
    with pytest.raises(BlockDecodingError, match="speculative"):
        PagedContinuousBatchingEngine(
            cfg, params, max_len=PAD, slots=2, page_size=PAGE,
            speculative={"enabled": True, "draft_config": draft,
                         "draft_params": init_params(
                             draft, jax.random.PRNGKey(1))})
    with pytest.raises(BlockDecodingError, match="page_size"):
        PagedContinuousBatchingEngine(cfg, params, max_len=60, slots=2,
                                      page_size=6)
    for steps in (0, 5):
        with pytest.raises(BlockDecodingError, match="denoising_steps"):
            PagedContinuousBatchingEngine(
                cfg, params, max_len=PAD, slots=2, page_size=PAGE,
                denoising_steps=steps)
    with pytest.raises(BlockDecodingError, match="remasking"):
        PagedContinuousBatchingEngine(
            cfg, params, max_len=PAD, slots=2, page_size=PAGE,
            remasking="low_confidence_dynamic")
    with pytest.raises(BlockDecodingError, match="prefill_chunk"):
        PagedContinuousBatchingEngine(
            cfg, params, max_len=PAD, slots=2, page_size=PAGE,
            prefill_chunk=6)
    with pytest.raises(BlockDecodingError, match="denoising_steps"):
        PagedContinuousBatchingEngine(
            tiny_llama(), init_params(tiny_llama(), jax.random.PRNGKey(0)),
            max_len=PAD, slots=2, page_size=PAGE, denoising_steps=2)
    with pytest.raises(BlockDecodingError, match="greedily"):
        engines(4).submit([1, 2, 3], max_new_tokens=4,
                          temperature=0.7).result(timeout=30)


def test_model_server_returns_unmask_pass(model):
    """Through the serving graph: ``denoising_steps`` and ``remasking`` as
    class arguments, ``return_unmask_pass`` in a request's body."""
    import mlrun_tpu
    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

    MODEL_PRESETS["tiny-sdar-f32"] = lambda **over: tiny_sdar(
        dtype=jnp.float32, **over)
    fn = mlrun_tpu.new_function("sdar-graph", kind="serving")
    fn.set_topology("router")
    route = fn.add_model(
        "llm", class_name="mlrun_tpu.serving.llm.LLMModelServer",
        model_preset="tiny-sdar-f32", continuous_batching=True, paged=True,
        page_size=PAGE, slots=2, max_len=PAD, n_pages=8, warmup=False,
        max_new_tokens=6, denoising_steps=2,
        remasking="low_confidence_static", attention_impl="kernel")
    server = fn.to_mock_server()
    try:
        prompt = [9, 8, 7, 6, 5, 4]
        body = server.test("/v2/models/llm/infer", body={
            "inputs": [prompt, prompt[:5]], "return_unmask_pass": True,
            "timing": True})
        plain = server.test("/v2/models/llm/infer",
                            body={"inputs": [prompt]})
    finally:
        route.object.engine.stop()
    assert [len(t) for t in body["outputs"]] == [6, 6]
    assert [len(p) for p in body["unmask_pass"]] == [6, 6]
    assert [len(p) for p in body["unmask_confidence"]] == [6, 6]
    assert all(0.0 < c <= 1.0 for c in body["unmask_confidence"][0])
    assert all(p in (0, 1) for p in body["unmask_pass"][0])
    assert len(body["timing"]) == 2
    assert "unmask_pass" not in plain and plain["outputs"] == \
        [body["outputs"][0]]
    _check_against_reference(model, prompt, body["outputs"][0],
                             body["unmask_pass"][0], 2)
