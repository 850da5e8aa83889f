"""A block-diffusion model's pass looks one pass ahead (serving/paged.py
``_denoise_tick``, docs/serving.md "The scheduler's iteration"): the pass
unmasks its share of each block on the device, pass p+1 is dispatched from
pass p's block state there, and the host reads p while p+1 runs. What must
not change is the answer: ``tokens``, ``unmask_pass`` and
``unmask_confidence`` are those of the synchronous pass with the selection
on the host (kept here as ``_SyncDenoise``, the reference, with the host's
``_unmask``), a row that meets its end-of-sequence id in a committed block
loses what the pass behind held for it, and whatever needs the committed
state reads the pass in flight first. CPU-only (Pallas interpret mode)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos, fail_first
from mlrun_tpu.models import init_params, tiny_sdar
from mlrun_tpu.obs import TickRecord, get_tick_log
from mlrun_tpu.serving.llm_batch import EngineStoppedError
from mlrun_tpu.serving.paged import (
    PagedContinuousBatchingEngine,
    _most_confident,
)

from . import sdar_reference as ref

B, PAGE, PAD = 4, 8, 64
LENGTHS = [7, 9, 14, 3, 21]         # P mod B of 3, 1, 2, 3 and 1
MAX_NEW = [9, 5, 12, 6, 10]


class _SyncDenoise(PagedContinuousBatchingEngine):
    """The pass as it was before the lookahead, the reference: one pass is
    built, dispatched, read and committed inside one iteration, and the
    host chooses what it unmasks (``ref.pick``, the rule as the host
    applied it: a sort by confidence, equal ones by position; the device's
    own choice is not read)."""

    def _denoise_tick(self, active) -> int:
        tick = self._tick
        tick.kind = "denoise"
        size = self.block_length
        chunk = np.zeros((self.slots, size), np.int32)
        masked = np.zeros((self.slots, size), bool)
        for i in active:
            slot = self._slot_state[i]
            chunk[i] = slot.block_ids
            masked[i] = slot.block_masked
        packed = self._denoise_paged(
            self.params, jnp.asarray(chunk), self._pool,
            jnp.asarray(self._page_table), jnp.asarray(self._pos),
            masked=jnp.asarray(masked),
            count=jnp.zeros((self.slots,), jnp.int32),
            prev_ids=self._no_block[0], prev_masked=self._no_block[1],
            from_prev=jnp.zeros((self.slots,), bool))
        host, self._pool = np.asarray(packed[0]), packed[1]
        lanes = self.slots * size
        x0 = host[:lanes].reshape(self.slots, size)
        confidence = host[lanes:2 * lanes].view(np.float32).reshape(
            self.slots, size)
        for i in active:
            if masked[i].any():
                tick.tokens_out += self._unmask(
                    self._slot_state[i], x0[i], confidence[i])
            else:
                self._commit_block(i)
        return len(active)

    def _unmask(self, slot, x0, confidence) -> int:
        steps, m0, s = self.denoising_steps, slot.block_m0, \
            slot.passes_in_block
        count = m0 // steps + (1 if s < m0 % steps else 0)
        chosen = ref.pick(confidence, slot.block_masked, count)
        for j in chosen:
            slot.block_ids[j] = int(x0[j])
            slot.block_masked[j] = False
            slot.block_pass[j] = s
            slot.block_confidence[j] = float(confidence[j])
        slot.passes_in_block = s + 1
        return len(chosen)

    def _commit_block(self, index: int):
        slot = self._slot_state[index]
        size = self.block_length
        first = max(0, slot.prompt_len - slot.block_base)
        new = slot.block_ids[first:]
        slot.unmask_pass.extend(slot.block_pass[first:])
        slot.unmask_confidence.extend(slot.block_confidence[first:])
        if slot.eos_id is not None and slot.eos_id in new:
            new = new[:new.index(slot.eos_id) + 1]
        slot.tokens.extend(new)
        slot.remaining -= len(new)
        base = slot.block_base + size
        self._pos[index] = base
        ended = slot.eos_id is not None and bool(new) \
            and new[-1] == slot.eos_id
        if ended or slot.remaining <= 0 or base + size > self.max_len:
            self._finish(index)
        else:
            self._open_block(slot, base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_sdar(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, ref.fields_of(cfg)


def _prompt(length: int, seed: int = 0) -> list:
    return np.random.default_rng(100 * seed + length).integers(
        0, 510, length).tolist()


def _engine(model, kind=PagedContinuousBatchingEngine, steps=4, **over):
    cfg, params, _ = model
    kwargs = dict(max_len=PAD, slots=2, prefill_buckets=(16, 32),
                  page_size=PAGE, attention_impl="kernel",
                  prefix_cache=False, denoising_steps=steps)
    kwargs.update(over)
    return kind(cfg, params, **kwargs)


def _by_hand(eng):
    """The test is the scheduler: submit() starts no thread."""
    eng.start = lambda: None
    return eng


def _tick(eng) -> int:
    eng._tick = TickRecord(0, time.perf_counter())
    return eng._decode_tick()


def _pages_all_free(eng) -> bool:
    return sorted(eng._free_pages) == list(range(eng.n_pages)) \
        and (eng._page_table == -1).all() and not eng._slot_pages \
        and not eng._pos.any()


def _run(eng, requests):
    """The answers to ``requests`` ((prompt, max_new) pairs), the second
    half submitted while the first is being decoded."""
    eng.start()
    try:
        half = len(requests) // 2
        futures = [eng.submit(p, max_new_tokens=n)
                   for p, n in requests[:half]]
        futures[0].result(timeout=300)
        futures += [eng.submit(p, max_new_tokens=n)
                    for p, n in requests[half:]]
        return [f.result(timeout=300) for f in futures], eng.stats
    finally:
        eng.stop()


def _want(model, prompt, max_new, steps=4):
    """(tokens, unmask_pass) of the plain reference."""
    return ref.generate(model[2], model[1], prompt, max_new, steps)[:2]


# -- the answers ---------------------------------------------------------------
@pytest.mark.parametrize("attention_impl", ["kernel", "reference"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_answers_over_staggered_admissions(model, steps, attention_impl):
    """Five requests of unequal length through two slots, each with a first
    block that holds a known prompt tail: every answer is the synchronous
    pass's, id for id, pass for pass and confidence for confidence, and
    the plain reference's; every pass was either dispatched over a pass in
    flight or read by a drain."""
    requests = [(_prompt(n), m) for n, m in zip(LENGTHS, MAX_NEW)]
    eng = _engine(model, steps=steps, attention_impl=attention_impl)
    outs, stats = _run(eng, requests)
    sync, _ = _run(_engine(model, _SyncDenoise, steps=steps,
                           attention_impl=attention_impl), requests)
    for (prompt, n), (tokens, got), (other, want) in zip(requests, outs,
                                                         sync):
        assert len(tokens) == n and tokens == other
        assert got["unmask_pass"] == want["unmask_pass"]
        assert got["unmask_confidence"] == pytest.approx(
            want["unmask_confidence"], rel=1e-5)
        assert (tokens, got["unmask_pass"]) == _want(model, prompt, n, steps)
    records = get_tick_log(eng._obs_name).records()
    passes = [r for r in records if r["rows"] and r["kind"] == "denoise"]
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] \
        == len(passes)
    assert stats["lookahead_ticks"] == sum(r["lookahead"] for r in records)
    assert stats["lookahead_ticks"] > stats["lookahead_drains"] > 0
    assert _pages_all_free(eng)


CASES = {
    "distinct": ([.2, .9, .4, .7], [1, 1, 1, 1], 2, [1, 3]),
    "all-equal-by-position": ([.5, .5, .5, .5], [1, 1, 1, 1], 2, [0, 1]),
    "tie-for-the-last-place": ([.3, .8, .3, .1], [1, 1, 1, 1], 2, [0, 1]),
    "tie-among-the-masked-only": ([.9, .4, .4, .4], [0, 1, 1, 1], 1, [1]),
    "an-unmasked-lane-is-no-rival": ([.99, .1, .98, .2], [0, 1, 0, 1], 1,
                                     [3]),
    "count-zero": ([.2, .9, .4, .7], [1, 1, 1, 1], 0, []),
    "count-above-the-masked": ([.2, .9, .4, .7], [0, 1, 0, 1], 3, [1, 3]),
    "nothing-masked": ([.2, .9, .4, .7], [0, 0, 0, 0], 2, []),
    "the-whole-block": ([.2, .9, .4, .7], [1, 1, 1, 1], 4, [0, 1, 2, 3]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_device_selection_on_hand_made_confidences(case):
    """The device's choice (``_most_confident``) on confidences made by
    hand, equal ones among them: the host rule's, lane for lane."""
    confidence, masked, count, want = CASES[case]
    assert sorted(ref.pick(confidence, masked, count)) == want
    chosen = _most_confident(
        jnp.asarray([confidence], jnp.float32),
        jnp.asarray([masked], bool), jnp.asarray([count], jnp.int32))
    assert np.flatnonzero(np.asarray(chosen)[0]).tolist() == want


@pytest.mark.parametrize("size", [4, 8])
def test_device_selection_against_the_host_rule(size):
    """Rows of coarse confidences (many equal), random masks and counts
    from 0 to beyond the block, all in one call: every row is chosen as
    the host rule chooses it."""
    rng = np.random.default_rng(size)
    rows = 256
    confidence = (rng.integers(0, 5, (rows, size)) / 4).astype(np.float32)
    masked = rng.random((rows, size)) < 0.6
    count = rng.integers(0, size + 2, rows).astype(np.int32)
    chosen = np.asarray(jax.jit(_most_confident)(
        jnp.asarray(confidence), jnp.asarray(masked), jnp.asarray(count)))
    for r in range(rows):
        want = ref.pick(confidence[r], masked[r], int(count[r]))
        assert np.flatnonzero(chosen[r]).tolist() == sorted(want)


def test_end_of_sequence_in_a_committed_block_discards_the_pass_behind(
        model):
    """A row's commit finds its end-of-sequence id: the host learns it when
    the next block's first pass is already under way with the row in it.
    The answer ends at the id, that pass's values for the row are thrown
    away, the row beside it and the request admitted into the freed slot
    (while that pass is in flight) read as if nothing had ridden along,
    and every page comes back once."""
    first_p, beside_p, after_p = _prompt(7), _prompt(9), _prompt(6)
    whole, _ = _want(model, first_p, 10)
    stop_at = next(i for i in range(1, 5) if whole[i] not in whole[:i])
    eng = _by_hand(_engine(model))
    first = eng.submit(first_p, max_new_tokens=10, eos_id=whole[stop_at])
    beside = eng.submit(beside_p, max_new_tokens=12)
    after = eng.submit(after_p, max_new_tokens=5)
    rode = 0
    for _ in range(80):
        eng._admission_tick()
        before = first.done()
        _tick(eng)
        if first.done() and not before:
            # the pass in flight was dispatched with the ended row in it
            assert eng._in_flight is not None and eng._in_flight.rows == [1]
            assert eng._in_flight.passes[0].index == 0
            assert eng._slot_pages.keys() == {1}
            rode += 1
        if all(f.done() for f in (first, beside, after)):
            break
    assert rode == 1 and eng._in_flight is None
    got, stats = first.result(timeout=0)
    assert got == whole[:stop_at + 1] and stats["generated"] == stop_at + 1
    assert len(stats["unmask_pass"]) == stop_at + 1
    for future, prompt, n in ((beside, beside_p, 12), (after, after_p, 5)):
        tokens, request = future.result(timeout=0)
        assert (tokens, request["unmask_pass"]) == _want(model, prompt, n)
    assert _pages_all_free(eng)
    assert eng.stats["tokens_out"] == stop_at + 1 + 12 + 5


def test_row_whose_last_block_ends_at_the_caches_end(model):
    """A prompt and an answer that fill the cache exactly: the last commit
    is known at dispatch by count, so nothing is dispatched behind it, no
    position past the cache is ever written, and the answer is whole."""
    prompt = _prompt(5)
    eng = _by_hand(_engine(model, max_len=16, slots=1,
                           prefill_buckets=(16,)))
    future = eng.submit(prompt, max_new_tokens=11)
    eng._admission_tick()
    dispatched = []
    while not future.done():
        dispatched.append(_tick(eng))
        assert int(eng._pos.max()) <= 12
    # 3 + 1, 4 + 1 and 4 + 1 passes, then the read of the last commit
    assert dispatched == [1] * 14 + [0]
    tokens, stats = future.result(timeout=0)
    assert (tokens, stats["unmask_pass"]) == _want(model, prompt, 11)
    assert _pages_all_free(eng)
    assert eng.stats["lookahead_ticks"] == 13
    assert eng.stats["lookahead_drains"] == 1


# -- the order -----------------------------------------------------------------
def test_next_pass_is_dispatched_before_the_last_is_read(model):
    """A spy on the dispatch and on the read: pass p+1 goes to the device
    before pass p's values come to the host, and the last pass is read by
    a drain with nothing behind it."""
    eng = _engine(model, steps=2)
    eng.warmup()
    events, names, kept = [], {}, []
    program, land = eng._denoise_paged, eng._land

    def dispatching(*args, **kwargs):
        out = program(*args, **kwargs)
        kept.append(out[0])             # an id names one array while it lives
        names[id(out[0])] = len(names)
        events.append(("dispatch", names[id(out[0])]))
        return out

    def landing(ahead, *args):
        events.append(("read", names[id(ahead.fetched)]))
        return land(ahead, *args)

    eng._denoise_paged, eng._land = dispatching, landing
    prompt = _prompt(8)
    eng.start()
    try:
        tokens, request = eng.generate(prompt, max_new_tokens=8,
                                       timeout=120)
        stats = eng.stats
    finally:
        eng.stop()
    assert (tokens, request["unmask_pass"]) == _want(model, prompt, 8, 2)
    want = [("dispatch", 0)]
    for p in range(5):                  # two blocks of 2 + 1 passes
        want += [("dispatch", p + 1), ("read", p)]
    assert events == want + [("read", 5)]
    assert stats["lookahead_ticks"] == 5 and stats["lookahead_drains"] == 1


# -- what drains ---------------------------------------------------------------
def _with_pass_in_flight(model, max_new=(8, 8), ticks=1, **over):
    eng = _by_hand(_engine(model, **over))
    prompts = [_prompt(8), _prompt(12)]
    futures = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, max_new)]
    eng._admission_tick()
    for _ in range(ticks):
        assert _tick(eng) == len(futures)
    assert eng._in_flight is not None
    return eng, prompts, futures


def test_stop_reads_the_pass_in_flight(model):
    """stop() with a pass in flight: the request whose last commit it was
    is answered, the other fails as stopped, none is left pending and the
    pages are all back."""
    eng, prompts, (short, long_) = _with_pass_in_flight(
        model, max_new=(4, 12), ticks=2, steps=1)
    assert eng._in_flight.passes[0] == (8, None, False)
    eng.stop()
    assert eng._in_flight is None and _pages_all_free(eng)
    tokens, stats = short.result(timeout=0)
    assert (tokens, stats["unmask_pass"]) == _want(model, prompts[0], 4, 1)
    with pytest.raises(EngineStoppedError):
        long_.result(timeout=0)
    assert eng.stats["lookahead_drains"] == 1


def test_crash_in_a_prefill_with_a_pass_in_flight_fails_every_future(model):
    """The scheduler dies in an admission's prefill while a pass is in
    flight: every future fails with the cause, none hangs, the pass is
    dropped unread and the page table and the free list agree."""
    eng = _engine(model)
    eng.warmup()
    eng.start()
    try:
        first = eng.submit(_prompt(5), max_new_tokens=48)
        deadline = time.monotonic() + 60
        while eng.stats["lookahead_ticks"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        with chaos.inject(FaultPoints.llm_prefill, fail_first(1),
                          error=RuntimeError("injected prefill fault")):
            second = eng.submit(_prompt(9), max_new_tokens=4)
            for future in (first, second):
                with pytest.raises(RuntimeError, match="injected"):
                    future.result(timeout=60)
    finally:
        eng.stop()
    assert eng._in_flight is None and _pages_all_free(eng)


def test_fail_pending_after_a_crash_drops_the_pass_unread(model):
    eng, _, futures = _with_pass_in_flight(model)
    eng._fail_pending(RuntimeError("boom"))
    assert eng._in_flight is None and _pages_all_free(eng)
    for future in futures:
        with pytest.raises(RuntimeError, match="boom"):
            future.result(timeout=0)
    assert eng.stats["lookahead_drains"] == 0


def test_reclaim_reads_the_pass_in_flight_first(model):
    """An admission that has to evict cached prefix pages drains first:
    the pass in flight is read and committed before any victim is chosen,
    in the iteration's admission part, and the answers are the plain
    reference's."""
    eng = _by_hand(_engine(model, prefix_cache=True, n_pages=6,
                           max_len=32))
    opener = _prompt(17)                            # 3 pages with 3 new
    first = eng.submit(opener, max_new_tokens=3)
    eng._admission_tick()
    while not first.done():
        _tick(eng)
    assert eng._prefix.cached_pages() == 2 and len(eng._free_pages) == 4
    second_p, third_p = _prompt(9), _prompt(20)
    second = eng.submit(second_p, max_new_tokens=3)     # 2 pages
    eng._admission_tick()
    assert _tick(eng) == 1 and eng._in_flight is not None
    third = eng.submit(third_p, max_new_tokens=12)      # 4 pages: evicts
    eng._tick = TickRecord(2, time.perf_counter())
    drains = eng.stats["lookahead_drains"]
    eng._admission_tick()
    assert eng._in_flight is None
    assert eng.stats["lookahead_drains"] == drains + 1
    assert eng._tick.tokens_out == 1 and eng._tick.admit_wait_s > 0.0
    assert eng.stats["prefix_evictions"] >= 1
    while not (second.done() and third.done()):
        _tick(eng)
    for future, prompt, n in ((second, second_p, 3), (third, third_p, 12)):
        tokens, stats = future.result(timeout=0)
        assert (tokens, stats["unmask_pass"]) == _want(model, prompt, n)
    eng.stop()
    assert len(eng._free_pages) + eng._prefix.cached_pages() == eng.n_pages
