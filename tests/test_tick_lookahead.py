"""The paged engine's plain decode tick looks one tick ahead
(serving/paged.py ``_plain_decode_tick``, docs/serving.md "The scheduler's
iteration"): tick k+1 is dispatched from tick k's tokens on the device and
tick k is read while k+1 runs. What must not change is the answer: greedy
streams are those of the full forward, of the dense engine and of the
synchronous tick (kept here as ``_SyncPaged``, the reference), sampled rows
draw the same keys in the same order, a row that meets its end-of-sequence
id loses the token that rode along, and whatever needs the committed state
reads the tick in flight first. CPU-only (Pallas interpret mode)."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos, fail_first
from mlrun_tpu.models import (
    init_params,
    init_permutation_params,
    permutation_pair,
    tiny_llama,
)
from mlrun_tpu.obs import TickRecord, get_tick_log
from mlrun_tpu.serving.llm_batch import (
    ContinuousBatchingEngine,
    EngineStoppedError,
)
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
from tests.greedy import assert_greedy_equal_up_to_tie, greedy_reference

PROMPTS = [[1, 7, 3, 9, 2], [4, 5, 6, 7, 8, 9, 1, 2, 3], [11, 12],
           [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [8, 6, 7, 5, 3, 0, 9]]
LENGTHS = [9, 4, 12, 6, 7]


class _SyncPaged(PagedContinuousBatchingEngine):
    """The tick as it was before the lookahead, the reference: one tick is
    built, dispatched, read and committed inside one iteration."""

    def _plain_decode_tick(self, active) -> int:
        last, _ = self._tick_inputs(active)
        args = (jnp.asarray(last), self._pool, jnp.array(self._page_table),
                jnp.array(self._pos)) + self._sampling_args(active)
        next_token, self._pool, _ = self._decode_paged(self.params, *args)
        tokens_host = np.asarray(next_token)
        for i in active:
            slot = self._slot_state[i]
            token = int(tokens_host[i])
            slot.tokens.append(token)
            slot.remaining -= 1
            self._pos[i] += 1
            if (slot.eos_id is not None and token == slot.eos_id) or \
                    slot.remaining <= 0 or \
                    slot.prompt_len + len(slot.tokens) >= self.max_len:
                self._finish(i)
        self._tick.tokens_out = len(active)
        return len(active)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(setup, kind=PagedContinuousBatchingEngine, **over):
    cfg, params = setup
    kwargs = dict(max_len=64, slots=2, prefill_buckets=(16,), page_size=8,
                  attention_impl="kernel", prefix_cache=False)
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


def _run(eng, requests, **submit_kw):
    """The answers to ``requests`` ((prompt, max_new) pairs), the second
    half submitted while the first is being decoded."""
    eng.start()
    try:
        half = len(requests) // 2
        futures = [eng.submit(p, max_new_tokens=n, **submit_kw)
                   for p, n in requests[:half]]
        futures[0].result(timeout=300)
        futures += [eng.submit(p, max_new_tokens=n, **submit_kw)
                    for p, n in requests[half:]]
        return [f.result(timeout=300)[0] for f in futures], eng.stats
    finally:
        eng.stop()


# -- the answers ---------------------------------------------------------------
@pytest.mark.parametrize("attention_impl", ["kernel", "reference"])
def test_greedy_streams_over_staggered_admissions(setup, attention_impl):
    """Five requests of unequal length through two slots: every answer is
    the synchronous tick's token for token, the full forward's and the
    dense engine's up to a bf16 tie, and every plain tick was either
    dispatched ahead of its predecessor's read or read by a drain."""
    cfg, params = setup
    requests = list(zip(PROMPTS, LENGTHS))
    eng = _engine(setup, attention_impl=attention_impl)
    outs, stats = _run(eng, requests)
    sync, _ = _run(_engine(setup, _SyncPaged,
                           attention_impl=attention_impl), requests)
    dense, _ = _run(ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(16,)), requests)
    assert outs == sync
    for (prompt, n), got, other in zip(requests, outs, dense):
        assert len(got) == n
        assert_greedy_equal_up_to_tie(cfg, params, prompt, got,
                                      greedy_reference(cfg, params,
                                                       prompt, n))
        assert_greedy_equal_up_to_tie(cfg, params, prompt, got, other)
    records = get_tick_log(eng._obs_name).records()
    plain = [r for r in records if r["rows"] and r["kind"] == "plain"]
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] \
        == len(plain)
    assert stats["lookahead_ticks"] == sum(r["lookahead"] for r in records)
    assert stats["lookahead_ticks"] > stats["lookahead_drains"] > 0
    assert _pages_all_free(eng)


def test_end_of_sequence_mid_stream_discards_the_token_that_rode_along(
        setup):
    """A row ends on its end-of-sequence id at tick k: the host learns it
    when k+1 is already under way with the row in it. The answer ends at
    the id, the token of k+1 is thrown away, the row beside it and the
    request admitted into the freed slot (while k+1 is in flight) read as
    if nothing had ridden along, and every page comes back once."""
    cfg, params = setup
    long_a = greedy_reference(cfg, params, PROMPTS[0], 10)
    stop_at = next(i for i in range(2, 10) if long_a[i] not in long_a[:i])
    eng = _by_hand(_engine(setup))
    first = eng.submit(PROMPTS[0], max_new_tokens=10, eos_id=long_a[stop_at])
    beside = eng.submit(PROMPTS[1], max_new_tokens=12)
    after = eng.submit(PROMPTS[2], max_new_tokens=5)
    rode = 0
    for _ in range(40):
        eng._admission_tick()
        before = first.done()
        _tick(eng)
        if first.done() and not before:
            # the tick in flight was dispatched with the ended row in it
            assert eng._in_flight is not None and eng._in_flight.rows == [1]
            assert eng._slot_pages.keys() == {1}
            rode += 1
        if all(f.done() for f in (first, beside, after)):
            break
    assert rode == 1 and eng._in_flight is None
    got, stats = first.result(timeout=0)
    assert got == long_a[:stop_at + 1] and stats["generated"] == stop_at + 1
    assert_greedy_equal_up_to_tie(
        cfg, params, PROMPTS[1], beside.result(timeout=0)[0],
        greedy_reference(cfg, params, PROMPTS[1], 12))
    assert_greedy_equal_up_to_tie(
        cfg, params, PROMPTS[2], after.result(timeout=0)[0],
        greedy_reference(cfg, params, PROMPTS[2], 5))
    assert _pages_all_free(eng)
    assert eng.stats["tokens_out"] == stop_at + 1 + 12 + 5


def test_row_that_ends_at_the_caches_end_is_known_by_count(setup):
    """A prompt and an answer that fill the cache exactly: the last tick is
    known at dispatch by count, so nothing is dispatched behind it, no
    position past the cache is ever written, and the answer is whole."""
    cfg, params = setup
    prompt = [(5 * i + 2) % 89 for i in range(11)]
    eng = _by_hand(_engine(setup, max_len=16, slots=1))
    future = eng.submit(prompt, max_new_tokens=5)
    eng._admission_tick()
    dispatched = []
    while not future.done():
        dispatched.append(_tick(eng))
        assert int(eng._pos.max()) <= 15
    assert dispatched == [1, 1, 1, 1, 0]
    assert_greedy_equal_up_to_tie(cfg, params, prompt,
                                  future.result(timeout=0)[0],
                                  greedy_reference(cfg, params, prompt, 5))
    # by count alone: a row one token short of the cache's end does not
    # outlive the tick in flight, whatever it has left to generate
    slot = dataclasses.replace(eng._slot_state[0], request_id=0,
                               prompt_len=11, tokens=[1, 2, 3, 4],
                               remaining=9)
    assert not eng._outlives_tick(slot)
    slot.tokens = [1, 2, 3]
    assert eng._outlives_tick(slot)
    slot.remaining = 1
    assert not eng._outlives_tick(slot)


@pytest.mark.parametrize("ends_early", [False, True],
                         ids=["whole", "sampled-row-meets-eos"])
def test_sampled_rows_draw_the_synchronous_ticks_keys(setup, ends_early):
    """Sampled and greedy rows side by side under a fixed seed: the streams
    are the synchronous tick's, key for key. Where the only sampled row
    ends on an end-of-sequence id, the tick that rode along drew a key the
    synchronous engine never drew: the engine's key goes back, so the next
    sampled request reads the same either way."""
    def run(kind, eos=None):
        eng = _engine(setup, kind, seed=3)
        eng.start()
        try:
            both = [eng.submit(PROMPTS[0], max_new_tokens=9, eos_id=eos,
                               temperature=0.9, top_k=20),
                    eng.submit(PROMPTS[1], max_new_tokens=7)]
            outs = [f.result(timeout=300)[0] for f in both]
            outs.append(eng.generate(PROMPTS[2], max_new_tokens=6,
                                     temperature=0.7, top_p=0.9)[0])
            return outs
        finally:
            eng.stop()

    eos = None
    if ends_early:
        whole = run(_SyncPaged)[0]
        at = next(i for i in range(2, 8) if whole[i] not in whole[:i])
        eos = whole[at]
    ahead, sync = run(PagedContinuousBatchingEngine, eos), \
        run(_SyncPaged, eos)
    assert ahead == sync
    assert len(ahead[1]) == 7 and len(ahead[2]) == 6
    if ends_early:
        assert ahead[0] == whole[:at + 1]


# -- the order -----------------------------------------------------------------
def test_next_tick_is_dispatched_before_the_last_is_read(setup):
    """A spy on the dispatch and on the read: tick k+1 goes to the device
    before tick k's tokens come to the host, at most one tick is in
    flight behind the one being read, and the last tick is read by a drain
    with nothing behind it."""
    eng = _engine(setup)
    eng.warmup()
    events, names = [], {}
    program, land = eng._decode_paged, eng._land

    def dispatching(*args, **kwargs):
        out = program(*args, **kwargs)
        names[id(out[0])] = len(names)
        events.append(("dispatch", names[id(out[0])]))
        return out

    def landing(ahead, *args):
        events.append(("read", names[id(ahead.next_token)]))
        return land(ahead, *args)

    eng._decode_paged, eng._land = dispatching, landing
    eng.start()
    try:
        tokens, _ = eng.generate(PROMPTS[0], max_new_tokens=6, timeout=120)
        stats = eng.stats
    finally:
        eng.stop()
    assert len(tokens) == 6
    want = [("dispatch", 0)]
    for k in range(4):
        want += [("dispatch", k + 1), ("read", k)]
    assert events == want + [("read", 4)]
    assert stats["lookahead_ticks"] == 4 and stats["lookahead_drains"] == 1


def test_a_lone_request_resolves_without_other_traffic(setup):
    """The last tick of the only request is read by the next iteration,
    not by the next arrival: the future resolves while the engine idles."""
    eng = _engine(setup)
    eng.warmup()
    eng.start()
    try:
        started = time.monotonic()
        tokens, _ = eng.submit(PROMPTS[1], max_new_tokens=4).result(
            timeout=60)
        assert time.monotonic() - started < 60
        assert len(tokens) == 4 and eng._in_flight is None
        assert eng.stats["completed"] == 1
    finally:
        eng.stop()


# -- what drains ---------------------------------------------------------------
def _with_tick_in_flight(setup, max_new=(6, 6), **over):
    eng = _by_hand(_engine(setup, **over))
    futures = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(PROMPTS, max_new)]
    eng._admission_tick()
    assert _tick(eng) == len(futures) and eng._in_flight is not None
    return eng, futures


def test_stop_reads_the_tick_in_flight(setup):
    """stop() with a tick in flight: the request whose last tick it was is
    answered, the other fails as stopped, none is left pending and the
    pages are all back."""
    cfg, params = setup
    eng, (short, long_) = _with_tick_in_flight(setup, max_new=(2, 6))
    eng.stop()
    assert eng._in_flight is None and _pages_all_free(eng)
    assert short.result(timeout=0)[0] \
        == greedy_reference(cfg, params, PROMPTS[0], 2)
    with pytest.raises(EngineStoppedError):
        long_.result(timeout=0)
    assert eng.stats["lookahead_drains"] == 1


def test_crash_with_a_tick_in_flight_fails_every_future(setup):
    """The scheduler dies in an admission's prefill while a tick is in
    flight: every future fails with the cause, none hangs, the tick is
    dropped unread and the page table and the free list agree."""
    eng = _engine(setup)
    eng.warmup()
    eng.start()
    try:
        first = eng.submit(PROMPTS[0], max_new_tokens=40)
        deadline = time.monotonic() + 60
        while eng.stats["lookahead_ticks"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        with chaos.inject(FaultPoints.llm_prefill, fail_first(1),
                          error=RuntimeError("injected prefill fault")):
            second = eng.submit(PROMPTS[1], max_new_tokens=4)
            for future in (first, second):
                with pytest.raises(RuntimeError, match="injected"):
                    future.result(timeout=60)
    finally:
        eng.stop()
    assert eng._in_flight is None and _pages_all_free(eng)


def test_fail_pending_after_a_crash_drops_the_tick_unread(setup):
    eng, futures = _with_tick_in_flight(setup)
    eng._fail_pending(RuntimeError("boom"))
    assert eng._in_flight is None and _pages_all_free(eng)
    for future in futures:
        with pytest.raises(RuntimeError, match="boom"):
            future.result(timeout=0)
    assert eng.stats["lookahead_drains"] == 0


def test_control_op_reads_the_tick_in_flight_first(setup):
    """fetch_prefix reads pool pages on the host: the tick in flight is
    read and committed before it, and the stream goes on as the full
    forward's."""
    cfg, params = setup
    prompt = [(7 * i + 3) % 101 for i in range(19)]     # two full pages
    eng = _by_hand(_engine(setup, prefix_cache=True))
    future = eng.submit(prompt, max_new_tokens=6)
    eng._admission_tick()
    assert _tick(eng) == 1 and eng._in_flight is not None
    fetched = eng.fetch_prefix(prompt)
    eng._tick = TickRecord(1, time.perf_counter())
    eng._control_tick()
    assert eng._in_flight is None and eng.stats["lookahead_drains"] == 1
    assert eng._tick.tokens_out == 1 and eng._tick.admit_wait_s > 0.0
    handoff = fetched.result(timeout=0)
    assert handoff is not None and handoff.prompt_len == 16
    assert len(eng._slot_state[0].tokens) == 2
    while not future.done():
        _tick(eng)
    assert_greedy_equal_up_to_tie(cfg, params, prompt,
                                  future.result(timeout=0)[0],
                                  greedy_reference(cfg, params, prompt, 6))
    eng.stop()
    assert len(eng._free_pages) + eng._prefix.cached_pages() == eng.n_pages


def test_reclaim_reads_the_tick_in_flight_first(setup):
    """An admission that has to evict cached prefix pages drains first:
    the rows that the tick in flight ends give their pages back before
    any victim is chosen."""
    eng = _by_hand(_engine(setup, prefix_cache=True, n_pages=6, slots=2,
                           max_len=32))
    prompt = [(3 * i + 5) % 97 for i in range(17)]      # 3 pages with 4 new
    first = eng.submit(prompt, max_new_tokens=3)
    eng._admission_tick()
    while not first.done():
        _tick(eng)
    assert eng._prefix.cached_pages() == 2 and len(eng._free_pages) == 4
    other = [(11 * i + 1) % 89 for i in range(9)]
    second = eng.submit(other, max_new_tokens=3)        # 2 pages
    eng._admission_tick()
    assert _tick(eng) == 1 and eng._in_flight is not None
    third = eng.submit([(13 * i + 2) % 83 for i in range(20)],
                       max_new_tokens=12)               # 4 pages: evicts
    eng._tick = TickRecord(2, time.perf_counter())
    drains = eng.stats["lookahead_drains"]
    eng._admission_tick()
    assert eng._in_flight is None
    assert eng.stats["lookahead_drains"] == drains + 1
    assert eng._tick.tokens_out == 1
    assert eng.stats["prefix_evictions"] >= 1
    while not (second.done() and third.done()):
        _tick(eng)
    assert len(second.result(timeout=0)[0]) == 3
    assert len(third.result(timeout=0)[0]) == 12
    eng.stop()


def test_speculative_round_after_a_plain_tick_drains():
    """Plain ticks (the verify fault parks three rounds) and speculative
    rounds alternate: a round drafts from committed tokens, so the plain
    tick in flight is read before it; the stream is the plain engine's
    and the two counters add up to the plain ticks run."""
    cfg = dataclasses.replace(tiny_llama(attention_impl="reference"),
                              vocab_size=64, tie_embeddings=False)
    target_perm, draft_perm = permutation_pair(cfg.vocab_size, overlap=0.7)
    target = init_permutation_params(cfg, target_perm)
    draft = init_permutation_params(cfg, draft_perm)
    prompt = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1, 2]
    kwargs = dict(max_len=64, slots=2, prefill_buckets=(16,), page_size=8)
    plain = PagedContinuousBatchingEngine(cfg, target, **kwargs)
    try:
        expect, _ = plain.generate(prompt, max_new_tokens=12)
    finally:
        plain.stop()
    eng = PagedContinuousBatchingEngine(
        cfg, target, speculative={"enabled": True, "k": 4,
                                  "draft_config": cfg,
                                  "draft_params": draft}, **kwargs)
    try:
        with chaos.inject(FaultPoints.llm_spec_verify, fail_first(3),
                          error=RuntimeError("injected verify fault")):
            out, _ = eng.generate(prompt, max_new_tokens=12)
        stats = eng.stats
    finally:
        eng.stop()
    assert out == expect
    records = get_tick_log(eng._obs_name).records()
    plain_ticks = [r for r in records if r["kind"] == "plain" and r["rows"]]
    assert len(plain_ticks) == 3 and stats["spec_rounds"] > 0
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] == 3
    assert stats["lookahead_drains"] >= 1
    assert sum(r["tokens_out"] for r in records) == 12 - 1
