"""The scheduler loop's account of its own wall time (obs/ticklog.py; docs/
observability.md "Tick log"): the records close over it, a stalled
iteration leaves a record, a request names its iterations, and the
benchmark's readers over all of it (benchmarks/harness/readers_loop.py).
CPU-only (Pallas interpret mode), tier-1-fast."""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos
from mlrun_tpu.chaos import registry as chaos_registry
from mlrun_tpu.models import init_params, tiny_llama, tiny_sdar
from mlrun_tpu.obs import (
    TickLog,
    TickRecord,
    get_flight_recorder,
    get_tick_log,
    ticklog,
)
from mlrun_tpu.obs.stats import nearest_rank
from mlrun_tpu.serving import llm_batch, paged
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

PROMPTS = [[1, 7, 3, 9, 2], [4, 5, 6, 7, 8, 9, 1, 2, 3], [11, 12],
           [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]]
LONG = list(range(1, 30))
NEW_FIELDS = ("gap_s", "inflight_wait_s", "prefill_wait_s", "dry_s",
              "after_prefill_s", "admissions", "cpu_s", "gc_s", "gc_gen",
              "nivcsw", "majflt", "idle_s", "cpu_span_s")


@pytest.fixture(scope="module")
def dense():
    cfg = tiny_llama(attention_impl="reference")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def block():
    cfg = tiny_sdar(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, **over):
    cfg, params = model
    kwargs = dict(max_len=64, slots=2, prefill_buckets=(16, 32), page_size=8,
                  attention_impl="kernel", prefix_cache=False)
    kwargs.update(over)
    return PagedContinuousBatchingEngine(cfg, params, **kwargs)


def _identities(records):
    """The four identities of a run's records, oldest first."""
    for r in records:
        loop_s = r["t1"] - r["t0"]
        device_wait = r["t_fetched"] - r["t_dispatched"] + r["admit_wait_s"]
        assert r["inflight_wait_s"] >= 0.0 and r["prefill_wait_s"] >= 0.0
        assert r["inflight_wait_s"] + r["prefill_wait_s"] \
            <= r["admit_wait_s"] + 1e-9, r
        assert 0.0 <= r["dry_s"] <= r["gap_s"] + loop_s - device_wait + 1e-9, r
        assert 0.0 <= r["cpu_s"] <= r["cpu_span_s"] + 1e-3, r
        assert 0.0 <= r["idle_s"] <= r["gap_s"] and r["after_prefill_s"] >= 0.0
    assert records[0]["gap_s"] == 0.0
    rest = records[1:]
    assert sum(r["gap_s"] + r["t1"] - r["t0"] for r in rest) \
        == pytest.approx(records[-1]["t1"] - records[0]["t1"], abs=1e-9)
    assert all(a["t1"] + b["gap_s"] == pytest.approx(b["t0"], abs=1e-9)
               for a, b in zip(records, rest))
    # a reading of the thread's clock covers the spans of the records since
    # the one before it; the first record and one after idle polls start a
    # reading at their end (what preceded the polls is carried)
    covered = 0.0
    for i, r in enumerate(records):
        if i == 0 or r["idle_s"] > 0:
            assert r["cpu_s"] == r["cpu_span_s"] == 0.0
            covered += r["gap_s"] - r["idle_s"]
            continue
        covered += r["gap_s"] + r["t1"] - r["t0"]
        if r["cpu_span_s"]:
            assert r["cpu_span_s"] == pytest.approx(covered, abs=1e-9)
            covered = 0.0
        else:
            assert r["cpu_s"] == 0.0 and r["nivcsw"] == r["majflt"] == 0


# -- (a) the identities, under a fake clock and on drained engines ------------
class _Clock:
    """``time`` for the scheduler's modules: every reading of the wall
    clock moves it on by a millisecond and of the thread's clock by a tenth
    of one, ``sleep`` and a planted delay move the wall clock alone."""

    def __init__(self):
        self.now, self.cpu = 1000.0, 5.0

    def perf_counter(self):
        self.now += 0.001
        return self.now

    def thread_time(self):
        self.cpu += 0.0001
        return self.cpu

    def sleep(self, seconds):
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def clock(monkeypatch):
    fake = _Clock()
    for module in (llm_batch, paged, chaos_registry):
        monkeypatch.setattr(module, "time", fake)
    return fake


def _drive(eng, clock, futures, limit=400):
    """The loop by hand, on this thread, until the futures are done and the
    tick in flight is read."""
    for _ in range(limit):
        if all(f.done() for f in futures) and eng._in_flight is None:
            return
        eng._iterate(clock.perf_counter())
    raise AssertionError("the engine did not drain")


def _by_hand(model, clock, prompts=PROMPTS, new=6, **over):
    eng = _engine(model, **over)
    eng.start = lambda: None
    futures = [eng.submit(p, max_new_tokens=new) for p in prompts]
    _drive(eng, clock, futures)
    return eng, get_tick_log(eng._obs_name).records()


def test_identities_hold_under_a_fake_clock(dense, clock):
    eng, records = _by_hand(dense, clock, new=40)
    _identities(records)
    # every reading is a millisecond: the sums are whole readings
    assert sum(r["admissions"] for r in records) == len(PROMPTS)
    # the thread's clock is read every CPU_EVERY records after the first,
    # which starts the reading: nowhere else in a run with no long record
    every = ticklog.CPU_EVERY
    assert len(records) > 2 * every
    assert max(r["gap_s"] + r["t1"] - r["t0"] for r in records) \
        < ticklog.STALL_FLOOR_S
    read = [i for i, r in enumerate(records) if r["cpu_span_s"]]
    assert read == list(range(every, len(records), every))
    assert all(records[i]["cpu_s"] == pytest.approx(0.0001) for i in read)
    assert clock.cpu == pytest.approx(5.0 + 0.0001 * (1 + len(read)))
    admitted = [r for r in records if r["admissions"]]
    assert all(r["prefill_wait_s"] > 0 and r["after_prefill_s"] > 0
               and r["dry_s"] > 0 for r in admitted)
    stats = eng.stats
    loop = sum(r["t1"] - r["t0"] for r in records)
    assert stats["sched_cpu_share"] == pytest.approx(
        sum(r["cpu_s"] for r in records)
        / sum(r["cpu_span_s"] for r in records))
    assert stats["device_dry_share"] == pytest.approx(
        sum(r["dry_s"] for r in records)
        / (loop + sum(r["gap_s"] for r in records)))
    assert stats["sched_stalls"] == 0 and stats["sched_stall_s_max"] == 0.0


def _serve(eng, prompts, new=6):
    """The prompts through a started engine, to the end; the engine stopped."""
    eng.start()
    try:
        for future in [eng.submit(p, max_new_tokens=new) for p in prompts]:
            future.result(timeout=300)
    finally:
        eng.stop()
    return eng


def _run_chunked(dense):
    eng = _serve(_engine(dense, prefill_chunk=8), PROMPTS + [LONG])
    assert eng.stats["prefill_chunks"] > len(PROMPTS) + 1
    return eng


def _run_block(block):
    size = block[0].block_length
    return _serve(_engine(block, denoising_steps=2),
                  [p[:len(p) - len(p) % size] or p + p for p in PROMPTS],
                  new=2 * size)


def _run_drained_for_an_export(dense):
    """A prefill-only request while another decodes: the export reads the
    pool, so the tick in flight is drained inside admission."""
    eng = _engine(dense, prefix_cache=True)
    eng.start()
    try:
        first = eng.submit(PROMPTS[1], max_new_tokens=54)
        limit = time.monotonic() + 120
        while len(get_tick_log(eng._obs_name)) < 3:
            assert time.monotonic() < limit
            time.sleep(0.001)
        eng.submit_prefill(PROMPTS[3]).result(timeout=300)
        first.result(timeout=300)
    finally:
        eng.stop()
    assert eng.stats["lookahead_drains"] >= 2
    return eng


@pytest.mark.parametrize("scenario", ["plain", "chunked", "block", "drain"])
def test_identities_hold_on_a_drained_engine(dense, block, scenario):
    eng = {"plain": lambda: _serve(_engine(dense), PROMPTS),
           "chunked": lambda: _run_chunked(dense),
           "block": lambda: _run_block(block),
           "drain": lambda: _run_drained_for_an_export(dense)}[scenario]()
    records = get_tick_log(eng._obs_name).records()
    _identities(records)
    assert sum(r["admissions"] for r in records) == eng.stats["requests"]
    waited = sum(r["inflight_wait_s"] for r in records)
    fetched_first = sum(r["prefill_wait_s"] for r in records)
    if scenario == "block":
        # an admission of a block model fetches no token
        assert fetched_first == 0.0
        assert all(r["after_prefill_s"] == 0.0 for r in records)
    else:
        assert fetched_first > 0.0
    if scenario == "drain":
        assert waited > 0.0
    stats = eng.stats
    assert 0.0 <= stats["device_dry_share"] <= 1.0
    assert 0.0 <= stats.get("sched_cpu_share", 0.0) <= 1.0 + 1e-3


# -- (b) what a host delay moves ----------------------------------------------
def _delayed(engine, name, clock, seconds, when=lambda: True):
    inner = getattr(engine, name)

    def late(*args, **kwargs):
        if when():
            clock.sleep(seconds)
        return inner(*args, **kwargs)

    setattr(engine, name, late)


@pytest.mark.parametrize("where, dry, after", [
    # between the first token's fetch and the insert's enqueue
    ("_complete_storage", 1, 1),
    # between the insert and the next tick's dispatch
    ("_activate_slot", 0, 1),
    # in the commit of a tick while the next one is in flight
    ("_commit_tokens", 0, 0)])
def test_a_host_delay_moves_what_it_should(dense, monkeypatch, where, dry,
                                           after):
    sums = []
    for delay in (0.0, 0.5):
        fake = _Clock()
        for module in (llm_batch, paged):
            monkeypatch.setattr(module, "time", fake)
        eng = _engine(dense)
        eng.start = lambda: None
        _delayed(eng, where, fake, delay,
                 when=lambda: where != "_commit_tokens"
                 or eng._in_flight is not None)
        futures = [eng.submit(PROMPTS[0], max_new_tokens=6)]
        _drive(eng, fake, futures)
        records = get_tick_log(eng._obs_name).records()
        _identities(records)
        sums.append((sum(r["dry_s"] for r in records),
                     sum(r["after_prefill_s"] for r in records),
                     sum(r["t1"] - r["t0"] for r in records)))
    (dry0, after0, loop0), (dry1, after1, loop1) = sums
    # one admission, so one delay where it is in an admission; a tick a
    # token after the first, all but the last with one in flight behind it
    delays = 1 if where != "_commit_tokens" else 4
    assert loop1 - loop0 == pytest.approx(0.5 * delays)
    assert dry1 - dry0 == pytest.approx(0.5 * dry)
    assert after1 - after0 == pytest.approx(0.5 * after)


# -- (c) a stalled iteration leaves a record ----------------------------------
def _stalls(eng):
    return [e for e in get_flight_recorder().events(kind="sched.stall")
            if e["engine"] == eng._obs_name]


def test_a_planted_delay_leaves_one_stall_record(dense, clock):
    eng = _engine(dense)
    eng.start = lambda: None
    future = eng.submit(PROMPTS[1], max_new_tokens=50)
    while len(get_tick_log(eng._obs_name)) < ticklog.STALL_LEAST + 4:
        eng._iterate(clock.perf_counter())
    assert not _stalls(eng)
    with chaos.inject(FaultPoints.fleet_degrade, delay=0.3,
                      match=lambda ctx: ctx["engine"] == eng._obs_name):
        eng._iterate(clock.perf_counter())
    _drive(eng, clock, [future])
    (stall,) = _stalls(eng)
    assert stall["phase"] == "admit"
    assert stall["off_cpu"] == pytest.approx(0.3, abs=0.01)
    assert stall["span_s"] == pytest.approx(
        stall["admit_wait"] + stall["fetch_wait"] + stall["cpu"]
        + stall["off_cpu"])
    assert stall["record"]["n"] == stall["n"]
    assert stall["record"]["t1"] - stall["record"]["t0"] > 0.3
    # a record that long reads the thread's clock whenever the last reading
    # was: the reading covers the stall and the iterations since
    assert stall["cpu_span"] >= stall["span_s"] and stall["cpu"] > 0.0
    stats = eng.stats
    assert stats["sched_stalls"] == 1
    assert stats["sched_stall_s_max"] == pytest.approx(stall["span_s"])
    assert "sched_stalls" in eng._COUNTER_STATS


def test_an_undisturbed_run_leaves_no_stall_record(dense, clock):
    eng, records = _by_hand(dense, clock, prompts=[PROMPTS[1]], new=50)
    assert len([r for r in records if r["rows"]]) > ticklog.STALL_LEAST
    assert not _stalls(eng) and eng.stats["sched_stalls"] == 0


def _poll_idle(eng, clock, seconds):
    """The loop with nothing to do, as ``_loop`` runs it: a poll, 2 ms of
    sleep."""
    until = clock.now + seconds
    while clock.now < until:
        assert eng._iterate(clock.perf_counter()) == 0
        clock.sleep(0.002)


def test_a_quiet_spell_is_no_stall(dense, clock):
    """A request that arrives after the loop had nothing to do for half a
    second: its record's gap holds the spell, its span does not."""
    eng, before = _by_hand(dense, clock, prompts=[PROMPTS[1]], new=40)
    assert len([r for r in before if r["rows"]]) > ticklog.STALL_LEAST
    cpu_reads = clock.cpu
    _poll_idle(eng, clock, 0.5)
    # the first poll cut the thread's reading short, the others read nothing
    assert clock.cpu == pytest.approx(cpu_reads + 0.0001)
    _drive(eng, clock, [eng.submit(PROMPTS[0], max_new_tokens=40)])
    records = get_tick_log(eng._obs_name).records()
    _identities(records)
    (woken,) = [r for r in records if r["idle_s"]]
    assert woken["n"] > before[-1]["n"] and woken["admissions"] == 1
    assert 0.49 < woken["idle_s"] < woken["gap_s"] < woken["idle_s"] + 0.01
    assert not _stalls(eng)
    stats = eng.stats
    assert stats["sched_stalls"] == 0 and stats["sched_stall_s_max"] == 0.0
    parts = ticklog.stall_parts(woken)
    assert parts["span_s"] == pytest.approx(
        woken["gap_s"] - woken["idle_s"] + woken["t1"] - woken["t0"])
    assert parts["span_s"] < ticklog.STALL_FLOOR_S and parts["phase"] != "gap"
    # what the cut reading found is in the next one, with its seconds
    later = [r for r in records if r["n"] > woken["n"] and r["cpu_span_s"]]
    assert later[0]["cpu_s"] == pytest.approx(0.0002)
    # the benchmark's longest iteration leaves the spell out too
    from benchmarks.harness import readers_loop

    ctx = {"ticks": records, "finished": [
        {"sent": records[0]["t0"] - 1.0, "done": records[-1]["t1"]}]}
    assert readers_loop.iteration_max(ctx, "span") \
        < 1e3 * ticklog.STALL_FLOOR_S


def test_a_stall_behind_a_quiet_spell_is_still_one(dense, clock):
    eng, _ = _by_hand(dense, clock, prompts=[PROMPTS[1]], new=40)
    _poll_idle(eng, clock, 0.5)
    future = eng.submit(PROMPTS[0], max_new_tokens=4)
    with chaos.inject(FaultPoints.fleet_degrade, delay=0.3,
                      match=lambda ctx: ctx["engine"] == eng._obs_name):
        eng._iterate(clock.perf_counter())
    _drive(eng, clock, [future])
    (stall,) = _stalls(eng)
    assert stall["phase"] == "admit" and stall["record"]["idle_s"] > 0.49
    assert 0.3 < stall["span_s"] < 0.4
    # the record after a poll starts a reading of the thread's clock: it
    # holds none, and everything but the waits reads as off the CPU
    assert stall["cpu"] == stall["cpu_span"] == 0.0
    assert stall["off_cpu"] == pytest.approx(
        stall["span_s"] - stall["admit_wait"] - stall["fetch_wait"])


def _looped(n, loop_s, gap_s=0.0, rows=1):
    record = TickRecord(n, 10.0 * n)
    record.admitted(record.t0)
    record.t1 = record.t0 + loop_s
    record.gap_s, record.rows = gap_s, rows
    return record


def test_the_log_declares_a_stall_by_its_own_median():
    log = TickLog()
    # nothing is a stall before the ring holds STALL_LEAST records with rows
    assert not any(log.append(_looped(n, 5.0))
                   for n in range(ticklog.STALL_LEAST - 1))
    assert not log.append(_looped(100, 0.02))
    # the median is 5 s: the floor and eight medians both have to be passed
    assert not log.append(_looped(101, 39.0))
    assert log.append(_looped(102, 20.0, gap_s=21.0))
    assert not log.append(_looped(103, 0.5, rows=0))
    quick = TickLog()
    for n in range(ticklog.STALL_LEAST):
        quick.append(_looped(n, 0.002))
    # eight medians are 16 ms: the floor of 0.1 s decides
    assert not quick.append(_looped(200, 0.09))
    assert quick.append(_looped(201, 0.11))
    assert quick.append(_looped(202, 0.05, gap_s=0.06, rows=0))
    # the wait for an iteration's own prefills is work, and the seconds in
    # which the loop had nothing to do are nobody's
    admitting = _looped(203, 0.25)
    admitting.admissions, admitting.prefill_wait_s = 2, 0.16
    assert not quick.append(admitting)
    admitting = _looped(204, 0.25)
    admitting.admissions, admitting.prefill_wait_s = 2, 0.14
    assert quick.append(admitting)
    woken = _looped(205, 0.05, gap_s=3.0)
    woken.idle_s = 2.96
    assert not quick.append(woken)
    woken = _looped(206, 0.05, gap_s=3.0)
    woken.idle_s = 2.94
    assert quick.append(woken)
    # the median is taken anew after as many appends as it had samples (32,
    # then 64, 128, ...), every STALL_WINDOW records at most, not sooner
    slow = TickLog()
    for n in range(ticklog.STALL_LEAST):
        slow.append(_looped(n, 0.002))
    for n in range(ticklog.STALL_LEAST - 2):
        assert not slow.append(_looped(100 + n, 0.05))
    assert slow.append(_looped(200, 0.11))          # still the floor
    # 64 samples now, half of them 50 ms: 8 x 26 ms from here
    assert not slow.append(_looped(201, 0.11))
    assert slow.append(_looped(202, 0.21))
    assert slow._refresh_in == 2 * ticklog.STALL_LEAST - 1
    for n in range(3 * ticklog.STALL_WINDOW):
        slow.append(_looped(1000 + n, 0.001))
    assert slow._refresh_in <= ticklog.STALL_WINDOW
    assert slow.append(_looped(5000, 0.11))         # the floor again


def test_summary_and_latest_follow_the_ring():
    log = TickLog(size=3)
    for n in range(5):
        record = _looped(n, 1.0 + n, gap_s=0.5, rows=n % 2)
        record.t_admit = record.t0 + 0.25
        record.cpu_s, record.cpu_span_s, record.dry_s = 0.5, 2.0 * n, 0.25
        log.append(record)
    summary = log.summary()             # records 2, 3, 4: loops 3, 4, 5
    assert summary["sched_cpu_share"] == pytest.approx(1.5 / 18.0)
    assert summary["device_dry_share"] == pytest.approx(0.75 / 13.5)
    assert log.latest(8) == ([4.0], [3.75])
    assert "sched_cpu_share" not in TickLog().summary()
    unread = TickLog()
    unread.append(_looped(0, 1.0))      # no reading of the thread's clock yet
    assert "sched_cpu_share" not in unread.summary()
    assert unread.summary()["device_dry_share"] == 0.0
    fields = TickRecord().as_dict()
    assert all(fields[name] == 0 for name in NEW_FIELDS)
    assert tuple(fields)[-len(NEW_FIELDS):] == NEW_FIELDS


def test_stats_percentiles_read_the_tick_log(dense):
    """One source: the inter-token and decode-tick percentiles are those of
    the log's newest ``latency_window`` records with rows."""
    cfg, params = dense
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(16,), page_size=8,
        prefix_cache=False, latency_window=5)
    eng.start()
    try:
        eng.submit(PROMPTS[0], max_new_tokens=12).result(timeout=300)
    finally:
        eng.stop()
    stats = eng.stats
    newest = [r for r in get_tick_log(eng._obs_name).records()
              if r["rows"]][-5:]
    assert len(newest) == 5
    loops = sorted(r["t1"] - r["t0"] for r in newest)
    ticks = sorted(r["t1"] - r["t_admit"] for r in newest)
    assert stats["itl_p50_s"] == nearest_rank(loops, 0.50)
    assert stats["itl_p95_s"] == nearest_rank(loops, 0.95)
    assert stats["decode_tick_p50_s"] == nearest_rank(ticks, 0.50)
    assert stats["decode_tick_p95_s"] == nearest_rank(ticks, 0.95)
    assert not hasattr(eng, "_itl_ring") and not hasattr(eng, "_tick_ring")


# -- (d) the collector's seconds ----------------------------------------------
def test_a_collection_of_another_thread_lands_in_a_record(dense):
    watchers = ticklog._gc_watchers
    eng = _engine(dense)
    eng.start()
    try:
        assert ticklog._on_gc in gc.callbacks
        future = eng.submit(PROMPTS[1], max_new_tokens=50)
        limit = time.monotonic() + 120
        while len(get_tick_log(eng._obs_name)) < 3:
            assert time.monotonic() < limit
            time.sleep(0.001)
        assert threading.current_thread() is not eng._thread
        gc.collect()
        future.result(timeout=300)
    finally:
        eng.stop()
        eng.stop()                      # a second stop takes no watcher away
    records = get_tick_log(eng._obs_name).records()
    collected = [r for r in records if r["gc_s"] > 0]
    assert collected and max(r["gc_gen"] for r in collected) == 2
    assert all(r["gc_s"] <= r["gap_s"] + r["t1"] - r["t0"] + 1e-6
               for r in collected)
    assert ticklog._gc_watchers == watchers
    if not watchers:
        assert ticklog._on_gc not in gc.callbacks


def test_the_gc_watch_counts_its_watchers():
    before = ticklog.gc_sums()
    watchers = ticklog._gc_watchers
    ticklog.watch_gc()
    ticklog.watch_gc()
    try:
        assert gc.callbacks.count(ticklog._on_gc) == 1
        gc.collect(1)
        after = ticklog.gc_sums()
        assert after[0] > before[0] and after[2] == before[2] + 1
        ticklog.unwatch_gc()
        assert ticklog._on_gc in gc.callbacks
    finally:
        ticklog.unwatch_gc()
    assert ticklog._gc_watchers == watchers
    assert (ticklog._on_gc in gc.callbacks) == bool(watchers)
    switches, faults = ticklog.thread_usage()
    assert switches >= 0 and faults >= 0


# -- (e) a request names its iterations ---------------------------------------
def test_a_request_names_the_iterations_that_served_it(dense):
    eng = _engine(dense, request_ledger=True, prefill_chunk=8)
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=7) for p in (LONG, PROMPTS[0])]
        results = [f.result(timeout=300) for f in futures]
    finally:
        eng.stop()
    records = {r["n"]: r for r in get_tick_log(eng._obs_name).records()}
    for (tokens, stats), prompt in zip(results, (LONG, PROMPTS[0])):
        timing = stats["timing"]
        first, last = timing["tick_first"], timing["tick_last"]
        assert first <= last and first in records and last in records
        served = [r for n, r in records.items() if first <= n <= last]
        # its prompt's chunks and the ticks that yielded its tokens (the
        # first comes from the prefill) lie between the two
        assert sum(r["prefill_dispatches"] for r in served) \
            >= timing["prefill_chunks"] == -(-len(prompt) // 8)
        assert sum(r["tokens_out"] > 0 for r in served) >= len(tokens) - 1
        assert records[first]["prefill_tokens"] > 0
        assert records[last]["tokens_out"] > 0


# -- (f) the benchmark's readers over handed-in records -----------------------
def _record(n, t0, gap=0.0, admit=0.0, inflight=0.0, prefill=0.0, dry=0.0,
            after=0.0, admissions=0, cpu=0.0, cpu_span=0.0, idle=0.0,
            device=0.025, host=0.003):
    built = t0 + admit + host / 3
    return {"n": n, "t0": t0, "t_admit": t0 + admit, "t_built": built,
            "t_dispatched": built + host / 3,
            "t_fetched": built + host / 3 + device,
            "t1": built + 2 * host / 3 + device,
            "admit_wait_s": inflight + prefill, "rows": 32, "kind": "plain",
            "prefill_tokens": 200 * admissions, "lookahead": 1,
            "gap_s": gap, "inflight_wait_s": inflight,
            "prefill_wait_s": prefill, "dry_s": dry,
            "after_prefill_s": after, "admissions": admissions, "cpu_s": cpu,
            "gc_s": 0.0, "gc_gen": 0, "nivcsw": 0, "majflt": 0,
            "idle_s": idle, "cpu_span_s": cpu_span}


RECORDS = [
    _record(0, 100.0, gap=3.0),                 # its gap starts before
    _record(1, 100.1, gap=0.072, admit=0.06, inflight=0.012, prefill=0.02,
            dry=0.004, after=0.006, admissions=2, cpu=0.02, cpu_span=0.16),
    _record(2, 100.3, gap=0.112),
    # three seconds of idle polls before it: no part of its span
    _record(3, 103.4, gap=3.072, idle=3.0, admit=0.5, prefill=0.02,
            dry=0.002, after=0.003, admissions=1, cpu=0.01, cpu_span=0.64),
    _record(4, 109.99, gap=9.0)]                # ends outside the window
LOOPS = 0.088 + 0.028 + 0.528
PARENT = [{k: v for k, v in r.items() if k not in NEW_FIELDS}
          for r in RECORDS]


def _ctx(ticks):
    # a traced interval that holds none of the records: these readers read
    # the whole window whatever it says
    return {"ticks": ticks, "traced": [108.0, 109.0],
            "finished": [{"sent": 99.0, "done": 105.0},
                         {"sent": 101.0, "done": 110.0}]}


@pytest.mark.parametrize("reader, args, expected", [
    ("device_dry_share", {}, 100 * 0.006 / 11.0),
    ("after_prefill_ms", {}, 1e3 * 0.009 / 3),
    ("loop_share", {"part": "admit_own"}, 100 * (0.56 - 0.012) / LOOPS),
    ("loop_share", {"part": "cpu"}, 100 * 0.03 / 0.8),
    ("iteration_max", {"part": "span"}, 1e3 * (0.072 + 0.528)),
    ("iteration_max", {"part": "host"}, 1e3 * (0.6 - 0.025 - 0.02)),
])
@pytest.mark.parametrize("ticks", ["ours", "parent"])
def test_loop_readers_on_handed_in_records(reader, args, expected, ticks,
                                           capsys):
    from benchmarks.harness import readers_loop

    read = getattr(readers_loop, reader)
    if ticks == "parent":
        # records without the new fields, and none at all: nothing to read
        assert read(_ctx(PARENT), **args) is None
        assert read(_ctx([]), **args) is None
        assert read({"ticks": RECORDS}, **args) is None
        return
    assert read(_ctx(RECORDS), **args) == pytest.approx(expected)
    said = capsys.readouterr().err
    if args.get("part") == "span":
        assert "[bench] longest iteration" in said
        assert '"n": 3' in said and '"phase": "admit"' in said
    else:
        assert not said


def test_loop_readers_find_the_engines_own_log(dense):
    from benchmarks.harness import readers_loop

    sent = time.perf_counter()
    # long enough for a reading of the thread's clock to fall due
    _serve(_engine(dense), PROMPTS, new=40)
    ctx = {"finished": [{"sent": sent, "done": time.perf_counter()}],
           "traced": [0.0, 1.0]}
    assert 0.0 <= readers_loop.device_dry_share(ctx) < 100.0
    assert readers_loop.after_prefill_ms(ctx) > 0.0
    assert 0.0 < readers_loop.loop_share(ctx, "admit_own") < 100.0
    assert 0.0 < readers_loop.loop_share(ctx, "cpu") <= 100.1
    assert readers_loop.iteration_max(ctx, "host") \
        <= readers_loop.iteration_max(ctx, "span")
