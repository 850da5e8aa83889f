"""The scheduler's tick log (obs/ticklog.py), its spans in the profiler's
trace, the stable names of the jitted steps, the span clock, and the
benchmark's readers over them (benchmarks/harness/readers_ticks.py).
CPU-only (Pallas interpret mode), tier-1-fast."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.obs import TickLog, TickRecord, get_tick_log, tick_logs
from mlrun_tpu.obs import ticklog, tracing
from mlrun_tpu.serving.llm import init_kv_cache
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

PROMPTS = [[1, 7, 3, 9, 2], [4, 5, 6, 7, 8, 9, 1, 2, 3], [11, 12],
           [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]]
ORDERED = ("t0", "t_admit", "t_built", "t_dispatched", "t_fetched", "t1")
SCHED_CHILDREN = ("mlt.sched.admit", "mlt.sched.build", "mlt.sched.dispatch",
                  "mlt.sched.fetch", "mlt.sched.commit")
# an admission's own parts, siblings of those; admit (expiry, control)
# closes before the first claim, and a claim follows every admission: the
# next request's, or the one that finds none
ADMISSION = ("mlt.sched.prefill", "mlt.sched.first_token",
             "mlt.sched.insert", "mlt.sched.activate", "mlt.sched.claim")


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(setup, **over):
    cfg, params = setup
    kwargs = dict(max_len=64, slots=2, prefill_buckets=(16,), page_size=8,
                  attention_impl="kernel", prefix_cache=False)
    kwargs.update(over)
    return PagedContinuousBatchingEngine(cfg, params, **kwargs)


# -- the log against what the engine did --------------------------------------
def test_tick_log_identities_and_survives_stop(setup):
    """Over a drained run: every dispatch is a record with rows, every
    token a tick yielded is in some record's ``tokens_out`` (the iteration
    that committed it, one after the one that dispatched it), and with no
    end-of-sequence id met no row rode along unseen, so the rows dispatched
    are the tokens committed."""
    eng = _engine(setup)
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
        outs = [f.result(timeout=300)[0] for f in futures]
    finally:
        eng.stop()
    name, stats = eng._obs_name, eng.stats
    del eng
    records = get_tick_log(name).records()      # readable once it is gone
    assert all(len(tokens) == 6 for tokens in outs)
    decoding = [r for r in records if r["rows"]]
    assert len(decoding) == stats["attn_kernel_ticks"]
    # the first token of a request comes from its prefill
    assert sum(r["tokens_out"] for r in records) \
        == stats["tokens_out"] - stats["completed"]
    assert sum(r["rows"] for r in records) \
        == sum(r["tokens_out"] for r in records)
    assert sum(r["prefill_tokens"] for r in records) \
        == sum(len(p) for p in PROMPTS)
    assert sum(r["lookahead"] for r in records) == stats["lookahead_ticks"]
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] \
        == len(decoding)
    for r in records:
        times = [r[key] for key in ORDERED]
        assert times == sorted(times), r
        assert 0.0 <= r["admit_wait_s"] <= r["t_admit"] - r["t0"]
        assert r["kind"] == "plain"
        assert r["lookahead"] in (0, 1) and r["lookahead"] <= r["rows"]
    assert [r["n"] for r in records] == sorted({r["n"] for r in records})
    assert all(a["t1"] <= b["t0"] for a, b in zip(records, records[1:]))
    assert 1.0 <= stats["tick_rows_mean"] <= 2.0
    assert 0.0 <= stats["tick_admit_share"] <= 1.0
    assert 0.0 <= stats["tick_host_share"] <= 1.0


def test_denoise_records_under_the_lookahead():
    """A block-diffusion model's passes look one pass ahead too: a
    ``denoise`` record holds the rows, positions, context and expert
    counters of the pass its iteration dispatched and, as ``tokens_out``,
    what the pass dispatched before it unmasked. Over a drained run whose
    answers end on a block's edge: every dispatch is a record with rows,
    ``sum(lookahead)`` is ``lookahead_ticks``, the two counters add up to
    the dispatches, and the positions unmasked are the tokens returned."""
    from mlrun_tpu.models import tiny_sdar

    cfg = tiny_sdar(dtype=jnp.float32)
    eng = PagedContinuousBatchingEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), max_len=64, slots=2,
        prefill_buckets=(16,), page_size=8, attention_impl="kernel",
        prefix_cache=False, denoising_steps=2)
    block = cfg.block_length
    prompts = [p[:len(p) - len(p) % block] or p + p for p in PROMPTS]
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=2 * block) for p in prompts]
        outs = [f.result(timeout=300)[0] for f in futures]
    finally:
        eng.stop()
    stats = eng.stats
    records = get_tick_log(eng._obs_name).records()
    assert all(len(tokens) == 2 * block for tokens in outs)
    passes = [r for r in records if r["rows"]]
    assert all(r["kind"] == "denoise" for r in records)
    assert sum(r["lookahead"] for r in records) == stats["lookahead_ticks"]
    assert stats["lookahead_ticks"] + stats["lookahead_drains"] \
        == len(passes)
    assert stats["lookahead_ticks"] > stats["lookahead_drains"] > 0
    assert sum(r["tokens_out"] for r in records) == stats["tokens_out"] \
        == sum(len(tokens) for tokens in outs)
    # two denoising passes and a commit a block, two blocks a request
    assert sum(r["rows"] for r in records) == 6 * len(prompts)
    assert sum(r["commit_rows"] for r in records) == 2 * len(prompts)
    for r in records:
        times = [r[key] for key in ORDERED]
        assert times == sorted(times), r
        assert r["lookahead"] in (0, 1) and r["lookahead"] <= r["rows"]
        assert r["positions"] == r["rows"] * block
        assert r["expert_pairs"] \
            == r["positions"] * cfg.top_k * cfg.n_layers
        assert (r["experts_touched"] > 0) == (r["rows"] > 0)
    assert stats["expert_pairs"] == sum(r["expert_pairs"] for r in records)
    assert all(a["t1"] <= b["t0"] for a, b in zip(records, records[1:]))


def test_tick_ctx_tokens_are_the_slots_lengths(setup):
    """Ticks driven by hand: a tick's rows are the live slots that the
    tick in flight does not complete by count, and the record says they
    attend their prompt and what was generated so far, the token still in
    flight with it. The record of the iteration that only reads the last
    tick has no rows, and the tokens it committed."""
    eng = _engine(setup)
    eng.start = lambda: None
    futures = [eng.submit(p, max_new_tokens=5) for p in PROMPTS[:2]]
    eng._admission_tick()
    seen = 0
    while not all(f.done() for f in futures):
        ahead = eng._in_flight
        riding = set(ahead.rows) if ahead is not None else set()
        rows = [i for i, s in enumerate(eng._slot_state) if s.active
                and (i not in riding or eng._outlives_tick(s))]
        expected = sum(
            eng._slot_state[i].prompt_len + len(eng._slot_state[i].tokens)
            + (i in riding) for i in rows)
        assert expected == int(eng._pos[rows].sum()) + len(rows)
        eng._tick = TickRecord(seen, time.perf_counter())
        assert eng._decode_tick() == len(rows)
        assert eng._tick.ctx_tokens == expected
        assert eng._tick.lookahead == int(bool(rows) and ahead is not None)
        assert eng._tick.tokens_out == len(riding)
        assert eng._tick.t_built <= eng._tick.t_dispatched \
            <= eng._tick.t_fetched
        seen += 1
    # the first token comes from the prefill, four ticks follow, and one
    # more iteration reads the last of them
    assert seen == 5 and eng._in_flight is None
    assert [len(f.result(timeout=0)[0]) for f in futures] == [5, 5]


def test_tick_log_ring_is_bounded_and_sums_follow_it():
    log = TickLog(size=4)
    made = []
    for n in range(10):
        record = TickRecord(n, 10.0 * n)
        record.admitted(10.0 * n + 1.0 + n)         # admission: 1 + n seconds
        record.t_built = record.t_dispatched = record.t_admit + 0.5
        record.t_fetched = record.t_dispatched + 2.0
        record.t1 = record.t_fetched + 0.5
        record.admit_wait_s = 0.25
        record.rows = n % 3                     # some only admitted
        log.append(record)
        made.append(record)
    kept = made[-4:]
    assert len(log) == 4
    assert [r["n"] for r in log.records()] == [6, 7, 8, 9]
    assert [r["n"] for r in log.records(start=70.0, end=85.0)] == [7]
    summary = log.summary()
    loop = sum(r.t1 - r.t0 for r in kept)
    assert summary["tick_admit_share"] == pytest.approx(
        sum(r.t_admit - r.t0 for r in kept) / loop)
    assert summary["tick_host_share"] == pytest.approx(
        (loop - 4 * 2.25) / loop)
    assert summary["tick_rows_mean"] == pytest.approx(
        sum(r.rows for r in kept) / sum(1 for r in kept if r.rows))
    assert TickLog().summary() == {}


def test_tick_log_registry_keeps_the_newest():
    first = get_tick_log("test-registry-0")
    assert get_tick_log("test-registry-0") is first
    for n in range(1, ticklog.KEPT_LOGS + 1):
        get_tick_log(f"test-registry-{n}")
    kept = tick_logs()
    assert len(kept) == ticklog.KEPT_LOGS
    assert "test-registry-0" not in kept
    assert f"test-registry-{ticklog.KEPT_LOGS}" in kept


# -- names --------------------------------------------------------------------
def _lowered(setup, program: str):
    cfg, params = setup
    eng = _engine(setup)
    small = init_kv_cache(cfg, 1, eng.max_len)
    ids = jnp.full((eng.pages_per_slot,), -1, jnp.int32)
    table = jnp.asarray(eng._page_table)
    pos = jnp.asarray(eng._pos)
    step = jnp.zeros((eng.slots, 1), jnp.int32)
    if program == "mlt_prefill":
        return eng._prefill.lower(params, jnp.zeros((1, 16), jnp.int32),
                                  small)
    if program == "mlt_decode":
        return eng._decode_paged.lower(params, step, eng._pool, table, pos)
    if program == "mlt_insert":
        return eng._insert_paged.lower(eng._pool, small, ids)
    if program == "mlt_gather":
        return eng._gather_paged.lower(eng._pool, small, ids)
    if program == "mlt_verify":
        return eng._make_verify_fn().lower(
            params, jnp.zeros((eng.slots, 3), jnp.int32), eng._pool, table,
            pos)
    from mlrun_tpu.training import TrainConfig, Trainer

    trainer = Trainer(cfg, TrainConfig(total_steps=2))
    trainer.init(0)
    spec = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    return trainer.step_fn.lower(trainer.state, spec, spec)


@pytest.mark.parametrize("program", [
    "mlt_prefill", "mlt_decode", "mlt_insert", "mlt_gather", "mlt_verify",
    "mlt_train_step"])
def test_programs_lower_under_stable_names(setup, program):
    text = _lowered(setup, program).as_text()
    assert f"module @jit_{program} " in text.split("\n", 1)[0]


def test_dense_engine_programs_share_the_names(setup):
    from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine

    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, slots=2,
                                   prefill_buckets=(16,))
    small = init_kv_cache(cfg, 1, 32)
    step = jnp.zeros((2, 1), jnp.int32)
    lowered = {
        "mlt_prefill": eng._prefill.lower(
            params, jnp.zeros((1, 16), jnp.int32), small),
        "mlt_decode": eng._decode.lower(params, step, eng._cache),
        "mlt_insert": eng._insert.lower(eng._cache, small, 0, 16),
        "mlt_verify": eng._make_verify_fn().lower(
            params, jnp.zeros((2, 3), jnp.int32), eng._cache),
    }
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text().split("\n", 1)[0]


def test_kernel_names_and_scopes_in_the_programs(setup):
    """The benchmark finds the kernels by these names; the scopes are what
    a profile groups the rest by."""
    cfg, params = setup
    eng = _engine(setup, attention_impl="flash")
    small = init_kv_cache(cfg, 1, eng.max_len)
    decode = str(jax.make_jaxpr(eng._decode_paged)(
        params, jnp.zeros((eng.slots, 1), jnp.int32), eng._pool,
        jnp.asarray(eng._page_table), jnp.asarray(eng._pos)))
    assert "name=paged_decode" in decode
    prefill = str(jax.make_jaxpr(eng._prefill)(
        params, jnp.zeros((1, 16), jnp.int32), small))
    assert "name=flash_v2" in prefill
    scoped = eng._decode_paged.lower(
        params, jnp.zeros((eng.slots, 1), jnp.int32), eng._pool,
        jnp.asarray(eng._page_table),
        jnp.asarray(eng._pos)).as_text(debug_info=True)
    for scope in ("embed", "layer/attn", "layer/mlp", "head"):
        assert f"jit(mlt_decode)/{scope}" in scoped, scope


# -- the same boundaries in the profiler's trace ------------------------------
def test_profile_holds_the_scheduler_spans(setup, tmp_path):
    """Three ticks under the profiler: on the host plane every iteration
    opens with an mlt.sched.tick that carries its index, and its parts
    follow as siblings, none inside another, before the next: admit
    (expiry and control, then the claim), for each admission prefill,
    first_token, insert, activate and admit again for the next claim, then
    build and dispatch of the tick it sends, then fetch and commit of the
    tick sent an iteration earlier (none in the first; the last iteration
    reads the third tick and sends nothing)."""
    limit = time.monotonic() + 120.0
    eng = _engine(setup)
    eng.warmup()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.start()
        tokens, _ = eng.submit(PROMPTS[0], max_new_tokens=4).result(
            timeout=max(1.0, limit - time.monotonic()))
    finally:
        eng.stop()
        jax.profiler.stop_trace()
    assert len(tokens) == 4
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path[0])
    host = [p for p in profile.planes if p.name == "/host:CPU"]
    assert host
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for line in host[0].lines for e in line.events
        if e.name.startswith("mlt.sched."))
    records = get_tick_log(eng._obs_name).records()
    worked = {r["n"]: r for r in records if r["rows"] or r["tokens_out"]}
    assert [(r["rows"], r["tokens_out"], r["lookahead"])
            for r in worked.values()] \
        == [(1, 0, 0), (1, 1, 1), (1, 1, 1), (0, 1, 0)]
    opened = [i for i, e in enumerate(events) if e[2] == "mlt.sched.tick"]
    seen = 0
    for i, following in zip(opened, opened[1:] + [len(events)]):
        record = worked.get(events[i][3].get("n"))
        if record is None:
            continue
        seen += 1
        parts = [e for e in events[i + 1:following]
                 if e[2] in SCHED_CHILDREN + ADMISSION]
        want = ["mlt.sched.admit", "mlt.sched.claim"] \
            + list(ADMISSION) * record["admissions"] \
            + [name for name, there in zip(SCHED_CHILDREN[1:], (
                record["rows"], record["rows"], record["tokens_out"],
                record["tokens_out"])) if there]
        assert [e[2] for e in parts] == want
        assert all(a[1] <= b[0] for a, b in zip([events[i]] + parts, parts))
    assert seen == 4
    assert sum(r["admissions"] for r in worked.values()) == 1
    assert time.monotonic() < limit


def test_annotate_reads_its_flag_once(monkeypatch):
    from mlrun_tpu.config import mlconf
    from mlrun_tpu.obs import get_tracer
    from mlrun_tpu.utils import profiler

    made = []
    monkeypatch.setattr(profiler, "_annotation", None)
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda name, **metadata: made.append((name, metadata)))
    profiler.annotate("region", n=3)
    with get_tracer().span("request") as span:
        profiler.annotate("region")
        previous = mlconf.observability.xla_annotations
        mlconf.observability.xla_annotations = False    # read no more
        try:
            profiler.annotate("region")
        finally:
            mlconf.observability.xla_annotations = previous
    stamped = f"region|trace={span.trace_id[:16]}"
    assert made == [("region", {"n": 3}), (stamped, {}), (stamped, {})]
    assert profiler.named("mlt_x", len).__name__ == "mlt_x"


# -- one clock for request spans ----------------------------------------------
def test_span_durations_survive_a_wall_clock_stepping_back(monkeypatch):
    tracer = tracing.Tracer()
    wall = iter([1000.0, 900.0, 800.0, 700.0, 600.0, 500.0])
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    with tracer.span("outer") as outer:
        inner = tracer.emit("inner", outer.trace_id, outer.span_id,
                            start=tracing.wall_now())
    assert outer.end >= outer.start and inner.end >= inner.start
    assert outer.to_dict()["duration_s"] >= 0.0
    assert outer.start <= inner.start <= inner.end <= outer.end
    # the anchor puts the monotone clock on the wall
    assert tracing.wall_at(tracing.PERF0) == tracing.WALL0
    assert abs(tracing.wall_now() - tracing.WALL0) < 3600.0


# -- the benchmark's readers over the log (no JAX) ----------------------------
def _tick(n, t0, admit=0.0, wait=0.0, rows=32, ctx=9600, device=0.025,
          host=0.003):
    built = t0 + admit + host / 3
    return {"n": n, "t0": t0, "t_admit": t0 + admit, "t_built": built,
            "t_dispatched": built + host / 3,
            "t_fetched": built + host / 3 + device,
            "t1": built + 2 * host / 3 + device, "admit_wait_s": wait,
            "rows": rows, "ctx_tokens": ctx, "prefill_tokens": 0,
            "kind": "plain"}


def _synthetic(ticks, calls, seconds=0.05, requests=0):
    fields = {"n_layers": 2, "n_heads": 32, "n_kv_heads": 8,
              "head_dim": 128}
    finished = [{"sent": 99.0, "done": 111.0,
                 "timing": {"wall_s": 1.0, "trace_id": f"t{i}"}}
                for i in range(requests)]
    return {"traced": [100.0, 110.0], "ticks": ticks, "fields": fields,
            "finished": finished,
            "spans": [{"trace_id": f"t{i}", "duration_s": 1.002}
                      for i in range(requests)]
            + [{"trace_id": "unfinished", "duration_s": None}],
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"op_seconds": {"paged_decode": seconds},
                      "op_counts": {"paged_decode": calls}}}


TICKS = [_tick(0, 100.0), _tick(1, 100.1, admit=0.05, wait=0.04),
         _tick(2, 100.3, rows=16, ctx=3200),
         _tick(3, 100.4, admit=0.02, rows=0, device=0.0, host=0.0),
         _tick(4, 109.99)]                      # ends outside the interval
LOOP = 3 * 0.028 + 0.05 + 0.02
# K and V of the attended tokens read once (4096 bytes a token), q in and
# the output back (16384 bytes a row), in each of the two layers
ROOFLINE = 100 * 2 * (22400 * 4096 + 80 * 16384) / 819e9 / 0.05


@pytest.mark.parametrize("reader, args, ctx, expected", [
    ("tick_share", {"part": "host"}, _synthetic(TICKS, 6),
     100 * (LOOP - 3 * 0.025 - 0.04) / LOOP),
    ("tick_share", {"part": "admit"}, _synthetic(TICKS, 6),
     100 * 0.07 / LOOP),
    ("tick_share", {"part": "admit"}, _synthetic([], 6), None),
    ("tick_rows", {}, _synthetic(TICKS, 6), 80 / 3),
    ("tick_rows", {}, _synthetic(TICKS[3:], 6), None),
    # 3 decode ticks x 2 layers = 6 calls, scaled to the trace's count
    ("kernel_roofline_ticks", {"pattern": "paged_decode"},
     _synthetic(TICKS, 6), ROOFLINE),
    ("kernel_roofline_ticks", {"pattern": "paged_decode"},
     _synthetic(TICKS, 7), ROOFLINE * 7 / 6),
    ("kernel_roofline_ticks", {"pattern": "paged_decode"},
     _synthetic(TICKS, 8), None),               # a third more calls
    ("kernel_roofline_ticks", {"pattern": "paged_decode"},
     dict(_synthetic(TICKS, 6), trace=None), None),
    ("span_self", {"least": 100}, _synthetic(TICKS, 6, requests=120), 2.0),
    ("span_self", {"least": 100}, _synthetic(TICKS, 6, requests=99), None),
])
def test_readers_ticks_on_a_synthetic_context(reader, args, ctx, expected):
    from benchmarks.harness import readers_ticks

    value = getattr(readers_ticks, reader)(ctx, **args)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


def test_readers_ticks_find_the_engines_own_log(setup):
    """Without ``ctx['ticks']`` the readers take the process-wide log of
    the engine that worked in the window, and with no interval named the
    window is first ``sent`` to last ``done``."""
    from benchmarks.harness import readers_ticks

    eng = _engine(setup)
    eng.start()
    try:
        sent = time.perf_counter()
        eng.submit(PROMPTS[1], max_new_tokens=5).result(timeout=300)
    finally:
        eng.stop()
    done = time.perf_counter()
    ctx = {"finished": [{"sent": sent, "done": done, "timing": None}]}
    assert readers_ticks.tick_rows(ctx) == 1.0
    assert 0.0 < readers_ticks.tick_share(ctx, "admit") < 100.0
    assert readers_ticks.tick_rows({"traced": [done, done + 1.0]}) is None
    assert np.isclose(
        readers_ticks.tick_share(ctx, "host")
        + 100.0 * sum(r["t_fetched"] - r["t_dispatched"]
                      + r["admit_wait_s"] for r in readers_ticks._ticks(ctx))
        / sum(r["t1"] - r["t0"] for r in readers_ticks._ticks(ctx)), 100.0)
