"""Tail-latency forensics (docs/observability.md "Request attribution,
exemplars & trace assembly"): the per-request phase ledger
(obs/reqledger.py), histogram exemplars + OpenMetrics negotiation,
cross-replica trace assembly with critical-path analysis, and the
alert→exemplar→waterfall round trip.

Closure discipline mirrors test_goodput_flight: the ledger invariant
(Σ phase seconds == request wall) is asserted with ZERO tolerance under
a fake clock — including on real engines, whose ``_ledger_clock`` is
injectable — and within ±0.1s against an externally measured wall on
the real clock.
"""

import itertools
import json
import threading

import pytest

import mlrun_tpu
from mlrun_tpu.obs import (
    REGISTRY,
    RequestLedger,
    Tracer,
    get_tracer,
    merge_timing,
    parse_exposition,
    parse_trace_header,
)
from mlrun_tpu.obs.debug import trace_snapshot
from mlrun_tpu.obs.traceview import assemble, critical_path


# -- ledger unit behavior ----------------------------------------------------

def test_ledger_fake_clock_exact_closure_full_sequence():
    """The exact transition sequence an engine request walks — submit →
    rate-limit check → queue → adapter load → chunked prefill → decode
    active/stall alternation — sums to wall with ZERO tolerance."""
    clock = itertools.count(0).__next__
    ledger = RequestLedger(trace_id="ab" * 16, clock=clock)
    ledger.enter("rate_limit_wait")     # admission +1
    ledger.enter("admission")           # rate_limit_wait +1
    ledger.enter("queue_wait")          # admission +1
    ledger.enter("adapter_load_wait")   # queue_wait +1
    ledger.enter("admission")           # adapter_load_wait +1
    ledger.enter("prefill")             # admission +1
    for _ in range(3):                  # 3 decode ticks
        ledger.enter("decode_active")
        ledger.enter("decode_stall")
    timing = ledger.close()
    assert timing["attribution_closed"]
    # 13 clock ticks elapsed between construction and close (one read
    # per transition) — attribution covers every one of them
    assert timing["wall_s"] == sum(timing["phases"].values()) == 13
    assert timing["phases"]["decode_active"] == 3
    assert timing["phases"]["prefill"] == 1
    assert timing["trace_id"] == "ab" * 16
    # idempotent close returns the same attribution
    assert ledger.close() == timing


def test_ledger_close_renames_open_interval_and_attribute_adds_wall():
    clock = itertools.count(0).__next__
    ledger = RequestLedger(clock=clock)
    ledger.enter("prefill")
    ledger.attribute("redispatch_backoff", 5.0)
    timing = ledger.close("handoff")
    # the trailing open interval belongs to handoff, not prefill; the
    # out-of-band backoff advanced the wall with its phase
    assert timing["phases"]["handoff"] == 1
    assert timing["phases"]["redispatch_backoff"] == 5.0
    assert timing["wall_s"] == sum(timing["phases"].values())
    assert timing["attribution_closed"]


def test_merge_timing_preserves_closure():
    def closed(phases):
        return {"wall_s": sum(phases.values()), "phases": dict(phases),
                "attribution_closed": True}

    a = closed({"prefill": 2.0, "handoff": 1.0})
    b = closed({"queue_wait": 0.5, "handoff": 0.25})
    merged = merge_timing(dict(a), b)
    assert merged["phases"] == {"prefill": 2.0, "handoff": 1.25,
                                "queue_wait": 0.5}
    assert merged["wall_s"] == pytest.approx(
        sum(merged["phases"].values()))


# -- engines: closure + greedy parity ----------------------------------------

def _tiny_engine(cls, **kwargs):
    import jax

    from mlrun_tpu.models import init_params, tiny_llama

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    defaults = dict(max_len=64, slots=2, prefill_buckets=(64,))
    defaults.update(kwargs)
    engine = cls(config, params, **defaults)
    engine.start()
    return engine


def _paged(**kwargs):
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    kwargs.setdefault("page_size", 16)
    return _tiny_engine(PagedContinuousBatchingEngine, **kwargs)


def _dense(**kwargs):
    from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine

    return _tiny_engine(ContinuousBatchingEngine, **kwargs)


PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]


def _run_one(engine, prompt=PROMPT, max_new=4, fake_clock=False):
    import time

    if fake_clock:
        engine._ledger_clock = itertools.count(0).__next__
    t0 = time.perf_counter()
    tokens, stats = engine.generate(prompt, max_new_tokens=max_new)
    wall = time.perf_counter() - t0
    return tokens, stats.get("timing"), wall


@pytest.mark.parametrize("make", [_dense, _paged],
                         ids=["dense", "paged"])
def test_engine_ledger_closure_and_greedy_parity(make):
    """Dense AND paged engines: Σ phases == wall exactly under a fake
    ledger clock on the REAL engine, inside the externally measured
    wall on the real clock, and greedy tokens bit-identical with the
    ledger on vs off."""
    on = make(request_ledger=True)
    try:
        tokens_cold, timing, wall = _run_one(on)
        assert timing is not None and timing["attribution_closed"]
        assert timing["wall_s"] == pytest.approx(
            sum(timing["phases"].values()), abs=1e-9)
        # the real clock is a sanity bound, not the identity (the fake
        # clock below holds that exactly): the ledger opens after the
        # caller's clock and closes before it, and what lies between the
        # two (a thread's wake-up on a loaded machine) is not seconds
        assert 0 < timing["wall_s"] <= wall
        assert wall - timing["wall_s"] < 5.0
        assert {"prefill", "decode_active"} <= set(timing["phases"])
        # zero-tolerance closure under a fake clock driving the same
        # real scheduler path (integer phase durations)
        tokens_fake, fake_timing, _ = _run_one(on, fake_clock=True)
        assert fake_timing["attribution_closed"]
        assert fake_timing["wall_s"] == sum(
            fake_timing["phases"].values())
        assert float(fake_timing["wall_s"]).is_integer()
    finally:
        on.stop()
    off = make(request_ledger=False)
    try:
        tokens_off, timing_off, _ = _run_one(off)
        assert timing_off is None  # no ledger, no timing field
        assert tokens_off == tokens_cold == tokens_fake
    finally:
        off.stop()


def test_paged_prefix_hit_ledger_notes_cached_prefix():
    engine = _paged(request_ledger=True)
    try:
        long_prompt = list(range(1, 40))
        tokens_cold, cold, _ = _run_one(engine, prompt=long_prompt)
        # the hit request runs under a fake ledger clock: exact integer
        # closure through the prefix-gather admission path too
        tokens_hit, hit, _ = _run_one(engine, prompt=long_prompt,
                                      fake_clock=True)
        assert engine.stats["prefix_hits"] >= 1
        assert cold["attribution_closed"] and hit["attribution_closed"]
        assert hit["wall_s"] == sum(hit["phases"].values())
        assert float(hit["wall_s"]).is_integer()
        # the hit admission gathered cached pages instead of
        # prefilling them — the ledger records the reused prefix
        assert cold.get("cached_prefix", 0) == 0
        assert hit["cached_prefix"] > 0
        assert tokens_hit == tokens_cold
    finally:
        engine.stop()


def test_handoff_ledger_spans_both_hops():
    """submit_prefill closes the prefill-side ledger into ``handoff``
    (riding the KVHandoff); submit_prefilled's decode-side ledger
    carries the import as ``handoff`` — both closed, and decode greedy
    output matches the single-engine path."""
    prefill = _paged(request_ledger=True)
    decode = _paged(request_ledger=True)
    single = _paged(request_ledger=True)
    # fake clocks on BOTH hops: zero-tolerance closure across the
    # export (prefill side) and import (decode side) paths
    prefill._ledger_clock = itertools.count(0).__next__
    decode._ledger_clock = itertools.count(0).__next__
    try:
        handoff = prefill.submit_prefill(PROMPT).result(timeout=120)
        assert handoff.timing is not None
        assert handoff.timing["attribution_closed"]
        assert handoff.timing["wall_s"] == sum(
            handoff.timing["phases"].values())
        assert float(handoff.timing["wall_s"]).is_integer()
        assert handoff.timing["phases"].get("handoff", 0) >= 0
        assert "prefill" in handoff.timing["phases"]
        tokens, stats = decode.submit_prefilled(
            handoff, max_new_tokens=4).result(timeout=120)
        timing = stats["timing"]
        assert timing["attribution_closed"]
        assert timing["wall_s"] == sum(timing["phases"].values())
        assert float(timing["wall_s"]).is_integer()
        assert "handoff" in timing["phases"]
        assert "prefill" not in timing["phases"]  # no prefill ran here
        ref_tokens, _ = single.generate(PROMPT, max_new_tokens=4)
        assert tokens == ref_tokens
    finally:
        prefill.stop()
        decode.stop()
        single.stop()


def test_fleet_merged_timing_sums_to_client_wall():
    import time

    import jax

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))

    def factory(role):
        return PagedContinuousBatchingEngine(
            config, params, max_len=64, slots=2, page_size=16,
            prefill_buckets=(64,))

    fleet = EngineFleet(factory, replicas=1, prefill_replicas=1)
    fleet.start()
    try:
        t0 = time.perf_counter()
        _, stats = fleet.generate(PROMPT, max_new_tokens=4)
        wall = time.perf_counter() - t0
        timing = stats["timing"]
        assert timing["attribution_closed"]
        # the fleet merged prefill-hop + decode-hop ledgers, then
        # attributed the dispatch/transfer remainder to "network":
        # attribution sums to the CLIENT-observed wall
        assert timing["wall_s"] == pytest.approx(
            sum(timing["phases"].values()), abs=1e-9)
        assert abs(timing["wall_s"] - wall) < 0.1
        assert "handoff" in timing["phases"]
        assert "prefill" in timing["phases"]
    finally:
        fleet.stop()


def test_fleet_redispatch_backoff_attributed():
    from concurrent.futures import Future

    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.resilience import EngineStoppedError

    class _FakeEngine:
        page_size = 8

        def __init__(self, fail_with=None):
            self.replica = ""
            self._stopped = False
            self._slot_state = ()
            self.fail_with = fail_with

        def _queue_depth(self):
            return 0

        def start(self):
            pass

        def stop(self, timeout=10.0):
            self._stopped = True

        def submit(self, prompt, **kwargs):
            future = Future()
            if self.fail_with is not None:
                future.set_exception(self.fail_with)
            else:
                future.set_result((list(prompt)[:1], {
                    "ttft_s": 0.001,
                    "timing": {"wall_s": 0.001,
                               "phases": {"prefill": 0.001},
                               "attribution_closed": True}}))
            return future

        @property
        def stats(self):
            return {"requests": 0, "completed": 0, "queue_depth": 0}

    engines = [_FakeEngine(), _FakeEngine()]
    pool = list(engines)
    fleet = EngineFleet(lambda role: pool.pop(0), replicas=2,
                        route_block_tokens=8, backoff=0.01)
    prompt = list(range(32))
    primary_id = fleet._ring.lookup(fleet.routing_key(prompt))
    primary = next(r.engine for r in fleet.replicas
                   if r.id == primary_id)
    primary.fail_with = EngineStoppedError("replica died")
    _, stats = fleet.submit(prompt, max_new_tokens=4).result(timeout=10)
    timing = stats["timing"]
    assert timing["phases"]["redispatch_backoff"] > 0
    assert timing["attribution_closed"]
    assert timing["wall_s"] >= sum(timing["phases"].values()) - 1e-9
    fleet.stop()


# -- exemplars ----------------------------------------------------------------

def test_histogram_exemplar_slots_and_openmetrics_render():
    from mlrun_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("t_ex_seconds", "h", buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="aa11")
    h.observe(0.07, exemplar="bb22")   # same bucket: last write wins
    h.observe(5.0, exemplar="cc33")    # +Inf slot
    h.observe(0.5)                     # no exemplar: slot stays empty
    found = h.exemplars()
    by_le = {e["le"]: e["labels"]["trace_id"] for e in found}
    assert by_le[0.1] == "bb22"
    assert by_le[float("inf")] == "cc33"
    assert 1.0 not in by_le
    om = reg.render(openmetrics=True)
    assert om.rstrip().endswith("# EOF")
    assert '# {trace_id="bb22"} 0.07' in om
    # the default format stays exemplar-free (Prometheus text 0.0.4)
    plain = reg.render()
    assert "trace_id" not in plain and "# EOF" not in plain
    # round trip through the strict parser
    samples, types, exemplars = parse_exposition(om)
    assert types["t_ex_seconds"] == "histogram"
    carried = {ex["labels"]["trace_id"] for ex in exemplars.values()}
    assert carried == {"bb22", "cc33"}


def test_openmetrics_counter_naming_round_trips():
    """OpenMetrics spec: a counter family ``foo`` exposes ``foo_total``
    samples — the OM render strips our ``_total`` family suffix on the
    TYPE/HELP lines (sample names stay byte-identical) and the
    federation parser maps the samples back to counter semantics, so a
    strict scraper AND our own aggregator both accept the output."""
    from mlrun_tpu.obs import MetricsAggregator, MetricsRegistry
    from mlrun_tpu.obs.federation import sample_kind

    reg = MetricsRegistry()
    reg.counter("t_om_events_total", "c", labels=("k",)).inc(3, k="a")
    reg.counter("t_om_wait_seconds", "c2").inc(1.5)  # no _total suffix
    om = reg.render(openmetrics=True)
    assert "# TYPE t_om_events counter" in om
    assert 't_om_events_total{k="a"} 3' in om
    assert "# TYPE t_om_wait_seconds counter" in om
    assert "t_om_wait_seconds_total 1.5" in om
    samples, types, _ = parse_exposition(om)
    assert sample_kind("t_om_events_total", types) == \
        ("t_om_events", "counter")
    assert sample_kind("t_om_wait_seconds_total", types) == \
        ("t_om_wait_seconds", "counter")
    # counter semantics survive the aggregator: two sources SUM
    agg = MetricsAggregator(stale_after=60, max_series=64)
    agg.ingest_text("r0", om, at=1.0)
    agg.ingest_text("r1", om, at=1.0)
    assert agg.value("t_om_events_total", 1.0, k="a") == 6
    # the default format is unchanged (names as declared)
    plain = reg.render()
    assert "# TYPE t_om_events_total counter" in plain
    assert "t_om_wait_seconds 1.5" in plain


def test_exemplar_round_trip_survives_odd_labels_and_inf_values():
    """The renderer's own output must ALWAYS parse — an exemplar label
    value containing '}' or a quote, or an +Inf observation, must not
    poison a replica's whole federated scrape."""
    from mlrun_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("t_odd_seconds", "h", buckets=(0.1,))
    h.observe(0.05, exemplar={"tenant": 'a}b"c'})
    h.observe(float("inf"), exemplar="dead02")  # +Inf bucket + value
    om = reg.render(openmetrics=True)
    samples, _, exemplars = parse_exposition(om)  # must not raise
    values = {e["labels"].get("tenant") or e["labels"].get("trace_id")
              for e in exemplars.values()}
    assert 'a}b\\"c' in values  # escaped form round-trips
    assert "dead02" in values


def test_retire_adapter_phases_prunes_series():
    """Version churn (the canary loop mints `tenant@vN` ids) must not
    exhaust the phase family's label-set cap: AdapterRegistry.retire
    releases the retired identity's per-phase series."""
    import jax

    from mlrun_tpu.models import tiny_llama
    from mlrun_tpu.models.lora import init_lora_nonzero
    from mlrun_tpu.obs import REQUEST_PHASE_SECONDS, export_phases
    from mlrun_tpu.serving.adapters import AdapterRegistry

    export_phases({"phases": {"prefill": 0.01, "decode_active": 0.02}},
                  adapter="churn@v1")
    assert REQUEST_PHASE_SECONDS.value(
        phase="prefill", adapter="churn@v1")["count"] == 1
    config = tiny_llama(attention_impl="reference")
    registry = AdapterRegistry(config, sources={
        "churn@v1": init_lora_nonzero(config, jax.random.PRNGKey(0))})
    registry.retire("churn@v1")
    assert REQUEST_PHASE_SECONDS.value(
        phase="prefill", adapter="churn@v1")["count"] == 0
    assert REQUEST_PHASE_SECONDS.value(
        phase="decode_active", adapter="churn@v1")["count"] == 0


def test_parser_tolerates_hash_brace_in_label_values():
    """A client-supplied label value containing ' # {' (adapter ids are
    label values) must parse as a sample, not poison the whole scrape
    as a malformed exemplar."""
    text = '# HELP w w\n# TYPE w gauge\nw{adapter=" # {x"} 1'
    samples, _, exemplars = parse_exposition(text)
    assert list(samples.values()) == [1.0]
    assert not exemplars


def test_remote_network_gap_is_per_item():
    """Each batch item's caller-visible wall is the HOP wall (the batch
    returns together): the network gap is hop minus THAT item's server
    wall, so every item's timing sums to the caller-visible wall."""
    from mlrun_tpu.serving.remote import _attribute_network

    body = {"timing": [
        {"wall_s": 1.0, "phases": {"prefill": 1.0},
         "attribution_closed": True},
        {"wall_s": 3.0, "phases": {"prefill": 3.0},
         "attribution_closed": True},
    ]}
    _attribute_network(body, hop_s=3.5)
    fast, slow = body["timing"]
    assert fast["wall_s"] == pytest.approx(3.5)
    assert fast["phases"]["network"] == pytest.approx(2.5)
    assert slow["wall_s"] == pytest.approx(3.5)
    assert slow["phases"]["network"] == pytest.approx(0.5)
    for timing in body["timing"]:
        assert timing["wall_s"] == pytest.approx(
            sum(timing["phases"].values()))


def test_federation_carries_exemplars_outside_budget():
    from mlrun_tpu.obs import MetricsAggregator, MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("t_fed_seconds", "h", buckets=(0.1, 1.0),
                      labels=("replica",))
    h.observe(0.05, exemplar="dead01", replica="r0")
    text = reg.render(openmetrics=True)
    agg = MetricsAggregator(stale_after=60, max_series=64)
    agg.ingest_text("r0", text, at=10.0)
    carried = agg.exemplars("t_fed_seconds", 10.0)
    assert [e["labels"]["trace_id"] for e in carried] == ["dead01"]
    assert agg.exemplars("t_fed_seconds", 10.0,
                         match={"replica": "nope"}) == []
    assert agg.dropped_series == 0
    # identical re-ingest: same series count, exemplar still carried
    before = agg.series_count(10.0)
    agg.ingest_text("r0", text, at=20.0)
    assert agg.series_count(20.0) == before
    assert agg.exemplars("t_fed_seconds", 20.0)
    # a stale source's exemplars leave with its samples
    assert agg.exemplars("t_fed_seconds", 120.0) == []


# -- alert → exemplar → waterfall round trip ---------------------------------

def test_slo_breach_names_exemplar_and_trace_reconciles(tmp_path):
    """Acceptance round trip: a fake-clock SLO breach carries >= 1
    exemplar trace id from a REAL request, the flight-recorder breach
    entry names the same ids, and the assembled /debug/trace waterfall
    for that id reconciles with the request's phase ledger."""
    from mlrun_tpu.db.sqlitedb import SQLiteRunDB
    from mlrun_tpu.obs import (
        LLM_TTFT,
        SLO,
        SLOEvaluator,
        TimeSeriesStore,
        get_flight_recorder,
    )
    from mlrun_tpu.service.alerts import get_alert_template

    engine = _paged(request_ledger=True)
    tracer = get_tracer()
    try:
        with tracer.span("forensics.request") as span:
            _, stats = engine.generate(PROMPT, max_new_tokens=4)
            trace_id = span.trace_id
    finally:
        engine.stop()
    timing = stats["timing"]
    assert timing["trace_id"] == trace_id
    # the engine's TTFT observation carried the trace id as exemplar
    assert any(e["labels"].get("trace_id") == trace_id
               for e in LLM_TTFT.exemplars())

    # synthetic windowed histogram data breaches the latency objective
    # at fake time 99 (every observation slow)
    store = TimeSeriesStore(resolution_s=1.0)
    cum = 0.0
    for t in range(0, 100):
        cum += 10
        for le, value in (("0.05", 0.0), ("+Inf", cum)):
            store.record("mlt_llm_ttft_seconds_bucket", value, at=t,
                         labels={"le": le}, kind="counter")
        store.record("mlt_llm_ttft_seconds_count", cum, at=t,
                     kind="counter")
    slo = SLO("ttft-forensics", "latency", target=1e-6, q=0.95)
    evaluator = SLOEvaluator(store, [slo], fast_window=10,
                             slow_window=30, fast_burn=1.0,
                             slow_burn=1.0, project="p1")
    db = SQLiteRunDB(str(tmp_path / "slo.db"))
    config = get_alert_template("SLOBurnRate")
    config["name"] = "ttft-forensics-burn"
    db.store_alert_config("ttft-forensics-burn", config, "p1")
    assert evaluator.process(db, at=99) == ["ttft-forensics-burn"]

    # the persisted breach event names the trace id...
    events = db.list_events("p1", kind="slo_burn_rate")
    exemplar_ids = [e.get("trace_id")
                    for e in events[-1].get("exemplars", [])]
    assert trace_id in exemplar_ids
    # ...the flight-recorder breach entry names the same ids...
    breaches = get_flight_recorder().events(kind="slo.breach")
    assert breaches and trace_id in breaches[-1]["exemplar_trace_ids"]
    # ...and the waterfall reconciles with the request's own ledger
    waterfall = trace_snapshot(trace_id, local_only=True)
    assert not waterfall["partial"]
    names = {s["name"] for s in waterfall["spans"]}
    assert {"forensics.request", "llm.prefill", "llm.decode"} <= names
    recon = waterfall["reconciliation"]
    assert recon["ledger_wall_s"] == pytest.approx(
        timing["wall_s"], rel=0.01)
    assert abs(recon["delta_s"]) < 0.1
    assert waterfall["phase_totals"]["prefill"] > 0


# -- trace assembly / critical path ------------------------------------------

def _span(name, span_id, parent, start, end, **attrs):
    return {"name": name, "trace_id": "t1", "span_id": span_id,
            "parent_id": parent, "start": start, "end": end,
            "status": "ok", "attrs": attrs}


def test_critical_path_partitions_root_and_attributes_gaps():
    spans = [
        _span("server.run", "root", None, 0.0, 10.0),
        _span("llm.prefill", "p", "root", 1.0, 5.0, replica="r0"),
        _span("llm.decode", "d", "root", 5.5, 9.0, replica="r1"),
        # concurrent span overlapping the decode — not blocking
        _span("step.other", "x", "root", 5.6, 8.0),
    ]
    segments = critical_path(spans)
    # segments partition the root duration exactly
    assert sum(s["self_s"] for s in segments) == pytest.approx(10.0)
    picked = [s["name"] for s in segments]
    assert "llm.prefill" in picked and "llm.decode" in picked
    assert "step.other" not in picked  # overlapped, skipped
    out = assemble("t1", spans)
    # gap time landed on the parent's phase (server.run → queue_wait):
    # 0→1 before prefill, 5→5.5 between spans, 9→10 after decode
    assert out["phase_totals"]["queue_wait"] == pytest.approx(2.5)
    assert out["phase_totals"]["prefill"] == pytest.approx(4.0)
    assert out["replicas"] == ["r0", "r1"]


def test_trace_snapshot_validates_id_and_degrades_on_dead_peer():
    with pytest.raises(ValueError):
        trace_snapshot("not hex!")
    with pytest.raises(ValueError):
        trace_snapshot("a" * 65)
    tracer = get_tracer()
    with tracer.span("degraded.request") as span:
        trace_id = span.trace_id
    out = trace_snapshot(trace_id, peers=["http://127.0.0.1:9"],
                         timeout=0.2)
    assert out["partial"] is True
    assert not out["sources"]["http://127.0.0.1:9"]["ok"]
    assert any(s["name"] == "degraded.request" for s in out["spans"])


# -- satellite: trace-header hardening + ring bound --------------------------

def test_parse_trace_header_malformed_inputs():
    trace = "ab" * 16
    # mixed-case header name and bare trace id (no span part)
    assert parse_trace_header({"X-Mlt-TRACE": trace}) == (trace, None)
    assert parse_trace_header({"x-mlt-trace": f"{trace}-aaaabbbb"}) \
        == (trace, "aaaabbbb")
    # overlong span part dropped, trace kept
    assert parse_trace_header(
        {"x-mlt-trace": f"{trace}-{'a' * 33}"}) == (trace, None)
    # non-hex span part dropped, trace kept
    assert parse_trace_header(
        {"x-mlt-trace": f"{trace}-zzzz"}) == (trace, None)
    # empty span part (trailing dash)
    assert parse_trace_header({"x-mlt-trace": f"{trace}-"}) \
        == (trace, None)
    # non-hex / overlong / empty trace ids are rejected outright
    assert parse_trace_header({"x-mlt-trace": "zz-aaaa"}) == (None, None)
    assert parse_trace_header({"x-mlt-trace": "a" * 65}) == (None, None)
    assert parse_trace_header({"x-mlt-trace": ""}) == (None, None)
    # bytes keys/values (raw ASGI layers) decode instead of mangling
    assert parse_trace_header(
        {b"x-mlt-trace": f"{trace}-aaaabbbb".encode()}) \
        == (trace, "aaaabbbb")
    assert parse_trace_header({b"x-mlt-trace": b"\xff\xfe"}) \
        == (None, None)
    assert parse_trace_header(None) == (None, None)


def test_span_ring_bound_under_concurrent_emitters():
    tracer = Tracer(ring=64)
    errors = []

    def emit(worker):
        try:
            for i in range(200):
                tracer.emit(f"w{worker}.{i}", trace_id="ab" * 16)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=emit, args=(w,))
               for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(tracer.spans()) == 64  # bounded, newest kept


def test_trace_jsonl_rotation_bounded(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    cap = 4096
    tracer = Tracer(ring=16, path=path, max_bytes=cap)
    for i in range(400):
        tracer.emit(f"rot.{i}", trace_id="ab" * 16,
                    attrs={"pad": "x" * 64})
    import os

    main_size = os.path.getsize(path)
    pred = path + ".1"
    pred_size = os.path.getsize(pred) if os.path.exists(pred) else 0
    assert os.path.exists(pred)  # the loop rotated at least once
    assert main_size <= cap
    assert main_size + pred_size <= 2 * cap
    # rotated files hold valid JSONL
    with open(pred) as fp:
        for line in fp:
            json.loads(line)


# -- v2 envelope + gateway endpoint ------------------------------------------

def test_v2_timing_field_is_opt_in():
    from mlrun_tpu.serving.llm import LLMModelServer

    fn = mlrun_tpu.new_function("reqtrace-v2", kind="serving")
    graph = fn.set_topology("flow")
    graph.to(LLMModelServer, name="m", model_preset="tiny",
             continuous_batching=True, paged=True, slots=2,
             max_len=64, page_size=16, max_new_tokens=4,
             warmup=False).respond()
    server = fn.to_mock_server(namespace={"LLMModelServer":
                                          LLMModelServer})
    try:
        plain = server.run(
            mlrun_tpu.serving.server.MockEvent(
                body={"inputs": [PROMPT]}), get_body=True)
        assert "timing" not in plain
        timed = server.run(
            mlrun_tpu.serving.server.MockEvent(
                body={"inputs": [PROMPT], "timing": True}),
            get_body=True)
        assert len(timed["timing"]) == 1
        timing = timed["timing"][0]
        assert timing["attribution_closed"]
        assert timing["wall_s"] == pytest.approx(
            sum(timing["phases"].values()), abs=1e-9)
        assert timed["outputs"] == plain["outputs"]
    finally:
        model = server.graph.steps["m"]._object
        if getattr(model, "engine", None) is not None:
            model.engine.stop()


# -- bench smoke --------------------------------------------------------------

def test_bench_reqtrace_smoke():
    """Tier-1 bench smoke (CPU-noise-robust, like PRs 7-11): structure
    + the closure/exemplar claims; the <=1.05 overhead acceptance
    number lives in BENCH_r12.json produced by `make bench-reqtrace`."""
    import bench_serve

    out = bench_serve.run_reqtrace(requests=4, rounds=1,
                                   prefix_tokens=32, suffix_tokens=4,
                                   max_new=4, page_size=16, max_len=64)
    assert out["mode"] == "reqtrace"
    assert out["attribution_closed"] is True
    assert out["requests_with_timing"] == 4
    assert out["exemplar_present"] is True
    assert out["ledger_on"]["p50_ttft_ms"] > 0
    assert out["ledger_off"]["p50_ttft_ms"] > 0
    assert out["overhead_ratio_p50_ttft"] > 0
    assert "prefill" in out["phases_sample"]
