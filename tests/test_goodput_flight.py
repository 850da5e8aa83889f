"""Goodput accounting + black-box flight recorder (ISSUE 10).

The run-lifecycle observability layer: every wall-second of a run lands
in goodput or a typed badput bucket (summing to wall time by
construction — fake-clock exact, real-trainer ± a tick), lifecycle gaps
are attributed out-of-band by the monitor, ``SLO(kind="goodput")``
burns through the unchanged multi-window evaluator, and failures leave
a JSONL flight-recorder artifact carrying the decision sequence (stall
detection AND the retry decision — the acceptance artifact).
"""

import gc
import json
import os
import threading
import time
from datetime import datetime, timedelta, timezone

import pytest

import mlrun_tpu
from mlrun_tpu.chaos import chaos
from mlrun_tpu.model import RunObject
from mlrun_tpu.obs import (
    BADPUT_SECONDS,
    SLO,
    FlightRecorder,
    GoodputLedger,
    SLOEvaluator,
    TimeSeriesStore,
    get_flight_recorder,
    nearest_rank,
    record_badput,
)

from . import fake_k8s


# -- ledger: fake-clock attribution ------------------------------------------

def test_ledger_fake_clock_preempt_resubmit_rewarm_sums_exactly():
    """Simulated preempted-run lifecycle on a fake clock: chaos-delayed
    input, a preemption checkpoint, the monitor's downtime attribution,
    and a warm re-compile after resubmit — every bucket lands and the
    attribution sums to wall time exactly (the ± tick tolerance is only
    for real clocks)."""
    t = [0.0]
    ledger = GoodputLedger(run="r-fake", clock=lambda: t[0])

    def spend(phase, seconds):
        # start `phase` now; the clock then advances inside it — the
        # NEXT transition (or close) attributes the elapsed time to it
        ledger.enter(phase)
        t[0] += seconds

    # steps 1-2: chaos-delayed input, h2d, dispatch
    spend("data_wait", 0.5)
    spend("h2d", 0.1)
    spend("step", 2.0)
    spend("data_wait", 0.5)
    spend("step", 2.0)
    # warm re-compile after the (simulated) resubmit
    spend("re_warm", 3.0)
    spend("step", 5.0)
    spend("metric_flush", 0.4)
    spend("checkpoint", 1.0)         # preemption final save
    # monitor-side: eviction -> replacement gap, out-of-band
    ledger.attribute("preemption_downtime", 7.5)
    summary = ledger.close()

    assert summary["wall_s"] == pytest.approx(14.5 + 7.5)
    assert summary["goodput_s"] == pytest.approx(9.0)
    assert summary["badput"]["data_wait"] == pytest.approx(1.0)
    assert summary["badput"]["re_warm"] == pytest.approx(3.0)
    assert summary["badput"]["h2d"] == pytest.approx(0.1)
    assert summary["badput"]["metric_flush"] == pytest.approx(0.4)
    assert summary["badput"]["checkpoint"] == pytest.approx(1.0)
    assert summary["badput"]["preemption_downtime"] == pytest.approx(7.5)
    # THE invariant: attribution closes over wall time, zero tolerance
    assert summary["goodput_s"] + summary["badput_s"] == \
        pytest.approx(summary["wall_s"], abs=1e-9)
    assert summary["goodput_fraction"] == pytest.approx(9.0 / 22.0)


def test_ledger_transfer_and_close_phase_keep_wall_invariant():
    t = [0.0]
    ledger = GoodputLedger(clock=lambda: t[0])
    ledger.enter("step")
    t[0] = 10.0
    ledger.enter("step")                     # land the dispatch interval
    ledger.transfer("step", "compile", 6.0)  # reclassify measured compile
    ledger.transfer("h2d", "compile", 5.0)   # empty source: clamps to 0
    t[0] = 12.0
    summary = ledger.close("stall")          # trailing time -> stall
    assert summary["goodput_s"] == pytest.approx(4.0)
    assert summary["badput"]["compile"] == pytest.approx(6.0)
    assert summary["badput"]["stall"] == pytest.approx(2.0)
    assert summary["goodput_s"] + summary["badput_s"] == \
        pytest.approx(summary["wall_s"], abs=1e-9)


# -- trainer: chaos preemption + resubmit + warm re-compile ------------------

@pytest.mark.chaos
def test_trainer_chaos_preempt_resubmit_rewarm(tmp_path, monkeypatch):
    """A chaos run (``train.prefetch`` + preemption + resubmit): both
    fits' buckets sum to wall time (± a tick), the chaos fires and the
    preemption land on the flight ring and drain to a JSONL artifact,
    and the resumed fit classifies its (cache-warm) first dispatch as
    ``re_warm`` — the elasticity tax, told apart from a cold compile."""
    import jax

    from mlrun_tpu.config import mlconf
    from mlrun_tpu.models import tiny_llama
    from mlrun_tpu.training import (
        TrainConfig,
        Trainer,
        synthetic_token_stream,
    )
    from mlrun_tpu.training.preemption import PreemptionGuard
    from mlrun_tpu.utils import compile_cache

    recorder = get_flight_recorder()
    recorder.configure(directory=str(tmp_path / "flight"))
    previous_cache = str(mlconf.training.get("compile_cache_dir", "") or "")
    mlconf.training.compile_cache_dir = str(tmp_path / "cc")
    config = tiny_llama(attention_impl="reference")
    try:
        # -- run 1: chaos-delayed input pipeline, preempted mid-run ------
        trainer = Trainer(config, TrainConfig(total_steps=12))
        trainer.init(0)
        guard = PreemptionGuard()  # programmatic request(), no signals

        def stopper(step, metrics, _trainer):
            if step >= 3:
                guard.request()
            return True

        with chaos.inject("train.prefetch", delay=0.005):
            out = trainer.fit(
                synthetic_token_stream(8, 32, config.vocab_size),
                steps=10, log_every=2, prefetch=2, callbacks=[stopper],
                preemption_guard=guard)
        assert out["preempted"] is True
        s1 = trainer.goodput.summary()
        # buckets sum to wall ± one tick
        assert s1["goodput_s"] + s1["badput_s"] == \
            pytest.approx(s1["wall_s"], abs=0.1)
        assert s1["badput"]["compile"] > 0          # cold first dispatch
        assert 0 < s1["goodput_fraction"] < 1

        # flight artifact from the preemption exit: chaos fires AND the
        # preemption events are in the sequence
        path = recorder.last_dump_path
        assert path and os.path.exists(path)
        with open(path) as fp:
            lines = [json.loads(line) for line in fp if line.strip()]
        assert lines[0]["flight_dump"] and lines[0]["reason"] == "preemption"
        kinds = [line.get("kind") for line in lines[1:]]
        for expected in ("chaos.fire", "train.fit_begin", "train.preempt",
                         "train.preempt_exit"):
            assert expected in kinds, (expected, sorted(set(kinds)))
        # events are ordered: the fit began before it was preempted
        assert kinds.index("train.fit_begin") < kinds.index("train.preempt")

        # -- monitor-side: the resubmit gap is badput too ----------------
        before = BADPUT_SECONDS.value(run="gp-run",
                                      bucket="preemption_downtime")
        record_badput("preemption_downtime", 2.5, run="gp-run")
        assert BADPUT_SECONDS.value(
            run="gp-run", bucket="preemption_downtime") == \
            pytest.approx(before + 2.5)

        # -- run 2: the resubmitted process resumes and re-warms ---------
        monkeypatch.setenv("MLT_RESUME_FROM_CHECKPOINT",
                           str(tmp_path / "ckpt"))
        monkeypatch.setenv("MLT_RESUME_STEP", "4")

        class FakeManager:
            directory = str(tmp_path / "ckpt")

            def restore(self, state, step=None):
                return state

        resumed = Trainer(config, TrainConfig(total_steps=12))
        resumed.init(0)
        out2 = resumed.fit(
            synthetic_token_stream(8, 32, config.vocab_size),
            steps=4, log_every=2, checkpoint_manager=FakeManager())
        assert "preempted" not in out2
        s2 = resumed.goodput.summary()
        assert s2["goodput_s"] + s2["badput_s"] == \
            pytest.approx(s2["wall_s"], abs=0.1)
        # the first dispatch of a RESUMED run is re_warm, never compile —
        # and through the persistent cache it must be far below the cold
        # compile the first run paid
        assert "compile" not in s2["badput"]
        assert s2["badput"]["re_warm"] > 0
        assert s2["badput"]["re_warm"] < s1["badput"]["compile"]
    finally:
        recorder.configure(directory="")
        mlconf.training.compile_cache_dir = previous_cache
        if previous_cache:
            compile_cache.configure(previous_cache)
        else:
            compile_cache.disable()


# -- monitor: stall escalation leaves the artifact ---------------------------

@pytest.fixture()
def cluster(monkeypatch):
    return fake_k8s.install(monkeypatch)


@pytest.fixture()
def db(tmp_path):
    from mlrun_tpu.db.sqlitedb import SQLiteRunDB

    return SQLiteRunDB(dsn=str(tmp_path / "gp.db"),
                       logs_dir=str(tmp_path / "logs"))


@pytest.fixture()
def handler(cluster, db):
    from mlrun_tpu.service.runtime_handlers import (
        KubernetesProvider,
        TpuJobHandler,
    )

    return TpuJobHandler(db, KubernetesProvider(namespace="testns"))


def _launch(handler, db, uid, retry_policy=None):
    fn = mlrun_tpu.new_function("train", kind="tpujob", project="p1")
    fn.with_tpu_topology("tpu-v5-lite-podslice", "2x4")
    run = RunObject()
    run.metadata.uid = uid
    run.metadata.name = "train"
    run.metadata.project = "p1"
    if retry_policy:
        run.spec.retry_policy = retry_policy
    db.store_run(run.to_dict(), uid, "p1")
    handler.run(fn, run)
    return f"train-{uid[:8]}"


def _age_resource(handler, uid, seconds):
    rid, project, started = handler._resources[uid]
    handler._resources[uid] = (rid, project, started - seconds)


def _stall(handler, db, uid, policy):
    stale = (datetime.now(timezone.utc) - timedelta(seconds=60)).isoformat()
    name = _launch(handler, db, uid=uid, retry_policy=policy)
    db.update_run({"status.last_heartbeat": stale}, uid, "p1")
    _age_resource(handler, uid, 60)
    handler.monitor_runs()
    return name


@pytest.mark.chaos
def test_stall_abort_leaves_flight_artifact(handler, cluster, db, tmp_path):
    """ISSUE 10 acceptance: a stall-aborted run leaves a flight JSONL
    artifact whose event sequence includes the stall detection and the
    decision taken — and the silent window is attributed as ``stall``
    badput for the run."""
    recorder = get_flight_recorder()
    recorder.configure(directory=str(tmp_path / "flight"))
    uid = "90dfee7abc12"
    try:
        stall_before = BADPUT_SECONDS.value(run=uid, bucket="stall")
        _stall(handler, db, uid,
               {"stall_timeout": 5.0, "on_stall": "abort"})
        run = db.read_run(uid, "p1")
        assert run["status"]["state"] == "aborted"

        path = recorder.last_dump_path
        assert path and os.path.exists(path)
        with open(path) as fp:
            lines = [json.loads(line) for line in fp if line.strip()]
        assert lines[0]["reason"] == "stall-abort"
        assert lines[0]["run"] == uid
        # filter to THIS run's events: the process-shared ring carries
        # earlier tests' lifecycle decisions too (by design)
        ours = [line for line in lines[1:] if line.get("uid") == uid]
        kinds = [line.get("kind") for line in ours]
        detect = kinds.index("run.stall_detected")
        abort = kinds.index("run.stall_abort")
        assert detect < abort  # detection precedes the decision
        assert ours[detect]["silent_s"] > 5.0

        # the silent window is stall badput, keyed by run uid
        assert BADPUT_SECONDS.value(run=uid, bucket="stall") > stall_before
    finally:
        recorder.configure(directory="")


@pytest.mark.chaos
def test_stall_resubmit_artifact_carries_retry_decision(
        handler, cluster, db, tmp_path):
    recorder = get_flight_recorder()
    recorder.configure(directory=str(tmp_path / "flight"))
    uid = "41bee2901234"
    try:
        name = _stall(handler, db, uid,
                      {"max_retries": 1, "backoff": 0,
                       "stall_timeout": 5.0, "on_stall": "resubmit"})
        assert f"{name}-r1" in cluster.jobsets  # the retry happened
        path = recorder.last_dump_path
        assert path and os.path.exists(path)
        with open(path) as fp:
            lines = [json.loads(line) for line in fp if line.strip()]
        assert lines[0]["reason"] == "stall-resubmit"
        # the ring is process-shared: earlier tests' lifecycle events
        # are legitimately in the artifact too — order THIS run's
        # detection against THIS run's retry decision
        ours = [line for line in lines[1:] if line.get("uid") == uid]
        kinds = [line.get("kind") for line in ours]
        assert kinds.index("run.stall_detected") < \
            kinds.index("run.resubmit")
        resubmits = [line for line in ours
                     if line.get("kind") == "run.resubmit"]
        assert any(r.get("failure_class") == "stalled" for r in resubmits)
    finally:
        recorder.configure(directory="")


def test_retry_backoff_attributed_as_badput(handler, cluster, db):
    """A scheduled retry's backoff window is resubmit-gap (or, for a
    preemption, downtime) badput — the monitor attributes it because
    the run process is dead for its duration."""
    uid = "77aa88bb99cc"
    before = BADPUT_SECONDS.value(run=uid, bucket="resubmit_gap")
    name = _launch(handler, db, uid=uid,
                   retry_policy={"max_retries": 1, "backoff": 30.0,
                                 "jitter": 0.0})
    cluster.kill_jobset(name)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    assert run["status"]["state"] == "pending"  # parked for retry
    gap = BADPUT_SECONDS.value(run=uid, bucket="resubmit_gap") - before
    assert gap == pytest.approx(30.0, rel=0.2)  # the computed backoff


# -- SLO(kind="goodput") through the unchanged burn-rate path ----------------

def test_goodput_slo_burns_on_badput():
    store = TimeSeriesStore(resolution_s=1.0)
    good = bad = 0.0
    for t in range(100):
        # healthy until t=60, then 50% badput (way over a 10% budget)
        good += 1.0
        bad += 1.0 if t >= 60 else 0.02
        store.record("mlt_badput_seconds_total", bad, at=t,
                     labels={"run": "r1", "bucket": "preemption_downtime"},
                     kind="counter")
        store.record("mlt_goodput_wall_seconds_total", good + bad, at=t,
                     labels={"run": "r1"}, kind="counter")
    slo = SLO("train-goodput", "goodput", target=0.90, run="r1")
    assert slo.budget == pytest.approx(0.10)
    evaluator = SLOEvaluator(store, [slo], fast_window=10, slow_window=30,
                             fast_burn=2.0, slow_burn=1.5)
    assert not evaluator.evaluate(50)[0].breaching
    status = evaluator.evaluate(99)[0]
    assert status.breaching
    assert status.burn_fast == pytest.approx(0.5 / 0.10, rel=0.1)


def test_goodput_slo_validation():
    with pytest.raises(ValueError):
        SLO("x", "goodput", target=1.5)     # fraction floor only
    with pytest.raises(ValueError):
        SLO("x", "latency", target=1.0, run="r1")  # run= is goodput-only
    slo = SLO("x", "goodput", target=0.9,
              bad_labels={"bucket": "preemption_downtime"})
    assert slo.bad == "mlt_badput_seconds_total"
    assert slo.bad_labels == {"bucket": "preemption_downtime"}
    from_config = SLO.from_config(
        {"name": "y", "kind": "goodput", "target": 0.8, "run": "r2"})
    assert from_config.total_labels == {"run": "r2"}


# -- satellite: one shared nearest-rank percentile ---------------------------

def test_nearest_rank_fixes_one_rank_high_bias():
    samples = [float(v) for v in range(1, 101)]  # 1..100 sorted
    # p95 of 100 samples is the 95th order statistic — the old
    # int(n*0.95) indexing returned 96
    assert nearest_rank(samples, 0.95) == 95.0
    assert nearest_rank(samples, 0.50) == 50.0
    assert nearest_rank(samples, 1.0) == 100.0
    assert nearest_rank([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        nearest_rank([], 0.95)

    from mlrun_tpu.serving.llm_batch import _percentile

    assert _percentile(samples, 0.95) == nearest_rank(samples, 0.95)


# -- satellite: memory exposition --------------------------------------------

def test_memory_collector_publishes_and_retires():
    from mlrun_tpu.obs import REGISTRY, register_memory_collector

    class Owner:
        pass

    owner = Owner()
    register_memory_collector(owner)
    text = REGISTRY.render()
    assert "# TYPE mlt_device_mem_bytes gauge" in text
    # host RSS is always numeric on linux; device stats may be absent
    # on the CPU backend — the collector sets only numeric values
    rss = [line for line in text.splitlines()
           if line.startswith("mlt_host_rss_bytes")]
    assert rss and float(rss[0].split()[-1]) > 0

    # the collector retires once every registered owner is gone — WITH
    # its series (a frozen memory snapshot must not be scraped forever)
    import mlrun_tpu.obs as obs_pkg

    del owner
    gc.collect()
    REGISTRY.render()
    assert obs_pkg._memory_active[0] is False
    after = REGISTRY.render()
    assert not [line for line in after.splitlines()
                if line.startswith(("mlt_host_rss_bytes ",
                                    "mlt_device_mem_bytes{"))]


# -- satellite: profile_run hardening + on-demand arming ---------------------

def test_profile_run_stop_failure_does_not_mask_block_error(monkeypatch,
                                                            tmp_path):
    import jax

    from mlrun_tpu.utils.profiler import profile_run

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def broken_stop():
        raise RuntimeError("profiler backend wedged")

    monkeypatch.setattr(jax.profiler, "stop_trace", broken_stop)

    class Ctx:
        artifact_path = str(tmp_path)

        def __init__(self):
            self.metrics = {}
            self.artifacts = []

        def log_metrics(self, metrics, step=None):
            self.metrics.update(metrics)

        def log_artifact(self, key, **kwargs):
            self.artifacts.append(key)

    ctx = Ctx()
    with pytest.raises(ValueError, match="the real bug"):
        with profile_run(context=ctx):
            raise ValueError("the real bug")
    # capture wall time recorded on context METRICS despite both the
    # block error and the stop_trace failure
    assert "xla_trace_wall_s" in ctx.metrics

    # happy path records the wall time too, and registers the artifact
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    ctx2 = Ctx()
    with profile_run(context=ctx2, key="trace2"):
        pass
    assert ctx2.metrics["xla_trace_wall_s"] >= 0
    assert ctx2.artifacts == ["trace2"]


def test_arm_profile_tick_lifecycle(monkeypatch, tmp_path):
    import jax

    from mlrun_tpu.utils import profiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))

    with pytest.raises(ValueError):
        profiler.arm_profile()  # needs a bound
    assert profiler.tick("trainer") is None  # dark path

    out = profiler.arm_profile(steps=2, output_dir=str(tmp_path / "tr"))
    assert out["armed"] is True
    assert profiler.profile_status()["armed"]["steps"] == 2

    assert profiler.tick("trainer") == "started"
    assert calls[0][0] == "start"
    # another source's ticks must not count down the trainer's capture
    assert profiler.tick("engine-7") is None
    assert profiler.tick("trainer") == "active"
    assert profiler.tick("trainer") == "stopped"
    assert calls[-1] == ("stop",)
    status = profiler.profile_status()
    assert status["active"] is None and status["armed"] is None
    assert status["last"]["dir"] == str(tmp_path / "tr")
    assert status["last"]["wall_s"] >= 0

    # disarm drops a pending request before any loop claims it
    profiler.arm_profile(seconds=30.0)
    assert profiler.disarm_profile() is True
    assert profiler.tick("trainer") is None

    # a capture whose claiming loop stops ticking must not wedge the
    # profiler forever: any other live source rescues it past the
    # orphan timeout, stopping the trace and releasing the claim
    profiler.arm_profile(steps=100, output_dir=str(tmp_path / "orph"))
    assert profiler.tick("dead-loop") == "started"
    assert profiler.tick("live-loop") is None  # claim still fresh
    with profiler._profile_lock:
        profiler._active["last_tick"] -= \
            profiler.ORPHAN_TICK_TIMEOUT_S + 1
    assert profiler.tick("live-loop") == "stopped"
    status = profiler.profile_status()
    assert status["active"] is None
    assert status["last"]["reason"] == "orphaned"

    # ...and the HTTP-exposed disarm can stop an active capture (the
    # operator remedy): arm, claim, disarm(stop_active=True)
    profiler.arm_profile(steps=100, output_dir=str(tmp_path / "dis"))
    assert profiler.tick("wedged") == "started"
    assert profiler.disarm_profile(stop_active=True) is True
    status = profiler.profile_status()
    assert status["active"] is None
    assert status["last"]["reason"] == "disarmed"
    assert calls[-1] == ("stop",)


# -- debug endpoints on the serving gateway ----------------------------------

@pytest.fixture()
def gateway_url():
    import asyncio
    import socket

    from aiohttp import web

    from mlrun_tpu.serving.asgi import build_serving_app

    def echo(data):
        return {"ok": True}

    fn = mlrun_tpu.new_function("dbg", kind="serving")
    graph = fn.set_topology("flow")
    graph.to(name="echo", handler=echo).respond()
    server = fn.to_mock_server(namespace={"echo": echo})

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    loop = asyncio.new_event_loop()
    started = threading.Event()
    box = {}

    async def serve():
        runner = web.AppRunner(build_serving_app(server))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        started.set()
        while not box.get("stop"):
            await asyncio.sleep(0.02)
        await runner.cleanup()

    thread = threading.Thread(
        target=lambda: (asyncio.set_event_loop(loop),
                        loop.run_until_complete(serve())), daemon=True)
    thread.start()
    assert started.wait(15)
    try:
        yield f"http://127.0.0.1:{port}"
    finally:
        box["stop"] = True
        thread.join(timeout=5)


def test_debug_endpoints_on_gateway(gateway_url, monkeypatch):
    import requests

    from mlrun_tpu.obs import flight_record
    from mlrun_tpu.utils import profiler

    flight_record("test.debug_endpoint", marker="gw-visible")
    resp = requests.get(gateway_url + "/debug/flight",
                        params={"kind": "test.*"}, timeout=10)
    assert resp.status_code == 200
    payload = resp.json()
    assert any(e["kind"] == "test.debug_endpoint"
               and e["marker"] == "gw-visible"
               for e in payload["events"])
    assert payload["ring"] >= len(payload["events"])
    # limit + bad-limit contract
    limited = requests.get(gateway_url + "/debug/flight",
                           params={"kind": "test.*", "limit": 1},
                           timeout=10).json()
    assert len(limited["events"]) == 1
    assert requests.get(gateway_url + "/debug/flight",
                        params={"limit": "bogus"},
                        timeout=10).status_code == 400

    # profile arming over HTTP (no loop ticks here — arm, read, disarm)
    profiler.disarm_profile()
    resp = requests.post(gateway_url + "/debug/profile",
                         json={"steps": 3}, timeout=10)
    assert resp.status_code == 200 and resp.json()["armed"] is True
    status = requests.get(gateway_url + "/debug/profile", timeout=10).json()
    assert status["armed"]["steps"] == 3
    assert requests.post(gateway_url + "/debug/profile",
                         json={}, timeout=10).status_code == 400
    # the HTTP surface must not be an arbitrary-path write primitive:
    # client output_dir rejected, key restricted to a safe path segment
    assert requests.post(
        gateway_url + "/debug/profile",
        json={"steps": 1, "output_dir": "/etc/cron.d/x"},
        timeout=10).status_code == 400
    assert requests.post(
        gateway_url + "/debug/profile",
        json={"steps": 1, "key": "../../escape"},
        timeout=10).status_code == 400
    # a pure-dot key matches the charset but resolves OUT of traces/
    assert requests.post(
        gateway_url + "/debug/profile",
        json={"steps": 1, "key": ".."},
        timeout=10).status_code == 400
    resp = requests.post(gateway_url + "/debug/profile",
                         json={"disarm": True}, timeout=10)
    assert resp.json()["disarmed"] is True


# -- engine crash leaves an artifact; clean stop does not --------------------

@pytest.mark.chaos
def test_engine_crash_dumps_flight_artifact(tmp_path):
    import jax

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine

    recorder = get_flight_recorder()
    recorder.configure(directory=str(tmp_path / "flight"))
    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(config, params, max_len=64, slots=2,
                                      prefill_buckets=(32,))
    try:
        dumps_before = recorder.dumps
        with chaos.inject("llm.prefill",
                          error=RuntimeError("injected device loss")):
            future = engine.submit(list(range(1, 9)), max_new_tokens=4)
            with pytest.raises(Exception):
                future.result(timeout=30)
        deadline = time.time() + 10
        while recorder.dumps == dumps_before and time.time() < deadline:
            time.sleep(0.05)
        assert recorder.dumps > dumps_before
        with open(recorder.last_dump_path) as fp:
            lines = [json.loads(line) for line in fp if line.strip()]
        assert lines[0]["reason"] == "engine-crash"
        kinds = {line.get("kind") for line in lines[1:]}
        assert "engine.crash" in kinds
        assert "chaos.fire" in kinds

        # a CLEAN stop must not spray post-mortems
        dumps_after_crash = recorder.dumps
        engine2 = ContinuousBatchingEngine(config, params, max_len=64,
                                           slots=2, prefill_buckets=(32,))
        engine2.start()
        engine2.stop()
        assert recorder.dumps == dumps_after_crash
    finally:
        engine.stop()
        recorder.configure(directory="")


def test_release_run_bounded_series_retirement():
    """A rotating run population must not consume the goodput families'
    label budget: the most recent RECENT_RUNS_KEPT finished runs stay
    scrapeable (the terminal attribution must survive until federation
    reads it), older ones retire."""
    from mlrun_tpu.obs import goodput

    prefix = "ret-test-"
    for index in range(goodput.RECENT_RUNS_KEPT + 5):
        uid = f"{prefix}{index:04d}"
        record_badput("stall", 1.0, run=uid)
        goodput.release_run(uid)
    # the oldest overflowed out; the newest is still scrapeable
    assert BADPUT_SECONDS.value(run=f"{prefix}0000", bucket="stall") == 0.0
    newest = f"{prefix}{goodput.RECENT_RUNS_KEPT + 4:04d}"
    assert BADPUT_SECONDS.value(run=newest, bucket="stall") == 1.0
    # the cross-family admission gate: a run past the budget is dropped
    # on EVERY family atomically (badput landing without its wall
    # series would corrupt the SLO bad/total ratio), and retirement
    # frees the slot
    with goodput._admit_lock:
        overflow = [f"gate-{i}" for i in range(
            goodput.RUN_LABEL_BUDGET - len(goodput._admitted_runs))]
        goodput._admitted_runs.update(overflow)  # fill to the budget
    try:
        record_badput("stall", 1.0, run="gate-victim")
        assert BADPUT_SECONDS.value(run="gate-victim",
                                    bucket="stall") == 0.0
        from mlrun_tpu.obs import WALL_SECONDS

        assert WALL_SECONDS.value(run="gate-victim") == 0.0
        goodput.retire_run(overflow[0])          # frees one slot
        record_badput("stall", 1.0, run="gate-victim")
        assert BADPUT_SECONDS.value(run="gate-victim",
                                    bucket="stall") == 1.0
        assert WALL_SECONDS.value(run="gate-victim") == 1.0
    finally:
        for uid in overflow:
            goodput.retire_run(uid)
        goodput.retire_run("gate-victim")
    # cleanup: drain this test's uids from the shared recent queue
    for index in range(goodput.RECENT_RUNS_KEPT + 5):
        uid = f"{prefix}{index:04d}"
        with goodput._recent_lock:
            if uid in goodput._recent_runs:
                goodput._recent_runs.remove(uid)
        goodput.retire_run(uid)


def test_fit_inside_caller_except_block_does_not_dump_crash():
    """fit() returning normally while a CALLER frame is handling an
    unrelated exception must not dump a spurious train-crash artifact
    (the sys.exc_info()-in-finally false positive)."""
    import jax

    from mlrun_tpu.models import tiny_llama
    from mlrun_tpu.training import TrainConfig, Trainer

    recorder = get_flight_recorder()
    dumps_before = recorder.dumps
    trainer = Trainer(tiny_llama(attention_impl="reference"),
                      TrainConfig(total_steps=2))
    trainer.init(0)
    try:
        raise RuntimeError("outer failure being handled")
    except RuntimeError:
        # steps=0: the loop body never runs, no compile — fast path
        out = trainer.fit(iter([]), steps=0, log_every=1)
    assert out == {}
    assert recorder.dumps == dumps_before
    assert not recorder.events(kind="train.exception", limit=1) or \
        recorder.events(kind="train.exception")[-1].get("error") != \
        "outer failure being handled"


def test_flight_ring_bounded_and_filtered():
    recorder = FlightRecorder(ring=32)
    for index in range(100):
        recorder.record("spam.tick", index=index)
    assert len(recorder) == 32
    events = recorder.events(kind="spam.tick", limit=5)
    assert len(events) == 5
    assert events[-1]["index"] == 99          # newest kept
    assert events[0]["index"] == 95
    assert recorder.events(kind="nope") == []
    # seq strictly increases -> a reader can order interleaved events
    seqs = [event["seq"] for event in recorder.events()]
    assert seqs == sorted(seqs)
