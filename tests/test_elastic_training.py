"""Elastic multi-slice training (ISSUE 13): survive slice preemption by
resharding onto the survivors.

The closed loop under test: an injected ``train.slice_fail`` mid-fit →
the run reshards onto the surviving virtual slice (sharding-agnostic
checkpoint restore at the shrunk world size) → the post-reshard loss
trajectory is BITWISE equal to a fresh run started from the same
checkpoint at the smaller world size → the replacement slice joins and
the run grows back — with the detect→reshard→continue→grow chain
asserted in flight-recorder order. Service side: a failed slice of a
live JobSet gets only a replacement slice Job (survivors keep running),
never a full resubmit.
"""

import os
import tempfile

import jax
import numpy as np
import pytest

import mlrun_tpu
from mlrun_tpu.chaos import chaos, fail_nth
from mlrun_tpu.common.retry import FailureClass, classify_failure
from mlrun_tpu.k8s.jobset import (
    TopologyError,
    hosts_for_topology,
    parse_topology,
)
from mlrun_tpu.model import RunObject
from mlrun_tpu.models import tiny_llama
from mlrun_tpu.obs import get_flight_recorder
from mlrun_tpu.parallel.mesh import _detect_num_slices, make_mesh, refit_shape
from mlrun_tpu.training import (
    CheckpointManager,
    ElasticGuard,
    TrainConfig,
    Trainer,
    synthetic_token_stream,
)

from . import fake_k8s

pytestmark = pytest.mark.chaos


# -- satellite: typed topology validation ------------------------------------

def test_parse_topology_rejects_bad_dims():
    assert parse_topology("2x4") == (2, 4)
    assert parse_topology("4X4x4") == (4, 4, 4)
    for bad in ("2x0", "0x4", "-2x4", "2.5x4", "2x", "x4", "", "ax4"):
        with pytest.raises(TopologyError):
            parse_topology(bad)
    # typed subclass: existing ValueError handlers keep working
    with pytest.raises(ValueError):
        parse_topology("2x0")


def test_hosts_for_topology_rejects_bad_chips_per_host():
    assert hosts_for_topology("2x4", chips_per_host=4) == 2
    for bad in (0, -4, "four"):
        with pytest.raises(TopologyError):
            hosts_for_topology("2x4", chips_per_host=bad)
    # a 0-host JobSet can no longer be silently produced
    with pytest.raises(TopologyError):
        hosts_for_topology("0x0", chips_per_host=4)
    # ...including through the production build path: an explicit 0
    # must not silently become the config default
    from mlrun_tpu.k8s.jobset import build_jobset

    with pytest.raises(TopologyError):
        build_jobset("t", "ns", {"containers": [{}]},
                     accelerator="tpu-v5-lite-podslice", topology="2x4",
                     chips_per_host=0)


# -- satellite: slice detection on virtual backends --------------------------

def test_detect_num_slices_cpu_fallback_and_env_override(monkeypatch):
    # CPU virtual devices carry no slice topology → 1 slice, never raises
    monkeypatch.delenv("MLT_NUM_SLICES", raising=False)
    assert _detect_num_slices(jax.devices()) == 1

    class Weird:  # attribute probing must not raise either
        @property
        def slice_index(self):
            raise RuntimeError("no topology")

    assert _detect_num_slices([Weird()]) == 1
    monkeypatch.setenv("MLT_NUM_SLICES", "2")
    assert _detect_num_slices(jax.devices()) == 2
    monkeypatch.setenv("MLT_NUM_SLICES", "banana")  # malformed → detection
    assert _detect_num_slices(jax.devices()) == 1


def test_make_mesh_virtual_multi_slice(monkeypatch):
    """MLT_NUM_SLICES pushes make_mesh down the hybrid path; on CPU the
    slice-topology-free fallback still builds a usable mesh."""
    monkeypatch.setenv("MLT_NUM_SLICES", "2")
    mesh = make_mesh({"data": 2, "fsdp": 4}, devices=jax.devices())
    assert dict(mesh.shape) == {"data": 2, "fsdp": 4}


def test_reshard_survives_global_num_slices_override(monkeypatch):
    """Regression: MLT_NUM_SLICES describes the FULL device set — a
    post-slice-loss reshard over the survivors must not re-apply it
    (it used to fail the DCN divisibility check mid-recovery, killing
    the run the elastic path exists to save)."""
    monkeypatch.setenv("MLT_NUM_SLICES", "2")
    cfg = tiny_llama(attention_impl="reference")
    devices = jax.devices()
    trainer = Trainer(cfg, TrainConfig(),
                      mesh=make_mesh({"data": 2, "fsdp": 4},
                                     devices=devices))
    trainer.init(0)
    # explicit survivor slice count (what fit passes from the guard)
    info = trainer.reshard(devices[:4], num_slices=1)
    assert info["world_to"] == 4
    # and the detection clamp: a direct reshard with the stale global
    # override still recovers instead of raising
    trainer2 = Trainer(cfg, TrainConfig(),
                       mesh=make_mesh({"data": 2, "fsdp": 4},
                                      devices=devices))
    trainer2.init(0)
    assert trainer2.reshard(devices[:4])["world_to"] == 4


def test_refit_shape_shrink_and_grow():
    # the DCN/data (first) axis absorbs the slice loss
    assert refit_shape({"data": 2, "fsdp": 4}, 4) == {"data": 1, "fsdp": 4}
    assert refit_shape({"data": 1, "fsdp": 4}, 8) == {"data": 2, "fsdp": 4}
    # single-axis meshes rescale that axis
    assert refit_shape({"fsdp": 8}, 4) == {"fsdp": 4}
    # prefer_axis overrides declaration order
    assert refit_shape({"data": 2, "fsdp": 2}, 8, prefer_axis="fsdp") == \
        {"data": 2, "fsdp": 4}
    with pytest.raises(ValueError):
        refit_shape({"data": 3, "fsdp": 3}, 4)


# -- satellite: classifier ----------------------------------------------------

def test_classifier_slice_preempted_outranks_generic_preemption():
    assert classify_failure(reason="slice 1 preempted on node drain") == \
        FailureClass.slice_preempted
    assert classify_failure(run_error="FailedSlices: [1]") == \
        FailureClass.slice_preempted
    # whole-job eviction stays the generic class
    assert classify_failure(reason="Evicted") == FailureClass.preemption
    assert FailureClass.slice_preempted in FailureClass.retryable()


def test_retry_policy_schema_accepts_slice_preempted():
    from mlrun_tpu.common.schemas import RetryPolicy

    policy = RetryPolicy(max_retries=1, retry_on=["slice_preempted"])
    assert policy.retry_on == ["slice_preempted"]


# -- elastic guard ------------------------------------------------------------

def test_elastic_guard_partition_events_and_bounds():
    devices = jax.devices()
    guard = ElasticGuard(devices=devices, num_slices=2)
    assert guard.num_slices == 2
    assert len(guard.devices) == len(devices)
    assert guard.lost_fraction() == 0.0

    guard.fail_slice(1)
    assert guard.degraded and guard.failed_slices == [1]
    assert guard.devices == list(devices[:4])
    assert guard.lost_fraction() == pytest.approx(0.5)
    event = guard.poll()
    assert (event.kind, event.slice_index) == ("fail", 1)
    assert list(event.devices) == list(devices[:4])
    assert guard.poll() is None          # one event per change
    guard.fail_slice(1)                  # idempotent
    assert guard.poll() is None

    with pytest.raises(ValueError):      # losing EVERY slice ≠ elastic
        guard.fail_slice(0)
    with pytest.raises(ValueError):
        guard.fail_slice(7)

    guard.join_slice(1)
    event = guard.poll()
    assert (event.kind, event.slice_index) == ("join", 1)
    assert len(event.devices) == len(devices)

    with pytest.raises(ValueError):      # devices must split evenly
        ElasticGuard(devices=devices[:5], num_slices=2)


# -- satellite: checkpoint restore across world-size change -------------------

def test_checkpoint_restore_across_world_size(tmp_path):
    """The load-bearing invariant: a checkpoint written at 4 devices
    restores at 2 and at 8 with value-identical pytrees."""
    cfg = tiny_llama(attention_impl="reference")
    devices = jax.devices()
    trainer4 = Trainer(cfg, TrainConfig(),
                       mesh=make_mesh({"fsdp": 4}, devices=devices[:4]))
    trainer4.init(0)
    trainer4.fit(synthetic_token_stream(4, 32, cfg.vocab_size), steps=2,
                 log_every=10, prefetch=0)
    manager = CheckpointManager(str(tmp_path / "xw"))
    assert manager.save(2, trainer4.state, force=True)
    manager.wait()
    want = jax.tree_util.tree_leaves(trainer4.state.params)

    for n in (2, 8):
        other = Trainer(cfg, TrainConfig(),
                        mesh=make_mesh({"fsdp": n}, devices=devices[:n]))
        other.init(1)
        restored = manager.restore(other.state, step=2)
        assert int(restored.step) == 2
        got = jax.tree_util.tree_leaves(restored.params)
        for g, w in zip(got, want):
            assert g.sharding.mesh.devices.size == n
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # optimizer state reshards too (same invariant, different tree)
        for g, w in zip(jax.tree_util.tree_leaves(restored.opt_state),
                        jax.tree_util.tree_leaves(trainer4.state.opt_state)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    manager.close()


# -- the closed loop ----------------------------------------------------------

def test_elastic_closed_loop_shrink_parity_grow(tmp_path):
    """Acceptance: injected ``train.slice_fail`` mid-fit → reshard onto
    survivors → loss-trajectory parity vs a fresh same-checkpoint run at
    the smaller world size → grow-back on rejoin, flight chain in
    order, attribution closed with ``reshard``/``degraded`` priced."""
    cfg = tiny_llama(attention_impl="reference")
    devices = jax.devices()
    mesh = make_mesh({"data": 2, "fsdp": 4}, devices=devices)
    guard = ElasticGuard(devices=devices, num_slices=2)
    trainer = Trainer(cfg, TrainConfig(), mesh=mesh)
    trainer.init(0)
    manager = CheckpointManager(str(tmp_path / "el"))

    def save_at_2(step, metrics, tr):
        if int(tr.state.step) == 2:
            manager.save(2, tr.state, force=True)
            manager.wait()

    recorder = get_flight_recorder()
    recorder.clear()
    recorder.configure(directory=str(tmp_path / "flight"))
    try:
        # polls are 1-based: the 5th poll is loop step 4 (4 batches
        # consumed), the 8th is loop step 7
        with chaos.inject(
                "train.slice_fail", fail_nth(5),
                action=lambda p, ctx: ctx["box"].__setitem__("fail", 1)), \
             chaos.inject(
                "train.slice_fail", fail_nth(8),
                action=lambda p, ctx: ctx["box"].__setitem__("join", 1)):
            out = trainer.fit(
                synthetic_token_stream(8, 32, cfg.vocab_size), steps=10,
                log_every=1, callbacks=[save_at_2],
                checkpoint_manager=manager, elastic_guard=guard,
                prefetch=0)
    finally:
        recorder.configure(directory="")

    history = trainer.metrics_history
    assert [h["world_size"] for h in history] == \
        [8, 8, 8, 8, 4, 4, 4, 8, 8, 8]
    # restored to the step-2 checkpoint, then advanced one step per batch
    assert [h["step"] for h in history] == [1, 2, 3, 4, 3, 4, 5, 6, 7, 8]
    assert out["world_size"] == 8 and out["step"] == 8

    # the detect→reshard→continue→grow chain, in ring order
    kinds = [e["kind"] for e in recorder.events(kind="train.*")
             if e["kind"] not in ("train.step", "train.fit_begin",
                                  "train.reshard_warm")]
    assert kinds == ["train.slice_fail", "train.reshard",
                     "train.slice_join", "train.grow"]
    fail_event = recorder.events(kind="train.slice_fail")[0]
    assert fail_event["survivors"] == 4
    assert len(fail_event["survivor_devices"]) == 4
    reshard_event = recorder.events(kind="train.reshard")[0]
    assert reshard_event["decision"] == "restore_checkpoint"
    assert reshard_event["world_from"] == 8
    assert reshard_event["world_to"] == 4
    assert reshard_event["restored_step"] == 2
    grow_event = recorder.events(kind="train.grow")[0]
    assert grow_event["decision"] == "carry_live_state"
    assert grow_event["world_to"] == 8
    # the recompiles happen where they should: after reshard and grow
    warm = recorder.events(kind="train.reshard_warm")
    assert [e["loop_step"] for e in warm] == [4, 7]

    # flight-recorder dump on slice loss: survivor set + reshard decision
    dump_path = recorder.last_dump_path
    assert dump_path and "slice-preemption" in dump_path
    import json

    with open(dump_path) as fp:
        header = json.loads(fp.readline())
    assert header["reason"] == "slice-preemption"
    assert len(header["survivors"]) == 4
    assert header["decision"] == "restore_checkpoint"

    # goodput: reshard + degraded priced, attribution sums to wall
    summary = trainer.goodput.summary()
    assert summary["badput"]["reshard"] > 0
    assert summary["badput"]["degraded"] > 0
    assert summary["goodput_s"] + summary["badput_s"] == \
        pytest.approx(summary["wall_s"], abs=0.1)

    # PARITY: a fresh run restored from the same checkpoint at the
    # smaller world size, fed the same batches, produces the same losses
    # bit for bit (same program, same mesh, same values)
    ref = Trainer(cfg, TrainConfig(),
                  mesh=make_mesh({"data": 1, "fsdp": 4},
                                 devices=devices[:4]))
    ref.init(7)  # different seed: the restore must fully overwrite
    ref.state = manager.restore(ref.state, step=2)
    ref_stream = synthetic_token_stream(8, 32, cfg.vocab_size)
    for _ in range(4):  # the elastic run consumed 4 batches pre-fail
        next(ref_stream)
    ref.fit(ref_stream, steps=3, log_every=1, prefetch=0)
    elastic_losses = [h["loss"] for h in history[4:7]]
    ref_losses = [h["loss"] for h in ref.metrics_history]
    assert elastic_losses == ref_losses
    manager.close()


def test_reshard_without_checkpoint_carries_live_state():
    """Simulation-only degraded mode: no checkpoint exists, so the
    reshard carries the live state (on hardware the shards would be
    gone — the decision is recorded so post-mortems can tell)."""
    cfg = tiny_llama(attention_impl="reference")
    devices = jax.devices()
    trainer = Trainer(cfg, TrainConfig(),
                      mesh=make_mesh({"data": 2, "fsdp": 4},
                                     devices=devices))
    trainer.init(0)
    before = [np.asarray(x) for x in
              jax.tree_util.tree_leaves(trainer.state.params)]
    info = trainer.reshard(devices[:4], checkpoint_manager=None)
    assert info["decision"] == "carry_live_state"
    assert info["world_to"] == 4
    assert dict(trainer.mesh.shape) == {"data": 1, "fsdp": 4}
    after = jax.tree_util.tree_leaves(trainer.state.params)
    for b, a in zip(before, after):
        assert a.sharding.mesh.devices.size == 4
        np.testing.assert_array_equal(b, np.asarray(a))


# -- service side: slice replacement, not full resubmit ----------------------

@pytest.fixture()
def cluster(monkeypatch):
    return fake_k8s.install(monkeypatch)


@pytest.fixture()
def db(tmp_path):
    from mlrun_tpu.db.sqlitedb import SQLiteRunDB

    return SQLiteRunDB(dsn=str(tmp_path / "el.db"),
                       logs_dir=str(tmp_path / "logs"))


@pytest.fixture()
def handler(cluster, db):
    from mlrun_tpu.service.runtime_handlers import (
        KubernetesProvider,
        TpuJobHandler,
    )

    return TpuJobHandler(db, KubernetesProvider(namespace="testns"))


def _launch_elastic(handler, db, uid="e1a57c001234", retry_policy=None,
                    num_slices=2, elastic=True):
    fn = mlrun_tpu.new_function("train", kind="tpujob", project="p1")
    fn.with_tpu_topology("tpu-v5-lite-podslice", "2x4",
                         num_slices=num_slices)
    if elastic:
        fn.with_elastic()
    run = RunObject()
    run.metadata.uid = uid
    run.metadata.name = "train"
    run.metadata.project = "p1"
    if retry_policy:
        run.spec.retry_policy = retry_policy
    db.store_run(run.to_dict(), uid, "p1")
    handler.run(fn, run)
    return f"train-{uid[:8]}"


def test_elastic_jobset_spec(cluster, db, handler):
    name = _launch_elastic(handler, db)
    js = cluster.jobsets[name]
    assert js["metadata"]["annotations"]["mlrun-tpu/elastic"] == "true"
    assert js["spec"]["replicatedJobs"][0]["replicas"] == 2
    # the restart budget is floored at num_slices so one child-Job
    # failure can't fail the whole JobSet before the service reacts
    assert js["spec"]["failurePolicy"]["maxRestarts"] >= 2


def test_slice_preempted_gets_replacement_not_full_resubmit(
        cluster, db, handler):
    uid = "e1a57c001234"
    name = _launch_elastic(handler, db,
                           retry_policy={"max_retries": 2, "backoff": 0})
    db.update_run({"status.checkpoint": {"path": "/ckpts/train",
                                         "step": 40}}, uid, "p1")
    get_flight_recorder().clear()
    cluster.fail_slice(name, 1)
    handler.monitor_runs()

    run = db.read_run(uid, "p1")
    # one slice gone, job alive: NOT a failure, NOT a full resubmit
    assert run["status"]["state"] == "running"
    assert run["status"].get("retry_count", 0) == 0
    assert run["status"]["degraded_slices"] == [1]
    assert run["status"]["slice_replacements"] == 1
    assert name in cluster.jobsets               # survivors kept running
    assert f"{name}-r1" not in cluster.jobsets   # no whole-job replacement
    # only the failed child Job was recycled, with warm re-entry env
    assert ("delete", "job", f"{name}-slice-1") in cluster.events
    env = {e["name"]: e.get("value")
           for e in cluster.jobsets[name]["spec"]["replicatedJobs"][0][
               "template"]["spec"]["template"]["spec"]["containers"][0][
               "env"]}
    assert env["MLT_RESUME_FROM_CHECKPOINT"] == "/ckpts/train"
    assert env["MLT_RESUME_STEP"] == "40"

    # the fake controller recreated the child Job → next tick records
    # the grow-back
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    assert run["status"]["degraded_slices"] == []
    kinds = [e["kind"] for e in get_flight_recorder().events(kind="run.*")]
    assert kinds == ["run.slice_preempted", "run.slice_replacement",
                     "run.slice_rejoined"]


def test_stuck_replacement_is_not_resubmitted_every_tick(
        cluster, db, handler):
    uid = "e1a57c005678"
    name = _launch_elastic(handler, db, uid=uid,
                           retry_policy={"max_retries": 2, "backoff": 0})
    cluster.stuck_slice_jobs.add(name)  # replacement never comes up
    cluster.fail_slice(name, 0)
    handler.monitor_runs()
    deletes = [e for e in cluster.events if e[0] == "delete"]
    assert len(deletes) == 1
    handler.monitor_runs()  # still failed, replacement pending
    handler.monitor_runs()
    deletes = [e for e in cluster.events if e[0] == "delete"]
    assert len(deletes) == 1  # no double submit for the same slice
    run = db.read_run(uid, "p1")
    assert run["status"]["slice_replacements"] == 1


def test_non_elastic_run_gets_no_slice_replacement(cluster, db, handler):
    """Elasticity is an opt-in: a run without with_elastic() has no
    reshard machinery in-pod — its failed slice must take the ordinary
    job-level failure path, never a survivors-keep-running replacement."""
    uid = "e1a57c00noel"
    name = _launch_elastic(handler, db, uid=uid, elastic=False,
                           retry_policy={"max_retries": 2, "backoff": 0})
    cluster.fail_slice(name, 1)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    assert run["status"].get("slice_replacements", 0) == 0
    assert run["status"].get("degraded_slices") is None
    assert not [e for e in cluster.events if e[0] == "delete"]


def test_stall_watchdog_survives_pending_replacement(cluster, db, handler):
    """A replacement stuck pending must not blind the stall watchdog:
    if the survivors wedge while waiting, the heartbeat escalation
    still fires."""
    import time
    from datetime import datetime, timedelta, timezone

    uid = "e1a57c00wdge"
    name = _launch_elastic(
        handler, db, uid=uid,
        retry_policy={"max_retries": 2, "backoff": 0,
                      "stall_timeout": 5.0, "on_stall": "abort"})
    cluster.stuck_slice_jobs.add(name)
    cluster.fail_slice(name, 1)
    handler.monitor_runs()  # submits the (stuck) replacement
    assert db.read_run(uid, "p1")["status"]["slice_replacements"] == 1
    # survivors go heartbeat-silent while the replacement is pending
    stale = (datetime.now(timezone.utc)
             - timedelta(seconds=60)).isoformat()
    db.update_run({"status.last_heartbeat": stale}, uid, "p1")
    rid, project, started = handler._resources[uid]
    handler._resources[uid] = (rid, project, started - 60)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    assert run["status"]["state"] == "aborted"
    assert run["status"]["failure_class"] == FailureClass.stalled


def test_multi_slice_failures_respect_budget_per_slice(cluster, db, handler):
    """Two slices failing in one tick must not jointly overrun
    max_retries — the budget is re-checked per replacement."""
    uid = "e1a57c00two0"
    name = _launch_elastic(handler, db, uid=uid, num_slices=3,
                           retry_policy={"max_retries": 1, "backoff": 0})
    cluster.stuck_slice_jobs.add(name)  # keep both listed as failed
    cluster.fail_slice(name, 1)
    cluster.fail_slice(name, 2)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    assert run["status"]["slice_replacements"] == 1  # budget is 1
    deletes = [e for e in cluster.events if e[0] == "delete"]
    assert len(deletes) == 1


def test_slice_replacement_respects_retry_budget(cluster, db, handler):
    uid = "e1a57c00beef"
    name = _launch_elastic(handler, db, uid=uid,
                           retry_policy={"max_retries": 0})
    cluster.fail_slice(name, 1)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    # no budget → no replacement; the run is degraded but not failed
    # (a later full-job failure takes the ordinary terminal path)
    assert run["status"].get("slice_replacements", 0) == 0
    assert not [e for e in cluster.events if e[0] == "delete"]


def test_all_slices_failed_is_a_dead_job_not_elastic(cluster, db, handler):
    uid = "e1a57c00dead"
    name = _launch_elastic(handler, db, uid=uid,
                           retry_policy={"max_retries": 2, "backoff": 0})
    cluster.fail_slice(name, 0)
    cluster.fail_slice(name, 1)
    handler.monitor_runs()
    run = db.read_run(uid, "p1")
    # every slice gone → NOT handled by the elastic path
    assert run["status"].get("slice_replacements", 0) == 0
    assert not [e for e in cluster.events if e[0] == "delete"]


# -- bench smoke --------------------------------------------------------------

def test_bench_elastic_smoke():
    """The BENCH_r13 A/B runs and its invariants hold: attribution
    closed in both arms, the full-resubmit arm is charged the downtime,
    the elastic arm shows reshard and degraded badput and the shrunken
    world. Which arm's goodput fraction comes out higher is a ratio of
    CPU wall-clock seconds here — it says nothing about a TPU and is
    not asserted."""
    import bench

    out = bench.run_elastic(steps=8, batch=8, seq=32, fail_at=3,
                            rejoin_at=6, checkpoint_every=2,
                            downtime_s=5.0)
    assert out["metric"] == "train_elastic_goodput_fraction"
    detail = out["detail"]
    assert detail["attribution_closed"]
    assert detail["full_resubmit"]["badput_s"]["preemption_downtime"] == 5.0
    assert detail["elastic"]["badput_s"]["reshard"] > 0
    assert detail["elastic"]["badput_s"]["degraded"] > 0
    assert 4 in detail["elastic"]["world_sizes"]
    assert out["vs_baseline"] > 0
