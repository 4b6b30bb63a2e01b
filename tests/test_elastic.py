"""Elastic/recovery tests: checkpoint auto-resume through a simulated crash,
heartbeat staleness -> gang restart (SURVEY.md §5)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.api.types import ConditionType, RestartPolicy, jax_job
from kubeflow_tpu.controller.cluster import FakeCluster, PodPhase
from kubeflow_tpu.controller.heartbeat import (
    FileHeartbeatTracker, check_heartbeats,
)
from kubeflow_tpu.controller.reconciler import JobController
from kubeflow_tpu.models import llama
from kubeflow_tpu.training import (
    Trainer, TrainerConfig, lm_loss_fn, put_batch, synthetic_lm_batches,
)
from kubeflow_tpu.training.loop import Heartbeat, fit
from kubeflow_tpu.training.metrics import MetricsWriter


def _make_trainer(mesh, cfg):
    return Trainer(
        mesh=mesh,
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        params_logical_axes=llama.param_logical_axes(cfg),
        loss_fn=lm_loss_fn(llama.forward, cfg),
        config=TrainerConfig(learning_rate=1e-3, warmup_steps=2,
                             total_steps=100),
    )


def test_fit_resumes_after_crash(tmp_path, mesh8):
    """Train 6 steps with checkpoints, 'crash', re-fit: training continues
    from the saved step with identical state."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    ckpt = str(tmp_path / "ckpt")
    batch = put_batch(mesh8, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))
    batches = lambda: iter([batch] * 100)

    t1 = _make_trainer(mesh8, cfg)
    r1 = fit(t1, batches(), rng=jax.random.key(0), max_steps=6,
             checkpoint_dir=ckpt, checkpoint_every=3)
    assert r1.final_step == 6 and r1.resumed_from is None
    params_after_6 = jax.device_get(t1.params)

    # crash: brand-new trainer process resumes from the checkpoint
    t2 = _make_trainer(mesh8, cfg)
    r2 = fit(t2, batches(), rng=jax.random.key(999),   # different rng: ignored
             max_steps=10, checkpoint_dir=ckpt, checkpoint_every=3)
    assert r2.resumed_from == 6
    assert r2.final_step == 10

    # the resumed run really started from step-6 state: re-running from the
    # checkpoint for 0 extra steps yields the same params
    t3 = _make_trainer(mesh8, cfg)
    r3 = fit(t3, batches(), rng=jax.random.key(5), max_steps=6,
             checkpoint_dir=ckpt)
    # latest checkpoint is now step 10; so resume lands at 10 and trains 0
    assert r3.resumed_from == 10 and r3.final_step == 10


def test_resume_matches_uninterrupted(tmp_path, mesh8):
    """Crash-resume with the step-indexed data stream reproduces exactly the
    params of an uninterrupted run (deterministic data-skip contract)."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    ckpt = str(tmp_path / "ckpt")

    def batches(start_step):
        return (put_batch(mesh8, b) for b in synthetic_lm_batches(
            cfg.vocab_size, 8, 32, seed=7, start_step=start_step))

    ta = _make_trainer(mesh8, cfg)
    fit(ta, batches, rng=jax.random.key(0), max_steps=8)

    # interrupted at step 4 (checkpointed), resumed to 8
    tb = _make_trainer(mesh8, cfg)
    fit(tb, batches, rng=jax.random.key(0), max_steps=4,
        checkpoint_dir=ckpt, checkpoint_every=2)  # final step == in-loop save
    tc = _make_trainer(mesh8, cfg)
    r = fit(tc, batches, rng=jax.random.key(123), max_steps=8,
            checkpoint_dir=ckpt, checkpoint_every=2)
    assert r.resumed_from == 4 and r.final_step == 8

    a = jax.device_get(ta.params)
    c = jax.device_get(tc.params)
    for pa, pc in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(c)):
        np.testing.assert_allclose(pa, pc, rtol=2e-5, atol=2e-6)


def test_fit_writes_metrics_and_heartbeat(tmp_path, mesh8):
    cfg = llama.llama_tiny(dtype=jnp.float32)
    batch = put_batch(mesh8, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))
    hb_path = str(tmp_path / "hb" / "w0.hb")
    metrics = MetricsWriter(str(tmp_path / "m.jsonl"))
    t = _make_trainer(mesh8, cfg)
    fit(t, iter([batch] * 10), rng=jax.random.key(0), max_steps=4,
        metrics=metrics, metrics_every=1, heartbeat=Heartbeat(hb_path))
    assert os.path.exists(hb_path)
    assert open(hb_path).read() == "4"
    assert metrics.latest("loss") is not None


def test_resume_on_different_mesh_shape(tmp_path, mesh8):
    """Slice-replacement elasticity: a checkpoint written by an
    8-way-fsdp world restores into a 4-device fsdp=4 world (and back),
    bitwise — recovery must not depend on the original mesh surviving."""
    import jax as _jax

    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    cfg = llama.llama_tiny(dtype=jnp.float32)
    ckpt = str(tmp_path / "ckpt")

    def batches(start_step):
        return (put_batch(mesh8, b) for b in synthetic_lm_batches(
            cfg.vocab_size, 8, 32, seed=3, start_step=start_step))

    t8 = _make_trainer(mesh8, cfg)
    fit(t8, batches, rng=jax.random.key(0), max_steps=4,
        checkpoint_dir=ckpt, checkpoint_every=2)

    # the replacement slice is half the size: 4 devices, fsdp=4
    mesh4 = build_mesh(MeshConfig(fsdp=4, data=1),
                       devices=_jax.devices()[:4])

    def batches4(start_step):
        return (put_batch(mesh4, b) for b in synthetic_lm_batches(
            cfg.vocab_size, 8, 32, seed=3, start_step=start_step))

    t4 = _make_trainer(mesh4, cfg)
    r = fit(t4, batches4, rng=jax.random.key(9), max_steps=6,
            checkpoint_dir=ckpt, checkpoint_every=2)
    assert r.resumed_from == 4 and r.final_step == 6

    # uninterrupted 8-way run to step 6 must match the cross-mesh resume
    t_ref = _make_trainer(mesh8, cfg)
    fit(t_ref, batches, rng=jax.random.key(0), max_steps=6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(t_ref.params)),
                    jax.tree_util.tree_leaves(jax.device_get(t4.params))):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_checkpoint_mirror_survives_local_disk_loss(tmp_path):
    """Remote-durability path (SURVEY.md §5): checkpoints mirror to a
    second location (the mounted-bucket role) and restore falls back to the
    mirror when the local directory is gone — slice-replacement recovery."""
    import shutil

    from kubeflow_tpu.training.checkpoint import CheckpointManager

    local, mirror = str(tmp_path / "local"), str(tmp_path / "mirror")
    state = {"w": np.arange(8.0), "step": np.asarray(3)}
    mgr = CheckpointManager(local, mirror=mirror, async_save=False)
    assert mgr.save(1, {"w": state["w"] * 0, "step": np.asarray(1)})
    assert mgr.save(3, state)
    mgr.wait()
    assert sorted(os.listdir(mirror)) == ["1", "3"]
    mgr.close()

    shutil.rmtree(local)                         # the node lost its disk
    mgr2 = CheckpointManager(local, mirror=mirror, async_save=False)
    step, restored = mgr2.restore(template=state)
    assert step == 3
    np.testing.assert_array_equal(restored["w"], state["w"])
    mgr2.close()

    # explicit-step restore must fetch THAT step from the mirror, not
    # just the newest one
    shutil.rmtree(local)
    mgr3 = CheckpointManager(local, mirror=mirror, async_save=False)
    step, restored = mgr3.restore(
        step=1, template={"w": state["w"], "step": state["step"]})
    assert step == 1
    np.testing.assert_array_equal(restored["w"], state["w"] * 0)
    mgr3.close()


def test_restore_prefers_newer_mirror_over_stale_local(tmp_path):
    """Restart-aware restore (elastic recovery): a replacement may land on
    a node whose local checkpoint dir is STALE (it served an older
    incarnation) — the newest step wins from the mirror, and an explicit
    step absent locally is fetched too."""
    import shutil

    from kubeflow_tpu.training.checkpoint import CheckpointManager

    local, mirror = str(tmp_path / "local"), str(tmp_path / "mirror")
    state2 = {"w": np.arange(4.0) * 2}
    state4 = {"w": np.arange(4.0) * 4}
    mgr = CheckpointManager(local, mirror=mirror, async_save=False)
    assert mgr.save(2, state2) and mgr.save(4, state4)
    mgr.wait()
    mgr.close()

    # the node's local disk rolled back: step 4 lost locally, mirror has it
    shutil.rmtree(os.path.join(local, "4"))
    mgr2 = CheckpointManager(local, mirror=mirror, async_save=False)
    step, restored = mgr2.restore(template=state4)
    assert step == 4
    np.testing.assert_array_equal(restored["w"], state4["w"])
    mgr2.close()

    # explicit-step restore of a step only the mirror holds
    shutil.rmtree(os.path.join(local, "2"))
    mgr3 = CheckpointManager(local, mirror=mirror, async_save=False)
    step, restored = mgr3.restore(step=2, template=state2)
    assert step == 2
    np.testing.assert_array_equal(restored["w"], state2["w"])
    mgr3.close()


def test_grad_accum_matches_full_batch(mesh8):
    """grad_accum=2 over the same global batch produces the same update and
    the same metrics (tokens summed, loss averaged) as a single full step."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    batch = put_batch(mesh8, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))

    def mk(accum):
        t = Trainer(
            mesh=mesh8,
            init_params_fn=lambda rng: llama.init_params(rng, cfg),
            params_logical_axes=llama.param_logical_axes(cfg),
            loss_fn=lm_loss_fn(llama.forward, cfg),
            config=TrainerConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=100, grad_accum=accum),
        )
        t.init_state(jax.random.key(0))
        return t

    t1, t2 = mk(1), mk(2)
    m1, m2 = t1.train_step(batch), t2.train_step(batch)
    assert float(m1["tokens"]) == float(m2["tokens"])
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(t1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(t2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_grad_accum_uneven_mask_matches_full_batch(mesh8):
    """ADVICE r2(c) regression: with mask density varying across microbatches,
    accumulation must reproduce the GLOBAL token-weighted mean (loss-sum and
    token-count accumulated, one divide at the end) — not the mean of
    per-microbatch means."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (8, 33), dtype=np.int32)
    mask = np.ones((8, 33), dtype=np.float32)
    mask[4:, 8:] = 0.0   # microbatch 1 (rows 4-7) has 4x fewer live tokens
    batch = put_batch(mesh8, {"tokens": jnp.asarray(tokens),
                              "mask": jnp.asarray(mask)})

    def mk(accum):
        t = Trainer(
            mesh=mesh8,
            init_params_fn=lambda rng: llama.init_params(rng, cfg),
            params_logical_axes=llama.param_logical_axes(cfg),
            loss_fn=lm_loss_fn(llama.forward, cfg),
            config=TrainerConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=100, grad_accum=accum),
        )
        t.init_state(jax.random.key(0))
        return t

    t1, t2 = mk(1), mk(2)
    m1, m2 = t1.train_step(batch), t2.train_step(batch)
    assert float(m1["tokens"]) == float(m2["tokens"])
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(t1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(t2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_heartbeat_staleness_triggers_gang_restart(tmp_path):
    cluster = FakeCluster()
    ctl = JobController(cluster)
    job = jax_job("hb-job", workers=2)
    job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
    ctl.submit(job)
    ctl.reconcile("default", "hb-job")
    for (ns, n), pod in list(cluster.pods.items()):
        cluster.set_phase(ns, n, PodPhase.RUNNING)
    ctl.reconcile("default", "hb-job")

    tracker = FileHeartbeatTracker(str(tmp_path / "hb"), timeout_s=10,
                                   startup_grace_s=30)
    now = time.time()

    # both beating: healthy. Pods are aged past the beats below so the
    # stale beat really belongs to THIS incarnation (a beat predating the
    # pod start falls under the startup grace instead — see
    # test_stale_beat_from_previous_incarnation_gets_grace)
    for pod in cluster.list_pods("default", {"job-name": "hb-job"}):
        pod.created_at = now - 200
        with open(tracker.path_for("hb-job", pod.name), "w") as f:
            f.write("1")
    assert check_heartbeats(ctl, "default", "hb-job", tracker) == []

    # worker-1's heartbeat goes stale -> pod failed -> gang restart
    pods = cluster.list_pods("default", {"job-name": "hb-job"})
    stale_path = tracker.path_for("hb-job", pods[1].name)
    os.utime(stale_path, (now - 100, now - 100))
    stale = check_heartbeats(ctl, "default", "hb-job", tracker, now=now)
    assert stale == [pods[1].name]
    job = ctl.get("default", "hb-job")
    assert job.status.restart_count == 1          # whole-gang restart fired


def test_heartbeat_startup_grace(tmp_path):
    tracker = FileHeartbeatTracker(str(tmp_path), timeout_s=10,
                                   startup_grace_s=300)
    now = time.time()
    # no file yet, pod just started: not stale
    assert not tracker.is_stale("j", "p0", pod_started_at=now - 5, now=now)
    # no file after the grace window: stale
    assert tracker.is_stale("j", "p0", pod_started_at=now - 400, now=now)


def test_stale_beat_from_previous_incarnation_gets_grace(tmp_path):
    """Elastic recovery: a replacement pod reuses its predecessor's name,
    so the old incarnation's last beat is still on disk — it must count
    as 'never beat yet' (startup grace), not instantly fail the fresh
    pod; and the grace must still expire if the new pod never beats."""
    tracker = FileHeartbeatTracker(str(tmp_path), timeout_s=10,
                                   startup_grace_s=60)
    now = time.time()
    path = tracker.path_for("j", "w1")
    with open(path, "w") as f:
        f.write("7")
    os.utime(path, (now - 100, now - 100))      # old incarnation's beat
    # new pod started 5s ago: grace, not stale
    assert not tracker.is_stale("j", "w1", pod_started_at=now - 5, now=now)
    # the new pod never beat past the grace window: stale
    assert tracker.is_stale("j", "w1", pod_started_at=now - 90, now=now)
    # the beat postdates the pod: normal timeout semantics
    assert tracker.is_stale("j", "w1", pod_started_at=now - 200, now=now)


# ---------------------------------------------------------------------------
# Per-worker warm replacement (elastic recovery tentpole)
# ---------------------------------------------------------------------------

def _elastic_job(ctl, cluster, name="el", workers=3, backoff_limit=3,
                 base_s=0.0):
    from kubeflow_tpu.api.types import RunPolicy

    job = jax_job(name, workers=workers, mesh={"data": workers},
                  run_policy=RunPolicy(backoff_limit=backoff_limit))
    job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
    ctl.submit(job)
    ctl.reconcile("default", name)
    cluster.run_scheduled()
    ctl.reconcile("default", name)
    return job


def test_worker_replacement_preserves_gang():
    """A non-coordinator worker death on a warm-capable cluster replaces
    ONE pod: survivors stay, the gang reservation and job uid survive,
    the replacement carries the dead rank's env under a new
    worker-incarnation id, and no gang restart is counted."""
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True            # warm capacity (zygote-style)
    ctl = JobController(cluster)
    job = _elastic_job(ctl, cluster, "el", workers=3)
    uid = job.uid
    from kubeflow_tpu.api.types import ConditionType

    assert job.status.condition() == ConditionType.RUNNING

    cluster.set_phase("default", "el-worker-2", PodPhase.FAILED, -9)
    ctl.reconcile("default", "el")

    assert job.status.restart_count == 0           # NOT a gang restart
    assert job.status.worker_replacements == 1
    assert job.status.rendezvous_epoch == 1
    assert job.status.replacement_counts == {"el-worker-2": 1}
    assert job.uid == uid
    cond = job.status.condition()
    assert cond == ConditionType.RESTARTING
    assert job.status.conditions[-1].reason == "WorkerReplacement#1"
    # survivors kept their pods AND got the re-rendezvous signal
    for name in ("el-worker-0", "el-worker-1"):
        pod = cluster.get_pod("default", name)
        assert pod is not None and pod.phase == PodPhase.RUNNING
        assert pod.env["KFT_RENDEZVOUS_EPOCH"] == "1"
    assert "restart_pod_process el-worker-0" in cluster.events
    # the dead pod is gone; gang reservation was NOT released
    assert cluster.get_pod("default", "el-worker-2") is None
    assert ctl.scheduler.is_admitted("default", "el")

    # next reconcile recreates ONLY the dead rank, stamped with the new
    # incarnation + the dead worker's rank env
    ctl.reconcile("default", "el")
    repl = cluster.get_pod("default", "el-worker-2")
    assert repl is not None and repl.phase == PodPhase.PENDING
    assert repl.env["KFT_WORKER_INCARNATION"] == "1"
    assert repl.env["KFT_RENDEZVOUS_EPOCH"] == "1"
    assert repl.env["KFT_PROCESS_ID"] == "2"       # same rank
    cluster.run_scheduled()
    ctl.reconcile("default", "el")
    assert job.status.condition() == ConditionType.RUNNING
    # recovery timeline recorded for the bench decomposition
    events = [e["event"] for e in ctl.recovery_log[("default", "el")]]
    assert "worker_failed" in events and "replacement" in events
    assert "survivor_restarted" in events


def test_coordinator_death_falls_back_to_gang_restart():
    """Global rank 0 hosts the rendezvous service of a multi-process
    world — its death must take the counted gang-restart path."""
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    ctl = JobController(cluster)
    job = _elastic_job(ctl, cluster, "coord", workers=2)
    cluster.set_phase("default", "coord-worker-0", PodPhase.FAILED, -9)
    ctl.reconcile("default", "coord")
    assert job.status.worker_replacements == 0
    assert job.status.restart_count == 1
    assert ctl.metrics.get("gang_restarts_total") == 1
    reasons = [e.get("reason") for e in
               ctl.recovery_log[("default", "coord")]]
    assert "coordinator_died" in reasons


def test_single_worker_job_is_always_replaceable():
    """A 1-process world has no rendezvous service to lose: its only
    worker replaces warm, never gang-restarts."""
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    ctl = JobController(cluster)
    job = _elastic_job(ctl, cluster, "solo", workers=1)
    cluster.set_phase("default", "solo-worker-0", PodPhase.FAILED, -9)
    ctl.reconcile("default", "solo")
    assert job.status.worker_replacements == 1
    assert job.status.restart_count == 0


def test_no_claimable_standby_falls_back_to_gang_restart():
    """With a REAL pool attached but dry, replacement would cold-start —
    the reconciler must take the counted gang restart instead."""
    from kubeflow_tpu.controller.reconciler import JobController

    class DryPool:
        def standby_count(self, cls=None):
            return 0

        def claimable(self, cls=None):
            return 0

    cluster = FakeCluster()
    cluster.warm_pool = DryPool()
    ctl = JobController(cluster)
    job = _elastic_job(ctl, cluster, "dry", workers=2)
    cluster.set_phase("default", "dry-worker-1", PodPhase.FAILED, -9)
    ctl.reconcile("default", "dry")
    assert job.status.worker_replacements == 0
    assert job.status.restart_count == 1
    reasons = [e.get("reason") for e in ctl.recovery_log[("default", "dry")]]
    assert "no_claimable_standby" in reasons


def test_replacement_budget_exhausted_falls_back_then_fails():
    """Per-worker backoff accounting: a rank that keeps dying burns ITS
    replacement budget first, then the job takes one counted gang
    restart, then terminal failure — and the job is never wedged."""
    from kubeflow_tpu.api.types import ConditionType
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    ctl = JobController(cluster, restart_backoff_base_s=0.0)
    job = _elastic_job(ctl, cluster, "flap", workers=2, backoff_limit=1)

    def kill_and_recover(name):
        cluster.set_phase("default", name, PodPhase.FAILED, -9)
        ctl.reconcile("default", "flap")      # handle failure
        ctl.reconcile("default", "flap")      # recreate
        cluster.run_scheduled()
        ctl.reconcile("default", "flap")

    kill_and_recover("flap-worker-1")         # replacement #1 (budget 1/1)
    assert job.status.worker_replacements == 1
    assert job.status.restart_count == 0
    kill_and_recover("flap-worker-1")         # budget burned -> gang restart
    assert job.status.worker_replacements == 1
    assert job.status.restart_count == 1
    # the gang restart reset per-worker budgets: pods exist again
    pods = cluster.list_pods("default", {"job-name": "flap"})
    assert len(pods) == 2
    assert job.status.replacement_counts == {}
    kill_and_recover("flap-worker-1")         # fresh budget: replace again
    assert job.status.worker_replacements == 2
    kill_and_recover("flap-worker-1")         # budget + backoff exhausted
    assert job.status.condition() == ConditionType.FAILED


def test_survivor_restart_failure_escalates_to_gang_restart():
    """A re-rendezvous signal that fails to DELIVER leaves that survivor
    wedged in the old world — the attempt must fall back to the counted
    gang restart (uniform teardown), never commit a half-recovered gang."""
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    cluster.restart_pod_process = lambda ns, name, env=None: False
    ctl = JobController(cluster)
    job = _elastic_job(ctl, cluster, "wedge", workers=3)
    cluster.set_phase("default", "wedge-worker-2", PodPhase.FAILED, -9)
    ctl.reconcile("default", "wedge")
    assert job.status.worker_replacements == 0
    assert job.status.restart_count == 1
    reasons = [e.get("reason") for e in
               ctl.recovery_log[("default", "wedge")]]
    assert "survivor_restart_failed" in reasons


def test_second_failure_during_recovery_converges():
    """Satellite: chaos kills the replacement before its first step. The
    job must converge to a second replacement (same rank, incarnation 2)
    — never a wedged Pending gang, and never a double-fired replacement
    for one death."""
    from kubeflow_tpu.api.types import ConditionType
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    ctl = JobController(cluster, restart_backoff_base_s=0.0)
    job = _elastic_job(ctl, cluster, "sec", workers=2, backoff_limit=3)

    cluster.set_phase("default", "sec-worker-1", PodPhase.FAILED, -9)
    ctl.reconcile("default", "sec")
    assert job.status.worker_replacements == 1
    ctl.reconcile("default", "sec")           # replacement recreated
    repl = cluster.get_pod("default", "sec-worker-1")
    assert repl is not None and repl.env["KFT_WORKER_INCARNATION"] == "1"

    # a reconcile pass BEFORE anything changes must not double-fire
    ctl.reconcile("default", "sec")
    assert job.status.worker_replacements == 1

    # the replacement dies before first-step-after (scheduled chaos)
    cluster.run_scheduled()
    cluster.set_phase("default", "sec-worker-1", PodPhase.FAILED, -9)
    ctl.reconcile("default", "sec")
    assert job.status.worker_replacements == 2
    assert job.status.restart_count == 0
    ctl.reconcile("default", "sec")
    repl = cluster.get_pod("default", "sec-worker-1")
    assert repl is not None and repl.env["KFT_WORKER_INCARNATION"] == "2"
    assert repl.env["KFT_RENDEZVOUS_EPOCH"] == "2"
    cluster.run_scheduled()
    ctl.reconcile("default", "sec")
    assert job.status.condition() == ConditionType.RUNNING
    # the gang never lost its reservation through both recoveries
    assert ctl.scheduler.is_admitted("default", "sec")


def test_restart_backoff_is_exponential_and_visible():
    """Satellite: requeue after attempt n>=2 waits exponentially (with
    jitter), the delay is visible in the job condition, and pod
    recreation really is gated until the clock expires."""
    from kubeflow_tpu.api.types import ConditionType
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    ctl = JobController(cluster, restart_backoff_base_s=0.3,
                        restart_backoff_cap_s=60.0,
                        restart_backoff_jitter=0.0)
    job = _elastic_job(ctl, cluster, "bk", workers=2, backoff_limit=4)

    # first gang restart: immediate requeue (attempt 1 -> no delay)
    cluster.set_phase("default", "bk-worker-1", PodPhase.FAILED, -9)
    ctl.reconcile("default", "bk")
    assert job.status.restart_count == 1
    ctl.reconcile("default", "bk")
    assert len(cluster.list_pods("default", {"job-name": "bk"})) == 2
    cluster.run_scheduled()
    ctl.reconcile("default", "bk")

    # second gang restart: backoff = base * 2^0 = 0.3s, visible in the
    # condition, and recreation waits for it
    cluster.set_phase("default", "bk-worker-0", PodPhase.FAILED, -9)
    ctl.reconcile("default", "bk")
    assert job.status.restart_count == 2
    assert "backoff 0.3s" in job.status.conditions[-1].message
    assert ctl.metrics["restart_backoff_seconds"] == pytest.approx(0.3)
    ctl.reconcile("default", "bk")
    assert cluster.list_pods("default", {"job-name": "bk"}) == []  # gated
    time.sleep(0.35)
    ctl.reconcile("default", "bk")
    assert len(cluster.list_pods("default", {"job-name": "bk"})) == 2
    assert job.status.condition() == ConditionType.RESTARTING


def test_kubelet_in_place_restart_on_epoch_bump(tmp_path):
    """The survivor re-rendezvous signal on the kube backend: bumping the
    restart-epoch annotation makes the image-less kubelet kill and
    respawn the pod's PROCESS while the pod object (name, labels, claim,
    phase) survives — and the bounce is never reported as a failure."""
    import sys

    from kubeflow_tpu.controller import (
        FakeKubeApiServer, FakeKubelet, KubeCluster,
    )
    from kubeflow_tpu.controller.cluster import Pod, create_and_admit

    srv = FakeKubeApiServer().start()
    kubelet = None
    try:
        kube = KubeCluster(srv.url)
        kubelet = FakeKubelet(srv.url, log_dir=str(tmp_path / "pods"))
        kubelet.start()
        pod = Pod(name="surv", namespace="default",
                  labels={"job-name": "j"}, env={"KFT_RENDEZVOUS_EPOCH": "0"},
                  command=[sys.executable, "-c",
                           "import os,time;"
                           "print('worker-epoch=%s'"
                           " % os.environ['KFT_RENDEZVOUS_EPOCH'],"
                           "flush=True); time.sleep(60)"])
        create_and_admit(kube, pod)
        deadline = time.time() + 30
        while time.time() < deadline and "worker-epoch=0" not in \
                kubelet.pod_log("default", "surv"):
            time.sleep(0.05)
        proc0 = kubelet.procs.get(("default", "surv"))
        assert proc0 is not None
        pid0 = proc0.pid

        assert kube.restart_pod_process(
            "default", "surv", {"KFT_RENDEZVOUS_EPOCH": "1"})
        deadline = time.time() + 30
        while time.time() < deadline and kubelet.restarts < 1:
            time.sleep(0.05)
        assert kubelet.restarts == 1
        proc1 = kubelet.procs.get(("default", "surv"))
        assert proc1 is not None and proc1.pid != pid0
        # the pod survived as the SAME object: still running, never FAILED
        got = kube.get_pod("default", "surv")
        assert got.phase == PodPhase.RUNNING
        # the respawned process saw the new epoch env (annotation wins)
        deadline = time.time() + 10
        log = ""
        while time.time() < deadline and "worker-epoch=1" not in log:
            log = kubelet.pod_log("default", "surv")
            time.sleep(0.05)
        assert "worker-epoch=0" in log and "worker-epoch=1" in log
        # idempotent: the same epoch does not bounce again
        time.sleep(0.3)
        assert kubelet.restarts == 1
    finally:
        if kubelet is not None:
            kubelet.stop()
        srv.stop()


@pytest.mark.slow
def test_mirror_alarm_lands_condition_end_to_end(tmp_path):
    """Satellite: a real worker process whose checkpoint mirror is dead
    must land a CheckpointMirrorDegraded condition on the owning job with
    ZERO manual plumbing — fit()'s default mirror alarm -> operator-
    injected KFT_WARNING_FILE -> warning sweep -> job condition."""
    import sys

    from kubeflow_tpu.controller import (
        JobController, LocalProcessCluster, Operator,
    )

    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"))
    ctl = JobController(cluster)
    op = Operator(ctl, heartbeat_dir=str(tmp_path / "hb"),
                  reconcile_period=0.1, heartbeat_period=0.2)
    op.start(port=0)
    try:
        job = jax_job(
            "mirr", workers=1, mesh={"data": 1},
            command=[sys.executable, "-m",
                     "kubeflow_tpu.rendezvous.worker_check"],
            env={"PYTHONPATH": "/root/repo:" + os.environ.get(
                     "PYTHONPATH", ""),
                 "JAX_PLATFORMS": "cpu",
                 "KFT_TRAIN_STEPS": "2",
                 "KFT_CHECKPOINT_DIR": str(tmp_path / "ckpt"),
                 "KFT_CHECKPOINT_EVERY": "1",
                 # remote scheme without a client: every mirror sync
                 # raises — exactly a dead bucket
                 "KFT_CHECKPOINT_MIRROR": "gs://kft-no-such-bucket/x",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
        op.submit(job)
        deadline = time.time() + 120
        warns = []
        while time.time() < deadline:
            out = ctl.get("default", "mirr")
            warns = out.status.warnings()
            if warns:
                break
            time.sleep(0.25)
        assert warns, (
            "no Warning condition arrived; job="
            f"{out.status.condition()} log={cluster.pod_log('default', 'mirr-worker-0')[-800:]}")
        assert warns[0].reason == "CheckpointMirrorDegraded"
        assert op.metrics.get(
            "kft_worker_warnings_total",
            {"reason": "CheckpointMirrorDegraded"}) >= 1
        # advisory only: the job itself is not failed by a dead mirror
        assert out.status.condition() not in (None, "Failed")
    finally:
        op.stop()
        cluster.shutdown()


@pytest.mark.slow
def test_warm_replacement_resumes_with_loss_continuity(tmp_path):
    """The tentpole e2e on real processes: chaos SIGKILLs a training
    worker mid-run; the operator detects it, replaces ONLY that worker
    (warm, zygote-forked — no gang restart counted), and training resumes
    from the latest checkpoint at the exact step with the loss curve
    EXACTLY matching an uninterrupted run at every post-resume step."""
    import sys

    from kubeflow_tpu.controller import (
        FaultInjector, JobController, LocalProcessCluster, Operator,
    )
    from kubeflow_tpu.training.metrics import read_metrics

    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"),
                                  warm_pool=True)
    ctl = JobController(cluster)
    op = Operator(ctl, heartbeat_dir=str(tmp_path / "hb"),
                  reconcile_period=0.1, heartbeat_period=0.2)
    op.start(port=0)
    chaos = FaultInjector(cluster)
    cluster._ensure_zygote(wait_s=60)       # pool warm OUTSIDE the story

    def env(tag, extra=None):
        e = {"PYTHONPATH": "/root/repo:" + os.environ.get("PYTHONPATH", ""),
             "JAX_PLATFORMS": "cpu",
             "KFT_TRAIN_STEPS": "6",
             "KFT_METRICS_PATH": str(tmp_path / f"{tag}.jsonl"),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
        e.update(extra or {})
        return e

    def losses(tag):
        out = {}
        for r in read_metrics(str(tmp_path / f"{tag}.jsonl")):
            if "loss" in r:
                out[int(r["step"])] = r["loss"]
        return out

    def wait_done(name, timeout=180):
        deadline = time.time() + timeout
        while time.time() < deadline:
            out = ctl.get("default", name)
            if out is not None and out.status.is_finished():
                return out
            time.sleep(0.2)
        raise TimeoutError(name)

    try:
        # uninterrupted reference run (publishes the depot entry too)
        op.submit(jax_job(
            "rec-base", workers=1, mesh={"data": 1},
            command=[sys.executable, "-m",
                     "kubeflow_tpu.rendezvous.worker_check"],
            env=env("base")))
        base = wait_done("rec-base")
        assert base.status.condition() == ConditionType.SUCCEEDED, \
            cluster.pod_log("default", "rec-base-worker-0")[-800:]
        base_losses = losses("base")
        assert set(base_losses) >= {1, 2, 3, 4, 5, 6}

        # victim run: checkpoints every 2 steps, paced so the kill lands
        # mid-run with a checkpoint behind it
        job = jax_job(
            "rec-victim", workers=1, mesh={"data": 1},
            command=[sys.executable, "-m",
                     "kubeflow_tpu.rendezvous.worker_check"],
            env=env("victim", {
                "KFT_CHECKPOINT_DIR": str(tmp_path / "ckpt"),
                "KFT_CHECKPOINT_EVERY": "2",
                "KFT_STEP_SLEEP": "0.5"}))
        job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
        op.submit(job)
        # wait until step >= 3 has run (checkpoint at 2 exists), then kill
        deadline = time.time() + 120
        while time.time() < deadline and losses("victim").get(3) is None:
            time.sleep(0.1)
        assert losses("victim").get(3) is not None
        assert chaos.kill_pod("default", "rec-victim-worker-0")

        done = wait_done("rec-victim")
        assert done.status.condition() == ConditionType.SUCCEEDED, \
            cluster.pod_log("default", "rec-victim-worker-0")[-800:]
        # per-worker replacement, not a gang restart
        assert done.status.worker_replacements == 1
        assert done.status.restart_count == 0
        # the replacement resumed from a real checkpoint at the exact
        # step (log is the replacement's — recreate truncates it)
        log = cluster.pod_log("default", "rec-victim-worker-0")
        assert "resumed_from=" in log and "resumed_from=None" not in log
        assert "incarnation=1" in log
        # warm path: the replacement deserialized the depot entry
        # published by the earlier runs — no cold train-step compile
        assert "depot=hit" in log

        # loss-curve continuity: every post-resume step's loss EXACTLY
        # matches the uninterrupted run (checkpoint restore is exact and
        # the data stream is step-indexed)
        victim_losses = losses("victim")
        assert victim_losses[6] == base_losses[6]
        for step in (4, 5, 6):
            assert victim_losses[step] == base_losses[step], (
                step, victim_losses, base_losses)
    finally:
        op.stop()
        cluster.shutdown()


def test_replacement_status_yaml_roundtrip():
    """A restarted controller must keep the per-worker budget, the total,
    and the epoch (the CR status subresource role)."""
    from kubeflow_tpu.api.types import ConditionType, from_yaml, to_yaml

    job = jax_job("rt", workers=2)
    job.status.conditions.append(
        __import__("kubeflow_tpu.api.types", fromlist=["Condition"])
        .Condition(type=ConditionType.RESTARTING, reason="WorkerReplacement#2"))
    job.status.worker_replacements = 2
    job.status.rendezvous_epoch = 3
    job.status.replacement_counts = {"rt-worker-1": 2}
    back = from_yaml(to_yaml(job))
    assert back.status.worker_replacements == 2
    assert back.status.rendezvous_epoch == 3
    assert back.status.replacement_counts == {"rt-worker-1": 2}
