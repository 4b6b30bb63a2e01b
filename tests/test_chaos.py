"""Fault-injection harness tests (SURVEY.md §5): randomized pod kills, and
unattended recovery of a real job under repeated chaos."""

import os
import sys
import time

import pytest

from kubeflow_tpu.api.types import ConditionType, RestartPolicy, jax_job
from kubeflow_tpu.controller import (
    FakeCluster, FaultInjector, JobController, LocalProcessCluster, Operator,
    PodPhase,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_CMD = [sys.executable, "-m", "kubeflow_tpu.rendezvous.worker_check"]


def test_injector_kills_fake_pods_and_job_gang_restarts():
    cluster = FakeCluster()
    ctl = JobController(cluster)
    job = jax_job("chaotic", workers=2, mesh={"data": 2})
    job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
    ctl.submit(job)
    ctl.reconcile("default", "chaotic")
    for pod in cluster.list_pods("default", {"job-name": "chaotic"}):
        cluster.set_phase("default", pod.name, PodPhase.RUNNING)

    chaos = FaultInjector(cluster, seed=1)
    victim = chaos.kill_random("default", {"job-name": "chaotic"})
    assert victim is not None and chaos.kills == [("default", victim)]
    ctl.reconcile("default", "chaotic")
    out = ctl.get("default", "chaotic")
    assert out.status.restart_count >= 1          # gang restart happened
    # fresh pods exist again (recreated by the restart)
    fresh = cluster.list_pods("default", {"job-name": "chaotic"})
    assert all(p.phase == PodPhase.PENDING for p in fresh)


def test_injector_scheduled_chaos_respects_max_kills():
    cluster = FakeCluster()
    ctl = JobController(cluster)
    job = jax_job("bounded", workers=4, mesh={"data": 4})
    ctl.submit(job)
    ctl.reconcile("default", "bounded")
    for pod in cluster.list_pods("default", {"job-name": "bounded"}):
        cluster.set_phase("default", pod.name, PodPhase.RUNNING)
    chaos = FaultInjector(cluster, seed=2)
    chaos.start("default", {"job-name": "bounded"},
                period_s=0.02, max_kills=2)
    deadline = time.time() + 30
    while time.time() < deadline and len(chaos.kills) < 2:
        time.sleep(0.05)
    time.sleep(0.2)
    chaos.stop()
    assert len(chaos.kills) == 2                   # bounded blast radius


def test_real_job_survives_scheduled_chaos(tmp_path):
    """The recovery e2e: a real 2-process job under a chaos schedule that
    SIGKILLs up to two workers still reaches Succeeded unattended."""
    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"))
    ctl = JobController(cluster)
    op = Operator(ctl, heartbeat_dir=str(tmp_path / "hb"),
                  reconcile_period=0.1, heartbeat_period=0.25)
    op.start(port=0)
    chaos = FaultInjector(cluster, seed=3)
    try:
        job = jax_job(
            "chaos-e2e", workers=2, mesh={"data": 2}, command=WORKER_CMD,
            env={"PYTHONPATH": _REPO_ROOT + ":" + os.environ.get(
                     "PYTHONPATH", ""),
                 "JAX_PLATFORMS": "cpu",
                 "KFT_TRAIN_STEPS": "3",
                 "KFT_METRICS_PATH": str(tmp_path / "m.jsonl"),
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
        job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
        op.submit(job)
        # wait until workers are actually alive, then unleash chaos
        deadline = time.time() + 60
        while time.time() < deadline and not any(
                k[1].startswith("chaos-e2e") and p.poll() is None
                for k, p in list(cluster.procs.items())):
            time.sleep(0.1)
        chaos.start("default", {"job-name": "chaos-e2e"},
                    period_s=1.5, max_kills=2)
        deadline = time.time() + 180
        while time.time() < deadline:
            out = ctl.get("default", "chaos-e2e")
            if out is not None and out.status.is_finished():
                break
            time.sleep(0.3)
        chaos.stop()
        assert out.status.condition() == ConditionType.SUCCEEDED
        if chaos.kills:
            assert out.status.restart_count >= 1
    finally:
        chaos.stop()
        op.stop()
        cluster.shutdown()


def test_injector_kills_kube_pod_via_apiserver():
    """Satellite: FaultInjector drives the KubeCluster backend instead of
    raising TypeError — without a node agent, the kill travels through
    the fake apiserver's status subresource with a retryable signal exit
    code, and the reconciler recovers from it like any preemption."""
    from kubeflow_tpu.controller import FakeKubeApiServer, KubeCluster

    srv = FakeKubeApiServer().start()
    try:
        kube = KubeCluster(srv.url)
        ctl = JobController(kube)
        job = jax_job("kchaos", workers=2, mesh={"data": 2})
        job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
        ctl.submit(job)
        ctl.reconcile("default", "kchaos")
        kube.run_scheduled()

        chaos = FaultInjector(kube, seed=1)
        victim = chaos.kill_random("default", {"job-name": "kchaos"})
        assert victim is not None
        pod = kube.get_pod("default", victim)
        assert pod.phase == PodPhase.FAILED and pod.exit_code == -9
        ctl.reconcile("default", "kchaos")
        out = ctl.get("default", "kchaos")
        assert out.status.restart_count >= 1       # retryable, recovered
    finally:
        srv.stop()


def test_injector_max_kills_race_safe_under_concurrency():
    """Satellite: the max_kills budget must hold even when the scheduled
    loop and concurrent direct kill_pod calls race over it."""
    import threading

    cluster = FakeCluster()
    ctl = JobController(cluster)
    job = jax_job("race", workers=16, mesh={"data": 16})
    ctl.submit(job)
    ctl.reconcile("default", "race")
    for pod in cluster.list_pods("default", {"job-name": "race"}):
        cluster.set_phase("default", pod.name, PodPhase.RUNNING)

    chaos = FaultInjector(cluster, seed=4)
    chaos.start("default", {"job-name": "race"},
                period_s=0.01, max_kills=3)
    barrier = threading.Barrier(8)

    def hammer(i):
        barrier.wait()
        for j in range(16):
            chaos.kill_pod("default", f"race-worker-{(i * 16 + j) % 16}")

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert chaos.wait_for_kill(3, timeout_s=10)
    time.sleep(0.1)
    chaos.stop()
    assert len(chaos.kills) == 3                   # never overshoots


def test_dead_checkpoint_mirror_surfaces_warning_condition(
        tmp_path, monkeypatch):
    """Kill the checkpoint-mirror path (copy_fn always raises): the worker's
    CheckpointManager must keep the step loop alive, count the failure, and
    raise the alarm through the KFT_WARNING_FILE contract; the operator's
    warning sweep must turn that into a job Warning condition + metric
    WITHOUT disturbing the job's phase."""
    from kubeflow_tpu.training.checkpoint import CheckpointManager

    cluster = FakeCluster()
    ctl = JobController(cluster)
    op = Operator(ctl, heartbeat_dir=str(tmp_path / "hb"))
    job = jax_job("mirror-job", workers=1, mesh={"data": 1})
    op.submit(job)
    ctl.reconcile("default", "mirror-job")
    pods = cluster.list_pods("default", {"job-name": "mirror-job"})
    assert pods, "reconcile created no pods"
    pod = pods[0]
    # operator injected the warning-file contract alongside the heartbeat
    assert "KFT_WARNING_FILE" in pod.env
    warn_path = pod.env["KFT_WARNING_FILE"]

    # ---- worker side: mirror replication is dead --------------------
    monkeypatch.setenv("KFT_WARNING_FILE", warn_path)

    def broken_copy(src, dst):
        raise OSError("mirror bucket unreachable")

    mgr = CheckpointManager(
        str(tmp_path / "local"), mirror=str(tmp_path / "mirror"),
        async_save=False, copy_fn=broken_copy)
    mgr.save(1, {"w": [1.0, 2.0]})          # kicks the mirror thread
    deadline = time.time() + 30
    while time.time() < deadline and mgr.mirror_errors == 0:
        time.sleep(0.05)
    assert mgr.mirror_errors >= 1
    assert "mirror bucket unreachable" in mgr.last_mirror_error
    # the step loop survived: a later save still works
    assert mgr.save(2, {"w": [3.0, 4.0]})
    mgr._mirror_stop.set()
    mgr._mirror_kick.set()

    # ---- controller side: sweep -> condition + metric ---------------
    op._collect_warnings("default", "mirror-job")
    out = ctl.get("default", "mirror-job")
    warns = out.status.warnings()
    assert warns and warns[0].reason == "CheckpointMirrorDegraded"
    assert "mirror bucket unreachable" in warns[0].message
    # advisory only: phase untouched, job not finished
    assert out.status.condition() == ConditionType.CREATED
    assert not out.status.is_finished()
    assert op.metrics.get("kft_worker_warnings_total",
                          {"reason": "CheckpointMirrorDegraded"}) == 1
    # idempotent: a second sweep must not duplicate the condition
    op._collect_warnings("default", "mirror-job")
    assert len(ctl.get("default", "mirror-job").status.warnings()) == 1
