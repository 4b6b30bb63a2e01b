"""E2E: JAXJob on LocalProcessCluster — real subprocesses, real
jax.distributed rendezvous over the operator-injected env, real cross-process
collective. The kind-cluster e2e analogue (SURVEY.md §4.3) without Docker."""

import os
import sys

import pytest

from kubeflow_tpu.api.types import ConditionType, RunPolicy, jax_job
from kubeflow_tpu.client import TrainingClient
from kubeflow_tpu.controller import JobController, LocalProcessCluster


WORKER_CMD = [sys.executable, "-m", "kubeflow_tpu.rendezvous.worker_check"]


def base_env(tmp_path):
    return {
        "PYTHONPATH": "/root/repo:" + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "KFT_METRICS_PATH": str(tmp_path / "metrics.jsonl"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }


@pytest.fixture()
def client(tmp_path):
    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"))
    ctl = JobController(cluster)
    yield TrainingClient(ctl)
    cluster.shutdown()


def test_jaxjob_2proc_world(client, tmp_path):
    job = client.create_jax_job(
        "e2e-world", workers=2, command=WORKER_CMD,
        mesh={"data": 2}, env=base_env(tmp_path),
    )
    done = client.wait_for_job_conditions("e2e-world", timeout=120)
    logs = client.get_job_logs("e2e-world", index=0)
    assert done.status.condition() == ConditionType.SUCCEEDED, logs
    assert "world ok" in logs
    # metrics arrived through the file contract, not stdout scraping
    from kubeflow_tpu.training.metrics import read_metrics

    recs = read_metrics(str(tmp_path / "metrics.jsonl"))
    assert any(r.get("world_ok") == 1.0 for r in recs)


def test_jaxjob_multidevice_fsdp_world(client, tmp_path):
    """Multi-host-shaped world: 2 processes x 2 devices = a 4-device global
    mesh with FSDP sharding ACROSS process boundaries — the DCN/ICI
    two-tier layout every real slice job uses, plus real cross-process
    training steps."""
    env = base_env(tmp_path)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["KFT_TRAIN_STEPS"] = "3"
    job = client.create_jax_job(
        "e2e-fsdp", workers=2, command=WORKER_CMD,
        mesh={"fsdp": 4}, env=env,
    )
    done = client.wait_for_job_conditions("e2e-fsdp", timeout=180)
    logs = client.get_job_logs("e2e-fsdp", index=0)
    assert done.status.condition() == ConditionType.SUCCEEDED, logs
    assert "devices=4" in logs
    assert "trained to step 3" in logs
    from kubeflow_tpu.training.metrics import read_metrics

    recs = read_metrics(str(tmp_path / "metrics.jsonl"))
    assert any("loss" in r for r in recs)


def test_jaxjob_failure_restarts_then_fails(client, tmp_path):
    bad_cmd = [sys.executable, "-c", "import sys; sys.exit(1)"]
    client.create_jax_job(
        "e2e-fail", workers=1, command=bad_cmd, env=base_env(tmp_path),
        run_policy=RunPolicy(backoff_limit=1),
    )
    done = client.wait_for_job_conditions("e2e-fail", timeout=60)
    assert done.status.condition() == ConditionType.FAILED
    assert done.status.restart_count == 1


def test_jaxjob_world_via_warm_pool(tmp_path):
    """warm_pool=True: workers fork from the pre-imported zygote instead
    of paying a cold interpreter + jax import (the submit->first-step
    lever, BASELINE.md row 2) — the same 2-process world must rendezvous
    and run its collective, and the phases file must show the fork-warm
    import path."""
    import json

    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"),
                                  warm_pool=True)
    ctl = JobController(cluster)
    client = TrainingClient(ctl)
    try:
        env = base_env(tmp_path)
        env["KFT_PHASES_PATH"] = str(tmp_path / "phases")
        client.create_jax_job(
            "e2e-warm", workers=2, command=WORKER_CMD,
            mesh={"data": 2}, env=env,
        )
        done = client.wait_for_job_conditions("e2e-warm", timeout=180)
        logs = client.get_job_logs("e2e-warm", index=0)
        assert done.status.condition() == ConditionType.SUCCEEDED, logs
        assert "world ok" in logs
        phases = json.load(open(str(tmp_path / "phases") + ".0"))
        # forked from the zygote: jax was already imported, so the
        # import phase is near-zero (vs seconds on a cold interpreter)
        assert phases["imports_done"] - phases["proc_start"] < 2.0
        assert phases["rendezvous_done"] >= phases["imports_done"]
    finally:
        cluster.shutdown()


def test_warm_pool_failed_pod_reports_failed(tmp_path):
    """A zygote-forked pod that dies (bad module / sys.exit) must surface
    as FAILED with its exit code — fast-exit children coalesce the
    pid+exit socket messages, which once wedged the pod Pending."""
    import time

    from kubeflow_tpu.controller.cluster import (
        Pod, PodPhase, admit_pod,
    )

    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"),
                                  warm_pool=True)
    try:
        assert cluster._ensure_zygote(wait_s=120) is not None
        pod = Pod(name="doomed", namespace="default", labels={}, env={},
                  command=[sys.executable, "-m",
                           "kubeflow_tpu.no_such_module"])
        cluster.create_pod(pod)
        admit_pod(cluster, pod)
        deadline = time.time() + 60
        while time.time() < deadline:
            p = cluster.get_pod("default", "doomed")
            if p.phase == PodPhase.FAILED:
                break
            time.sleep(0.1)
        assert p.phase == PodPhase.FAILED and p.exit_code == 1
        assert "no_such_module" in cluster.pod_log("default", "doomed")
    finally:
        cluster.shutdown()


def test_warm_pool_ineligible_command_falls_back_visibly(tmp_path):
    """A warm_pool cluster handed a command that is NOT
    [sys.executable, -m, module] (e.g. a renamed entrypoint) must still
    run the pod — cold spawn — but say so: the cluster counter ticks and
    the pod log names the reason, so a rename silently regressing submit
    latency back to cold-start shows up in bench output instead of
    nowhere."""
    import time

    from kubeflow_tpu.controller.cluster import Pod, PodPhase, admit_pod

    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"),
                                  warm_pool=True)
    try:
        assert cluster.zygote_fallbacks == 0
        pod = Pod(name="renamed", namespace="default", labels={}, env={},
                  command=[sys.executable, "-c", "print('cold ok')"])
        cluster.create_pod(pod)
        admit_pod(cluster, pod)
        deadline = time.time() + 60
        while time.time() < deadline:
            p = cluster.get_pod("default", "renamed")
            if p.phase == PodPhase.SUCCEEDED:
                break
            time.sleep(0.05)
        assert p.phase == PodPhase.SUCCEEDED
        assert cluster.zygote_fallbacks == 1
        log = cluster.pod_log("default", "renamed")
        assert "warm-pool fallback" in log
        assert "cold ok" in log
    finally:
        cluster.shutdown()
