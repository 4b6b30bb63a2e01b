"""Warm-pool subsystem (controller/warmpool.py) over the fake apiserver:
claim races, dead zygotes, informer restarts, operator co-tenancy, and the
real pre-imported-fork e2e with the image-less kubelet.

The races here are the ones that corrupt a pool silently in production:
two jobs claiming the last standby (exactly one may win), a zygote dying
in the claim→use window (the job must still start, cold), and an informer
restart re-LISTing pool members (membership must not double-count).
"""

import json
import os
import socket
import sys
import threading
import time

import pytest

from kubeflow_tpu.api.types import ConditionType, jax_job
from kubeflow_tpu.controller import (
    FakeKubeApiServer, FakeKubelet, JobController, KubeCluster, Operator,
    WarmPoolController,
)
from kubeflow_tpu.controller.cluster import Pod, PodPhase
from kubeflow_tpu.controller.warmpool import (
    POOL_CLASS_LABEL, POOL_STATE_LABEL, ZYGOTE_ADDR_ANNOTATION,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE_ENV = {
    "PYTHONPATH": REPO + ":" + os.environ.get("PYTHONPATH", ""),
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}
ZYGOTE_CMD = [sys.executable, "-m", "kubeflow_tpu.rendezvous.zygote",
              "tcp://127.0.0.1:0"]


@pytest.fixture()
def apiserver():
    srv = FakeKubeApiServer().start()
    yield srv
    srv.stop()


@pytest.fixture()
def kube(apiserver):
    return KubeCluster(apiserver.url)


class StubZygote:
    """Protocol-faithful resident-zygote stand-in (no jax import): accepts
    one connection per claim, acks a pid, then reports an exit."""

    def __init__(self, exit_code: int = 0, hold_s: float = 0.05):
        self.exit_code = exit_code
        self.hold_s = hold_s
        self.requests: list[dict] = []
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(8)
        self.addr = "127.0.0.1:%d" % self._srv.getsockname()[1]
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn):
        try:
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
            self.requests.append(json.loads(buf))
            conn.sendall(json.dumps({"pid": 4242}).encode() + b"\n")
            time.sleep(self.hold_s)
            conn.sendall(json.dumps(
                {"exit": self.exit_code}).encode() + b"\n")
        except OSError:
            pass
        finally:
            conn.close()

    def close(self):
        self._srv.close()


def make_standby(kube, addr, name="kft-warm-default-0", cls="default"):
    """A Running standby pod whose zygote address is already announced —
    the state a claimable pool member is in."""
    pod = Pod(name=name, namespace="default",
              labels={POOL_CLASS_LABEL: cls, POOL_STATE_LABEL: "standby"},
              env={}, command=list(ZYGOTE_CMD), gang=False)
    kube.create_pod(pod)
    kube.set_phase("default", name, PodPhase.RUNNING)
    kube.patch_pod("default", name, {"metadata": {"annotations": {
        ZYGOTE_ADDR_ANNOTATION: addr}}})
    return pod


def job_pod(name="j-worker-0", job="j", uid="u1"):
    return Pod(name=name, namespace="default",
               labels={"job-name": job, "job-uid": uid,
                       "replica-type": "Worker", "replica-index": "0"},
               env={"KFT_PROCESS_ID": "0"},
               command=[sys.executable, "-m", "some.worker"], gang=True)


# ------------------------------------------------------------ claim race --

def test_concurrent_claim_of_last_standby_has_one_winner(kube):
    """Two admissions race for the LAST warm pod: the compare-and-swap
    label patch (apiserver 409s the stale resourceVersion) lets exactly
    one win; the loser cold-falls-back, counted."""
    stub = StubZygote(hold_s=0.5)
    make_standby(kube, stub.addr)
    pool = WarmPoolController(kube, size=1, command=ZYGOTE_CMD)
    results = {}
    barrier = threading.Barrier(2)

    def claim(i):
        pod = job_pod(name=f"j{i}-worker-0", job=f"j{i}", uid=f"u{i}")
        barrier.wait()
        results[i] = pool.claim_and_exec(pod)

    ts = [threading.Thread(target=claim, args=(i,)) for i in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=20)
    won = [r for r in results.values() if r is not None]
    assert len(won) == 1, results
    assert pool.claims == 1 and pool.fallbacks == 1
    # the winner's worker really reached the zygote
    assert len(stub.requests) == 1
    argv = stub.requests[0]["argv"]
    assert argv[1:3] == ["-m", "some.worker"]
    # server truth: the pod is claimed, labeled into exactly one gang
    doc = kube._request("GET", kube._pod_path("default",
                                             "kft-warm-default-0"))
    labels = doc["metadata"]["labels"]
    assert labels[POOL_STATE_LABEL] == "claimed"
    assert labels["job-name"] in ("j0", "j1")


def test_claim_watcher_reports_worker_exit_as_pod_phase(kube):
    """The held claim connection is the container-status reporter: the
    zygote's {"exit": 0} turns into pod phase Succeeded on the server."""
    stub = StubZygote(exit_code=0, hold_s=0.05)
    make_standby(kube, stub.addr)
    pool = WarmPoolController(kube, size=1, command=ZYGOTE_CMD)
    claimed = pool.claim_and_exec(job_pod())
    assert claimed is not None
    deadline = time.time() + 10
    pod = None
    while time.time() < deadline:
        pod = kube.get_pod("default", claimed.name)
        if pod is not None and pod.phase == PodPhase.SUCCEEDED:
            break
        time.sleep(0.05)
    assert pod is not None and pod.phase == PodPhase.SUCCEEDED
    assert pod.exit_code == 0


# ------------------------------------------------- dead zygote fallback --

def test_zygote_dead_between_claim_and_use_falls_back_cold(apiserver, kube):
    """A standby whose zygote died after announcing: the claim wins the
    label patch but the dial fails — the corpse is reaped (visible in
    dead_claims), the pool replenishes, and the JOB STILL STARTS via the
    normal cold path."""
    # an address that is guaranteed refused: bind, learn the port, close
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_addr = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()
    make_standby(kube, dead_addr)
    pool = WarmPoolController(kube, size=1, command=ZYGOTE_CMD,
                              dial_timeout_s=0.5)
    kube.warm_pool = pool

    ctl = JobController(kube)
    job = jax_job("deadzy", workers=1, mesh={"data": 1},
                  command=[sys.executable, "-m", "some.worker"])
    ctl.submit(job)
    ctl.reconcile("default", "deadzy")

    assert pool.dead_claims == 1 and pool.fallbacks == 1
    assert pool.claims == 0
    # the corpse was reaped from the server
    assert apiserver.get("api/v1/pods", "default",
                         "kft-warm-default-0") is None
    # the job's own pod went through the cold path: gate lifted, runnable
    doc = apiserver.get("api/v1/pods", "default", "deadzy-worker-0")
    assert doc is not None and doc["spec"]["schedulingGates"] == []
    # replenish is reconcile's job, not the claim path's
    pool.reconcile()
    assert pool.standby_count() == 1


# -------------------------------------------- informer restart counting --

def test_informer_restart_does_not_double_count_pool(kube):
    """Stop+start of the informer re-LISTs the world; pool membership is
    keyed by name, so the standby census and the replenish loop must both
    see the same N — no phantom members, no extra creates."""
    pool = WarmPoolController(kube, size=2, command=ZYGOTE_CMD)
    pool.reconcile()
    assert pool.standby_count() == 2 and pool.created == 2
    kube.start_informer("")
    try:
        assert pool.standby_count() == 2
    finally:
        kube.stop_informer()
    deadline = time.time() + 10      # stop may lag a blocked watch read
    while kube.informer_running and time.time() < deadline:
        time.sleep(0.05)
    kube.start_informer("")
    try:
        assert pool.standby_count() == 2
        pool.reconcile()             # and the census drives creation
        assert pool.created == 2, "informer restart spawned phantom creates"
    finally:
        kube.stop_informer()


# ------------------------------------------------- operator co-tenancy --

def test_second_operator_does_not_detach_first(kube):
    """ADVICE r5 #1: op2 sharing op1's KubeCluster must not kill op1's
    informer on stop, and op1's event-driven reconcile must keep firing
    (subscriber list, not a single overwritable callback)."""
    op1 = Operator(JobController(kube), reconcile_slow_period=5.0)
    op1.start(port=0)
    op2 = Operator(JobController(kube), reconcile_slow_period=5.0)
    op2.start(port=0)
    try:
        assert op1._informer_owner and not op2._informer_owner
        op2.stop()
        assert kube.informer_running, "op2.stop() killed op1's informer"
        # op1's subscription survived op2's detach (op1's reconcile loop
        # consumes its own wake event, so observe the subscription and the
        # dispatch path separately: op1's callback is still registered,
        # and events still flow to subscribers)
        assert op1._pod_event_cb in kube._pod_event_subs, (
            "op2.stop() removed op1's pod-event subscription")
        assert op2._pod_event_cb not in kube._pod_event_subs
        got = threading.Event()
        kube.add_pod_event_listener(lambda e, p: got.set())
        kube.create_pod(Pod(name="wake", namespace="default", labels={},
                            env={}, command=[]))
        assert got.wait(timeout=10), "informer stopped dispatching events"
    finally:
        op1.stop()
    assert not kube.informer_running


# ---------------------------------------------------------------- e2e --

def test_warm_claim_end_to_end_with_kubelet(apiserver, tmp_path):
    """The whole subsystem, real processes: the pool keeps a standby
    zygote pod hot (imports paid once, off the clock), admission claims
    it, the worker forks pre-imported inside the SAME pod, phases arrive
    over the heartbeat transport, and the job succeeds — with a restarted
    client able to adopt the claim from the annotation alone."""
    kube = KubeCluster(apiserver.url)
    pool = WarmPoolController(kube, size=1, env=dict(BASE_ENV),
                              command=ZYGOTE_CMD)
    ctl = JobController(kube)
    op = Operator(ctl, heartbeat_dir=str(tmp_path / "hb"),
                  heartbeat_period=0.1, reconcile_slow_period=0.2,
                  serving_period=0.2, warm_pool=pool)
    op.start(port=0)
    kubelet = FakeKubelet(apiserver.url,
                          log_dir=str(tmp_path / "pods")).start()
    try:
        # pool warm barrier: standby created, zygote imported + announced
        deadline = time.time() + 120
        ready = False
        while time.time() < deadline and not ready:
            ready = any(
                kubelet.wait_announced(p.namespace, p.name, timeout_s=0.2)
                for p in pool._pool_pods("default", "standby") if p)
            time.sleep(0.1)
        assert ready, "standby zygote never announced"

        # the tcp fork server is token-fenced (an unauthenticated fork
        # endpoint on the pod network would be RCE): a peer without the
        # pod's KFT_ZYGOTE_TOKEN is refused before any fork
        standby = next(p for p in pool._pool_pods("default", "standby")
                       if p is not None)
        doc = kube._request("GET", kube._pod_path(
            standby.namespace, standby.name))
        addr = doc["metadata"]["annotations"][ZYGOTE_ADDR_ANNOTATION]
        host, _, port = addr.rpartition(":")
        with socket.create_connection((host, int(port)), timeout=5) as c:
            c.sendall(json.dumps({"argv": [sys.executable, "-m", "os"],
                                  "env": {}, "token": "wrong"}
                                 ).encode() + b"\n")
            buf = b""
            while b"\n" not in buf:
                chunk = c.recv(65536)
                if not chunk:
                    break
                buf += chunk
        assert b"pid" not in buf and b"error" in buf, buf

        op.submit(jax_job(
            "warm-e2e", workers=1, mesh={"data": 1},
            command=[sys.executable, "-m",
                     "kubeflow_tpu.rendezvous.worker_check"],
            env=dict(BASE_ENV)))
        deadline = time.time() + 120
        job = ctl.get("default", "warm-e2e")
        while time.time() < deadline and not job.status.is_finished():
            time.sleep(0.2)
        assert job.status.condition() == ConditionType.SUCCEEDED, (
            job.status.conditions,
            kubelet.pod_log("default", "kft-warm-default-0"))

        assert pool.claims == 1 and pool.fallbacks == 0
        # the pod that ran the worker IS the pool pod, not a cold one
        pods = kube.list_pods("default", {"job-name": "warm-e2e"})
        assert pods and all(p.name.startswith("kft-warm-") for p in pods)
        # phase stamps came over the HEARTBEAT transport (no shared-fs
        # phase files exist anywhere) and show the fork skipped imports
        phases = op.job_phases("default", "warm-e2e")
        assert phases, "no phases arrived over the heartbeat transport"
        ph = next(iter(phases.values()))
        assert ph["imports_done"] - ph["proc_start"] < 1.0, ph
        # a FRESH client adopts the claim alias from the annotation
        fresh = KubeCluster(apiserver.url)
        fresh.list_pods("default", {"job-name": "warm-e2e"})
        adopted = fresh.get_pod("default", "warm-e2e-worker-0")
        assert adopted is not None
        assert adopted.name.startswith("kft-warm-")
    finally:
        op.stop()
        kubelet.stop()
