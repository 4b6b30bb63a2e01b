"""MPMD pipeline parallelism (parallel/mpmd.py): schedules, transport,
numerics parity against the single-program SPMD oracle, bubble/overlap
measurement math, and the stage rendezvous the controller stamps.

The numerics contract under test is the ISSUE-15 acceptance: GPipe and
1F1B produce BITWISE-identical loss trajectories (same per-microbatch
programs, one fixed grad-reduce order), and both reproduce the SPMD
``pipeline_apply`` oracle — step-0 loss bitwise, later steps to XLA
fusion-level float32 round-off (separately-compiled programs reassociate
fusions; a REAL wiring bug diverges by orders of magnitude, not ulps)."""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.parallel.mpmd import (
    InProcFabric, PipelineRunConfig, StageRuntime, TCPStageChannel,
    aggregate_stats, analytic_bubble_bound, max_live_stash, run_inproc,
    run_oracle, schedule_ticks,
)
from kubeflow_tpu.rendezvous.bootstrap import stage_from_env

TINY = dict(n_stages=2, microbatches=4, global_batch=32, dim=48,
            layers_per_stage=2, steps=4)


# ------------------------------------------------------------ schedules --

def test_schedule_ticks_gpipe_and_1f1b():
    g = schedule_ticks("gpipe", 2, 0, 4)
    assert g == [("fwd", 0), ("fwd", 1), ("fwd", 2), ("fwd", 3),
                 ("bwd", 3), ("bwd", 2), ("bwd", 1), ("bwd", 0)]
    f0 = schedule_ticks("1f1b", 2, 0, 4)
    assert f0 == [("fwd", 0), ("fwd", 1), ("bwd", 0), ("fwd", 2),
                  ("bwd", 1), ("fwd", 3), ("bwd", 2), ("bwd", 3)]
    f1 = schedule_ticks("1f1b", 2, 1, 4)
    assert f1[0] == ("fwd", 0) and f1[1] == ("bwd", 0)
    # every schedule runs every microbatch exactly once per phase
    for ticks in (g, f0, f1):
        assert sorted(i for p, i in ticks if p == "fwd") == [0, 1, 2, 3]
        assert sorted(i for p, i in ticks if p == "bwd") == [0, 1, 2, 3]


def test_activation_stash_memory_contract():
    """THE 1F1B advantage: its stash never exceeds S live microbatches,
    while GPipe's grows to M — so at GPipe's M-sized activation budget,
    1F1B can run more microbatches and shrink the fill-drain bubble."""
    S = 4
    for M in (4, 8, 16):
        for s in range(S):
            assert max_live_stash(schedule_ticks("gpipe", S, s, M)) == M
            assert max_live_stash(schedule_ticks("1f1b", S, s, M)) <= S
    assert analytic_bubble_bound(2, 8) < analytic_bubble_bound(2, 4)


# ------------------------------------------------------------- numerics --

def test_gpipe_and_1f1b_bitwise_identical():
    cfg_g = PipelineRunConfig(schedule="gpipe", **TINY)
    cfg_f = PipelineRunConfig(schedule="1f1b", **TINY)
    _, losses_g = run_inproc(cfg_g)
    _, losses_f = run_inproc(cfg_f)
    assert len(losses_g) == TINY["steps"]
    assert losses_g == losses_f        # bitwise: schedule must not change math


def test_mpmd_matches_spmd_pipeline_oracle():
    """The MPMD run against the single-program pipeline_apply oracle:
    step-0 loss bitwise (same forward math through different programs),
    full trajectory within float32 fusion round-off."""
    cfg = PipelineRunConfig(schedule="1f1b", **TINY)
    _, losses = run_inproc(cfg)
    oracle = run_oracle(cfg)
    assert losses[0] == oracle[0]
    np.testing.assert_allclose(losses, oracle, rtol=2e-5, atol=0)


def test_three_stage_pipeline_runs_and_matches_oracle():
    cfg = PipelineRunConfig(n_stages=3, microbatches=3, global_batch=24,
                            dim=32, layers_per_stage=1, steps=3,
                            schedule="1f1b")
    _, losses = run_inproc(cfg)
    oracle = run_oracle(cfg)
    # not bitwise, step 0 included: three stage programs and the oracle's
    # one program are compiled separately, and XLA sums the loss's 24 rows
    # in another order (one float32 ulp here); a wiring bug is off by
    # orders of magnitude
    np.testing.assert_allclose(losses, oracle, rtol=2e-5, atol=0)


def test_per_stage_mesh_runs_and_agrees(mesh8):
    """Per-stage meshes: each stage's program auto-partitions its
    microbatch rows over its OWN 2-device mesh; the loss trajectory
    agrees with the single-device run (not bitwise — an intra-stage
    psum reassociates the row reduction)."""
    from jax.sharding import Mesh

    cfg = PipelineRunConfig(schedule="1f1b", **TINY)
    devs = jax.devices()
    meshes = [Mesh(np.array(devs[0:2]), ("stage_dp",)),
              Mesh(np.array(devs[2:4]), ("stage_dp",))]
    runtimes = [StageRuntime(cfg, s, mesh=meshes[s]) for s in range(2)]
    _, losses = run_inproc(cfg, runtimes=runtimes)
    _, base = run_inproc(cfg)
    np.testing.assert_allclose(losses, base, rtol=1e-5, atol=0)


# ------------------------------------------------------------ transport --

def test_tcp_channel_roundtrip_and_out_of_order_keys():
    a = TCPStageChannel("127.0.0.1:0", prev=None, next=None, stage=0)
    b = TCPStageChannel("127.0.0.1:0", prev=a.address, next=None, stage=1)
    a.next_addr = b.address
    try:
        # send two acts out of order; recv by key pairs them correctly
        a.send_act(0, 1, np.full((2, 2), 1.0, np.float32))
        a.send_act(0, 0, np.full((2, 2), 7.0, np.float32))
        got0 = b.recv_act(0, 0)
        got1 = b.recv_act(0, 1)
        assert got0[0, 0] == 7.0 and got1[0, 0] == 1.0
        b.send_grad(0, 0, np.zeros((1,), np.float32))
        assert a.recv_grad(0, 0).shape == (1,)
        s = a.stats.snapshot()
        assert s["sends"] == 2 and s["bytes_sent"] > 0 and s["wire_s"] > 0
        assert b.stats.snapshot()["recvs"] == 2
    finally:
        a.close()
        b.close()


def test_bind_falls_back_to_all_interfaces_for_service_names():
    """KFT_STAGE_BIND on the kube backend is a stage-Service DNS name a
    pod cannot bind(); the channel binds the PORT on all interfaces and
    keeps advertising the service name (the Service routes to the pod)."""
    ch = TCPStageChannel("job-stage-0.default.svc:0", prev=None, next=None,
                         stage=0)
    try:
        assert ch.address.startswith("job-stage-0.default.svc:")
        assert int(ch.address.rsplit(":", 1)[1]) > 0
    finally:
        ch.close()


def test_async_sender_failure_poisons_recv_promptly():
    """A 1F1B sender thread hitting a dead peer must surface the
    transport error to the compute thread's next recv (with the cause),
    not die silently and leave a 120s recv timeout."""
    tx = TCPStageChannel("127.0.0.1:0", prev=None,
                         next="127.0.0.1:1", stage=0,   # port 1: refused
                         blocking=False, timeout_s=30.0)
    # make the connect retry window short so the failure fires promptly
    tx.timeout_s = 0.3
    try:
        tx.send_act(0, 0, np.zeros((2,), np.float32))
        time.sleep(1.0)        # let the sender exhaust its connect window
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="stage transport failed"):
            tx.recv_grad(0, 0)
        assert time.perf_counter() - t0 < 1.0      # poison, not timeout
    finally:
        tx.close()


def test_extra_stage_proc_exits_cleanly(tmp_path):
    """workers_per_stage > 1: procs beyond 0 exit 0 with a note instead
    of racing proc 0 for the stage bind (EADDRINUSE)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "PYTHONPATH": repo + ":" + os.environ.get("PYTHONPATH", ""),
           "KFT_NUM_STAGES": "2", "KFT_STAGE_ID": "0",
           "KFT_STAGE_WORKERS": "2", "KFT_STAGE_PROC_ID": "1",
           "KFT_STAGE_BIND": "127.0.0.1:0"}
    proc = subprocess.run(
        [sys.executable, "-m", "kubeflow_tpu.parallel.mpmd"], env=env,
        capture_output=True, timeout=120)
    assert proc.returncode == 0
    assert b"proc 0 owns the stage program" in proc.stdout


def test_recv_timeout_raises():
    a = TCPStageChannel("127.0.0.1:0", prev=None, next=None, stage=0,
                        timeout_s=0.2)
    try:
        with pytest.raises(TimeoutError):
            a.recv_act(0, 0)
        assert a.stats.snapshot()["recv_block_s"] >= 0.2
    finally:
        a.close()


def test_async_send_hides_wire_time_blocking_exposes_it():
    """The overlap mechanism itself: with an emulated DCN delay, a
    blocking channel's send_block ~= wire (exposed), an async channel's
    send_block stays near zero (hidden in the sender thread)."""
    delay = 0.05
    payload = np.zeros((64, 64), np.float32)

    def run(blocking):
        rx = TCPStageChannel("127.0.0.1:0", prev=None, next=None, stage=1)
        tx = TCPStageChannel("127.0.0.1:0", prev=None, next=rx.address,
                             stage=0, blocking=blocking, delay_s=delay)
        try:
            for i in range(3):
                tx.send_act(0, i, payload)
            for i in range(3):
                rx.recv_act(0, i)
            return tx.stats.snapshot()
        finally:
            tx.close()
            rx.close()

    blocked = run(True)
    assert blocked["send_block_s"] >= 3 * delay
    hidden = run(False)
    assert hidden["wire_s"] >= 3 * delay
    assert hidden["send_block_s"] < delay


# ---------------------------------------------------------- measurement --

def test_aggregate_stats_math_is_exact():
    """Synthetic per-stage reports with known idle -> exact bubble and
    overlap numbers (the bench trusts this math)."""
    cfg = PipelineRunConfig(n_stages=2, microbatches=4, global_batch=32,
                            dim=8, steps=3, schedule="gpipe")
    mk = lambda busy: [{"t0": float(k), "t1": float(k) + 1.0,
                        "busy_s": busy, "send_block_s": 0.0}
                       for k in range(3)]
    reports = [
        {"stage": 0, "step_stats": mk(0.8), "max_stash": 4,
         "transport": {"wire_s": 1.0, "send_block_s": 0.25,
                       "recv_block_s": 0.0}},
        {"stage": 1, "step_stats": mk(0.6), "max_stash": 4,
         "transport": {"wire_s": 1.0, "send_block_s": 0.75,
                       "recv_block_s": 0.0}},
    ]
    agg = aggregate_stats(reports, cfg, skip_steps=1)
    # idle = (1-0.8) + (1-0.6) = 0.6 over S*window = 2.0 -> 0.3
    assert agg["bubble_fraction"] == pytest.approx(0.3)
    assert agg["steps_measured"] == 2
    assert agg["analytic_fill_drain_bound"] == pytest.approx(0.2)
    # overlap = 1 - (0.25+0.75)/2.0
    assert agg["dcn_overlap_fraction"] == pytest.approx(0.5)
    assert agg["est_basis"].startswith("measured")


def test_aggregate_stats_requires_all_stages():
    cfg = PipelineRunConfig(**TINY)
    with pytest.raises(ValueError):
        aggregate_stats([{"stage": 0, "step_stats": [], "max_stash": 1,
                          "transport": {}}], cfg)


def test_measured_gpipe_run_reports_bubble_and_overlap():
    """End-to-end in-proc measurement sanity: fractions exist, sit in
    (0, 1), and the blocking schedule exposes its wire time. (The
    agreement-with-analytic gate runs in the multi-process bench smoke,
    where stages don't share one XLA thread pool.)"""
    cfg = PipelineRunConfig(schedule="gpipe", **TINY)
    res, _ = run_inproc(cfg)
    agg = aggregate_stats(res, cfg)
    assert 0.0 < agg["bubble_fraction"] < 1.0
    assert agg["dcn_overlap_fraction"] is not None
    assert agg["dcn_wire_s"] > 0
    assert agg["max_activation_stash"] == cfg.microbatches


# --------------------------------------------- pipeline_apply aux mask --

def test_pipeline_apply_bubble_tick_aux_masking(mesh8):
    """Direct unit test of the stage_aux bubble masking (ISSUE-15
    satellite): a stage aux that pays +1 per EXECUTED tick would count
    S*(M+S-1) without masking; the contract is S*M/M = S (bubble ticks
    on zero-injected activations are masked out of the average)."""
    from jax.sharding import Mesh

    from kubeflow_tpu.parallel.pipeline import pipeline_apply

    S, M = 2, 4
    mesh = Mesh(mesh8.devices.reshape(8)[:S], ("pipeline",))

    def stage_fn(p, x):
        # aux = 1 + 0*x: constant per tick, nonzero even on bubble ticks
        return x + p, jnp.float32(1.0) + 0.0 * jnp.sum(x)

    fwd = pipeline_apply(stage_fn, mesh, microbatches=M, stage_aux=True)
    stacked = jnp.zeros((S, 1))          # per-stage scalar param, stage dim
    x = jnp.ones((8, 4), jnp.float32)
    y, aux = jax.jit(fwd)(stacked, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))
    # masked: each stage contributes exactly M valid ticks -> sum/M == S
    assert float(aux) == pytest.approx(S)


def test_stack_stage_params_returns_pytree():
    from kubeflow_tpu.parallel.pipeline import stack_stage_params

    stacked = stack_stage_params([{"w": jnp.ones((2,))},
                                  {"w": jnp.zeros((2,))}])
    assert isinstance(stacked, dict) and stacked["w"].shape == (2, 2)


# ------------------------------------------------------ stage rendezvous --

def test_stage_from_env_parses_and_defaults():
    info = stage_from_env({
        "KFT_NUM_STAGES": "3", "KFT_STAGE_ID": "1",
        "KFT_STAGE_BIND": "127.0.0.1:9001",
        "KFT_STAGE_PREV": "127.0.0.1:9000",
        "KFT_STAGE_NEXT": "127.0.0.1:9002"})
    assert info.stage_id == 1 and info.n_stages == 3
    assert not info.is_first and not info.is_last
    assert info.prev.endswith("9000") and info.next.endswith("9002")
    assert stage_from_env({"KFT_COORDINATOR": "x"}) is None


def test_pipeline_job_env_stamping_and_services():
    """The reconciler's stage rendezvous: per-stage services, per-pod
    stage env with neighbor addresses, stage labels — one gang job."""
    from kubeflow_tpu.api.types import pipeline_jax_job
    from kubeflow_tpu.controller.cluster import FakeCluster
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    ctl = JobController(cluster)
    job = ctl.submit(pipeline_jax_job(
        "pipe", stages=3, workers_per_stage=1,
        command=["python", "-m", "kubeflow_tpu.parallel.mpmd"]))
    ctl.reconcile("default", "pipe")

    assert cluster.get_service("default", "pipe-stage-0") is not None
    assert cluster.get_service("default", "pipe-stage-2") is not None
    pods = sorted(cluster.list_pods("default", {"job-name": "pipe"}),
                  key=lambda p: p.name)
    assert len(pods) == 3
    binds = {}
    for i, pod in enumerate(pods):
        env = pod.env
        assert env["KFT_NUM_STAGES"] == "3"
        assert env["KFT_STAGE_ID"] == str(i)
        assert pod.labels["pipeline-stage"] == str(i)
        binds[i] = env["KFT_STAGE_BIND"]
    # neighbor addresses point at the neighbor's own bind endpoint
    assert pods[0].env["KFT_STAGE_NEXT"] == binds[1]
    assert pods[1].env["KFT_STAGE_PREV"] == binds[0]
    assert pods[1].env["KFT_STAGE_NEXT"] == binds[2]
    assert pods[2].env["KFT_STAGE_PREV"] == binds[1]
    assert "KFT_STAGE_PREV" not in pods[0].env
    assert "KFT_STAGE_NEXT" not in pods[2].env
    # stage services survive job deletion cleanup
    ctl.delete("default", "pipe")
    assert cluster.get_service("default", "pipe-stage-0") is None


def test_pipeline_job_multiworker_stage_groups():
    from kubeflow_tpu.api.types import pipeline_jax_job
    from kubeflow_tpu.controller.cluster import FakeCluster
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    ctl = JobController(cluster)
    ctl.submit(pipeline_jax_job("pipe2", stages=2, workers_per_stage=2))
    ctl.reconcile("default", "pipe2")
    pods = sorted(cluster.list_pods("default", {"job-name": "pipe2"}),
                  key=lambda p: p.name)
    got = [(p.env["KFT_STAGE_ID"], p.env["KFT_STAGE_PROC_ID"]) for p in pods]
    assert got == [("0", "0"), ("0", "1"), ("1", "0"), ("1", "1")]
    assert all(p.env["KFT_STAGE_WORKERS"] == "2" for p in pods)


def test_pipeline_job_validation():
    from kubeflow_tpu.api.types import (
        ValidationError, jax_job, pipeline_jax_job, validate,
    )

    with pytest.raises(ValidationError):
        pipeline_jax_job("p", stages=1)
    bad = jax_job("p", workers=3, env={"KFT_NUM_STAGES": "2"})
    with pytest.raises(ValidationError):
        validate(bad)
    validate(jax_job("p", workers=4, env={"KFT_NUM_STAGES": "2"}))


def test_stage_worker_replacement_keeps_stage_identity():
    """A dead stage worker takes the PR 9 per-worker replacement path —
    NOT a gang restart — and the recreated pod carries the SAME stage
    rendezvous env (id, bind, neighbors) under a new incarnation, so the
    pipeline's wiring survives the death."""
    from kubeflow_tpu.api.types import pipeline_jax_job
    from kubeflow_tpu.controller.cluster import FakeCluster, PodPhase
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    cluster.warm_pool = True
    ctl = JobController(cluster)
    job = ctl.submit(pipeline_jax_job("pl", stages=3))
    ctl.reconcile("default", "pl")
    cluster.run_scheduled()
    ctl.reconcile("default", "pl")
    before = cluster.get_pod("default", "pl-worker-1")
    assert before.env["KFT_STAGE_ID"] == "1"
    bind = before.env["KFT_STAGE_BIND"]

    cluster.set_phase("default", "pl-worker-1", PodPhase.FAILED, -9)
    ctl.reconcile("default", "pl")
    assert job.status.restart_count == 0        # replacement, not restart
    assert job.status.worker_replacements == 1
    ctl.reconcile("default", "pl")              # recreate pass
    after = cluster.get_pod("default", "pl-worker-1")
    assert after is not None
    assert after.env["KFT_STAGE_ID"] == "1"
    assert after.env["KFT_STAGE_BIND"] == bind   # service-stable address
    assert after.env["KFT_WORKER_INCARNATION"] == "1"
    # neighbors were never re-stamped and still point at the same bind
    assert cluster.get_pod("default", "pl-worker-0").env[
        "KFT_STAGE_NEXT"] == bind
    assert cluster.get_pod("default", "pl-worker-2").env[
        "KFT_STAGE_PREV"] == bind


# --------------------------------------------------- multi-process e2e --

@pytest.mark.slow
def test_two_process_1f1b_worker_entry(tmp_path):
    """The real worker entry (`python -m kubeflow_tpu.parallel.mpmd`) as
    two OS processes over TCP: losses land in the report dir and match
    the in-proc run bitwise (same programs, same machine)."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ports = (free_port(), free_port())
    base = {**os.environ,
            "PYTHONPATH": repo + ":" + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "KFT_NUM_STAGES": "2",
            "KFT_MPMD_MICROBATCHES": "4", "KFT_MPMD_BATCH": "32",
            "KFT_MPMD_DIM": "48", "KFT_MPMD_LAYERS": "2",
            "KFT_MPMD_STEPS": "3", "KFT_MPMD_SCHEDULE": "1f1b",
            "KFT_MPMD_REPORT_DIR": str(tmp_path)}
    procs = []
    for sid in (0, 1):
        env = dict(base)
        env["KFT_STAGE_ID"] = str(sid)
        env["KFT_STAGE_BIND"] = f"127.0.0.1:{ports[sid]}"
        if sid == 0:
            env["KFT_STAGE_NEXT"] = f"127.0.0.1:{ports[1]}"
        else:
            env["KFT_STAGE_PREV"] = f"127.0.0.1:{ports[0]}"
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.parallel.mpmd"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out.decode()[-2000:]
    report = json.load(open(tmp_path / "stage-1.json"))
    cfg = PipelineRunConfig(n_stages=2, microbatches=4, global_batch=32,
                            dim=48, layers_per_stage=2, steps=3,
                            schedule="1f1b")
    _, inproc_losses = run_inproc(cfg)
    assert report["losses"] == inproc_losses
