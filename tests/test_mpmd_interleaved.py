"""Interleaved-1F1B (virtual stages) — ISSUE 19.

The schedule contract: each worker owns V model chunks (stage i,
i+S, ...); `schedule_ticks("interleaved-1f1b", ...)` emits 3-field
(kind, vchunk, mb) ticks whose cross-stage dependency graph is
deadlock-free, whose activation stash never exceeds the analytic
V-chunk bound, and whose loss is BITWISE identical to GPipe / plain
1F1B over the same chunk partition. Depot keys fold the virtual-chunk
index so warm resubmits hit PER CHUNK; the rendezvous env carries the
ring-wrap links and per-stage group identity."""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.parallel.mpmd import (
    PipelineRunConfig,
    StageRuntime,
    analytic_bubble_bound,
    interleaved_stash_bound,
    max_live_stash,
    run_inproc,
    run_oracle,
    schedule_ticks,
)
from kubeflow_tpu.rendezvous.bootstrap import stage_from_env

SHAPES = [(2, 4, 2), (2, 8, 2), (2, 4, 4), (3, 6, 2), (4, 8, 2)]


# ------------------------------------------------------- tick-plan validity --

def _simulate(S, M, V):
    """Event-driven replay of every stage's tick list against the true
    cross-stage dependencies; returns the completed-unit set (raises via
    assert if any stage wedges — a deadlocked plan)."""
    plans = {s: schedule_ticks("interleaved-1f1b", S, s, M,
                               virtual_stages=V) for s in range(S)}
    pos = {s: 0 for s in range(S)}
    done: set = set()
    T = S * V
    progress = True
    while progress:
        progress = False
        for s in range(S):
            while pos[s] < len(plans[s]):
                kind, v, mb = plans[s][pos[s]]
                c = s + v * S
                if kind == "fwd":
                    need = [("fwd", c - 1, mb)] if c > 0 else []
                else:
                    need = [("fwd", c, mb)]
                    if c < T - 1:
                        need.append(("bwd", c + 1, mb))
                if not all(n in done for n in need):
                    break
                done.add((kind, c, mb))
                pos[s] += 1
                progress = True
    stuck = {s: plans[s][pos[s]] for s in range(S)
             if pos[s] < len(plans[s])}
    assert not stuck, f"deadlocked plan S={S} M={M} V={V}: {stuck}"
    return done


@pytest.mark.parametrize("S,M,V", SHAPES)
def test_interleaved_plan_is_complete_and_deadlock_free(S, M, V):
    done = _simulate(S, M, V)
    # every (chunk, mb) forwarded AND backwarded exactly once
    assert len(done) == 2 * S * V * M
    for c in range(S * V):
        for mb in range(M):
            assert ("fwd", c, mb) in done and ("bwd", c, mb) in done


@pytest.mark.parametrize("S,M,V", SHAPES)
def test_interleaved_ticks_fwd_before_bwd_per_unit(S, M, V):
    for s in range(S):
        ticks = schedule_ticks("interleaved-1f1b", S, s, M,
                               virtual_stages=V)
        assert len(ticks) == 2 * V * M
        seen_fwd = set()
        for kind, v, mb in ticks:
            if kind == "fwd":
                assert (v, mb) not in seen_fwd
                seen_fwd.add((v, mb))
            else:
                assert (v, mb) in seen_fwd, \
                    f"bwd({v},{mb}) before its fwd at stage {s}"


@pytest.mark.parametrize("S,M,V", SHAPES)
def test_interleaved_stash_within_analytic_bound(S, M, V):
    for s in range(S):
        ticks = schedule_ticks("interleaved-1f1b", S, s, M,
                               virtual_stages=V)
        bound = interleaved_stash_bound(S, s, M, V)
        assert max_live_stash(ticks) <= bound
    # earlier stages stash at least as much as later ones
    bounds = [interleaved_stash_bound(S, s, M, V) for s in range(S)]
    assert bounds == sorted(bounds, reverse=True)


def test_interleaved_analytic_bound_below_plain_floor():
    # the point of the schedule: (S-1)/(V*M+S-1) < (S-1)/(M+S-1)
    for S, M, V in SHAPES:
        assert analytic_bubble_bound(S, M, V) < analytic_bubble_bound(S, M)
    assert analytic_bubble_bound(2, 8, 2) == pytest.approx(1 / 17)
    assert analytic_bubble_bound(2, 8) == pytest.approx(1 / 9)


def test_schedule_ticks_plain_schedules_keep_two_field_ticks():
    # back-compat: V=1 consumers unpack (kind, mb) tuples
    for sched in ("gpipe", "1f1b"):
        for t in schedule_ticks(sched, 2, 0, 4):
            assert len(t) == 2


def test_interleaved_config_validation():
    with pytest.raises(ValueError):
        PipelineRunConfig(schedule="interleaved-1f1b",
                          virtual_stages=1).validate()
    with pytest.raises(ValueError):
        PipelineRunConfig(schedule="interleaved-1f1b", n_stages=2,
                          microbatches=5, virtual_stages=2).validate()
    with pytest.raises(ValueError):
        PipelineRunConfig(schedule="1f1b", virtual_stages=2).validate()
    PipelineRunConfig(schedule="interleaved-1f1b", n_stages=2,
                      microbatches=4, virtual_stages=2).validate()


# ------------------------------------------------------- bitwise parity --

def _tiny(schedule, n_stages, virtual_stages=1):
    return PipelineRunConfig(
        schedule=schedule, n_stages=n_stages,
        virtual_stages=virtual_stages, microbatches=4, global_batch=8,
        dim=16, layers_per_stage=1, steps=3)


def test_mlp_interleaved_bitwise_vs_gpipe_1f1b_and_oracle():
    """Same 4-chunk partition driven by three schedules: the loss
    trajectories must be fully BITWISE identical — the same chunk
    programs run, and the fixed descending grad-reduce order makes the
    schedule invisible to the math."""
    _, li = run_inproc(_tiny("interleaved-1f1b", 2, 2))
    _, lg = run_inproc(_tiny("gpipe", 4))
    _, lf = run_inproc(_tiny("1f1b", 4))
    assert li == lg == lf
    lo = run_oracle(_tiny("interleaved-1f1b", 2, 2))
    # the SPMD oracle is ONE separately compiled program: XLA fuses and
    # reassociates it differently from the four chunk programs, so it
    # agrees to float32 round-off (an ulp at step 0 here), not bitwise
    assert max(abs(a - b) / abs(b) for a, b in zip(li, lo)) <= 2e-5


def test_interleaved_measured_stash_matches_accounting():
    results, _ = run_inproc(_tiny("interleaved-1f1b", 2, 2))
    for r in results:
        assert r.max_stash <= interleaved_stash_bound(2, r.stage, 4, 2)
    # stage 0 holds warmup fwds for both its chunks; stage 1 fewer
    assert results[0].max_stash > results[1].max_stash


# ------------------------------------------------------------ depot keys --

def test_depot_fingerprint_folds_virtual_stage():
    from kubeflow_tpu.parallel.depot import fingerprint

    hlo = "HloModule chunk"
    keys = {fingerprint(hlo, stage=0, vstage=v) for v in range(4)}
    assert len(keys) == 4, "virtual chunks must never collide"
    # vstage=None keeps the PR 11 key bytes (plain pipelines unchanged)
    assert fingerprint(hlo, stage=0) == fingerprint(hlo, stage=0,
                                                    vstage=None)
    assert fingerprint(hlo, stage=0) != fingerprint(hlo, stage=0,
                                                    vstage=0)
    # vstage composes with stage: (stage=0,v=1) != (stage=1,v=0)
    assert fingerprint(hlo, stage=0, vstage=1) != fingerprint(
        hlo, stage=1, vstage=0)


def test_interleaved_runtime_warm_hits_per_chunk(tmp_path):
    """A resubmitted interleaved stage deserializes EVERY chunk's
    programs from the depot — per-chunk keys, per-chunk outcomes."""
    from kubeflow_tpu.parallel.depot import DepotStats, DirectoryDepot

    depot = DirectoryDepot(str(tmp_path))
    cfg = _tiny("interleaved-1f1b", 2, 2)
    s1 = DepotStats()
    rt = StageRuntime(cfg, 0, depot=depot, depot_stats=s1)
    pub = rt.depot_summary()["outcomes"]
    assert set(pub) == {"fwd.c0", "bwd.c0", "fwd.c2", "bwd.c2"}
    assert all(v == "published" for v in pub.values())
    s2 = DepotStats()
    rt2 = StageRuntime(cfg, 0, depot=depot, depot_stats=s2)
    warm = rt2.depot_summary()
    assert warm["hit"] and set(warm["outcomes"]) == set(pub)
    assert all(v == "hit" for v in warm["outcomes"].values())
    # last stage additionally owns the head, keyed to the LAST chunk
    rt3 = StageRuntime(cfg, 1, depot=depot, depot_stats=DepotStats())
    assert set(rt3.depot_summary()["outcomes"]) == {
        "fwd.c1", "bwd.c1", "fwd.c3", "bwd.c3", "head.c3"}


# ---------------------------------------------------------- env contract --

def test_stage_from_env_interleaved_and_group_fields():
    info = stage_from_env({
        "KFT_NUM_STAGES": "2", "KFT_STAGE_ID": "1",
        "KFT_STAGE_BIND": "127.0.0.1:9001",
        "KFT_VIRTUAL_STAGES": "2",
        "KFT_STAGE_WRAP_NEXT": "127.0.0.1:9000",
        "KFT_STAGE_GROUP_SIZE": "2", "KFT_STAGE_GROUP_RANK": "1",
        "KFT_STAGE_GROUP_COORD": "127.0.0.1:9001"})
    assert info.virtual_stages == 2
    assert info.wrap_next == "127.0.0.1:9000" and info.wrap_prev is None
    assert info.group_size == 2 and info.group_rank == 1
    assert info.group_coord == "127.0.0.1:9001"
    # defaults: group identity falls back to the stage-worker fields
    legacy = stage_from_env({
        "KFT_NUM_STAGES": "2", "KFT_STAGE_WORKERS": "4",
        "KFT_STAGE_PROC_ID": "3"})
    assert legacy.virtual_stages == 1
    assert legacy.wrap_next is None and legacy.wrap_prev is None
    assert legacy.group_size == 4 and legacy.group_rank == 3


def test_reconciler_stamps_group_and_wrap_env():
    from kubeflow_tpu.api.types import pipeline_jax_job
    from kubeflow_tpu.controller.cluster import FakeCluster
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    ctl = JobController(cluster)
    ctl.submit(pipeline_jax_job("vp", stages=3, workers_per_stage=2,
                                virtual_stages=2))
    ctl.reconcile("default", "vp")
    pods = sorted(cluster.list_pods("default", {"job-name": "vp"}),
                  key=lambda p: p.name)
    assert len(pods) == 6
    for pod in pods:
        env = pod.env
        assert env["KFT_STAGE_GROUP_SIZE"] == "2"
        assert env["KFT_STAGE_GROUP_RANK"] == env["KFT_STAGE_PROC_ID"]
        sid = env["KFT_STAGE_ID"]
        assert env["KFT_STAGE_GROUP_COORD"] == \
            cluster.resolve("default", f"vp-stage-{sid}")
        assert env["KFT_VIRTUAL_STAGES"] == "2"
        # ring wrap: ONLY the ends carry wrap links
        if sid == "0":
            assert env["KFT_STAGE_WRAP_PREV"] == \
                cluster.resolve("default", "vp-stage-2")
            assert "KFT_STAGE_WRAP_NEXT" not in env
        elif sid == "2":
            assert env["KFT_STAGE_WRAP_NEXT"] == \
                cluster.resolve("default", "vp-stage-0")
            assert "KFT_STAGE_WRAP_PREV" not in env
        else:
            assert "KFT_STAGE_WRAP_NEXT" not in env
            assert "KFT_STAGE_WRAP_PREV" not in env
    # parsed StageInfo round-trips the stamped env
    info = stage_from_env(pods[0].env)
    assert info.group_size == 2 and info.virtual_stages == 2
    assert info.wrap_prev is not None


def test_plain_pipeline_job_stamps_no_virtual_env():
    from kubeflow_tpu.api.types import pipeline_jax_job
    from kubeflow_tpu.controller.cluster import FakeCluster
    from kubeflow_tpu.controller.reconciler import JobController

    cluster = FakeCluster()
    ctl = JobController(cluster)
    ctl.submit(pipeline_jax_job("pv1", stages=2))
    ctl.reconcile("default", "pv1")
    for pod in cluster.list_pods("default", {"job-name": "pv1"}):
        assert "KFT_VIRTUAL_STAGES" not in pod.env
        assert "KFT_STAGE_WRAP_NEXT" not in pod.env
        assert "KFT_STAGE_WRAP_PREV" not in pod.env
        # group identity is stamped unconditionally
        assert pod.env["KFT_STAGE_GROUP_SIZE"] == "1"


def test_pipeline_job_virtual_stages_validation():
    from kubeflow_tpu.api.types import ValidationError, pipeline_jax_job

    with pytest.raises(ValidationError):
        pipeline_jax_job("bad", stages=2, virtual_stages=0)
    job = pipeline_jax_job("ok", stages=2, virtual_stages=3)
    assert job.replica_specs["Worker"].template.env[
        "KFT_VIRTUAL_STAGES"] == "3"


# ------------------------------------------------------------ trace lanes --

def test_job_trace_gives_each_virtual_chunk_its_own_lane():
    from kubeflow_tpu.obs.export import build_job_trace

    spans = build_job_trace(
        "default", "j", "uid", {},
        worker_spans={"pod-0": [
            {"name": "pipeline.tick", "t0": 1.0, "t1": 2.0,
             "attrs": {"vstage": 0, "chunk": 0}},
            {"name": "pipeline.tick", "t0": 2.0, "t1": 3.0,
             "attrs": {"vstage": 1, "chunk": 2}},
        ]})
    ticks = [s for s in spans if s["name"] == "pipeline.tick"]
    assert {t["tid"] for t in ticks} == {0, 1}


# --------------------------------------------------- aot bubble projection --

def test_pipeline_mfu_projection_scales_by_analytic_ratio():
    from kubeflow_tpu.parallel.aot import (
        apply_pipeline_projection, pipeline_mfu_projection, ScaleProof,
    )

    measured = 0.05
    got = pipeline_mfu_projection(measured, n_stages=2, microbatches=8,
                                  virtual_stages=2,
                                  target_stages=8,
                                  target_microbatches=64,
                                  target_virtual_stages=2)
    expect = measured * analytic_bubble_bound(8, 64, 2) \
        / analytic_bubble_bound(2, 8, 2)
    assert got == pytest.approx(expect)
    proof = ScaleProof(name="p", topology="t", num_slices=2,
                       n_devices=64, mesh_axes={}, argument_gb=0,
                       temp_gb=0, output_gb=0, peak_gb=0, hbm_gb=95,
                       fits=True)
    proof.est_mfu = 0.5
    apply_pipeline_projection(proof, {
        "bubble_fraction": measured, "n_stages": 2, "microbatches": 8,
        "virtual_stages": 2})
    assert proof.pipe_bubble_measured == pytest.approx(0.05)
    assert proof.pipe_mfu == pytest.approx(
        0.5 * (1 - proof.pipe_bubble_projected), abs=1e-4)
    assert "S=8" in proof.pipe_basis


# ------------------------------------------------- llama through the runner --

_LLAMA_ENV = {"KFT_MPMD_SEQ": "8", "KFT_MPMD_VOCAB": "32",
              "KFT_MPMD_HEADS": "2", "KFT_MPMD_KV_HEADS": "1",
              "KFT_MPMD_MLP": "32"}


def _llama_cfg(schedule, n_stages, virtual_stages=1, layers=1, steps=2):
    return PipelineRunConfig(
        schedule=schedule, n_stages=n_stages,
        virtual_stages=virtual_stages, microbatches=4, global_batch=8,
        dim=16, layers_per_stage=layers, steps=steps)


def _llama_run(cfg):
    from kubeflow_tpu.parallel.pipeline_llama import mpmd_llama_spec

    spec = mpmd_llama_spec(cfg, {**_LLAMA_ENV})
    rts = [StageRuntime(cfg, s, spec=spec) for s in range(cfg.n_stages)]
    return run_inproc(cfg, runtimes=rts)


def test_llama_spec_chunks_and_batch_determinism():
    from kubeflow_tpu.parallel.pipeline_llama import mpmd_llama_spec

    cfg = _llama_cfg("interleaved-1f1b", 2, 2)
    spec = mpmd_llama_spec(cfg, {**_LLAMA_ENV})
    p0 = spec.chunk_params(cfg, 0)
    assert "embed" in p0 and p0["layers"]["wq"].shape[0] == 1
    p1 = spec.chunk_params(cfg, 1)
    assert "embed" not in p1
    hp = spec.head_params(cfg)
    assert set(hp) == {"final_norm", "lm_head"}
    # chunk 0 consumes int tokens; later chunks the hidden stream
    assert spec.example_x(cfg, 0).dtype == jnp.int32
    assert spec.example_x(cfg, 1).dtype == jnp.float32
    x1, t1 = spec.batch(cfg, 3)
    x2, t2 = spec.batch(cfg, 3)
    assert (x1 == x2).all() and (t1 == t2).all()
    x3, _ = spec.batch(cfg, 4)
    assert (x1 != x3).any()


def test_llama_interleaved_matches_spmd_oracle():
    """The acceptance trajectory gate at test scale: a REAL transformer
    through the interleaved MPMD runner vs the single-program SPMD
    oracle over the same 4-chunk partition — step-0 bitwise, whole
    trajectory within the PR 11 parity tolerance."""
    from kubeflow_tpu.parallel.pipeline_llama import (
        mpmd_llama_spec, run_mpmd_llama_oracle,
    )

    cfg = _llama_cfg("interleaved-1f1b", 2, 2)
    _, li = _llama_run(cfg)
    oracle = run_mpmd_llama_oracle(cfg, mpmd_llama_spec(cfg, {**_LLAMA_ENV}))
    assert li[0] == oracle[0], "step-0 must be bitwise"
    assert max(abs(a - b) / abs(b) for a, b in zip(li, oracle)) <= 2e-5


@pytest.mark.slow
def test_llama_schedule_and_partition_parity():
    """Matched partition (4 x 1-layer chunks): interleaved == gpipe ==
    1f1b fully bitwise. A DIFFERENT partition of the same model (2 x
    2-layer chunks) compiles different programs, so that comparison
    carries XLA fusion round-off and gates at the parity tolerance."""
    cfg_i = _llama_cfg("interleaved-1f1b", 2, 2, steps=3)
    _, li = _llama_run(cfg_i)
    _, lg = _llama_run(_llama_cfg("gpipe", 4, steps=3))
    _, lf = _llama_run(_llama_cfg("1f1b", 4, steps=3))
    assert li == lg == lf
    _, lp = _llama_run(_llama_cfg("1f1b", 2, layers=2, steps=3))
    assert lp[0] == li[0]
    assert max(abs(a - b) / abs(b) for a, b in zip(li, lp)) <= 2e-5


# ------------------------------------------------- wrap-link sender poison --

def test_dead_wrap_next_peer_poisons_recv_promptly():
    """A sender thread hitting a dead RING-WRAP peer (last stage's
    r+vS -> chunk (v+1)S activation hop back to worker 0) must poison
    the compute thread's next recv exactly like a straight-link death —
    the wrap links ride the same async sender machinery, so a regression
    here would leave an interleaved run wedged in a 120s recv timeout."""
    import time as _t

    import numpy as _np

    from kubeflow_tpu.parallel.mpmd import TCPStageChannel

    tx = TCPStageChannel("127.0.0.1:0", prev=None, next=None, stage=1,
                         blocking=False, timeout_s=30.0,
                         wrap_next="127.0.0.1:1")     # port 1: refused
    tx.timeout_s = 0.3
    try:
        tx.send_act(0, 0, _np.zeros((2,), _np.float32), vstage=1,
                    wrap=True)
        _t.sleep(1.0)          # let the sender exhaust its connect window
        t0 = _t.perf_counter()
        with pytest.raises(RuntimeError, match="stage transport failed"):
            tx.recv_grad(0, 0, vstage=1)
        assert _t.perf_counter() - t0 < 1.0        # poison, not timeout
    finally:
        tx.close()


def test_dead_wrap_prev_peer_poisons_recv_promptly():
    """Same contract for the reverse wrap hop: worker 0 returning
    grad-activations to the last stage over wrap_prev."""
    import time as _t

    import numpy as _np

    from kubeflow_tpu.parallel.mpmd import TCPStageChannel

    tx = TCPStageChannel("127.0.0.1:0", prev=None, next=None, stage=0,
                         blocking=False, timeout_s=30.0,
                         wrap_prev="127.0.0.1:1")     # port 1: refused
    tx.timeout_s = 0.3
    try:
        tx.send_grad(0, 0, _np.zeros((2,), _np.float32), vstage=0,
                     wrap=True)
        _t.sleep(1.0)
        t0 = _t.perf_counter()
        with pytest.raises(RuntimeError, match="stage transport failed"):
            tx.recv_act(0, 0, vstage=0)
        assert _t.perf_counter() - t0 < 1.0        # poison, not timeout
    finally:
        tx.close()
