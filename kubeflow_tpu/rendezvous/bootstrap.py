"""In-worker bootstrap: operator-injected env -> initialized JAX world + mesh.

The worker-side half of the rendezvous contract (SURVEY.md §2.8): the
controller stamps KFT_COORDINATOR / KFT_NUM_PROCESSES / KFT_PROCESS_ID (+
KFT_MESH / KFT_DCN topology), and this module turns them into
`jax.distributed.initialize()` + a canonical device mesh. The TPU-native
replacement for torchrun/TF_CONFIG/MPI-hostfile bootstrap.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

# executable-depot env contract (KFT_DEPOT / KFT_DEPOT_TOKEN /
# KFT_DEPOT_CACHE): re-exported here because this module IS the
# worker-side env contract — workers resolve their depot next to the
# compile cache below. The depot goes further than the cache: it ships
# the COMPILED executable across nodes (compile-once at gang width N),
# where jax_compilation_cache_dir only helps processes sharing a disk.
from kubeflow_tpu.parallel.depot import depot_from_env  # noqa: F401


@dataclasses.dataclass
class WorldInfo:
    coordinator: str
    num_processes: int
    process_id: int
    job_name: str = ""

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def load_downward_env(path: str = "/etc/podinfo/annotations",
                      env: Optional[dict] = None) -> dict:
    """Fold late-bound pod annotations into the env contract.

    On a real cluster (controller/kube.py KubeCluster), values decided at
    gang admission — after the pod spec is immutable — travel as
    ``kubeflow-tpu.org/env.<KEY>`` annotations surfaced through a
    downward-API volume. The file format is one ``key="escaped value"``
    per line. Direct env always wins; annotations only fill gaps."""
    env = env if env is not None else os.environ
    if not os.path.exists(path):
        return dict(env)
    out = dict(env)
    prefix = "kubeflow-tpu.org/env."
    with open(path) as f:
        for line in f:
            key, eq, raw = line.strip().partition("=")
            if not eq or not key.startswith(prefix):
                continue
            val = raw.strip()
            if val.startswith('"') and val.endswith('"'):
                # downward-API files escape values Go-string style
                val = val[1:-1].replace('\\"', '"').replace("\\\\", "\\")
            out.setdefault(key[len(prefix):], val)
    return out


@dataclasses.dataclass
class StageInfo:
    """MPMD pipeline-stage rendezvous (parallel/mpmd.py): which stage
    this worker belongs to and where its neighbors' transports live.
    Stamped by the reconciler next to the jax.distributed world env when
    a JAXJob's worker template carries KFT_NUM_STAGES — stage workers do
    NOT join one jax.distributed world (that is the SPMD contract); each
    stage is its own program, and these addresses are the activation /
    grad-activation point-to-point links between them."""

    stage_id: int
    n_stages: int
    bind: str                      # this stage's listen address
    prev: Optional[str] = None     # stage_id-1's address (grads go here)
    next: Optional[str] = None     # stage_id+1's address (acts go here)
    stage_workers: int = 1         # workers per stage (multi-host stages)
    stage_proc_id: int = 0         # rank within the stage's worker group
    # interleaved-1F1B (virtual stages): each worker owns V model chunks
    # (chunk stage_id, stage_id+S, ...). The chunk graph wraps around the
    # worker ring, so the last worker also sends activations to worker 0
    # (wrap_next) and worker 0 sends grads to the last worker (wrap_prev).
    virtual_stages: int = 1
    wrap_next: Optional[str] = None  # stage S-1 -> stage 0 activation link
    wrap_prev: Optional[str] = None  # stage 0 -> stage S-1 grad link
    # per-stage worker group identity (multi-worker stages): the group is
    # the future per-stage jax.distributed world; size/rank/coord are its
    # rendezvous triplet, stamped even before that world exists so the
    # contract round-trips today.
    group_size: int = 1
    group_rank: int = 0
    group_coord: Optional[str] = None
    # elastic pipeline (ISSUE 20): the rendezvous epoch this worker was
    # launched into (the reconciler bumps job.status.rendezvous_epoch on
    # every replacement/gang restart and stamps it on NEW pods; a
    # replacement stage worker announces it through the snapshot dir so
    # surviving stages reform in process), and the per-pod incarnation
    # counter distinguishing a replacement from the pod it replaced.
    epoch: int = 0
    incarnation: int = 0

    @property
    def is_first(self) -> bool:
        return self.stage_id == 0

    @property
    def is_last(self) -> bool:
        return self.stage_id == self.n_stages - 1


def stage_from_env(env: Optional[dict] = None) -> Optional[StageInfo]:
    """Parse the stage rendezvous env (downward-API annotations folded in
    like world_from_env). None when the job is not an MPMD pipeline."""
    env = env if env is not None else os.environ
    env = load_downward_env(env=env)
    if "KFT_NUM_STAGES" not in env:
        return None
    n = int(env["KFT_NUM_STAGES"])
    sid = int(env.get("KFT_STAGE_ID", "0"))
    workers = int(env.get("KFT_STAGE_WORKERS", "1"))
    return StageInfo(
        stage_id=sid,
        n_stages=n,
        bind=env.get("KFT_STAGE_BIND", "127.0.0.1:0"),
        prev=env.get("KFT_STAGE_PREV") or None,
        next=env.get("KFT_STAGE_NEXT") or None,
        stage_workers=workers,
        stage_proc_id=int(env.get("KFT_STAGE_PROC_ID", "0")),
        virtual_stages=int(env.get("KFT_VIRTUAL_STAGES", "1")),
        wrap_next=env.get("KFT_STAGE_WRAP_NEXT") or None,
        wrap_prev=env.get("KFT_STAGE_WRAP_PREV") or None,
        group_size=int(env.get("KFT_STAGE_GROUP_SIZE", str(workers))),
        group_rank=int(env.get("KFT_STAGE_GROUP_RANK",
                               env.get("KFT_STAGE_PROC_ID", "0"))),
        group_coord=env.get("KFT_STAGE_GROUP_COORD") or None,
        epoch=int(env.get("KFT_RENDEZVOUS_EPOCH", "0") or 0),
        incarnation=int(env.get("KFT_WORKER_INCARNATION", "0") or 0),
    )


def world_from_env(env: Optional[dict] = None) -> WorldInfo:
    env = env if env is not None else os.environ
    env = load_downward_env(env=env)
    return WorldInfo(
        coordinator=env.get("KFT_COORDINATOR", "127.0.0.1:8476"),
        num_processes=int(env.get("KFT_NUM_PROCESSES", "1")),
        process_id=int(env.get("KFT_PROCESS_ID", "0")),
        job_name=env.get("KFT_JOB_NAME", ""),
    )


def initialize(env: Optional[dict] = None, timeout_s: float = 300.0):
    """jax.distributed.initialize() from operator env; returns (world, mesh).

    Single-process jobs skip distributed init entirely (one less failure
    mode, and the common local/dev case).
    """
    import jax

    from kubeflow_tpu.utils import compile_cache

    # a restarted or resubmitted job's first-step compile becomes a cache
    # read — the dominant submit→first-step phase on anything but a
    # brand-new program (BASELINE.md row 2)
    compile_cache.ensure()

    world = world_from_env(env)
    if world.num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=world.coordinator,
            num_processes=world.num_processes,
            process_id=world.process_id,
            initialization_timeout=int(timeout_s),
        )
    from kubeflow_tpu.parallel.mesh import mesh_from_topology_env

    mesh = mesh_from_topology_env(load_downward_env(env=env))
    return world, mesh


def wait_for_workers(world: WorldInfo, deadline_s: float = 300.0) -> None:
    """Barrier on world size: jax.device_count() must reach the global count."""
    import jax

    t0 = time.time()
    expected = world.num_processes * jax.local_device_count()
    while jax.device_count() < expected:
        if time.time() - t0 > deadline_s:
            raise TimeoutError(
                f"only {jax.device_count()}/{expected} devices after {deadline_s}s"
            )
        time.sleep(1.0)
