"""Smoke workload for JAXJob e2e: rendezvous + a cross-process collective.

Run as ``python -m kubeflow_tpu.rendezvous.worker_check`` inside a pod. Reads
the operator env contract, initializes the distributed world, verifies the
global device count, runs a psum across the whole world, and writes metrics.
Exit 0 = healthy world. This is the 'MNIST-class CPU stand-in image' role
from the reference's e2e strategy (SURVEY.md §4.3).
"""

from __future__ import annotations

import os
import sys


def _phase(phases: dict, name: str, extra: dict | None = None,
           at: float | None = None) -> None:
    """Record a named absolute timestamp (``at`` overrides "now" for
    events measured elsewhere, e.g. the profiler window's stop time);
    flushed to KFT_PHASES_PATH so the operator/bench can decompose
    submit->first-step into pod spawn / imports / rendezvous / compile /
    step 1 (BASELINE.md row 2).

    Two transports behind the one env value, mirroring KFT_HEARTBEAT_FILE:
    a filesystem path (shared-fs backends) writes an atomic JSON file; an
    http(s) URL (kube backend — the operator injects its heartbeat route)
    POSTs {"phases": {...}} to the operator, which folds it into
    ``Operator.phase_reports``. Whole-dict posts each time: delivery is
    at-least-once and the receiver merges, so a lost or reordered POST
    costs one stamp's latency, never the decomposition.

    ``extra`` rides the same POST body (e.g. {"depot": counters} — the
    operator folds it into kft_depot_* metrics); on the file transport
    each extra key lands in its own ``{path}.{key}.{process}`` file."""
    import time

    phases[name] = time.time() if at is None else float(at)
    path = os.environ.get("KFT_PHASES_PATH")
    if not path:
        return
    import json

    proc = os.environ.get("KFT_PROCESS_ID", "0")
    if path.startswith(("http://", "https://")):
        import urllib.request

        try:
            req = urllib.request.Request(
                path, method="POST",
                data=json.dumps(
                    {"phases": phases, **(extra or {})}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=5).close()
        except Exception:
            pass        # like heartbeats: missed posts ARE the signal
        return
    try:
        with open(f"{path}.{os.getpid()}", "w") as f:
            json.dump(phases, f)
        os.replace(f"{path}.{os.getpid()}", f"{path}.{proc}")
        for key, val in (extra or {}).items():
            with open(f"{path}.{key}.{os.getpid()}", "w") as f:
                json.dump(val, f)
            os.replace(f"{path}.{key}.{os.getpid()}",
                       f"{path}.{key}.{proc}")
    except OSError:
        pass


def main() -> int:
    phases: dict = {}
    _phase(phases, "proc_start")
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.rendezvous.bootstrap import initialize
    from kubeflow_tpu.training.metrics import MetricsWriter

    _phase(phases, "imports_done")
    world, mesh = initialize()
    _phase(phases, "rendezvous_done")
    n_local = jax.local_device_count()
    n_global = jax.device_count()
    expected = world.num_processes * n_local
    assert n_global == expected, f"device_count {n_global} != {expected}"

    # cross-process collective: global mean over a data-sharded array
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(("data", "fsdp")))
    import numpy as np

    local = np.full((n_local, 4), float(world.process_id), np.float32)
    arr = jax.make_array_from_process_local_data(sharding, local)
    total = float(jax.jit(jnp.sum)(arr))
    expect_total = 4 * n_local * sum(range(world.num_processes))
    assert abs(total - expect_total) < 1e-5, f"psum {total} != {expect_total}"

    metrics_path = os.environ.get("KFT_METRICS_PATH")
    if metrics_path:
        MetricsWriter(metrics_path).write(
            0, world_ok=1.0, process_id=world.process_id, total=total
        )

    # optional real-training mode: KFT_TRAIN_STEPS makes this the
    # 'tiny CPU training image' of the operator e2e — an actual fit() on the
    # world mesh, so heartbeats/first-step latency come from real steps
    steps = int(os.environ.get("KFT_TRAIN_STEPS", "0"))
    if steps:
        import jax.numpy as jnp

        from kubeflow_tpu.models import llama
        from kubeflow_tpu.training import (
            Trainer, TrainerConfig, lm_loss_fn, put_batch,
            synthetic_lm_batches,
        )
        from kubeflow_tpu.training.loop import fit

        cfg = llama.llama_tiny(dtype=jnp.float32)
        trainer = Trainer(
            mesh=mesh,
            init_params_fn=lambda r: llama.init_params(r, cfg),
            params_logical_axes=llama.param_logical_axes(cfg),
            loss_fn=lm_loss_fn(llama.forward, cfg),
            config=TrainerConfig(learning_rate=1e-3, warmup_steps=2,
                                 total_steps=max(steps, 3)),
        )
        global_batch = max(2 * world.num_processes, 4)

        def batches(start):
            return (put_batch(mesh, b) for b in synthetic_lm_batches(
                cfg.vocab_size, global_batch, 16, start_step=start))

        # compile split from step 1 (the executable-depot fast path):
        # fetch the gang's train-step executable from the depot — or
        # compile and publish it — BEFORE fit, and stamp compile_done so
        # the submit→first-step decomposition separates compile from the
        # first real step. Followers (process_id > 0) wait for the
        # coordinator's publish instead of racing it with an identical
        # compile; every depot fallback is a counted local compile.
        from kubeflow_tpu.parallel.depot import DepotStats
        from kubeflow_tpu.rendezvous.bootstrap import depot_from_env

        dstats = DepotStats()
        try:
            depot = depot_from_env(stats=dstats)
        except Exception:
            # fail-open like every depot path: an unwritable KFT_DEPOT /
            # KFT_DEPOT_CACHE dir (read-only mount, deleted path) must
            # cost the fast path, never the job
            dstats.inc("fetch_errors")
            depot = None
        wait_s = (float(os.environ.get("KFT_DEPOT_WAIT_S", "120"))
                  if depot is not None and not world.is_coordinator
                  else 0.0)
        trainer.init_state(jax.random.key(0))
        # state_init_done..compile_done isolates the train-step
        # lower+compile (the depot-amortizable part) from the param/opt
        # init compiles and jit setup that precede it — without this
        # stamp a depot hit still looks compile-bound from outside
        _phase(phases, "state_init_done")

        # restart-aware resume handshake (elastic recovery): restore the
        # latest checkpoint BEFORE loading the compiled executable. A
        # replacement worker thus knows the exact step it takes over at
        # up front — and the ordering matters mechanically: a zygote-
        # forked child that deserializes the depot executable and THEN
        # runs the tensorstore restore corrupts its forked heap (observed
        # as SIGABRT/SIGSEGV after the first post-resume step); restore-
        # then-deserialize is stable. fit() skips its own restore via
        # already_resumed.
        from kubeflow_tpu.training.checkpoint import CheckpointManager
        from kubeflow_tpu.training.loop import restore_latest

        ckpt_dir = os.environ.get("KFT_CHECKPOINT_DIR")
        resumed = None
        if ckpt_dir:
            mgr = CheckpointManager(
                ckpt_dir,
                mirror=os.environ.get("KFT_CHECKPOINT_MIRROR") or None)
            resumed = restore_latest(trainer, mgr)
            mgr.close()
            if resumed is not None:
                phases["resumed_from_step"] = float(resumed)
                _phase(phases, "restore_done")

        depot_outcome = trainer.precompile(
            next(batches(0)), depot=depot, stats=dstats, wait_s=wait_s)
        # non-timestamp stamp riding the same merge transport: the bench's
        # recovery decomposition needs the replacement's depot outcome
        # without scraping logs (1.0 = executable deserialized, no compile)
        phases["depot_hit"] = 1.0 if depot_outcome == "hit" else 0.0
        _phase(phases, "compile_done",
               extra={"depot": dstats.snapshot()} if depot is not None
               else None)

        metrics = MetricsWriter(metrics_path) if metrics_path else None
        # recovery-bench pacing: a tiny CPU model finishes all its steps
        # inside one chaos tick — an optional per-step sleep widens the
        # kill window without changing the math
        step_sleep = float(os.environ.get("KFT_STEP_SLEEP", "0"))

        def _first_step(step, m):
            if "first_step_done" not in phases:
                _phase(phases, "first_step_done")
            if step_sleep:
                import time as _time

                _time.sleep(step_sleep)

        result = fit(trainer, batches, rng=jax.random.key(0),
                     max_steps=steps, metrics=metrics, metrics_every=1,
                     checkpoint_dir=ckpt_dir,
                     checkpoint_every=int(
                         os.environ.get("KFT_CHECKPOINT_EVERY", "100")),
                     on_step=_first_step, already_resumed=resumed)
        # profiler artifact stamp: fit() honored KFT_PROFILE_DIR /
        # KFT_PROFILE_STEPS from the pod env (training/loop contract).
        # Stamped ONLY when the window actually ran (result.profile), at
        # the REAL start/stop wall times — the job-trace worker.profile
        # span must cover the profiled window, not end-of-training, and
        # a run that never reached the window must not report a phantom
        # artifact. The trace-dir path rides as a string stamp, so the
        # operator's job trace carries WHERE the profile landed as a
        # span attr — no log scraping.
        if result.profile is not None:
            phases["profile_dir"] = result.profile["dir"]
            phases["profile_start"] = result.profile["t_start"]
            _phase(phases, "profile_done", at=result.profile["t_stop"])
        incarnation = os.environ.get("KFT_WORKER_INCARNATION", "0")
        print(f"worker {world.process_id}: trained to step "
              f"{result.final_step} (resumed_from={result.resumed_from}, "
              f"depot={depot_outcome}, incarnation={incarnation})")

    print(f"worker {world.process_id}/{world.num_processes}: world ok, "
          f"devices={n_global}, collective={total}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
