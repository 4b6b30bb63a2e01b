"""HPO trial worker: the swarm's shared-compile trial program.

Run as ``python -m kubeflow_tpu.hpo.trial_worker`` inside a trial pod
(the ``[sys.executable, -m, module]`` form a warm-pool zygote can fork).
The design rule the whole shared-compile leg rests on:

- SCALAR hyperparameters (learning rate ``KFT_TRIAL_LR``, weight decay
  ``KFT_TRIAL_WD``) are passed as TRACED arguments of the jitted train
  step — runtime values, not baked constants — so every trial of a
  structural config lowers to byte-identical HLO and shares ONE
  executable-depot entry (``fingerprint(stage="hpo-trial")``).
- STRUCTURAL hyperparameters (``KFT_TRIAL_WIDTH``/``KFT_TRIAL_DEPTH``)
  change the program's shapes: they legitimately fork the depot key
  (carried in the fingerprint ``extra``) and are counted as distinct
  entries, never a collision.

The trial objective is a deterministic convex toy — gradient descent on
``f(w) = ½‖w‖²`` with the update ``w ← (1 − lr − wd)·w`` — so the loss
curve is an exact function of (lr, wd, step): trials with small lr
plateau high and MedianStop/ASHA stop them mid-run (the reclaim arc),
while the compiled step is a real XLA executable exercising the depot.
Phases (proc_start/imports_done/state_init_done/compile_done/
first_step_done + the ``depot_outcome`` stamp) and the ``trial.load`` /
``trial.step`` spans ride the same heartbeat transport worker_check
uses, so bench decomposes submit→first-step per trial without logs.
"""

from __future__ import annotations

import os
import sys
import time

from kubeflow_tpu.rendezvous.worker_check import _phase


def lowered_step(width: int, depth: int):
    """Lower the trial train step for one structural config. ``lr`` and
    ``wd`` are abstract scalar ARGUMENTS — two trials differing only in
    scalars produce this exact same lowering."""
    import jax
    import jax.numpy as jnp

    def step(w, lr, wd):
        loss = 0.5 * jnp.sum(w * w)
        # d(loss)/dw = w; SGD with decoupled weight decay
        w_next = w - lr * w - wd * w
        return w_next, loss

    f32 = jnp.float32
    return jax.jit(step).lower(
        jax.ShapeDtypeStruct((depth, width), f32),
        jax.ShapeDtypeStruct((), f32),
        jax.ShapeDtypeStruct((), f32))


def main() -> int:
    phases: dict = {}
    _phase(phases, "proc_start")
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel.depot import (
        DepotStats, depot_from_env, load_or_compile,
    )
    from kubeflow_tpu.training.metrics import MetricsWriter

    _phase(phases, "imports_done")

    lr = float(os.environ.get("KFT_TRIAL_LR", "0.1"))
    wd = float(os.environ.get("KFT_TRIAL_WD", "0.0"))
    width = int(os.environ.get("KFT_TRIAL_WIDTH", "8"))
    depth = int(os.environ.get("KFT_TRIAL_DEPTH", "2"))
    steps = int(os.environ.get("KFT_TRAIN_STEPS", "8"))
    step_sleep = float(os.environ.get("KFT_STEP_SLEEP", "0"))

    dstats = DepotStats()
    try:
        depot = depot_from_env(stats=dstats)
    except Exception:
        dstats.inc("fetch_errors")      # fail-open, counted (depot rule)
        depot = None
    w = jnp.ones((depth, width), jnp.float32)
    _phase(phases, "state_init_done")

    # follower trials (KFT_DEPOT_WAIT_S, set by SwarmTrialRunner for all
    # but the first trial of each structural config) wait for the
    # designated publisher's entry instead of racing an identical compile
    wait_s = (float(os.environ.get("KFT_DEPOT_WAIT_S", "0"))
              if depot is not None else 0.0)
    compiled, outcome = load_or_compile(
        lowered_step(width, depth), depot,
        extra=(f"width={width}", f"depth={depth}"),
        stage="hpo-trial", stats=dstats, wait_s=wait_s)
    phases["depot_hit"] = 1.0 if outcome == "hit" else 0.0
    phases["depot_outcome"] = outcome
    _phase(phases, "compile_done",
           extra={"depot": dstats.snapshot()} if depot is not None
           else None)

    metrics_path = os.environ.get("KFT_METRICS_PATH")
    metrics = MetricsWriter(metrics_path) if metrics_path else None
    lr_arr = jnp.asarray(lr, jnp.float32)
    wd_arr = jnp.asarray(wd, jnp.float32)
    loss = float("nan")
    for i in range(steps):
        t_step = time.time()
        w, loss_dev = compiled(w, lr_arr, wd_arr)
        loss = float(loss_dev)
        if i == 0:
            t_now = time.time()
            # trial.load covers fork→ready-to-step (imports + state init
            # + depot fetch/compile); trial.step is the first real step —
            # both posted through the phases transport as explicit spans
            _phase(phases, "first_step_done", extra={"spans": [
                {"name": "trial.load", "t0": phases["proc_start"],
                 "t1": t_step,
                 "attrs": {"depot_outcome": outcome, "width": width,
                           "depth": depth}},
                {"name": "trial.step", "t0": t_step, "t1": t_now,
                 "attrs": {"step": 0}},
            ]})
        if metrics is not None:
            # the OBJECTIVE is width/depth-normalized (starts at exactly
            # 1.0 for every structural config, decays (1-lr-wd)^(2k)) so
            # MedianStop ranks trials by their scalars, not by which
            # structural config happens to have more parameters
            metrics.write(i, loss=loss / (0.5 * depth * width),
                          raw_loss=loss)
        if step_sleep:
            time.sleep(step_sleep)

    print(f"trial done: lr={lr} wd={wd} width={width} depth={depth} "
          f"steps={steps} loss={loss} depot={outcome}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
