"""The training loop, for a job file of kind ``train``.

One process drives every chip of the cell through the program's own path:
``parallel.build_mesh`` -> ``Trainer`` -> ``init_state`` -> ``precompile`` ->
``train_step``, as ``chip_smoke.py``'s train leg does. A commit is a finished
step (its loss fetched); the next step is dispatched before the host waits
for the last, so the device never waits for the host's batch. Throughput is
counted in whole steps between commit instants (rule 2 of README.md).
"""

from __future__ import annotations

import math
import time

import numpy as np

from lib import check, model, peaks, roofline, window


def run(ctx) -> dict:
    import jax

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshConfig, build_mesh
    from kubeflow_tpu.training import (
        Trainer, TrainerConfig, lm_loss_fn, put_batch, synthetic_lm_batches,
    )
    from reference import dense_gqa

    cfg, job, log = ctx.config, ctx.traffic, ctx.log
    chips = ctx.chips
    seq = 256 if ctx.rehearse else job["seq"]
    block = 128 if ctx.rehearse else job["attn_block"]
    mesh = build_mesh(MeshConfig(**job["mesh"]),
                      devices=jax.devices()[:chips])
    lcfg = model.llama_config(
        cfg, remat=job["remat"], attn_impl=job["attn_impl"],
        attn_block=block, z_loss=job["z_loss"])
    loss_fn = lm_loss_fn(llama.forward, lcfg)
    trainer = Trainer(
        mesh=mesh,
        init_params_fn=lambda rng: llama.init_params(rng, lcfg),
        params_logical_axes=llama.param_logical_axes(lcfg),
        loss_fn=loss_fn,
        config=TrainerConfig(
            learning_rate=job["learning_rate"],
            warmup_steps=job["warmup_steps"], total_steps=job["total_steps"],
            optimizer=job["optimizer"], grad_accum=job["grad_accum"]))
    trainer.init_state(jax.random.key(ctx.seed % (1 << 31)))
    jax.block_until_ready(trainer.params)
    ctx.mark("weights")

    micro = job["sequences_per_chip_per_micro_batch"] * chips
    global_batch = micro * job["grad_accum"]
    tokens_per_step = global_batch * seq
    batches = synthetic_lm_batches(cfg["vocab_size"], global_batch, seq,
                                   seed=ctx.seed % (1 << 31))
    first = next(batches)

    # the program's loss at the initial parameters against the reference's
    # (each sequence once per chip, so that the batch shards like a step's)
    two = first["tokens"][:job["check_sequences"]]
    with mesh:
        program_loss = float(jax.jit(lambda p, b: loss_fn(p, b)[0])(
            trainer.params,
            put_batch(mesh, {"tokens": np.repeat(two, chips, axis=0)})))
    checked = check.loss_agreement(
        program_loss, dense_gqa.lm_loss(trainer.params, two, cfg,
                                        z_loss=job["z_loss"]))
    log(f"check: {checked}")
    ctx.mark("check")

    dev_batch = put_batch(mesh, first)
    trainer.precompile(dev_batch)
    kernels = trainer._compiled_step.as_text().count("tpu_custom_call")
    ctx.mark("compile")

    losses = []

    def commit(metrics):
        losses.append(float(metrics["loss"]))          # waits for the step
        return time.time()

    for _ in range(job["warm_up_steps"]):
        commit(trainer.train_step(dev_batch))
        dev_batch = put_batch(mesh, next(batches))
    ctx.mark("warm_up")

    seconds = ctx.seconds
    commits = []                                       # (t, steps finished)
    t_open = ctx.open_window()
    pending = trainer.train_step(dev_batch)
    tracing = traced = False
    while True:
        dev_batch = put_batch(mesh, next(batches))
        nxt = trainer.train_step(dev_batch)            # dispatched, not awaited
        t = commit(pending)
        commits.append((t, len(commits) + 1))
        pending = nxt
        if ctx.trace and not traced:
            if not tracing and t >= t_open + (seconds - ctx.trace_seconds) / 2:
                ctx.start_trace()
                tracing = True
            elif tracing and t >= ctx.t_trace[0] + ctx.trace_seconds:
                ctx.stop_trace()
                traced = True
        if t >= commits[0][0] + seconds:
            break
    commit(pending)
    ctx.close_window()

    # a traced run is judged on the steps before the profiler started: its
    # start and stop stall the host for seconds
    w = window.commit_window(
        commits, t_open,
        ctx.t_trace[0] - t_open if ctx.t_trace else seconds)
    tok_s_chip = w[2] * tokens_per_step / (w[1] - w[0]) / chips
    values = {"train_tok_s_chip": tok_s_chip,
              "step_ms": 1000.0 * (w[1] - w[0]) / w[2]}
    if not ctx.rehearse:
        values["mfu_pct"] = (
            100.0 * roofline.train_flops_per_token(cfg, seq) * tok_s_chip
            / peaks.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"])
    in_window = losses[job["warm_up_steps"]:]
    bad = sum(not math.isfinite(x) for x in in_window)
    log(f"window: steps {w[2]} between commits {w[1] - w[0]:.3f} s apart, "
        f"tokens/step {tokens_per_step}, losses {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}, tpu_custom_calls in the step {kernels}")
    return {
        "values": values,
        "attempted": len(in_window),
        "failed": bad,
        "correct": bool(checked["ok"]) and bad == 0
        and (ctx.rehearse or job["attn_impl"] != "pallas" or kernels > 0),
        "spans": [],
    }
