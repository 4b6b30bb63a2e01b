#!/usr/bin/env python3
"""Chip smoke: the train step and the serving engine, once, on the TPU.

    python3 chip_smoke.py            # on a machine with a TPU; fails without

Drives the platform's two hot paths through the entry points a user calls,
at llama_1b's full width (d 2048, 16 layers, 16/8 heads x 128, seq 2048;
random weights from a seed):

1. train   — ``rendezvous.bootstrap.initialize()`` -> mesh -> ``Trainer`` ->
             ``training.loop.fit`` for a few steps on one repeated batch,
             Pallas flash attention; saves the weights in HF layout.
2. serve   — the predictor container command
             ``python -m kubeflow_tpu.serving.runtime`` on those weights,
             a few concurrent ``:predict`` requests over HTTP.
3. check   — the served tokens teacher-forced against ``llama.forward``,
             and each paged-decode kernel variant called directly against
             the gather oracle.

One process holds the chip at a time: this parent never imports jax, it
runs the legs as children in turn and waits for each to exit. The mesh
follows the devices JAX reports (one chip: all axes 1; N chips: train
fsdp=N, serve tensor=N). The last line of stdout is
``{"ok": true, "device": {...}}``; any failed leg or check, or no TPU,
is a non-zero exit and no such line.

``--cpu-debug`` runs the same legs at toy size on the CPU (Pallas in
interpret mode) to debug the script itself; the chip run never passes it.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 0
DEADLINE_S = 1150.0          # the contract allows 1200 s, compile included
EXIT_NO_CHIP = 3

# "full" trains with bench.py's recipe (remat="dots", adafactor, 16
# micro-batches of 2 x 2048 per chip). The compile-only v5e client plans
# 19.3 GiB for that step; the chip's own compiler fits it in 15.75 GiB.
SIZES = {
    "full": dict(
        model="llama_1b", batch_per_chip=32, seq=2048, steps=5,
        remat="dots", grad_accum=16,
        max_batch=32, max_seq=320, prompt_len=100, new_tokens=32,
        concurrent=4, shard_bytes=128 << 20),
    "toy": dict(
        model="llama_tiny", batch_per_chip=4, seq=128, steps=4,
        remat="dots", grad_accum=2,
        max_batch=4, max_seq=64, prompt_len=12, new_tokens=8,
        concurrent=4, shard_bytes=64 << 10),
}
# the served logits are bf16 (8 significant bits). Two correct programs
# (bucketed prefill + paged decode vs one full forward) round differently
# along 16 layers: on the v5e 157 of 160 tokens were the reference's exact
# argmax and the worst sat 3.2 bf16 steps under it. So every token must be
# within TIE_ULPS steps of the reference maximum, and MIN_EXACT of them its
# exact argmax; a wrong program misses by the logit spread (a hundred
# steps) on most tokens.
TIE_ULPS = 8
MIN_EXACT = 0.9
# the weights go from the train child to the server as files. One
# model.safetensors of llama_1b is 1.64 GB, and the driver's machine refused
# to grow a file that far ("File too large"): so they are saved as HF
# shards of ``shard_bytes``, whole tensors, none larger than the largest
# tensor (the 128 MiB embedding), in memory-backed scratch where the
# machine has room for it.
SHM = "/dev/shm"
SHM_FREE_BYTES = 8 << 30


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A leg or a check failed; the message says which."""


# ------------------------------------------------------------ child legs --

def _device(jax, size: str):
    dev = jax.devices()[0]
    if size == "full" and dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def _shards(x) -> list:
    """Per-device evidence that ``x`` is spread, not parked on device 0."""
    return [{"device": s.device.id, "shape": list(s.data.shape)}
            for s in x.addressable_shards]


def _on_every_device(shards: list, n: int) -> bool:
    return len({s["device"] for s in shards}) == n


def _file_sizes(d: str) -> dict:
    return {f: os.path.getsize(os.path.join(d, f)) for f in sorted(
        os.listdir(d))}


def leg_train(a) -> dict:
    import itertools
    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import SafetensorError

    from kubeflow_tpu.models import hf_llama, llama
    from kubeflow_tpu.rendezvous import bootstrap
    from kubeflow_tpu.training import (
        Trainer, TrainerConfig, lm_loss_fn, put_batch,
    )
    from kubeflow_tpu.training.loop import fit
    from kubeflow_tpu.utils import compile_cache

    size = SIZES[a.size]
    device = _device(jax, a.size)
    _, mesh = bootstrap.initialize()
    cache_dir, placed = compile_cache.ensure()
    make_cfg = getattr(llama, size["model"])
    batch, seq = size["batch_per_chip"] * device["count"], size["seq"]
    vocab = make_cfg().vocab_size
    tokens = np.random.default_rng(SEED).integers(
        1, vocab, (batch, seq + 1), dtype=np.int32)

    recipe = {"remat": size["remat"], "grad_accum": size["grad_accum"],
              "global_batch": batch, "seq": seq, "optimizer": "adafactor",
              "attn_impl": "pallas", "mesh": dict(mesh.shape)}
    cfg = make_cfg(remat=size["remat"], attn_impl="pallas")
    trainer = Trainer(
        mesh=mesh,
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        params_logical_axes=llama.param_logical_axes(cfg),
        loss_fn=lm_loss_fn(llama.forward, cfg),
        config=TrainerConfig(learning_rate=1e-3, warmup_steps=2,
                             total_steps=1000, optimizer="adafactor",
                             grad_accum=size["grad_accum"]))
    trainer.init_state(jax.random.key(SEED))
    dev_batch = put_batch(mesh, {"tokens": tokens})
    t0 = time.perf_counter()
    trainer.precompile(dev_batch)
    compile_s = time.perf_counter() - t0
    hlo = trainer._compiled_step.as_text()
    mem = trainer._compiled_step.memory_analysis()
    losses, stamps = [], []

    def on_step(step, m):
        losses.append(m["loss"])                # fit() already synced it
        stamps.append(time.perf_counter())

    t_run = time.perf_counter()
    fit(trainer, itertools.repeat(dev_batch), rng=jax.random.key(SEED),
        max_steps=size["steps"], on_step=on_step)

    step_s = [round(b - a_, 3)
              for a_, b in zip([t_run] + stamps[:-1], stamps)]
    stats = jax.local_devices()[0].memory_stats() or {}
    report = {
        "device": device, "vocab": vocab, "recipe": recipe,
        "compile_cache": {"dir": cache_dir, "placed_by_env": placed},
        "compile_s": round(compile_s, 2), "step_s": step_s,
        "losses": [round(x, 5) for x in losses],
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        # the compiler's plan for the step, then the allocator's view
        "compiled_bytes": {
            "argument": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes},
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "param_shards": _shards(trainer.params["layers"]["wq"]),
    }
    if len(losses) < 3 or not all(np.isfinite(losses)):
        raise SmokeFailure(f"train: bad losses {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"train: loss did not fall: {losses}")
    if a.size == "full" and report["tpu_custom_calls"] == 0:
        raise SmokeFailure("train: no tpu_custom_call in the compiled step "
                           "(attention is not the Mosaic kernel)")
    if not _on_every_device(report["param_shards"], device["count"]):
        raise SmokeFailure(f"train: params not on every device: "
                           f"{report['param_shards']}")

    # serving weights: bf16, HF layout, outside the checkout
    trainer.opt_state = None
    params = jax.jit(lambda p: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), p))(trainer.params)
    trainer.params = None
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    try:
        hf_llama.save_pretrained(a.model_dir, cfg, params,
                                 max_shard_bytes=size["shard_bytes"])
    except (OSError, SafetensorError) as e:
        raise SmokeFailure(
            f"train: could not write the weights: {e!r}; RLIMIT_FSIZE "
            f"{'none' if fsize == resource.RLIM_INFINITY else fsize}, "
            f"{a.model_dir} has {shutil.disk_usage(a.model_dir).free} bytes "
            f"free and holds {_file_sizes(a.model_dir)}")
    sizes = _file_sizes(a.model_dir)
    report["weights"] = {
        "dir": a.model_dir, "files": len(sizes),
        "bytes": sum(sizes.values()), "largest_file": max(sizes.values()),
        "file_size_limit": None if fsize == resource.RLIM_INFINITY else fsize}
    return report


def leg_check(a) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import hf_llama, llama
    from kubeflow_tpu.ops.attention import decode_attention
    from kubeflow_tpu.ops.paged_pool import dequant_gather_view
    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_sharded,
    )
    from kubeflow_tpu.parallel import MeshConfig, build_mesh
    from kubeflow_tpu.utils import compile_cache

    size = SIZES[a.size]
    device = _device(jax, a.size)
    cache_dir, placed = compile_cache.ensure()
    interpret = device["platform"] == "cpu"
    report = {"device": device,
              "compile_cache": {"dir": cache_dir, "placed_by_env": placed}}

    # --- served tokens, teacher-forced against llama.forward ---
    with open(a.pairs) as f:
        pairs = json.load(f)
    cfg, params = hf_llama.load_pretrained(a.model_dir, dtype=jnp.bfloat16)
    width = max(len(p["prompt"]) + len(p["tokens"]) for p in pairs)
    toks = np.zeros((len(pairs), width), np.int32)
    for i, p in enumerate(pairs):
        seq = p["prompt"] + p["tokens"]
        toks[i, :len(seq)] = seq
    t0 = time.perf_counter()
    logits = np.asarray(jax.jit(
        lambda p, t: llama.forward(p, t, cfg))(params, jnp.asarray(toks)))
    report["forward_s"] = round(time.perf_counter() - t0, 2)
    if not np.isfinite(logits).all():
        raise SmokeFailure("check: reference logits not finite")
    checked = exact = 0
    worst = 0.0
    for i, p in enumerate(pairs):
        for j, g in enumerate(p["tokens"]):
            row = logits[i, len(p["prompt"]) + j - 1]
            top = float(row.max())
            # one bf16 step at the maximum's magnitude
            ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 1e-30))) - 7)
            steps = (top - float(row[g])) / ulp
            checked += 1
            exact += steps <= 0
            worst = max(worst, steps)
            if steps > TIE_ULPS:
                raise SmokeFailure(
                    f"check: request {i} token {j} = {g} sits {steps:.1f} "
                    f"bf16 steps under the reference maximum "
                    f"{int(row.argmax())} (allowed {TIE_ULPS})")
    report["greedy"] = {"tokens_checked": checked, "exact_argmax": int(exact),
                        "worst_bf16_steps_under_max": round(worst, 2),
                        "allowed_bf16_steps": TIE_ULPS}
    if exact < MIN_EXACT * checked:
        raise SmokeFailure(f"check: only {exact} of {checked} served tokens "
                           "are the reference's argmax")
    del params, logits

    # --- each paged-decode kernel variant, directly, vs the gather oracle:
    # whole pools of LAYERS layers, each with its own contents, read at the
    # last one (the kernel is addressed by layer, never handed a slice)
    n = device["count"]
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, bs = size["max_batch"], 64 if a.size == "full" else 8
    nbp = size["max_seq"] // bs
    nb = b * nbp + 1
    layers, layer = 3, 2
    ks = jax.random.split(jax.random.key(SEED), 6)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (layers, nb, bs, kvh, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (layers, nb, bs, kvh, d), jnp.bfloat16)
    kq = jax.random.randint(ks[1], (layers, nb, bs, kvh, d), -127, 127,
                            jnp.int8)
    vq = jax.random.randint(ks[2], (layers, nb, bs, kvh, d), -127, 127,
                            jnp.int8)
    ksc = jax.random.uniform(ks[3], (layers, nb, kvh), jnp.float32,
                             0.005, 0.02)
    vsc = jax.random.uniform(ks[4], (layers, nb, kvh), jnp.float32,
                             0.005, 0.02)
    tables = jnp.asarray(1 + np.random.default_rng(SEED).permutation(
        nb - 1).reshape(b, nbp), jnp.int32)
    kv_len = jax.random.randint(ks[5], (b,), 1, nbp * bs + 1, jnp.int32)

    def oracle(k_view, v_view):
        return decode_attention(q[:, None], k_view.reshape(b, -1, kvh, d),
                                v_view.reshape(b, -1, kvh, d), kv_len)[:, 0]

    ref_bf16 = oracle(kp[layer, tables], vp[layer, tables])
    ref_int8 = oracle(dequant_gather_view(kq, ksc, layer, tables, cfg),
                      dequant_gather_view(vq, vsc, layer, tables, cfg))

    # mesh=None is the bare kernel; a 1-sized tensor axis needs no
    # partitioning either, so the wrapper really runs under shard_map only
    # where JAX reports several chips
    def kernel(mesh):
        def fn(q, kp, vp, k_scale=None, v_scale=None):
            return paged_decode_attention_sharded(
                q, kp, vp, layer, tables, kv_len, mesh=mesh,
                interpret=interpret, k_scale=k_scale, v_scale=v_scale)
        return jax.jit(fn)

    plain, sharded = kernel(None), kernel(build_mesh(MeshConfig(tensor=n)))
    kernels = {
        "paged_bf16": (plain, (q, kp, vp), ref_bf16),
        "paged_int8": (plain, (q, kq, vq, ksc, vsc), ref_int8),
        f"sharded_bf16_tensor{n}": (sharded, (q, kp, vp), ref_bf16),
        f"sharded_int8_tensor{n}": (sharded, (q, kq, vq, ksc, vsc),
                                    ref_int8),
    }
    report["kernels"] = {}
    for name, (fn, args, ref) in kernels.items():
        t0 = time.perf_counter()
        out = np.asarray(fn(*args).astype(jnp.float32))
        err = float(np.max(np.abs(out - np.asarray(ref, np.float32))))
        report["kernels"][name] = {
            "max_abs_err": round(err, 5),
            "compile_and_run_s": round(time.perf_counter() - t0, 2)}
        # bf16 outputs: the tolerance tests/test_paged_attention_kernel.py
        # uses for bf16 pools
        if not np.isfinite(out).all() or err > 2e-2:
            raise SmokeFailure(f"check: kernel {name} vs oracle: {err}")

    report["kernels"]["latent_prefill_chunk"] = _latent_prefill_check(
        a.size, interpret)

    # --- several chips: the server's own build path shards, per device ---
    if n > 1:
        from kubeflow_tpu.serving import runtime

        with open(a.serve_env) as f:
            model = runtime.build_model_from_env(json.load(f))
        model.load()
        try:
            pool = model.engine.cache["k"]
            report["serve_shards"] = {
                "kernel": model.engine.kernel,
                "pool_spec": [str(x) for x in pool.sharding.spec],
                "pool": _shards(pool),
                "wq": _shards(model.engine.params["layers"]["wq"])}
        finally:
            model.unload()
        sh = report["serve_shards"]
        # off the TPU kernel="auto" is the gather path, by design
        want = "gather" if interpret else "pallas"
        if (sh["kernel"] != want or sh["pool_spec"][3] != "tensor"
                or not _on_every_device(sh["pool"], n)
                or not _on_every_device(sh["wq"], n)):
            raise SmokeFailure(f"check: serving not sharded: {sh}")
    return report


def _latent_prefill_check(size: str, interpret: bool) -> dict:
    """The latent model's prefill chunk kernel against the plain form in
    float32, at JoyAI-LLM-Flash's widths: one slot, a chunk of 2,048
    queries at position 12,288 over a permuted table, read at the last of
    two layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_latent_prefill_attention,
    )

    h, latent, d_n, d_r, d_v, row, bs, chunk, q_start = (
        (32, 512, 128, 64, 128, 640, 64, 2048, 12288) if size == "full"
        else (4, 16, 8, 8, 8, 128, 8, 32, 40))
    nbp = (q_start + chunk) // bs
    scale = (d_n + d_r) ** -0.5
    ks = jax.random.split(jax.random.key(SEED + 1), 4)
    # queries of four times a normed row's size: a softmax that picks rows
    # (outputs of size ~1), not the mean of 14k rows that unit queries give
    q = 4.0 * jax.random.normal(ks[0], (1, chunk, h, d_n + d_r), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (2, nbp + 1, bs, row), jnp.bfloat16)
    w_uk = (latent ** -0.5 * jax.random.normal(ks[2], (latent, h, d_n))
            ).astype(jnp.bfloat16)
    w_uv = (latent ** -0.5 * jax.random.normal(ks[3], (latent, h, d_v))
            ).astype(jnp.bfloat16)
    tables = jnp.asarray(1 + np.random.default_rng(SEED).permutation(nbp),
                         jnp.int32)[None]
    start = jnp.asarray([q_start], jnp.int32)

    @jax.jit
    def kernel(q, pool, w_uk, w_uv):
        return paged_latent_prefill_attention(
            q, pool, w_uk, w_uv, 1, tables, start, rope_dim=d_r, scale=scale,
            interpret=interpret)

    @jax.jit
    def oracle(q, pool, w_uk, w_uv):
        f32 = jnp.float32
        rows = pool[1][tables[0]].reshape(nbp * bs, row).astype(f32)
        seen = jnp.arange(nbp * bs)[None] <= q_start + jnp.arange(chunk)[:, None]

        def head(x):                      # a head at a time: [C, T] scores
            q_h, wk, wv = x
            k = jnp.concatenate([rows[:, :latent] @ wk.astype(f32),
                                 rows[:, latent:latent + d_r]], -1)
            s = (q_h.astype(f32) @ k.T) * scale
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
            return p @ (rows[:, :latent] @ wv.astype(f32))

        o = jax.lax.map(head, (jnp.moveaxis(q[0], 1, 0),
                               jnp.moveaxis(w_uk, 1, 0),
                               jnp.moveaxis(w_uv, 1, 0)))
        return jnp.moveaxis(o, 0, 1)[None]

    t0 = time.perf_counter()
    out = np.asarray(kernel(q, pool, w_uk, w_uv).astype(jnp.float32))
    ref = np.asarray(oracle(q, pool, w_uk, w_uv))
    err = float(np.max(np.abs(out - ref)))
    top = float(np.abs(ref).max())
    # the kernel rounds a tile's keys, values and probabilities to bf16 for
    # its products (as the XLA form it replaced did) and its output to bf16;
    # the oracle rounds nothing. Allowed: TWO bf16 steps at the largest
    # output. On the v5e the error read 0.0150 on outputs up to 4.39 (half a
    # step there, the output's own rounding), and a kernel with the mask a
    # row off, the scale 1.225 times too large or the rotary key left out
    # 0.93, 1.07 and 4.63: thirty steps and more (my chip run, PR 32)
    tol = float(2.0 ** (np.floor(np.log2(top)) - 6))
    if not np.isfinite(out).all() or err > tol:
        raise SmokeFailure(f"check: latent prefill kernel vs oracle: {err} "
                           f"(allowed {tol})")
    return {"max_abs_err": round(err, 5), "max_abs_ref": round(top, 3),
            "allowed": tol,
            "compile_and_run_s": round(time.perf_counter() - t0, 2)}


def child_main(a) -> int:
    try:
        report = {"train": leg_train, "check": leg_check}[a.leg](a)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    with open(a.report, "w") as f:
        json.dump(report, f, indent=1)
    return 0


# ---------------------------------------------------------------- parent --

def scratch_root():
    """Where one run's weights live: TMPDIR if the caller chose one, else
    memory-backed /dev/shm where it has ample room, else the default."""
    if os.environ.get("TMPDIR"):
        return None
    try:
        if (os.access(SHM, os.W_OK)
                and shutil.disk_usage(SHM).free >= SHM_FREE_BYTES):
            return SHM
    except OSError:
        pass
    return None


class Parent:
    def __init__(self, size: str):
        self.size = size
        self.t_end = time.monotonic() + DEADLINE_S
        self.procs: list[subprocess.Popen] = []
        self.scratch = tempfile.mkdtemp(prefix="kft-chip-smoke-",
                                        dir=scratch_root())
        self.model_dir = os.path.join(self.scratch, "model")
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR)

    def left(self, cap: float) -> float:
        left = self.t_end - time.monotonic()
        if left <= 0:
            raise SmokeFailure("out of time")
        return min(cap, left)

    def child_env(self, **extra) -> dict:
        env = dict(os.environ)            # JAX_* pass through untouched
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env.update(extra)
        return env

    def cache_entries(self) -> int:
        # utils/compile_cache.py's rule, restated: importing the package
        # imports jax, which this process must not
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            HERE, ".jax_cache")
        return len(os.listdir(d)) if os.path.isdir(d) else 0

    def spawn(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        logf = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        proc = subprocess.Popen(argv, env=env, cwd=HERE, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        logf.close()
        self.procs.append(proc)
        return proc

    def run_leg(self, leg: str, cap: float, *extra) -> dict:
        report = os.path.join(OUT_DIR, f"{leg}.json")
        before = self.cache_entries()
        t0 = time.monotonic()
        proc = self.spawn(leg, [
            sys.executable, os.path.abspath(__file__), "--leg", leg,
            "--size", self.size, "--model-dir", self.model_dir,
            "--report", report, *extra], self.child_env())
        try:
            rc = proc.wait(timeout=self.left(cap))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{leg}: still running after {cap:.0f}s")
        if rc == EXIT_NO_CHIP:
            print(self.tail(leg, 3), file=sys.stderr, end="")
            raise SystemExit(EXIT_NO_CHIP)
        if rc != 0:
            raise SmokeFailure(f"{leg}: exit {rc}\n{self.tail(leg)}")
        with open(report) as f:
            out = json.load(f)
        out["wall_s"] = round(time.monotonic() - t0, 1)
        out["cache_entries_added"] = self.cache_entries() - before
        log(f"{leg}: {json.dumps(out)}")
        return out

    def tail(self, name: str, n: int = 40) -> str:
        with open(os.path.join(OUT_DIR, f"{name}.log"), errors="replace") as f:
            return "".join(line[:400].rstrip("\n") + "\n"
                           for line in f.readlines()[-n:])

    # ---- serve leg: the parent is only an HTTP client of the runtime ----

    def serve(self, train: dict) -> list:
        size = SIZES[self.size]
        device = train["device"]
        extra = {"KFT_MODEL_DIR": self.model_dir, "KFT_MODEL_NAME": "model",
                 "KFT_MAX_BATCH": str(size["max_batch"]),
                 "KFT_MAX_SEQ": str(size["max_seq"]),
                 "KFT_BIND": "127.0.0.1:0"}
        if device["count"] > 1:
            extra["KFT_MESH"] = f"tensor={device['count']}"
        if self.size == "full":
            # no quiet fall-back to the CPU in the one child that cannot
            # report its own platform over HTTP
            extra["JAX_PLATFORMS"] = "tpu"
        with open(os.path.join(OUT_DIR, "serve_env.json"), "w") as f:
            json.dump(extra, f)
        before = self.cache_entries()
        t0 = time.monotonic()
        proc = self.spawn("serve", [
            sys.executable, "-m", "kubeflow_tpu.serving.runtime"],
            self.child_env(**extra))
        try:
            url = self.wait_serving(proc)
            ready_s = time.monotonic() - t0
            rng = random.Random(SEED)
            n = 1 + size["concurrent"]
            prompts = [[rng.randrange(1, train["vocab"]) for _ in range(
                size["prompt_len"] - n // 2 + i)] for i in range(n)]
            t1 = time.monotonic()
            first = self.predict(url, prompts[0], size["new_tokens"])
            first_s = time.monotonic() - t1
            results: list = [None] * (n - 1)

            def worker(i):
                try:
                    results[i] = self.predict(
                        url, prompts[1 + i], size["new_tokens"])
                except SmokeFailure as e:   # re-raised below, in the parent
                    results[i] = e

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n - 1)]
            t2 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=self.left(400))
            concurrent_s = time.monotonic() - t2
            for r in results:
                if isinstance(r, SmokeFailure):
                    raise r
                if r is None:
                    raise SmokeFailure("serve: a request did not finish")
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=self.left(30)) as resp:
                metrics = resp.read().decode()
            m = re.search(r"^kft_model_kernel_downgrades_total(?:\{[^}]*\})?"
                          r" (\S+)$", metrics, re.M)
            if m is None or float(m.group(1)) != 0:
                raise SmokeFailure(
                    "serve: kft_model_kernel_downgrades_total is "
                    f"{m.group(1) if m else 'absent'}, want 0")
        except SmokeFailure as e:
            raise SmokeFailure(f"{e}\n{self.tail('serve')}")
        finally:
            self.stop(proc)
        log("serve: " + json.dumps({
            "command": "python -m kubeflow_tpu.serving.runtime",
            "env": extra, "ready_s": round(ready_s, 1),
            "first_request_s": round(first_s, 2),
            "concurrent_requests": n - 1,
            "concurrent_s": round(concurrent_s, 2),
            "new_tokens_each": size["new_tokens"],
            "kernel_downgrades": 0,
            "cache_entries_added": self.cache_entries() - before}))
        return [{"prompt": p, "tokens": t}
                for p, t in zip(prompts, [first] + results)]

    def wait_serving(self, proc) -> str:
        path = os.path.join(OUT_DIR, "serve.log")
        t_end = time.monotonic() + self.left(600)
        while time.monotonic() < t_end:
            with open(path, errors="replace") as f:
                m = re.search(r"^serving .* at (http://\S+)$", f.read(), re.M)
            if m:
                return m.group(1)
            if proc.poll() is not None:
                raise SmokeFailure(f"serve: runtime exited {proc.returncode} "
                                   "before serving")
            time.sleep(0.5)
        raise SmokeFailure("serve: runtime never printed its address")

    def predict(self, url: str, prompt: list, new_tokens: int) -> list:
        body = json.dumps({"instances": [prompt],
                           "parameters": {"max_tokens": new_tokens}}).encode()
        req = urllib.request.Request(
            url + "/v1/models/model:predict", data=body,
            headers={"Content-Type": "application/json"})
        try:        # urlopen raises HTTPError on any status but 2xx
            with urllib.request.urlopen(req, timeout=self.left(400)) as resp:
                rows = json.load(resp)["predictions"]
        except (urllib.error.URLError, TimeoutError) as e:
            detail = e.read().decode(errors="replace")[:500] \
                if isinstance(e, urllib.error.HTTPError) else ""
            raise SmokeFailure(f"serve: predict failed: {e!r} {detail}")
        if len(rows) != 1 or len(rows[0]) != new_tokens:
            raise SmokeFailure(f"serve: asked {new_tokens} tokens, got "
                               f"{[len(r) for r in rows]}")
        return [int(t) for t in rows[0]]

    def stop(self, proc) -> None:
        """The runtime serves until killed; the chip is free only once it
        has been waited for."""
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=30)

    def close(self) -> None:
        for proc in self.procs:
            self.stop(proc)
        shutil.rmtree(self.scratch, ignore_errors=True)

    def run(self) -> dict:
        train = self.run_leg("train", 700)
        pairs = self.serve(train)
        pairs_path = os.path.join(OUT_DIR, "pairs.json")
        with open(pairs_path, "w") as f:
            json.dump(pairs, f)
        check = self.run_leg(
            "check", 400, "--pairs", pairs_path,
            "--serve-env", os.path.join(OUT_DIR, "serve_env.json"))
        if check["device"] != train["device"]:
            raise SmokeFailure(f"legs saw different devices: "
                               f"{train['device']} vs {check['device']}")
        return train["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-debug", action="store_true",
                    help="toy size on the CPU, to debug this script only")
    ap.add_argument("--leg", choices=("train", "check"),
                    help=argparse.SUPPRESS)       # a child of this script
    for flag in ("--size", "--model-dir", "--report", "--pairs",
                 "--serve-env"):
        ap.add_argument(flag, help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "kubeflow_tpu")):
        print("chip_smoke: the kubeflow_tpu package is not beside this "
              "script", file=sys.stderr)
        return 2
    if a.leg:
        return child_main(a)

    def on_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)
    parent = Parent("toy" if a.cpu_debug else "full")
    try:
        device = parent.run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        parent.close()
    result = {"ok": True, "device": device}
    if a.cpu_debug:
        result["cpu_debug"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
