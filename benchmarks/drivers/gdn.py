"""The serving loop for traffic of kind ``gdn_backlog``: a closed backlog of
long generations (prompts of a few hundred to a few thousand tokens, answers
of one to three thousand) on a model whose layers are Gated DeltaNet linear
attention 3:1 with gated full attention and whose expert layers hold a share
of their experts (``qwen3-next-80b-a3b-l8``), through the same ``LLMEngine``
the other serving cells run.

The loop, the window and the counting are ``drivers/serve.py``'s (``_Loop``,
``_measure``), handed a copy of the traffic under the kind ``serve_backlog``;
the warm-up and the serving of the check's prompts are ``drivers/latent.py``'s,
which name no model. What this file brings:

- the model objects from ``lib/qwen3_next.py`` and the plain reference
  ``reference/qwen3_next.py``; the scheduler policy of the traffic file
  (``engine.scheduler``), radix prefix cache off (the model refuses it);
- the check, made AFTER the window on the engine as the window left it, the
  backlog's requests holding the other slots: seeded prompts of 1 and 3
  tokens (the conv tail's zero padding), 513 (a chunk and one token of
  carried state), 700 (a chunk boundary inside a 64-token sub-chunk) and
  1,400 (three chunks), each decoding ``max_tokens`` tokens through the
  cache and the state, teacher-forced through the reference one request at a
  time, logits only at the rows compared. The engine records its router's
  top-10 at EVERY row of these requests, the prompts' too; the reference
  takes the program's choices where they differ from its own by a near-tie
  (under ``check.route_tol``, in probability) and nowhere else, and one
  choice further off, at any row, makes the run incorrect.
  ``lib/check.greedy_agreement`` reads the logits: most served tokens the
  reference's argmax, and none more than ``check.logit_steps`` bf16 steps
  under its maximum (the traffic file gives the limit and its readings).
  The cache is freed before the float32 reference runs
  (``tests/test_qwen3_next.py`` runs the same functions on programs broken on
  purpose);
- a run is not ``correct`` either if the engine downgraded a kernel, if the
  compiled decode program has an operation as large as a pool or a state
  array (``LLMEngine.decode_pool_shaped_ops``: the state is updated in place
  or the step is not what it claims), or a program compiled inside the
  window;
- ``expert_load_max_over_mean`` over the 64 held experts and
  ``absent_pick_pct``, the share of all picks routed to experts this chip
  does not hold (the engine's two counters, over ramp and window).

Under ``--rehearse`` (``selftest.py``, CPU) every width not in
``lib/model.TINY`` and every length is cut here.
"""

from __future__ import annotations

import math
import time

import numpy as np

from drivers import latent, serve
from lib import check, qwen3_next as model_lib


def _rehearsal(cfg: dict, tr: dict):
    """Tiny widths and lengths for the CPU: lengths / 32, blocks of 8."""
    cfg = dict(cfg, **model_lib.TINY)
    eng = dict(tr["engine"], max_seq=tr["engine"]["max_seq"] // 32,
               kv_block_size=8, prefill_buckets=[16], max_batch=6)
    eng["kv_num_blocks"] = eng["max_batch"] * eng["max_seq"] // 8 + 1
    return cfg, dict(
        tr, engine=eng, ramp_s=3.0,
        pairs=[[max(1, p // 32), max(2, o // 32)] for p, o in tr["pairs"]],
        check=dict(tr["check"], prompt_lens=[1, 3, 17, 22, 40],
                   max_tokens=4))


def _compare(reqs, params, cfg, spec, log):
    """Teacher-force what the engine generated through the reference."""
    from reference import qwen3_next as reference

    t0 = time.time()
    logits, gaps, notes = [], [], {"route_disagreements": 0,
                                   "route_violations": 0, "route_gap": 0.0}
    for r in reqs:
        n = len(r.prompt)
        # the program's top-k at EVERY row: the prompt's rows but the last,
        # then one row a generated token (the first is the prompt's last)
        forced = np.concatenate([r.prompt_routing[:, :n - 1],
                                 np.stack(r.routing, 1)], axis=1)
        out = reference.forward(
            params, np.asarray(r.prompt + r.generated), cfg,
            rows=range(n - 1, n - 1 + len(r.generated)),
            forced=forced, forced_rows=range(forced.shape[1]),
            route_tol=spec["route_tol"],
            # whole blocks of rows: few lengths to compile
            pad_to=-(-(n + len(r.generated)) // 256) * 256)
        logits.append(np.asarray(out["logits"]))
        for key in ("route_disagreements", "route_violations"):
            notes[key] += out[key]
        notes["route_gap"] = max(notes["route_gap"], out["route_gap"])
        gaps += out["route_gaps"]
    # row j of a request's logits predicts its generated token j
    out = check.greedy_agreement(np.stack(logits),
                                 [(1, r.generated) for r in reqs])
    out.update(notes, routed_rows_compared=sum(
        r.routing[0].shape[0] * (len(r.prompt) - 1 + len(r.generated))
        for r in reqs),
        route_gaps_largest=sorted(gaps)[-5:],
        reference_s=round(time.time() - t0, 1))
    # lib/check's exact share, and this cell's own limit on the steps
    worst = out["worst_bf16_steps_under_max"]
    out["ok"] = bool(
        out["exact_argmax"] >= check.MIN_EXACT * out["tokens_checked"] > 0
        and math.isfinite(worst) and worst <= spec["logit_steps"]
        and notes["route_violations"] == 0)
    log(f"check: {out}")
    return out


def _router_load(load, absent, spans, layers, log):
    """The window's counters: tokens per held expert ``[layers, held]`` and
    the picks that went to experts held elsewhere."""
    steps = [s["attrs"] for s in spans if s["name"] == "decode.step"
             and "experts_hit" in s["attrs"]]
    hit = sum(a["experts_hit"] for a in steps) / max(
        1, layers * sum(a["device_steps"] for a in steps))
    values = {
        "expert_load_max_over_mean": float(
            (load.max(-1) / load.mean(-1)).mean()),
        "absent_pick_pct": float(100.0 * absent / (absent + load.sum()))}
    log(f"router: held experts hit a layer a decode step {hit:.2f} of "
        f"{load.shape[1]}, load max/mean "
        f"{values['expert_load_max_over_mean']:.3f}, picks to absent experts "
        f"{values['absent_pick_pct']:.2f} %")
    return values


def run(ctx) -> dict:
    import jax

    from kubeflow_tpu.obs.trace import SpanCollector
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.scheduler import SchedulerConfig

    cfg, tr, seed, log = ctx.config, ctx.traffic, ctx.seed, ctx.log
    if ctx.rehearse:
        cfg, tr = _rehearsal(cfg, tr)
    ctx.config = cfg                   # what the readers compute bytes from
    eng_args = tr["engine"]
    mcfg = model_lib.model_config(cfg)
    params = model_lib.serving_params(mcfg, seed)
    jax.block_until_ready(params)
    ctx.mark("weights")
    obs = SpanCollector(capacity=1 << 18)
    engine = LLMEngine(
        params, mcfg, max_batch=eng_args["max_batch"],
        max_seq=eng_args["max_seq"],
        prefill_buckets=eng_args["prefill_buckets"],
        kv_block_size=eng_args["kv_block_size"],
        kv_num_blocks=eng_args["kv_num_blocks"], obs=obs,
        scheduler=SchedulerConfig(radix_cache=False,
                                  **eng_args.get("scheduler", {})))
    log(f"cache: kv_row_bytes {engine.kv_row_bytes()}, slot_state_bytes "
        f"{engine.slot_state_bytes}")
    latent._warm_up(engine, cfg["vocab_size"], log)
    engine.precompile()
    log(f"decode program: {engine.decode_pool_shaped_ops} pool-shaped "
        "operations")
    ctx.mark("warm_up")

    before = engine.moe_tokens_per_expert.copy()
    absent0 = engine.moe_absent_picks
    t_measure = time.time()
    m = serve._measure(ctx, engine, dict(tr, kind="serve_backlog"),
                       trace=ctx.trace)
    m["values"].update(_router_load(
        (engine.moe_tokens_per_expert - before).astype(np.float64),
        engine.moe_absent_picks - absent0,
        [s for s in obs.snapshot() if s["t0"] >= t_measure],
        mcfg.n_layers, log))
    log("window: " + ", ".join(f"{k} {v}" for k, v in m["summary"].items()))
    log(f"pool: used at most {m['values']['pool_used_pct_max']} %")

    def memory(phase):
        stats = jax.devices()[0].memory_stats() or {}
        log(f"device memory after {phase}: peak "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB, in use "
            f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB")

    # the check, on the engine as the window left it (module docstring)
    reqs = latent._serve_checked(engine, cfg["vocab_size"], tr["check"],
                                 seed, log)
    memory("serving")
    spans = obs.snapshot()
    for buf in jax.tree.leaves(engine.cache):
        buf.delete()                   # the reference needs the room
    checked = _compare(reqs, params, cfg, tr["check"], log)
    memory("the reference")
    return {
        "values": m["values"],
        "attempted": m["attempted"],
        "failed": m["failed"],
        "correct": bool(checked["ok"]) and engine.kernel_downgrades == 0
        and (ctx.rehearse or engine.decode_pool_shaped_ops == 0)
        and not ctx.compiles_in_window,
        "spans": spans,
        "samples": m["loop"].samples,
    }
