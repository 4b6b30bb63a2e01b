"""The serving loop for traffic of kind ``cca_backlog``: a closed backlog of
reasoning requests (a short prompt, an output several times longer) on a
model with compressed convolutional attention and top-1 experts
(``zaya1-8b-l20``), through the same ``LLMEngine`` the other serving cells run.

The loop, the window and the counting are ``drivers/serve.py``'s (``_Loop``,
``_measure``: a ramp of ``ramp_s`` seconds, a window of ``--seconds`` by the
clock, rates between commits; rules 1-3 of README.md), handed a copy of the
traffic under the kind ``serve_backlog``. The warm-up (every prompt through
the one chunked-prefill program, the decode chunk at each trimmed length) and
the serving of the check's prompts are ``drivers/latent.py``'s, which name no
model. What this file brings:

- the model objects from ``lib/cca_moe.py`` and the plain reference
  ``reference/cca_moe.py``;
- the check, made AFTER the window on the engine as the window left it, the
  backlog's requests holding the other slots: seeded prompts of 1 and 2
  tokens (the convolutions' and the value shift's zero padding), of one chunk
  and one token (that token's mixing state comes from the previous chunk) and
  of three chunks, each decoding ``max_tokens`` tokens through the cache and
  the per-slot state, teacher-forced through the reference one request at a
  time, logits only at the rows compared. The engine records the choice its
  router made at EVERY row of these requests, the prompt's too: a compared
  row attends to all rows before it, and a prompt row whose near-tie the
  reference resolved the other way is a different expert's output under
  every later query (PERF.md section 6, PR 34: seed 1787135444). The
  reference takes the program's choice where it differs from its own by a
  near-tie (under ``check.route_tol``) and nowhere else, and one choice
  further off, at any row, makes the run incorrect. ``lib/check.greedy_agreement``
  (the 8-bf16-step rule) decides the rest. Pool and state are freed before
  the float32 reference runs (``tests/test_cca_moe.py`` runs the same two
  functions on programs broken on purpose);
- a run is not ``correct`` either if the engine downgraded a kernel or a
  program compiled inside the window;
- ``expert_load_max_over_mean`` and ``skip_pct``: the program's counter of
  tokens per router choice over ramp and window (``[layers, 17]``, column 16
  the skip): the busiest expert's load over the mean of the 16, averaged over
  the layers, and the share of tokens that chose no expert.

Under ``--rehearse`` (``selftest.py``, CPU) every width not in
``lib/model.TINY`` and every length is cut here.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import latent, serve
from lib import cca_moe as model_lib, check


def _rehearsal(cfg: dict, tr: dict):
    """Tiny widths and lengths for the CPU: lengths / 32, blocks of 8."""
    cfg = dict(cfg, **model_lib.TINY)
    eng = dict(tr["engine"], max_seq=tr["engine"]["max_seq"] // 32,
               kv_block_size=8, prefill_buckets=[16], max_batch=6)
    eng["kv_num_blocks"] = eng["max_batch"] * eng["max_seq"] // 8 + 1
    return cfg, dict(
        tr, engine=eng, ramp_s=3.0,
        pairs=[[max(1, p // 32), max(2, o // 32)] for p, o in tr["pairs"]],
        check=dict(tr["check"], prompt_lens=[1, 2, 17, 40], max_tokens=4))


def _compare(reqs, params, cfg, spec, log):
    """Teacher-force what the engine generated through the reference."""
    from reference import cca_moe as reference

    t0 = time.time()
    logits, gaps, notes = [], [], {"route_disagreements": 0,
                                   "route_violations": 0, "route_gap": 0.0}
    for r in reqs:
        n = len(r.prompt)
        # the program's choice at EVERY row: the prompt's rows but the last,
        # then one row a generated token (the first is the prompt's last)
        forced = np.concatenate([r.prompt_routing[:, :n - 1, 0],
                                 np.stack(r.routing, 1)[..., 0]], axis=1)
        out = reference.forward(
            params, np.asarray(r.prompt + r.generated), cfg,
            rows=range(n - 1, n - 1 + len(r.generated)),
            forced=forced, forced_rows=range(forced.shape[1]),
            route_tol=spec["route_tol"], q_block=256,
            # whole blocks of the reference's queries: few lengths to compile
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
    out["ok"] = bool(out["ok"] and notes["route_violations"] == 0)
    log(f"check: {out}")
    return out


def _router_load(load, spans, layers, log):
    """The window's counters of the router's choices ``[layers, E + 1]``."""
    experts = load[:, :-1]
    steps = [s["attrs"] for s in spans if s["name"] == "decode.step"
             and "experts_hit" in s["attrs"]]
    hit = sum(a["experts_hit"] for a in steps) / max(
        1, layers * sum(a["device_steps"] for a in steps))
    values = {
        "expert_load_max_over_mean": float(
            (experts.max(-1) / experts.mean(-1)).mean()),
        "skip_pct": float(100.0 * load[:, -1].sum() / load.sum())}
    log(f"router: experts hit a layer a decode step {hit:.2f} of "
        f"{experts.shape[1]}, load max/mean "
        f"{values['expert_load_max_over_mean']:.3f}, skipped "
        f"{values['skip_pct']:.2f} %; busiest and idlest expert of each "
        f"layer {experts.argmax(-1).tolist()} {experts.argmin(-1).tolist()}")
    return values


def run(ctx) -> dict:
    import jax

    from kubeflow_tpu.obs.trace import SpanCollector
    from kubeflow_tpu.serving.llm import LLMEngine

    cfg, tr, seed, log = ctx.config, ctx.traffic, ctx.seed, ctx.log
    if ctx.rehearse:
        cfg, tr = _rehearsal(cfg, tr)
    ctx.config = cfg                   # what the readers compute bytes from
    eng_args = tr["engine"]
    mcfg = model_lib.model_config(cfg)
    params = model_lib.serving_params(mcfg, seed)
    jax.block_until_ready(params)
    ctx.mark("weights")
    obs = SpanCollector(capacity=1 << 17)
    engine = LLMEngine(
        params, mcfg, max_batch=eng_args["max_batch"],
        max_seq=eng_args["max_seq"],
        prefill_buckets=eng_args["prefill_buckets"],
        kv_block_size=eng_args["kv_block_size"],
        kv_num_blocks=eng_args["kv_num_blocks"], obs=obs)
    log(f"cache: kv_row_bytes {engine.kv_row_bytes()}, slot_state_bytes "
        f"{engine.slot_state_bytes}")
    latent._warm_up(engine, cfg["vocab_size"], log)
    ctx.mark("warm_up")

    before = engine.moe_tokens_per_expert.copy()
    t_measure = time.time()
    m = serve._measure(ctx, engine, dict(tr, kind="serve_backlog"),
                       trace=ctx.trace)
    m["values"].update(_router_load(
        (engine.moe_tokens_per_expert - before).astype(np.float64),
        [s for s in obs.snapshot() if s["t0"] >= t_measure],
        mcfg.n_layers, log))
    log("window: " + ", ".join(f"{k} {v}" for k, v in m["summary"].items()))
    log(f"pool: used at most {m['values']['pool_used_pct_max']} %")

    # the check, on the engine as the window left it (module docstring)
    def memory(phase):
        stats = jax.devices()[0].memory_stats() or {}
        log(f"device memory after {phase}: peak "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB, in use "
            f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB")

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
        and not ctx.compiles_in_window,
        "spans": spans,
        "samples": m["loop"].samples,
    }
