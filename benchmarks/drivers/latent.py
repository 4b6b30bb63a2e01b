"""The serving loop for traffic of kind ``latent_backlog``: a closed backlog of
long documents on a latent-attention model with routed experts
(``joyai-llm-flash-l5``), through the same ``LLMEngine`` the dense cells run.

The loop, the window and the counting are ``drivers/serve.py``'s (``_Loop``,
``_measure``: a ramp of ``ramp_s`` seconds, a window of ``--seconds`` by the
clock, rates between commits; rules 1-3 of README.md). They know a backlog by
the kind ``serve_backlog``, so they are handed a copy of the traffic under
that kind. What this file brings is what a model that is not ``LlamaConfig``
needs:

- the model objects from ``lib/mla_moe.py`` and the plain reference
  ``reference/mla_moe.py``;
- a warm-up of THIS engine's shapes: every prompt streams through the one
  chunked-prefill program (no dense-scratch buckets to warm), a prompt of two
  chunks warms what accumulates the chunks' expert counts, and the decode
  chunk is warmed at each trimmed length as ``serve._warm_up`` does;
- the check, made AFTER the window on the engine as the window left it: the
  slots held by the backlog's requests, 14k-26k tokens each, and its queue
  not empty. Three seeded prompts, one of them over 16,384 tokens (nine
  chunks of prefill, then a walk of 260 blocks), join the queue and are
  served beside them (the decode batch beside them is logged), then
  teacher-forced through the reference one request at a time, logits only at
  the rows compared. The engine records the experts it routed those rows to;
  the reference takes the program's choice where it differs from its own by
  a near-tie (under ``check.route_tol``: with random weights the 8th and 9th
  of 256 scores lie closer than bf16 resolves) and nowhere else, and one
  choice further off than that makes the run incorrect.
  ``lib/check.greedy_agreement`` (the 8-bf16-step rule) decides the rest. The
  pool is freed before the float32 reference runs, so the chip never holds
  both (``tests/test_mla_moe.py`` runs the same two functions on programs
  broken on purpose);
- ``expert_load_max_over_mean``: the program's counter of assignments per
  expert over ramp and window, the busiest expert's over the mean, averaged
  over the expert layers.

Under ``--rehearse`` (``selftest.py``, CPU) every width not in
``lib/model.TINY`` and every length is cut here.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import serve
from lib import check, mla_moe as model_lib, traffic as traffic_lib


def _rehearsal(cfg: dict, tr: dict):
    """Tiny widths and lengths for the CPU: lengths / 128, blocks of 8."""
    cfg = dict(cfg, **model_lib.TINY)
    eng = dict(tr["engine"], max_seq=tr["engine"]["max_seq"] // 128,
               kv_block_size=8, prefill_buckets=[16], max_batch=6)
    eng["kv_num_blocks"] = eng["max_batch"] * eng["max_seq"] // 8 + 1
    return cfg, dict(
        tr, engine=eng, ramp_s=3.0,
        pairs=[[p // 128, max(2, o // 128)] for p, o in tr["pairs"]],
        check=dict(tr["check"], prompt_lens=[130, 40, 5], max_tokens=4))


def _warm_up(engine, vocab, log):
    from kubeflow_tpu.serving.llm import SamplingParams

    rng = np.random.default_rng(0)

    def ask(length, max_tokens):
        return engine.add_request(
            rng.integers(1, vocab, length, dtype=np.int32).tolist(),
            SamplingParams(max_tokens=max_tokens, temperature=0.0,
                           eos_id=None))

    chunk = engine.buckets[-1]
    serve._drain(engine, [ask(chunk + 1, 2)])   # two chunks, then decode
    # all slots taken and one request waiting: the scheduler trims the chunk
    # to the earliest finish, here the same for all — each power of two once
    steps = 1
    while steps < engine.decode_chunk:
        serve._drain(engine, [ask(8, steps + 1)
                              for _ in range(engine.max_batch + 1)])
        steps *= 2
    serve._drain(engine, [ask(8, 2 * engine.decode_chunk + 1)])   # full chunk
    seen = {s["attrs"].get("chunk_len") for s in engine.obs.snapshot()
            if s["name"] == "decode.step"}
    want = {1 << k for k in range(engine.decode_chunk.bit_length())
            if 1 << k <= engine.decode_chunk}
    if not want <= seen:
        log(f"WARNING: warm-up saw decode chunks {sorted(seen)}, "
            f"not all of {sorted(want)}")


def _serve_checked(engine, vocab, spec, seed, log):
    """The check's prompts through the engine as it stands: they wait
    behind what its queue holds and are served beside the live slots."""
    from kubeflow_tpu.serving.llm import SamplingParams

    t0 = time.time()
    reqs = [engine.add_request(
        traffic_lib.token_ids(seed, 10_000_000 + j, n, vocab),
        SamplingParams(max_tokens=spec["max_tokens"], temperature=0.0,
                       eos_id=None, record_routing=True))
        for j, n in enumerate(spec["prompt_lens"])]
    serve._drain(engine, reqs)
    # the decode steps that carried a checked request: how full they were
    t_first = min(r.t_first_token for r in reqs)
    beside = [s["attrs"]["batch"] for s in engine.obs.snapshot()
              if s["name"] == "decode.step" and s["t1"] >= t_first]
    log(f"check: served in {time.time() - t0:.1f} s, decode batch "
        f"{min(beside)}-{max(beside)} of {engine.max_batch}")
    return reqs


def _compare(reqs, params, cfg, spec, log):
    """Teacher-force what the engine generated through the reference."""
    from reference import mla_moe as reference

    t0 = time.time()
    logits, notes = [], {"route_disagreements": 0, "route_violations": 0,
                         "route_gap": 0.0}
    # in whole blocks of the reference's queries; the longest request at its
    # own length and the others at one: each function compiles twice
    need = [-(-(len(r.prompt) + spec["max_tokens"]) // 256) * 256
            for r in reqs]
    rest = sorted(need)[-2] if len(need) > 1 else need[0]
    for r, rows in zip(reqs, need):
        n = len(r.prompt)
        out = reference.forward(
            params, np.asarray(r.prompt + r.generated), cfg,
            rows=range(n - 1, n - 1 + len(r.generated)),
            forced=np.stack(r.routing, 1), route_tol=spec["route_tol"],
            pad_to=max(rows, rest), q_block=128)
        logits.append(np.asarray(out["logits"]))
        for key in ("route_disagreements", "route_violations"):
            notes[key] += out[key]
        notes["route_gap"] = max(notes["route_gap"], out["route_gap"])
    # row j of a request's logits predicts its generated token j
    out = check.greedy_agreement(np.stack(logits),
                                 [(1, r.generated) for r in reqs])
    out.update(notes, routed_rows_compared=sum(
        r.routing[0].shape[0] * len(r.generated) for r in reqs),
        reference_s=round(time.time() - t0, 1))
    out["ok"] = bool(out["ok"] and notes["route_violations"] == 0)
    log(f"check: {out}")
    return out


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
    _warm_up(engine, cfg["vocab_size"], log)
    ctx.mark("warm_up")

    before = engine.moe_tokens_per_expert.copy()
    m = serve._measure(ctx, engine, dict(tr, kind="serve_backlog"),
                       trace=ctx.trace)
    load = (engine.moe_tokens_per_expert - before).astype(np.float64)
    m["values"]["expert_load_max_over_mean"] = float(
        (load.max(-1) / load.mean(-1)).mean())
    log("window: " + ", ".join(f"{k} {v}" for k, v in m["summary"].items()))
    log(f"pool: kv_row_bytes {engine.kv_row_bytes()}, used at most "
        f"{m['values']['pool_used_pct_max']} %; expert load max/mean "
        f"{m['values']['expert_load_max_over_mean']:.3f}")

    # the check, on the engine as the window left it (module docstring)
    def memory(phase):
        stats = jax.devices()[0].memory_stats() or {}
        log(f"device memory after {phase}: peak "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB, in use "
            f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB")

    reqs = _serve_checked(engine, cfg["vocab_size"], tr["check"], seed, log)
    memory("serving")
    spans = obs.snapshot()
    for pool in jax.tree.leaves(engine.cache):
        pool.delete()                  # the reference needs the room
    checked = _compare(reqs, params, cfg, tr["check"], log)
    memory("the reference")
    return {
        "values": m["values"],
        "attempted": m["attempted"],
        "failed": m["failed"],
        "correct": bool(checked["ok"]) and engine.kernel_downgrades == 0,
        "spans": spans,
        "samples": m["loop"].samples,
    }
