"""The serving loop, for traffic of kind ``serve_open`` and ``serve_backlog``.

One thread (rule 3 of README.md): the harness builds ``LLMEngine`` with the
arguments ``LLMModel.load()`` passes and runs the body of ``LLMModel._loop``
itself — hand over what is due, ``engine.step()``, record the commit instant.
So this is the engine, scheduler, paged cache and kernels of the served path;
the HTTP server, JSON and the model's wake-up condition are left out.

The harness plays the front end's queue: requests that are due wait here, in
order, and the engine's own queue is topped up to ``engine.queue_depth``
before every step. That bounds the width of an admission batch, so the set of
prefill programs is known and every one of them is warmed up in set-up.
"""

from __future__ import annotations

import time

import numpy as np

from lib import check, model, traffic as traffic_lib, window

REHEARSAL_RPS = 2.4        # selftest.py on the CPU; a cell's own rate is a chip's


class _Loop:
    """Feeds the engine from the frozen sequence and records every commit."""

    def __init__(self, engine, traffic, cfg, seed, log):
        from kubeflow_tpu.serving.llm import SamplingParams

        self.engine, self.cfg = engine, cfg
        self.seed, self.log = seed, log
        self.sampling = SamplingParams
        self.depth = int(traffic["engine"]["queue_depth"])
        self.backlog = traffic["kind"] == "serve_backlog"
        self.gen = traffic_lib.requests(traffic)
        self.next = next(self.gen)
        self.t_origin = None
        self.pending = []          # due, not yet handed to the engine
        self.records = []          # every request handed over (or refused)
        self.by_index = {}         # index -> its record
        self.live = []             # handed over, not done
        self.commits = []          # (t, engine.generated_tokens)
        self.samples = []          # (t, live_tokens, pool_used_blocks)
        self.sampling_on = False   # traced runs only: keeps the loop lean
        self.max_late = 0.0
        self.refused = 0

    def _hand_over(self, now):
        eng = self.engine
        if self.backlog:
            while len(self.pending) < self.depth:
                self.pending.append(self.next + (now,))
                self.next = next(self.gen)
        else:
            while self.t_origin + self.next[1] <= now:
                due = self.t_origin + self.next[1]
                self.max_late = max(self.max_late, now - due)
                self.pending.append(self.next + (due,))
                self.next = next(self.gen)
        room = self.depth - eng.scheduler_stats()["queue_depth"]
        while self.pending and room > 0:
            i, _, plen, olen, due = self.pending.pop(0)
            if self.backlog and i < eng.max_batch:
                # spread the first batch's retirements (ramp, see traffic)
                olen = max(2, round(olen * (i + 1) / eng.max_batch))
            rec = {"i": i, "due": due, "prompt_len": plen, "output_len": olen,
                   "req": None}
            self.records.append(rec)
            self.by_index[i] = rec
            try:
                rec["req"] = eng.add_request(
                    traffic_lib.token_ids(self.seed, i, plen,
                                          self.cfg["vocab_size"]),
                    self.sampling(max_tokens=olen, temperature=0.0,
                                  eos_id=None))
                self.live.append(rec)
            except ValueError as e:          # refused at admission
                self.refused += 1
                self.log(f"refused request {i}: {e}")
            room -= 1

    def run(self, until):
        """Step until ``until(now)`` is true. Returns the last instant."""
        eng = self.engine
        if self.t_origin is None:
            self.t_origin = time.time()
        while True:
            now = time.time()
            if until(now):
                return now
            self._hand_over(now)
            if not eng.has_work():
                wait = self.t_origin + self.next[1] - time.time()
                time.sleep(max(0.0, min(wait, 0.002)))
                continue
            eng.step()
            t = time.time()
            self.commits.append((t, eng.generated_tokens))
            self.live = [r for r in self.live if not r["req"].done]
            if self.sampling_on:
                self.samples.append((
                    t,
                    sum(len(r["req"].prompt) + len(r["req"].generated)
                        for r in self.live if r["req"].t_first_token),
                    eng.paged.num_blocks - eng.paged.reclaimable_blocks))


def _drain(engine, reqs):
    while not all(r.done for r in reqs):
        engine.step()


def _admission_widths(traffic) -> dict:
    """``{bucket: widest admission batch}`` this traffic can cause. The
    engine batches the same-bucket head of its queue, which holds at most
    ``queue_depth`` consecutive requests of the frozen sequence: so no batch
    is wider than the longest cyclic run of one bucket, or than the depth."""
    buckets = sorted(traffic["engine"]["prefill_buckets"])
    seq = [next(b for b in buckets if p <= b) for p, _ in traffic["pairs"]]
    widest = {}
    for i, b in enumerate(seq):
        run = 1
        while run < len(seq) and seq[(i + run) % len(seq)] == b:
            run += 1
        widest[b] = max(widest.get(b, 0),
                        min(run, traffic["engine"]["queue_depth"]))
    return widest


def _warm_up(engine, traffic, vocab, log):
    """Every shape the window can use: each prefill bucket at each admission
    width, and the decode chunk at each of its trimmed lengths."""
    from kubeflow_tpu.serving.llm import SamplingParams

    rng = np.random.default_rng(0)

    def ask(length, max_tokens):
        return engine.add_request(
            rng.integers(1, vocab, length, dtype=np.int32).tolist(),
            SamplingParams(max_tokens=max_tokens, temperature=0.0,
                           eos_id=None))

    for bucket, widest in sorted(_admission_widths(traffic).items()):
        width = 1
        while width < 2 * widest:
            _drain(engine, [ask(bucket - 1, 2) for _ in range(width)])
            width *= 2
    # all slots taken and one request waiting: the scheduler trims the chunk
    # to the earliest finish, here the same for all — each power of two once
    short = engine.buckets[0] - 1
    chunk = 1
    while chunk < engine.decode_chunk:
        _drain(engine, [ask(short, chunk + 1)
                        for _ in range(engine.max_batch + 1)])
        chunk *= 2
    seen = {s["attrs"].get("chunk_len") for s in engine.obs.snapshot()
            if s["name"] == "decode.step"}
    want = {1 << k for k in range(engine.decode_chunk.bit_length())
            if 1 << k <= engine.decode_chunk}
    if not want <= seen:
        log(f"WARNING: warm-up saw decode chunks {sorted(seen)}, "
            f"not all of {sorted(want)}")


def _check(engine, params, cfg, seed, log):
    """Four seeded prompts through the engine, teacher-forced through the
    plain reference at the same widths."""
    from kubeflow_tpu.serving.llm import SamplingParams
    from reference import dense_gqa

    lens = (48, 96, 160, 256)
    reqs = []
    for k in (0, 2):           # two at a time: admission widths warm-up made
        pair = [engine.add_request(
            traffic_lib.token_ids(seed, 10_000_000 + j, lens[j],
                                  cfg["vocab_size"]),
            SamplingParams(max_tokens=16, temperature=0.0, eos_id=None))
            for j in (k, k + 1)]
        _drain(engine, pair)
        reqs += pair
    width = max(lens) + 16
    toks = np.zeros((len(reqs), width), np.int32)
    for k, r in enumerate(reqs):
        seq = r.prompt + r.generated
        toks[k, :len(seq)] = seq
    logits = np.asarray(dense_gqa.forward_logits(params, toks, cfg))
    out = check.greedy_agreement(
        logits, [(len(r.prompt), r.generated) for r in reqs])
    log(f"check: {out}")
    return out


def run(ctx) -> dict:
    import jax

    from kubeflow_tpu.obs.trace import SpanCollector
    from kubeflow_tpu.serving.llm import LLMEngine

    cfg, tr, seed, log = ctx.config, ctx.traffic, ctx.seed, ctx.log
    if ctx.rehearse and "rate_rps" in tr:
        # the same loop at a rate a CPU holds, so that ``failed`` stays 0
        tr = dict(tr, rate_rps=min(tr["rate_rps"], REHEARSAL_RPS))
    eng_args = tr["engine"]
    lcfg = model.llama_config(cfg)
    params = model.serving_params(lcfg, seed)
    jax.block_until_ready(params)
    ctx.mark("weights")
    obs = SpanCollector(capacity=1 << 17)
    engine = LLMEngine(
        params, lcfg, max_batch=eng_args["max_batch"],
        max_seq=eng_args["max_seq"],
        prefill_buckets=eng_args["prefill_buckets"],
        kv_block_size=eng_args["kv_block_size"],
        kv_num_blocks=eng_args["kv_num_blocks"], obs=obs)
    _warm_up(engine, tr, cfg["vocab_size"], log)
    ctx.mark("warm_up")
    checked = _check(engine, params, cfg, seed, log)
    ctx.mark("check")

    if ctx.sweep:
        for rate in ctx.sweep:
            m = _measure(ctx, engine, dict(tr, rate_rps=rate), trace=False)
            log(f"sweep rate_rps {rate}: " + ", ".join(
                f"{k} {m['summary'][k]}" for k in m["summary"]))
            _drain(engine, [r["req"] for r in m["loop"].live])
        return None
    m = _measure(ctx, engine, tr, trace=ctx.trace)
    log("window: " + ", ".join(f"{k} {v}" for k, v in m["summary"].items()))
    return {
        "values": m["values"],
        "attempted": m["attempted"],
        "failed": m["failed"],
        "correct": bool(checked["ok"]) and engine.kernel_downgrades == 0,
        "spans": obs.snapshot(),
        "samples": m["loop"].samples,
    }


def _measure(ctx, engine, tr, trace) -> dict:
    """Ramp, then one window of ``ctx.seconds``; the tails and the rate."""
    loop = _Loop(engine, tr, ctx.config, ctx.seed, ctx.log)
    loop.sampling_on = bool(trace)
    seconds = ctx.seconds
    backlog = tr["kind"] == "serve_backlog"
    exp = tr["expected"]
    t0 = time.time()
    loop.run(lambda now: now >= t0 + float(tr["ramp_s"]))
    ctx.mark("ramp")
    t_open = ctx.open_window()                 # set-up ends here
    first = loop.next[0]                       # first request not yet due
    if trace:
        half = max(0.0, (seconds - ctx.trace_seconds) / 2)
        loop.run(lambda now: now >= t_open + half)
        ctx.start_trace()
        loop.run(lambda now: now >= t_open + half + ctx.trace_seconds)
        ctx.stop_trace()
    loop.run(lambda now: now >= t_open + seconds)
    t_close = t_open + seconds
    queued = len(loop.pending) + engine.scheduler_stats()["queue_depth"]
    last = loop.next[0]                        # one past the last one due
    if not backlog:
        # the requests judged are whole passes of the list where the window
        # holds any (exactly the same requests in every run), else those due
        # in it; they still owe their first token
        passes = int(seconds / traffic_lib.period_s(tr) + 1e-3)
        if passes:
            last = first + passes * len(tr["pairs"])
        deadline = t_close + 3 * float(exp["ttft_s"])
        judged = range(first, last)
        loop.run(lambda now: now >= deadline or all(
            i in loop.by_index and (loop.by_index[i]["req"] is None or
                                    loop.by_index[i]["req"].t_first_token)
            for i in judged))
    due_in = [r for r in loop.records if first <= r["i"] < last]
    t_end = time.time()
    ctx.close_window()

    # attempted: an open loop's requests due in the window; a backlog's
    # requests that ended in it. failed: refused at admission, no first
    # token in time, or unfinished at twice the expected time.
    ttft, tpot, attempted, failed, finished = [], [], 0, 0, 0
    for r in loop.records:
        q = r["req"]
        due_here = first <= r["i"] < last
        if q is None:
            attempted += due_here
            failed += due_here
            continue
        done_here = q.done and t_open <= q.t_done < t_close
        finished += done_here
        stuck = not q.done and t_end > r["due"] + 2 * (
            exp["ttft_s"] + r["output_len"] * exp["tpot_s"])
        if done_here and len(q.generated) > 1:
            tpot.append((q.t_last_commit - q.t_first_token)
                        / (len(q.generated) - 1))
        if due_here and not backlog:
            attempted += 1
            if q.t_first_token:
                ttft.append(q.t_first_token - r["due"])
            failed += bool(stuck or not q.t_first_token)
        elif backlog and (done_here or stuck):
            attempted += 1
            failed += bool(stuck)
    if not backlog:                  # judged, but never even handed over
        missing = sum(i not in loop.by_index for i in range(first, last))
        attempted += missing
        failed += missing
    in_w = [s for s in loop.samples if t_open <= s[0] <= t_close]
    values = {
        "ttft_p90_s": window.percentile(ttft, 90),
        "tpot_p90_s": window.percentile(tpot, 90),
        "out_tok_s": window.commit_rate(loop.commits, t_open, seconds),
        "pool_used_pct_max": 100.0 * max(s[2] for s in in_w)
        / (engine.paged.num_blocks - 1) if in_w else None,
    }
    # the rate of consecutive fifths of the window: a ramp that is too short
    # shows as a drift here
    fifths = [window.commit_rate(loop.commits, t_open + k * seconds / 5,
                                 seconds / 5) for k in range(5)]
    summary = {
        "due": len(due_in), "finished": finished, "failed": failed,
        "refused": loop.refused,
        "queued_at_close": queued, "ttft_p50_s": window.percentile(ttft, 50),
        "ttft_p90_s": values["ttft_p90_s"],
        "tpot_p50_s": window.percentile(tpot, 50),
        "tpot_p90_s": values["tpot_p90_s"], "out_tok_s": values["out_tok_s"],
        "generator_max_late_s": loop.max_late,
        "prompt_tokens_due": sum(r["prompt_len"] for r in due_in),
        "output_tokens_asked": sum(r["output_len"] for r in due_in),
        "stalls": engine.scheduler_stats()["admission_stalls_total"],
        "out_tok_s_by_fifth": [f and round(f, 1) for f in fifths],
        "lost_to_slow_commits": window.lost_time(loop.commits, t_open,
                                                 seconds),
    }
    return {"values": values, "attempted": attempted, "failed": failed,
            "summary": summary, "loop": loop}
