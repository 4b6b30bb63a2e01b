#!/usr/bin/env python3
"""The readings that set the limits of ``qwen3-next-80b-a3b-l8.longgen-
offline``'s check (``check.route_tol`` and ``check.logit_steps`` in
``traffic/longgen-offline.json``).

    python3 benchmarks/gdn_controls.py [--tiny] SEED SOUND_SEEDS VARIANT ...

``VARIANT`` is ``none`` (the program, checked at ``SOUND_SEEDS`` seeds) or
one of the programs broken on purpose by ``tests/test_qwen3_next._break``:
``float8`` (the residual stream read through float8, the precision below
the configuration's), ``"state not carried"``, ``"conv tail dropped"``,
``"w for 1 + w"``, ``"attention gate left out"``. Each is an engine of the
cell's widths, ``max_batch`` and pool, serving the check's prompts beside a
backlog that holds every other slot; ``drivers/gdn._compare`` judges, and a
``CONTROL`` line a variant and seed gives its readings. ``--tiny`` runs the
rehearsal's widths on the CPU.

Not a metric and read by no metric: a tool for the builder of a limit.
"""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.join(os.path.dirname(HERE), "tests")]

import jax


def main():
    import pytest

    from drivers import gdn, latent
    from kubeflow_tpu.obs.trace import SpanCollector
    from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
    from kubeflow_tpu.serving.scheduler import SchedulerConfig
    from lib import model, qwen3_next as lib, traffic
    from test_qwen3_next import _break

    args = sys.argv[1:]
    tiny = args[0] == "--tiny"
    args = args[1:] if tiny else args
    seed, n_sound = int(args[0]), int(args[1])
    cfg = model.load_config("qwen3-next-80b-a3b-l8")
    tr = traffic.load("longgen-offline")
    if tiny:
        cfg, tr = gdn._rehearsal(cfg, tr)
    eng_args, spec = tr["engine"], tr["check"]
    mcfg = lib.model_config(cfg)
    params = lib.serving_params(mcfg, seed)
    vocab = cfg["vocab_size"]
    n_backlog = eng_args["max_batch"] - len(spec["prompt_lens"])
    for variant in args[2:]:
        t0 = time.time()
        mp = pytest.MonkeyPatch()
        _break(mp, None if variant == "none" else variant)
        eng = LLMEngine(
            params, mcfg, max_batch=eng_args["max_batch"],
            max_seq=eng_args["max_seq"],
            prefill_buckets=eng_args["prefill_buckets"],
            kv_block_size=eng_args["kv_block_size"],
            kv_num_blocks=eng_args["kv_num_blocks"],
            obs=SpanCollector(capacity=1 << 16),
            scheduler=SchedulerConfig(radix_cache=False,
                                      **eng_args["scheduler"]))
        # the backlog: every other slot, outliving the checks
        for i in range(n_backlog):
            eng.add_request(
                traffic.token_ids(seed, i, 4 if tiny else 128, vocab),
                SamplingParams(max_tokens=eng_args["max_seq"] // 3,
                               temperature=0.0, eos_id=None))
        seeds = [seed + j for j in range(n_sound if variant == "none" else 1)]
        served = [latent._serve_checked(eng, vocab, spec, s, print)
                  for s in seeds]
        print(f"{variant}: served in {time.time() - t0:.0f} s", flush=True)
        mp.undo()
        for buf in jax.tree.leaves(eng.cache):
            buf.delete()
        del eng
        for s, reqs in zip(seeds, served):
            out = gdn._compare(reqs, params, cfg, spec, print)
            print(f"CONTROL {variant} seed {s}: ok={out['ok']} "
                  f"steps={out['worst_bf16_steps_under_max']:.3f} "
                  f"exact={out['exact_argmax']}/{out['tokens_checked']} "
                  f"route_gap={out['route_gap']:.6f} "
                  f"violations={out['route_violations']} "
                  f"disagreements={out['route_disagreements']} "
                  f"largest={out['route_gaps_largest']} "
                  f"({time.time() - t0:.0f} s)", flush=True)


if __name__ == "__main__":
    main()
