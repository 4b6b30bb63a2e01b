"""Speculative decoding: drafter properties, verify-step correctness, and
the token-identity contract — greedy output through draft+verify must be
EXACTLY what the non-speculative engine produces, across ragged batches,
aborts, prefix-shared streams and chunked long prompts, and every verify
round must commit at least one token (the worst case IS a decode step,
never slower in device steps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
from kubeflow_tpu.serving.scheduler import SchedulerConfig
from kubeflow_tpu.serving.spec_decode import NgramDrafter, make_drafter


@pytest.fixture(scope="module")
def tiny32():
    """f32 end to end: the identity tests compare token streams across
    two different XLA programs (decode scan vs verify), so the fixture
    removes bf16 near-tie noise from what is a control-flow property."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engines(params, cfg, spec_k=3, **kw):
    base = LLMEngine(params, cfg,
                     scheduler=SchedulerConfig(spec_decode=False), **kw)
    spec = LLMEngine(params, cfg,
                     scheduler=SchedulerConfig(spec_decode=True,
                                               spec_k=spec_k), **kw)
    return base, spec


# ---------------------------------------------------------------- drafter


def test_ngram_drafter_most_recent_match():
    d = NgramDrafter(k=3, max_ngram=3, min_ngram=1)
    # trailing [1, 2] occurs twice before the suffix; the MOST RECENT
    # prior occurrence (index 3) supplies the continuation
    assert d.draft([1, 2, 9, 1, 2, 8, 7, 1, 2]) == [8, 7, 1]
    # longest n-gram wins over a shorter, more recent one
    assert d.draft([5, 6, 7, 8, 3, 7, 5, 6, 7]) == [8, 3, 7]


def test_ngram_drafter_bounds_and_no_match():
    d = NgramDrafter(k=2)
    assert d.draft([1, 2, 3, 4]) == []          # nothing repeats
    assert d.draft([7]) == []                    # too short to match
    assert d.draft([]) == []
    out = d.draft([1, 2, 3, 1, 2, 3, 1, 2])      # plenty to continue
    assert 1 <= len(out) <= 2                    # k caps the proposal
    assert out == [3, 1]


def test_drafter_registry():
    assert make_drafter("ngram", 4).k == 4
    assert make_drafter("prompt_lookup", 2).k == 2
    with pytest.raises(ValueError, match="spec_drafter"):
        make_drafter("medusa", 3)
    with pytest.raises(ValueError, match="spec_k"):
        NgramDrafter(k=0)


# ----------------------------------------------------- token identity


def test_spec_greedy_token_identical_ragged(tiny32):
    """Mixed prompt lengths + mixed budgets + slot churn (more requests
    than slots): spec output and logprobs must be the non-speculative
    stream exactly."""
    cfg, params = tiny32
    base, spec = _engines(params, cfg, max_batch=2, max_seq=64,
                          prefill_buckets=(8, 16), decode_chunk=3)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [3] * 12,
               [1, 2, 3, 1, 2, 3, 1, 2], [42, 17]]
    outs = {}
    for eng in (base, spec):
        reqs = [eng.add_request(p, SamplingParams(max_tokens=6 + (i % 3)))
                for i, p in enumerate(prompts)]
        while eng.has_work():
            eng.step()
        assert all(r.done for r in reqs)
        outs[eng] = [(r.generated, r.logprobs) for r in reqs]
    for (gb, lb), (gs, ls) in zip(outs[base], outs[spec]):
        assert gb == gs
        np.testing.assert_allclose(lb, ls, rtol=1e-4, atol=1e-5)
    st = spec.scheduler_stats()
    assert st["spec_dispatches_total"] > 0
    assert st["accepted_tokens_per_step"] >= 1.0


def test_spec_token_identical_prefix_shared_streams(tiny32):
    """The target workload: many streams sharing a system prompt through
    the radix cache, churning through fewer slots."""
    cfg, params = tiny32
    rng = np.random.default_rng(7)
    system = rng.integers(1, cfg.vocab_size, 16).tolist()
    prompts = [system + rng.integers(1, cfg.vocab_size, 6).tolist()
               for _ in range(10)]
    base, spec = _engines(params, cfg, max_batch=4, max_seq=64,
                          prefill_buckets=(24,), kv_block_size=8,
                          decode_chunk=4)
    r0 = base.generate(prompts, SamplingParams(max_tokens=16))
    r1 = spec.generate(prompts, SamplingParams(max_tokens=16))
    assert [r.generated for r in r0] == [r.generated for r in r1]
    assert spec.paged.prefix_hits > 0              # sharing really ran
    st = spec.scheduler_stats()
    assert st["accepted_tokens_per_step"] >= 1.0
    # the whole point: fewer device steps than one-token-per-step decode
    assert st["spec_committed_tokens_total"] >= st["spec_dispatches_total"]


def test_spec_token_identical_chunked_long_prompt(tiny32):
    """A prompt beyond every bucket streams through chunked prefill while
    other streams decode speculatively; mid-prefill table rows must mask
    to scratch in the verify dispatch exactly as they do in decode."""
    cfg, params = tiny32
    long_prompt = [(7 * i) % 250 + 1 for i in range(40)]   # > bucket 16
    short = [5, 6, 7]
    base, spec = _engines(params, cfg, max_batch=2, max_seq=128,
                          prefill_buckets=(16,))
    r0 = base.generate([long_prompt, short], SamplingParams(max_tokens=8))
    r1 = spec.generate([long_prompt, short], SamplingParams(max_tokens=8))
    assert [r.generated for r in r0] == [r.generated for r in r1]


def test_spec_abort_midflight_and_slot_reuse(tiny32):
    """Aborting one stream mid-spec frees its slot; the survivor's output
    is untouched and a late joiner decodes correctly."""
    cfg, params = tiny32
    _, spec = _engines(params, cfg, max_batch=2, max_seq=64,
                       prefill_buckets=(8,))
    a = spec.add_request([5, 6, 7], SamplingParams(max_tokens=1000))
    b = spec.add_request([9, 10, 11], SamplingParams(max_tokens=10))
    for _ in range(2):
        spec.step()
    spec.abort([a])
    late = spec.add_request([3, 1, 2], SamplingParams(max_tokens=6))
    while spec.has_work():
        spec.step()
    assert a.finish_reason == "abort"
    assert sorted(spec._free) == [0, 1]
    ref = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    for req in (b, late):
        (r,) = ref.generate([req.prompt],
                            SamplingParams(max_tokens=req.sampling.max_tokens))
        assert req.generated == r.generated


def test_spec_worst_case_drafter_never_below_decode(tiny32):
    """An adversarial drafter that only ever proposes wrong tokens: every
    verify still commits >= 1 token (the target's own next token), output
    stays token-identical, and accepted_tokens_per_step == 1.0 exactly."""
    cfg, params = tiny32

    class WrongDrafter:
        k = 3

        def draft(self, context):
            # the target model's greedy chain never emits token id 0
            # here (vocab argmax of a random-init tiny model over real
            # contexts): worst-case rejection every round
            return [0, 0, 0]

    base, spec = _engines(params, cfg, max_batch=2, max_seq=64,
                          prefill_buckets=(8,))
    spec.spec = WrongDrafter()
    prompts = [[5, 6, 7], [9, 10]]
    r0 = base.generate(prompts, SamplingParams(max_tokens=8))
    r1 = spec.generate(prompts, SamplingParams(max_tokens=8))
    assert [r.generated for r in r0] == [r.generated for r in r1]
    st = spec.scheduler_stats()
    assert st["spec_slot_rounds_total"] > 0
    # floor property: committed / slot_round can sink to 1.0, never below
    assert st["accepted_tokens_per_step"] >= 1.0


def test_spec_nongreedy_batch_falls_back(tiny32):
    """A non-greedy request in the batch disables speculation for the
    dispatch (acceptance is only exact for greedy) — counted, and with
    top_k=1 the sampled output still equals greedy."""
    cfg, params = tiny32
    _, spec = _engines(params, cfg, max_batch=2, max_seq=64,
                       prefill_buckets=(8,))
    reqs = spec.generate([[5, 6, 7], [9, 10]],
                         SamplingParams(max_tokens=6, temperature=0.7,
                                        top_k=1))
    assert all(r.done and len(r.generated) == 6 for r in reqs)
    st = spec.scheduler_stats()
    assert st["spec_fallbacks_total"] > 0
    assert st["spec_dispatches_total"] == 0
    ref = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    r0 = ref.generate([[5, 6, 7], [9, 10]], SamplingParams(max_tokens=6))
    assert [r.generated for r in r0] == [r.generated for r in reqs]


# ------------------------------------------------------- verify step


def test_verify_step_logits_match_sequential_decode(tiny32):
    """Low-level contract: feeding the greedy chain itself through ONE
    verify dispatch yields the same logits the decode path produces one
    step at a time (same KV writes, same masks)."""
    from kubeflow_tpu.serving import paged_kv

    cfg, params = tiny32
    pk = paged_kv.PagedKV(cfg=cfg, max_batch=2, max_seq=32, block_size=8,
                          num_blocks=9)
    assert pk.reserve(0, 3, 8) is not None
    assert pk.reserve(1, 5, 8) is not None
    tables = jnp.asarray(pk.tables)
    cache_d = jax.tree.map(jnp.copy, pk.cache)
    cache_d["len"] = jnp.asarray([3, 5], jnp.int32)
    cache_v = jax.tree.map(jnp.copy, cache_d)

    # sequential decode: 4 steps, greedy chain
    tok = jnp.asarray([11, 7], jnp.int32)
    chain = [np.asarray(tok)]
    dec_logits = []
    for _ in range(4):
        lg, cache_d, _ = paged_kv.paged_decode_step(
            params, tok, cfg, cache_d, tables, kernel="gather")
        dec_logits.append(np.asarray(lg))
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        chain.append(np.asarray(tok))

    # one verify dispatch over the same 4 input tokens
    tokens = jnp.asarray(np.stack(chain[:4], axis=1))        # [B, 4]
    limit = jnp.asarray([8, 16], jnp.int32)                  # reserved rows
    v_logits, cache_v = paged_kv.paged_verify_step(
        params, tokens, cfg, cache_v, tables, limit)
    v_logits = np.asarray(v_logits)
    for s in range(4):
        np.testing.assert_allclose(v_logits[:, s], dec_logits[s],
                                   rtol=1e-4, atol=1e-4)


def test_verify_step_tail_rows_mask_to_scratch(tiny32):
    """Rows past a slot's reserved tokens must scatter to the scratch
    block, never into live data: slot 1's blocks are fully used, and a
    verify whose tail would run past them leaves them intact."""
    from kubeflow_tpu.serving import paged_kv

    cfg, params = tiny32
    pk = paged_kv.PagedKV(cfg=cfg, max_batch=2, max_seq=16, block_size=8,
                          num_blocks=5)
    assert pk.reserve(0, 6, 1) is not None       # 1 block  = 8 rows
    assert pk.reserve(1, 6, 8) is not None       # 2 blocks = 16 rows
    tables = jnp.asarray(pk.tables)
    cache = jax.tree.map(jnp.copy, pk.cache)
    cache["len"] = jnp.asarray([6, 6], jnp.int32)
    blk1 = pk.slot_blocks(1)
    before = np.asarray(cache["k"][:, blk1])
    # width-4 verify: slot 0 rows 6..9, but its allocation ends at 8 —
    # rows 8,9 must land in scratch block 0
    tokens = jnp.asarray([[3, 4, 5, 6], [7, 8, 9, 10]], jnp.int32)
    limit = jnp.asarray([8, 16], jnp.int32)
    _, cache = paged_kv.paged_verify_step(
        params, tokens, cfg, cache, tables, limit)
    after_own = np.asarray(cache["k"][:, blk1])
    # slot 1's rows 6..9 are within ITS allocation and were written;
    # nothing of slot 0's overflow touched slot 1's blocks (rows 10..15
    # of slot 1 unchanged, rows 0..5 unchanged)
    np.testing.assert_array_equal(after_own[:, 1, 2:], before[:, 1, 2:])
    np.testing.assert_array_equal(after_own[:, 0, :6], before[:, 0, :6])


# ------------------------------------------------------- plumbing


def test_spec_env_plumbing():
    from kubeflow_tpu.serving.runtime import scheduler_from_env

    sc = scheduler_from_env({"KFT_SPEC_DECODE": "1", "KFT_SPEC_K": "7",
                             "KFT_SPEC_DRAFTER": "ngram"})
    assert sc.spec_decode and sc.spec_k == 7 and sc.spec_drafter == "ngram"
    sc = scheduler_from_env({"KFT_RADIX_CACHE": "1"})
    assert sc is not None and not sc.spec_decode and sc.spec_k == 3
    assert scheduler_from_env({}) is None


def test_spec_policy_stamps_predictor_env():
    """PredictorSpec.scheduler -> ISVC controller env stamps -> the same
    SchedulerConfig back out of scheduler_from_env (the PR 6 contract,
    extended with the spec knobs)."""
    import dataclasses

    from kubeflow_tpu.serving.runtime import scheduler_from_env
    from kubeflow_tpu.serving.types import SchedulerPolicy

    pol = SchedulerPolicy(prefill_tokens_per_step=64, spec_decode=True,
                          spec_k=5)
    env = {
        "KFT_PREFILL_QUOTA": str(pol.prefill_tokens_per_step),
        "KFT_INTERLEAVE_PREFILL": "1" if pol.interleave_prefill else "0",
        "KFT_ADAPTIVE_DECODE_CHUNK":
            "1" if pol.adaptive_decode_chunk else "0",
        "KFT_RADIX_CACHE": "1" if pol.radix_cache else "0",
        "KFT_SPEC_DECODE": "1" if pol.spec_decode else "0",
        "KFT_SPEC_K": str(pol.spec_k),
        "KFT_SPEC_DRAFTER": pol.spec_drafter,
    }
    assert scheduler_from_env(env) == pol
    # and the controller really stamps exactly these keys
    import inspect

    from kubeflow_tpu.serving import controller as isvc_controller

    src = inspect.getsource(isvc_controller)
    for key in env:
        assert key in src, f"ISVC controller does not stamp {key}"
    assert dataclasses.fields(SchedulerPolicy)  # stays a dataclass


def test_spec_counters_ride_model_stats(tiny32):
    """The /metrics surface: scheduler_stats carries the spec counter
    family, and LLMModel.stats exposes kernel_downgrades_total."""
    from kubeflow_tpu.serving.jax_model import LLMModel

    cfg, params = tiny32
    model = LLMModel("m", params, cfg, max_batch=2, max_seq=64,
                     prefill_buckets=(8,),
                     scheduler=SchedulerConfig(spec_decode=True))
    model.load()
    try:
        stats = model.stats()
        assert stats["kernel_downgrades_total"] == 0
        for key in ("spec_dispatches_total", "spec_committed_tokens_total",
                    "spec_fallbacks_total", "accepted_tokens_per_step"):
            assert key in stats["sched"]
    finally:
        model.unload()
