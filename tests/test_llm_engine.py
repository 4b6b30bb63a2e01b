"""Continuous-batching LLM engine tests: exactness vs the full forward pass,
request churn, sampling controls, and the HTTP generate endpoint."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.serving import (
    InferenceClient, LLMEngine, LLMModel, ModelRepository, ModelServer,
    SamplingParams,
)
from kubeflow_tpu.serving.llm import sample_logits


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def ref_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def assert_greedy_consistent(params, cfg, prompt, generated):
    """Teacher-forced check tolerant of EXACT logit ties (bf16 activations
    quantize; batched vs single decode may break a tie differently): every
    generated token must be a maximizer of the reference logits."""
    toks = list(prompt)
    for g in generated:
        logits = llama.forward(params, jnp.asarray([toks]), cfg)[0, -1]
        assert float(logits[g]) >= float(jnp.max(logits)) - 1e-6, \
            (toks, g, int(jnp.argmax(logits)))
        toks.append(g)


def test_engine_matches_full_forward(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=4, max_seq=64,
                    prefill_buckets=(8, 16))
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [3] * 12]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for r in reqs:
        assert r.generated == ref_greedy(params, cfg, r.prompt, 6)


def test_paged_kv_more_concurrency_per_byte(tiny):
    """The paged-KV property: a pool of 16 usable blocks x 8 tokens = 128
    resident tokens. A dense [max_batch, max_seq=64] arena of equal bytes
    holds exactly TWO slots; the paged engine runs SIX short requests
    concurrently inside the same budget — and still decodes exactly."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=8, max_seq=64,
                    prefill_buckets=(8,),
                    kv_block_size=8, kv_num_blocks=17)   # 16 usable + scratch
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    # 3 + 12 = 15 tokens -> 2 blocks each; max_tokens > decode_chunk so the
    # requests are still mid-flight after one chunked step
    reqs = [eng.add_request(p, SamplingParams(max_tokens=12))
            for p in prompts]
    eng.step()
    assert len(eng._active) == 6          # all resident at once: 12 blocks
    while eng.has_work():
        eng.step()
    for r in reqs:
        assert len(r.generated) == 12
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)


def test_paged_kv_pool_exhaustion_queues_fifo(tiny):
    """When the block pool is exhausted, admission stops at the queue head
    (FIFO under memory pressure) and the waiter runs once blocks free up."""
    cfg, params = tiny
    # 8 usable blocks x 8 tokens; each request reserves 4 blocks (2 prompt
    # tokens + 30 max_tokens = 32 tokens) -> exactly two fit
    eng = LLMEngine(params, cfg, max_batch=8, max_seq=64,
                    prefill_buckets=(8,),
                    kv_block_size=8, kv_num_blocks=9)
    reqs = [eng.add_request([i + 1, i + 2], SamplingParams(max_tokens=30))
            for i in range(3)]
    eng.step()
    assert len(eng._active) == 2 and not reqs[2].done
    assert eng.paged.allocator.free_blocks == 0
    while eng.has_work():
        eng.step()
    assert all(r.done for r in reqs)
    assert len(reqs[2].generated) == 30
    assert_greedy_consistent(params, cfg, reqs[2].prompt, reqs[2].generated)
    assert eng.paged.allocator.free_blocks == 8


def test_paged_kv_impossible_reservation_fails_fast(tiny):
    """A request whose block reservation can NEVER succeed must raise at
    add_request, not spin generate()'s drain loop forever."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=8, max_seq=64,
                    prefill_buckets=(8,),
                    kv_block_size=8, kv_num_blocks=4)     # 3 usable blocks
    with pytest.raises(ValueError, match="KV blocks"):
        eng.add_request([1, 2], SamplingParams(max_tokens=40))
    # a fitting request still serves normally
    r = eng.generate([[1, 2]], SamplingParams(max_tokens=4))[0]
    assert len(r.generated) == 4


def test_prefix_cache_shares_blocks_and_stays_exact(tiny):
    """vLLM-APC role: two requests with the same 16-token (2-block) prefix
    share those blocks — fewer pool blocks in flight — and decode output is
    unchanged versus an engine with the cache disabled."""
    cfg, params = tiny
    common = list(range(10, 26))                  # 16 tokens = 2 full blocks
    prompts = [common + [30], common + [40]]

    def run(prefix_cache):
        eng = LLMEngine(params, cfg, max_batch=4, max_seq=64,
                        prefill_buckets=(32,),
                        kv_block_size=8, kv_num_blocks=33)
        eng.paged.prefix_cache = prefix_cache
        reqs = [eng.add_request(p, SamplingParams(max_tokens=10))
                for p in prompts]
        eng.step()                                # admit both
        in_flight = eng.paged.allocator.free_blocks
        while eng.has_work():
            eng.step()
        return eng, reqs, in_flight

    eng_on, reqs_on, free_on = run(True)
    eng_off, reqs_off, free_off = run(False)
    # sharing leaves more of the pool free while both are resident
    assert free_on > free_off
    assert eng_on.paged.prefix_hits == 2          # request 2 reused 2 blocks
    for a, b in zip(reqs_on, reqs_off):
        assert a.generated == b.generated
        assert_greedy_consistent(params, cfg, a.prompt, a.generated)


def test_prefix_cache_eviction_reclaims_idle_blocks(tiny):
    """Cached blocks of finished requests are evictable: a workload that
    needs the whole pool still runs after the cache has filled."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(16,),
                    kv_block_size=8, kv_num_blocks=9)    # 8 usable
    # distinct 2-full-block prompts, run sequentially: each leaves 2 cached
    # blocks behind; the third+ need eviction to fit
    for i in range(4):
        p = [100 + 16 * i + j for j in range(16)]
        r = eng.generate([p], SamplingParams(max_tokens=4))[0]
        assert len(r.generated) == 4
    # everything is reclaimable once idle (free list + idle cached blocks)
    assert eng.paged.reclaimable_blocks == 8


def _paged(tiny_cfg, num_blocks, bs=8, max_seq=64):
    from kubeflow_tpu.serving.paged_kv import PagedKV

    return PagedKV(cfg=tiny_cfg, max_batch=4, max_seq=max_seq,
                   block_size=bs, num_blocks=num_blocks)


def test_prefix_cache_never_evicts_in_flight_shared_blocks(tiny):
    """Review repro: a reservation whose shared prefix blocks are the only
    eviction candidates must FAIL (pool too small), never evict-and-reuse
    a block it itself shares (which duplicated the block in the table)."""
    cfg, _ = tiny
    kv = _paged(cfg, num_blocks=6)               # 5 usable
    prompt = list(range(16))                      # 2 full blocks
    assert kv.reserve(0, 16, 8, prompt=prompt) == 0      # blocks for A
    kv.release(0)                                 # 2 cached idle
    # B shares 2 and needs 4 more distinct = 6 > 5 usable: must refuse
    out = kv.reserve(1, 16, 32, prompt=prompt)
    assert out is None
    assert kv.slot_blocks(1) == []
    # and the rollback left the cached blocks reusable
    assert kv.reserve(2, 16, 8, prompt=prompt) == 2      # now shares fine
    ids = kv.slot_blocks(2)
    assert len(ids) == len(set(ids))              # no duplicates, ever


def test_doomed_reservation_does_not_flush_cache(tiny):
    """A reservation that can NEVER fit (free + idle-cached < need) must
    refuse without evicting — a head-of-line retry every step would
    otherwise flush everyone's prefix cache for nothing."""
    cfg, _ = tiny
    kv = _paged(cfg, num_blocks=5)               # 4 usable
    assert kv.reserve(0, 16, 8, prompt=list(range(16))) == 0  # 3 blocks
    kv.release(0)                                 # 2 cached idle, 3 free...
    cached_before = kv.cached_block_ids()
    # needs 8 > 4 usable: doomed — capped at max_blocks_per_seq 8
    assert kv.reserve(1, 40, 24, prompt=list(range(200, 240))) is None
    assert kv.cached_block_ids() == cached_before   # cache untouched
    assert kv.radix.evictions == 0


def test_prefix_cache_partial_eviction_leaks_no_blocks(tiny):
    """Review repro: evicting only the head of a hash chain, then
    re-registering the same chain, must not orphan the surviving tail
    block (unreachable by both release() and the eviction loop)."""
    cfg, _ = tiny
    usable = 3
    kv = _paged(cfg, num_blocks=usable + 1)
    prompt_a = list(range(16))                    # chain h1,h2
    assert kv.reserve(0, 16, 8, prompt=prompt_a) == 0
    kv.release(0)                                 # h1,h2 cached idle
    # unrelated request forces eviction of exactly the LRU head (h1)
    assert kv.reserve(1, 8, 8, prompt=list(range(50, 58))) is not None
    kv.release(1)
    # same chain again: h1 misses, h2's stale mapping must be unlinked
    assert kv.reserve(2, 16, 8, prompt=prompt_a) is not None
    kv.release(2)
    # nothing leaked: every usable block is reclaimable and a full-pool
    # reservation still succeeds
    assert kv.reclaimable_blocks == usable
    assert kv.reserve(3, 8, 16, prompt=list(range(80, 88))) is not None
    assert len(set(kv.slot_blocks(3))) == len(kv.slot_blocks(3))


def test_engine_request_churn(tiny):
    """More requests than slots: slots must be recycled between steps."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=48,
                    prefill_buckets=(8,))
    prompts = [[i + 1, i + 2] for i in range(5)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=4))
    assert all(r.done and len(r.generated) == 4 for r in reqs)
    for r in reqs:
        assert r.generated == ref_greedy(params, cfg, r.prompt, 4)


def test_engine_join_mid_decode(tiny):
    """A request added while another decodes joins the same batch."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=4, max_seq=64,
                    prefill_buckets=(8,))
    first = eng.add_request([5, 6, 7], SamplingParams(max_tokens=10))
    for _ in range(3):
        eng.step()
    second = eng.add_request([9, 10], SamplingParams(max_tokens=4))
    while eng.has_work():
        eng.step()
    assert first.generated == ref_greedy(params, cfg, [5, 6, 7], 10)
    assert second.generated == ref_greedy(params, cfg, [9, 10], 4)


def test_engine_eos_stops(tiny):
    cfg, params = tiny
    prompt = [9, 10, 11, 12, 13]
    ref = ref_greedy(params, cfg, prompt, 3)
    eos = ref[2]
    assume_first_hit = ref.index(eos) + 1   # engine stops at FIRST eos
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    [r] = eng.generate([prompt], SamplingParams(max_tokens=50, eos_id=eos))
    assert r.generated[-1] == eos
    assert len(r.generated) == assume_first_hit
    assert r.finish_reason == "stop"


def test_sample_logits_controls():
    logits = jnp.asarray([[1.0, 2.0, 5.0, 0.5]] * 2)
    rng = jax.random.key(0)
    greedy = sample_logits(logits, rng, jnp.zeros(2), jnp.zeros(2, jnp.int32),
                           jnp.ones(2))
    assert greedy.tolist() == [2, 2]
    # top_k=1 forces the argmax even at high temperature
    forced = sample_logits(logits, rng, jnp.full((2,), 10.0),
                           jnp.ones(2, jnp.int32), jnp.ones(2))
    assert forced.tolist() == [2, 2]
    # tight top_p keeps only the head of the distribution
    nucleus = sample_logits(logits, rng, jnp.ones(2),
                            jnp.zeros(2, jnp.int32), jnp.full((2,), 0.5))
    assert all(t == 2 for t in nucleus.tolist())


def test_llm_streaming_generation(tiny):
    """SSE streaming parity: chunked token events over HTTP accumulate to
    exactly the non-streaming greedy output of the SAME engine, then a
    done record. The reference comparison is tie-tolerant
    (assert_greedy_consistent): bf16 logits tie exactly and the decode
    program's values can drift an ulp from the eager full-forward's, so
    exact-list equality against ref_greedy was a permanent flake — the
    sampler breaks true ties deterministically (lowest index,
    llm.greedy_argmax), but no sampler can make two different XLA
    programs produce the same near-tie."""
    cfg, params = tiny
    model = LLMModel("stream", params, cfg, max_batch=2, max_seq=64,
                     prefill_buckets=(8,))
    repo = ModelRepository()
    repo.register(model)
    srv = ModelServer(repo).start()
    try:
        cli = InferenceClient(srv.url)
        prompt = [5, 6, 7]
        events = list(cli.generate_stream("stream", prompt, max_tokens=20))
        assert events[-1]["done"] and events[-1]["length"] == 20
        token_events = [e for e in events if "tokens" in e]
        assert len(token_events) >= 2          # chunked, not one blob
        streamed = [t for e in token_events for t in e["tokens"]]
        # every streamed token is a maximizer of the reference logits
        assert_greedy_consistent(params, cfg, prompt, streamed)
        # and the stream IS the non-streaming output, token for token
        # (same engine, same decode program: exact, no tolerance)
        from kubeflow_tpu.serving import InferRequest, InferTensor

        req = InferRequest(
            model_name="stream",
            inputs=[InferTensor.from_numpy(
                "ids", np.array([prompt], np.int32))],
            parameters={"max_tokens": 20})
        predicted = cli.infer(req).as_numpy("tokens")[0].tolist()
        assert streamed == predicted

        # non-generative models reject the route cleanly
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            srv.url + "/v1/models/nope:generate_stream", data=b"{}",
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=5)
        except urllib.error.HTTPError as e:
            assert e.code == 404
        else:
            raise AssertionError("expected 404")

        # invalid request (prompt beyond the largest bucket) must be a
        # REAL 400 — generate_stream validates eagerly, before the
        # transport commits to 200 + a broken stream
        req = urllib.request.Request(
            srv.url + "/v1/models/stream:generate_stream",
            data=json.dumps({"inputs": list(range(500))}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=5)
        except urllib.error.HTTPError as e:
            assert e.code == 400
        else:
            raise AssertionError("expected 400")
    finally:
        srv.stop()


def test_stream_abort_frees_slot(tiny):
    """Closing the stream mid-generation aborts the request: the engine
    drains instead of decoding to max_tokens with no consumer."""
    cfg, params = tiny
    model = LLMModel("s2", params, cfg, max_batch=1, max_seq=64,
                     prefill_buckets=(8,))
    model.load()
    try:
        gen = model.generate_stream([5, 6, 7], {"max_tokens": 1000000000})
        first = next(gen)
        assert first["tokens"]
        gen.close()                        # client disconnect
        deadline = __import__("time").time() + 20
        while model.engine.has_work() and __import__("time").time() < deadline:
            __import__("time").sleep(0.05)
        assert not model.engine.has_work()
        assert model.engine._free == [0]   # slot back in the pool
    finally:
        model.unload()


def test_logprobs_match_teacher_forced_reference(tiny):
    """Every generated token carries its logprob under the MODEL
    distribution (OpenAI convention) — consistent with a teacher-forced
    full-forward log_softmax, across the prefill-sampled first token and
    chunked decode."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    prompt = [5, 6, 7]
    r = eng.generate([prompt], SamplingParams(max_tokens=6))[0]
    assert len(r.logprobs) == len(r.generated) == 6
    toks = list(prompt)
    for g, lp in zip(r.generated, r.logprobs):
        logits = llama.forward(params, jnp.asarray([toks]), cfg)[0, -1]
        assert abs(float(jax.nn.log_softmax(logits)[g]) - lp) < 2e-2
        assert lp <= 0.0
        toks.append(g)


def test_logprobs_surface_in_predict_and_stream(tiny):
    cfg, params = tiny
    model = LLMModel("lp", params, cfg, max_batch=2, max_seq=64,
                     prefill_buckets=(8,))
    model.load()
    try:
        from kubeflow_tpu.serving.protocol import InferRequest

        req = InferRequest.from_v1("lp", {
            "instances": [[5, 6, 7]],
            "parameters": {"max_tokens": 5, "logprobs": True}})
        out = model(req)
        lp = out.as_numpy("logprobs")
        toks = out.as_numpy("tokens")
        assert lp.shape == toks.shape and (lp <= 0.0).all()

        events = list(model.generate_stream(
            [5, 6, 7], {"max_tokens": 5, "logprobs": True}))
        streamed = [x for e in events if "tokens" in e
                    for x in e.get("logprobs", [])]
        assert len(streamed) == 5
        np.testing.assert_allclose(streamed, lp[0, :5], rtol=1e-5)
    finally:
        model.unload()


def test_stop_token_ids_end_generation(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    prompt = [5, 6, 7]
    ref = ref_greedy(params, cfg, prompt, 8)
    # stop fires at the FIRST occurrence: pick a token not seen before its
    # index (greedy decode loves repeating, e.g. [58, 123, 100, 100, ...])
    k = next(i for i, t in enumerate(ref) if t not in ref[:i] and i > 0)
    r = eng.generate([prompt], SamplingParams(
        max_tokens=50, stop_token_ids=(ref[k],)))[0]
    assert r.generated == ref[:k + 1]
    assert r.finish_reason == "stop"


def test_llm_http_generate(tiny):
    cfg, params = tiny
    model = LLMModel("llm", params, cfg, max_batch=2, max_seq=48,
                     prefill_buckets=(8,))
    repo = ModelRepository()
    repo.register(model)
    srv = ModelServer(repo).start()
    try:
        client = InferenceClient(srv.url)
        from kubeflow_tpu.serving import InferRequest, InferTensor
        req = InferRequest(
            model_name="llm",
            inputs=[InferTensor.from_numpy(
                "ids", np.array([[5, 6, 7], [9, 10, 0]], np.int32))],
            parameters={"max_tokens": 4})
        resp = client.infer(req)
        toks = resp.as_numpy("tokens")
        lens = resp.as_numpy("lengths")
        assert toks.shape == (2, 4) and lens.tolist() == [4, 4]
        assert toks[0].tolist() == ref_greedy(params, cfg, [5, 6, 7], 4)
        assert toks[1].tolist() == ref_greedy(params, cfg, [9, 10], 4)
    finally:
        srv.stop()
        model.unload()


def test_llm_concurrent_requests_batch(tiny):
    """Two threads submitting concurrently must both complete (and share the
    engine's decode loop)."""
    cfg, params = tiny
    model = LLMModel("llm", params, cfg, max_batch=4, max_seq=48,
                     prefill_buckets=(8,))
    model.load()
    from kubeflow_tpu.serving import InferRequest, InferTensor
    results = {}

    def run(tag, prompt):
        req = InferRequest(
            model_name="llm",
            inputs=[InferTensor.from_numpy(
                "ids", np.array([prompt], np.int32))],
            parameters={"max_tokens": 5})
        results[tag] = model(req).as_numpy("tokens")[0].tolist()

    t1 = threading.Thread(target=run, args=("a", [5, 6, 7]))
    t2 = threading.Thread(target=run, args=("b", [9, 10, 11]))
    t1.start(); t2.start(); t1.join(30); t2.join(30)
    model.unload()
    assert results["a"] == ref_greedy(params, cfg, [5, 6, 7], 5)
    assert results["b"] == ref_greedy(params, cfg, [9, 10, 11], 5)


def test_topp_applied_after_topk():
    """ADVICE r1(a) regression: the nucleus cutoff must be computed on the
    top-k-masked, renormalized distribution (vLLM/HF semantics). With probs
    [0.4, 0.3, 0.2, 0.1], top_k=2 renormalizes to [0.571, 0.429]; top_p=0.5
    then keeps ONLY the argmax. The pre-fix code computed the cutoff from
    the unmasked distribution (cum [0.4, 0.7, ...]) and kept two tokens."""
    probs = jnp.asarray([[0.4, 0.3, 0.2, 0.1]])
    logits = jnp.log(probs)
    for seed in range(64):
        tok = sample_logits(
            logits, jax.random.key(seed), jnp.ones(1),
            jnp.full((1,), 2, jnp.int32), jnp.full((1,), 0.5))
        assert int(tok[0]) == 0


def test_abort_frees_slots(tiny):
    """ADVICE r1(c) regression: aborting an in-flight request releases its
    decode slot so later requests are not starved."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=1, max_seq=64,
                    prefill_buckets=(8,))
    a = eng.add_request([5, 6, 7], SamplingParams(max_tokens=1000))
    eng.step()
    assert not eng._free                      # slot occupied by a
    eng.abort([a])
    assert a.done and a.finish_reason == "abort"
    b = eng.add_request([9, 10], SamplingParams(max_tokens=4))
    while eng.has_work():
        eng.step()
    assert b.done and len(b.generated) == 4
    assert len(eng._free) == 1                # slot came back


def test_llm_model_timeout_aborts(tiny):
    """A predict() timeout must not leave orphaned requests in the engine."""
    cfg, params = tiny
    model = LLMModel("llm", params, cfg, max_batch=1, max_seq=64,
                     prefill_buckets=(8,), request_timeout=0.0)
    model.load()
    try:
        from kubeflow_tpu.serving import InferRequest, InferTensor

        req = InferRequest("llm", inputs=[InferTensor(
            "input-0", [3], "INT32", [5, 6, 7])],
            parameters={"max_tokens": 500})
        with pytest.raises(TimeoutError):
            model.predict(req)
        # engine drains (aborted request retired), slot available again
        import time as _t
        t0 = _t.time()
        while model.engine.has_work() and _t.time() - t0 < 10:
            _t.sleep(0.05)
        assert not model.engine.has_work()
        model.request_timeout = 60.0
        req2 = InferRequest("llm", inputs=[InferTensor(
            "input-0", [2], "INT32", [9, 10])],
            parameters={"max_tokens": 3})
        out = model.predict(req2).as_numpy("tokens")
        assert out.shape == (1, 3)
    finally:
        model.unload()


def test_tensor_parallel_engine_matches_reference(tiny):
    """TP-sharded serving: params sharded by the logical-axis rules over a
    `tensor` axis, KV pool sharded on the kv-head dim — XLA auto-partitions
    the same jitted prefill/decode programs (SPMD over the mesh) and the
    outputs must stay greedy-consistent with the unsharded reference."""
    from kubeflow_tpu.parallel import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import tree_shardings

    cfg, params = tiny
    mesh = build_mesh(MeshConfig(tensor=2))
    shardings = tree_shardings(mesh, llama.param_logical_axes(cfg))
    tp_params = jax.device_put(params, shardings)
    eng = LLMEngine(tp_params, cfg, max_batch=4, max_seq=64,
                    prefill_buckets=(8, 16), mesh=mesh)
    # the KV pool really is distributed over the tensor axis
    assert len(eng.cache["k"].sharding.device_set) == 8
    spec = eng.cache["k"].sharding.spec
    assert spec[3] == "tensor"
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [3] * 12]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for r in reqs:
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)


def test_tensor_parallel_engine_rejects_indivisible_heads(tiny):
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    cfg, params = tiny   # n_kv_heads=2
    mesh = build_mesh(MeshConfig(tensor=4))
    with pytest.raises(ValueError, match="n_kv_heads"):
        LLMEngine(params, cfg, max_batch=2, max_seq=64,
                  prefill_buckets=(8,), mesh=mesh)


def test_chunked_prefill_long_prompt_matches_reference(tiny):
    """Prompts longer than every prefill bucket stream through paged
    chunked prefill (no dense scratch) and must stay greedy-exact."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=128,
                    prefill_buckets=(16,))
    long_prompt = [(7 * i) % 250 + 1 for i in range(50)]   # 50 > bucket 16
    short = [5, 6, 7]
    reqs = eng.generate([long_prompt, short], SamplingParams(max_tokens=6))
    # tie-tolerant: bf16 logits tie exactly and jit fusion may break the
    # tie differently than the eager reference (see assert_greedy_consistent)
    assert_greedy_consistent(params, cfg, long_prompt, reqs[0].generated)
    assert_greedy_consistent(params, cfg, short, reqs[1].generated)
    # non-chunk-multiple and exactly-chunk-multiple lengths
    for n in (16, 17, 32, 33):
        p = [(3 * i) % 250 + 1 for i in range(n)]
        (r,) = eng.generate([p], SamplingParams(max_tokens=4))
        assert_greedy_consistent(params, cfg, p, r.generated)


def test_chunked_prefill_releases_pool(tiny):
    """Chunked requests release every reserved block on completion."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=128,
                    prefill_buckets=(16,))
    free0 = eng.paged.reclaimable_blocks
    eng.generate([[(11 * i) % 250 + 1 for i in range(40)]],
                 SamplingParams(max_tokens=4))
    free1 = eng.paged.reclaimable_blocks
    assert free0 == free1


def test_burst_admission_batches_prefill(tiny):
    """A burst of same-bucket requests pays ONE prefill dispatch, not one
    per request."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=4, max_seq=64,
                    prefill_buckets=(16,))
    prompts = [[3 + i, 5, 7] for i in range(4)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=4))
    assert eng.prefill_dispatches == 1
    for r in reqs:
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)
    # mixed buckets split into one dispatch per bucket, FIFO order kept
    eng2 = LLMEngine(params, cfg, max_batch=4, max_seq=64,
                     prefill_buckets=(8, 16))
    mixed = [[1, 2], [4] * 12, [3, 9], [5] * 12]
    reqs = eng2.generate(mixed, SamplingParams(max_tokens=3))
    # FIFO prefix batching never reorders: alternating buckets means one
    # dispatch each
    assert eng2.prefill_dispatches == 4
    for r in reqs:
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)


def test_pipelined_decode_matches_synchronous(tiny):
    """Double-buffered decode (dispatch chunk N+1 before reading chunk N)
    must be invisible to outputs: greedy streams and their logprobs
    identical to synchronous mode, including slot reuse across
    retire/admit churn and a request joining mid-flight (its first token
    written into the device token carry and read back after the decode
    launch that takes it: every admission, pipelined; none,
    synchronous)."""
    cfg, params = tiny
    outs = {}
    for pipeline in (False, True):
        eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                        prefill_buckets=(8,), decode_chunk=3,
                        decode_pipeline=pipeline)
        # more requests than slots with uneven budgets: slots retire and
        # get reused while chunks are in flight
        reqs = [eng.add_request([3 + i, 4 + i],
                                SamplingParams(max_tokens=5 + (i % 3)))
                for i in range(4)]
        for _ in range(2):
            eng.step()
        late = eng.add_request([40, 41, 42], SamplingParams(max_tokens=6))
        while eng.has_work():
            eng.step()
        outs[pipeline] = [(r.generated, r.logprobs) for r in reqs + [late]]
        assert all(r.done for r in reqs + [late])
        assert eng.sched.first_on_device == (5 if pipeline else 0)
        for r in reqs + [late]:
            assert_greedy_consistent(params, cfg, r.prompt, r.generated)
    # bf16 ties could in principle differ across batch layouts, but the
    # two modes see identical batch compositions step-for-step here
    assert outs[True] == outs[False]


def test_engine_kernel_pallas_end_to_end(tiny):
    """The block-resident Pallas decode kernel (the TPU default), selected
    explicitly on CPU (interpret mode): the engine must run end-to-end
    through churn/retirement with sampling behavior and slot bookkeeping
    identical to the gather oracle."""
    cfg, params = tiny
    outs = {}
    for kern in ("gather", "pallas"):
        eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                        prefill_buckets=(8,), decode_chunk=3, kernel=kern)
        assert eng.kernel == kern
        # more requests than slots + uneven budgets: retirement mid-chunk,
        # slot reuse, and a mid-flight join all run on the kernel path
        reqs = [eng.add_request([3 + i, 4 + i],
                                SamplingParams(max_tokens=5 + (i % 2)))
                for i in range(3)]
        for _ in range(2):
            eng.step()
        late = eng.add_request([9, 10, 11], SamplingParams(max_tokens=4))
        while eng.has_work():
            eng.step()
        assert all(r.done for r in reqs + [late])
        assert sorted(eng._free) == [0, 1]         # every slot came back
        for r in reqs + [late]:
            assert len(r.generated) == r.sampling.max_tokens
            assert r.finish_reason == "length"
            assert_greedy_consistent(params, cfg, r.prompt, r.generated)
        outs[kern] = [r.generated for r in reqs + [late]]
    # both paths see identical batch compositions step-for-step; the
    # kernel must not change a single sampled token
    assert outs["pallas"] == outs["gather"]


def test_engine_kernel_auto_and_mesh_resolution(tiny):
    """kernel="auto" resolves to gather off-TPU (a PLATFORM rule, not a
    downgrade); an explicit "pallas" under a mesh is now a REAL path —
    the shard_map'd kernel — instead of the pre-ISSUE-11 error."""
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,))
    assert eng.kernel == "gather"          # auto on CPU
    assert eng.kernel_downgrades == 0
    mesh = build_mesh(MeshConfig(tensor=2))
    eng_tp = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                       prefill_buckets=(8,), mesh=mesh)
    assert eng_tp.kernel == "gather"       # auto on CPU, mesh or not
    assert eng_tp.kernel_downgrades == 0
    eng_pl = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                       prefill_buckets=(8,), mesh=mesh, kernel="pallas")
    assert eng_pl.kernel == "pallas"       # shard_map'd, no error
    assert eng_pl.kernel_downgrades == 0
    with pytest.raises(ValueError, match="kernel"):
        LLMEngine(params, cfg, max_batch=2, max_seq=64,
                  prefill_buckets=(8,), kernel="bogus")


def test_engine_counts_and_logs_kernel_downgrade(tiny, monkeypatch):
    """A resolution that downgrades (gpu platform / unshardable mesh)
    must COUNT (kft_model_kernel_downgrades_total rides stats()) and log
    once — never silently lose the fast path."""
    from kubeflow_tpu.serving import llm as llm_mod
    from kubeflow_tpu.serving import paged_kv as pk_mod

    cfg, params = tiny
    monkeypatch.setattr(
        pk_mod, "resolve_decode_kernel",
        lambda *a, **k: ("gather", "test topology: no mosaic path"))
    llm_mod._downgrades_logged.discard("test topology: no mosaic path")
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,), kernel="pallas")
    assert eng.kernel == "gather"
    assert eng.kernel_downgrades == 1
    assert "test topology: no mosaic path" in llm_mod._downgrades_logged
    # the engine still serves on the oracle path
    [r] = eng.generate([[5, 6, 7]], SamplingParams(max_tokens=3))
    assert len(r.generated) == 3


def test_tensor_parallel_engine_pallas_kernel_matches_gather(tiny):
    """The tentpole, engine-level: a TP-sharded engine on the shard_map'd
    pallas kernel produces the same greedy streams as the TP gather
    oracle engine, through churn and mid-flight joins."""
    from kubeflow_tpu.parallel import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import tree_shardings

    cfg, params = tiny
    mesh = build_mesh(MeshConfig(tensor=2))
    shardings = tree_shardings(mesh, llama.param_logical_axes(cfg))
    tp_params = jax.device_put(params, shardings)
    outs = {}
    for kern in ("gather", "pallas"):
        eng = LLMEngine(tp_params, cfg, max_batch=2, max_seq=64,
                        prefill_buckets=(8,), decode_chunk=3, mesh=mesh,
                        kernel=kern)
        assert eng.kernel == kern
        reqs = [eng.add_request([3 + i, 4 + i],
                                SamplingParams(max_tokens=5 + (i % 2)))
                for i in range(3)]
        for _ in range(2):
            eng.step()
        late = eng.add_request([9, 10, 11], SamplingParams(max_tokens=4))
        while eng.has_work():
            eng.step()
        assert all(r.done for r in reqs + [late])
        for r in reqs + [late]:
            assert_greedy_consistent(params, cfg, r.prompt, r.generated)
        outs[kern] = [r.generated for r in reqs + [late]]
    assert outs["pallas"] == outs["gather"]


def test_sampled_decode_variant_compiles_and_runs(tiny):
    """temperature>0 exercises the NON-greedy decode program (the full
    top-k/top-p sort inside the scan) — the greedy_only static fast path
    must not be the only variant the suite ever compiles. top_k=1 makes
    sampling deterministic (argmax survives the filter alone)."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,), decode_chunk=3)
    reqs = eng.generate(
        [[5, 6, 7], [9, 10]],
        SamplingParams(max_tokens=6, temperature=0.7, top_k=1))
    assert all(r.done and len(r.generated) == 6 for r in reqs)
    # top_k=1 keeps only the argmax: identical to greedy token-for-token
    for r in reqs:
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)


# ------------------------------------------- the first token on the device --


def _first_token_case(tiny, case):
    """(cfg, params, engine kwargs, [(prompt, max_tokens)], late request,
    whether the model routes experts) for one pipelined-vs-synchronous
    comparison."""
    from kubeflow_tpu.models import cca_moe
    from kubeflow_tpu.models import qwen3_next as q3

    if case == "chunked":               # three chunks of 16 beside decode
        cfg, params = tiny
        kw = dict(prefill_buckets=(16,), decode_chunk=3)
        reqs = [([3 + i, 4 + i], 5 + (i % 3)) for i in range(4)]
        late = ([(7 * i) % 250 + 1 for i in range(40)], 6)
        return cfg, params, kw, reqs, late, False
    if case == "qwen3_next":
        cfg = q3.qwen3_next_tiny(dtype=jnp.float32)
        params = q3.init_params(jax.random.key(3), cfg)
    else:
        cfg = cca_moe.cca_moe_tiny(dtype=jnp.float32)
        params = cca_moe.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, cfg.vocab_size, n).tolist(), m)
            for n, m in ((5, 6), (20, 4), (3, 7))]
    late = (rng.integers(1, cfg.vocab_size, 17).tolist(), 5)
    kw = dict(prefill_buckets=(16,), kv_block_size=8, decode_chunk=4)
    return cfg, params, kw, reqs, late, True


@pytest.mark.parametrize("case", ["chunked", "qwen3_next", "cca_moe"])
def test_first_token_on_device_matches_synchronous(tiny, case):
    """Pipelined, an admission's first token goes to the decode launch on
    the device and is read back after it; synchronous, it is read before.
    Greedy tokens, logprobs and routed experts are the same either way
    (the dense model's case: test_pipelined_decode_matches_synchronous):
    slots retiring and re-admitting while chunks are in flight, a prompt
    streamed through chunked prefill, Qwen3-Next's recurrent state rows
    (its chunks' expert counts read back with the token) and ZAYA1's slot
    rows. One engine serves both passes, so its programs compile once."""
    cfg, params, kw, prompts, late, moe_model = _first_token_case(tiny, case)
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64, **kw)
    outs = {}
    for pipeline in (False, True):
        eng.decode_pipeline = pipeline
        carried = eng.sched.first_on_device
        admitted = eng.sched.admitted + eng.sched.chunked_admitted
        sp = dict(record_routing=True) if moe_model else {}
        reqs = [eng.add_request(p, SamplingParams(max_tokens=m, **sp))
                for p, m in prompts]
        for _ in range(2):
            eng.step()
        reqs.append(eng.add_request(late[0], SamplingParams(
            max_tokens=late[1], **sp)))
        while eng.has_work():
            eng.step()
        assert all(r.done and len(r.generated) == m
                   for r, (_, m) in zip(reqs, prompts + [late]))
        outs[pipeline] = [(r.generated, r.logprobs,
                           [np.asarray(x).tolist() for x in r.routing],
                           None if r.prompt_routing is None
                           else np.asarray(r.prompt_routing).tolist())
                          for r in reqs]
        admitted = eng.sched.admitted + eng.sched.chunked_admitted - admitted
        assert eng.sched.first_on_device - carried == \
            (admitted if pipeline else 0)
        if moe_model:
            assert eng.moe_tokens_per_expert.sum() > 0
            assert all(len(r.routing) == len(r.generated) for r in reqs)
    assert outs[True] == outs[False]


def test_request_ending_on_its_first_token_frees_its_slot(tiny):
    """A request that ends on its first token retires at the read-back
    with exactly that token — ``max_tokens=1`` with no row in the decode
    chunk launched beside it and no trim of that chunk, its eos after one
    trimmed row — and its slot is taken again by the next request, which
    decodes from its own first token."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,), decode_chunk=3)
    free0 = eng.paged.reclaimable_blocks
    live = eng.add_request([5, 6, 7], SamplingParams(max_tokens=12))
    eng.step()
    eng.step()                          # a chunk of ``live`` in flight
    p1, p2, p3 = [9, 10], [11, 12, 13], [14, 15]
    first2 = ref_greedy(params, cfg, p2, 1)[0]
    one = eng.add_request(p1, SamplingParams(max_tokens=1))
    eos = eng.add_request(p2, SamplingParams(max_tokens=8, eos_id=first2))
    after = eng.add_request(p3, SamplingParams(max_tokens=3))
    short = eng.sched.short_chunks
    eng.step()
    assert one.done and one.generated == ref_greedy(params, cfg, p1, 1)
    assert one.finish_reason == "length" and len(one.logprobs) == 1
    # under queue pressure, yet ``one`` (its budget spent by its first
    # token) neither trims ``live``'s chunk nor takes a row of it
    assert eng.sched.short_chunks == short
    assert [r for _, r in eng._inflight["snapshot"]] == [live]
    while eng.has_work():
        eng.step()
    assert eos.generated == [first2] and eos.finish_reason == "stop"
    assert after.done and len(after.generated) == 3
    assert one.slot == eos.slot == after.slot != live.slot
    for r in (live, after):
        assert_greedy_consistent(params, cfg, r.prompt, r.generated)
    assert eng.sched.first_on_device == 3       # all but ``one``
    assert sorted(eng._free) == [0, 1] and not eng._active
    assert eng.paged.reclaimable_blocks == free0


def test_abort_between_admission_and_read_back(tiny):
    """An abort that lands after the admission's launch and before its
    first token is read back: no token is committed, no span is left
    open, and the slot comes back for the next request."""
    from kubeflow_tpu.obs.trace import SpanCollector

    cfg, params = tiny
    col = SpanCollector(capacity=1024)
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,), obs=col)
    free0 = eng.paged.reclaimable_blocks
    keep = eng.add_request([5, 6, 7], SamplingParams(max_tokens=12))
    eng.step()
    drop = eng.add_request([9, 10], SamplingParams(max_tokens=12))
    read_back = eng._commit_first_tokens

    def abort_first(*args, **kw):
        if eng._pending and not drop.aborted:
            assert drop.slot is not None and not drop.generated
            eng.abort([drop])          # another thread, between the two
        return read_back(*args, **kw)

    eng._commit_first_tokens = abort_first
    eng.step()
    assert drop.aborted and drop.generated == []
    while eng.has_work():
        eng.step()
    assert keep.done and len(keep.generated) == 12
    assert drop.finish_reason == "abort" and drop.generated == []
    assert col.open_count == 0
    assert sorted(eng._free) == [0, 1] and not eng._active
    assert eng.paged.reclaimable_blocks == free0
    again = eng.add_request([9, 10], SamplingParams(max_tokens=4))
    while eng.has_work():
        eng.step()
    assert again.generated == ref_greedy(params, cfg, [9, 10], 4)


def test_held_request_parks_with_its_first_token(tiny):
    """``hold_after_prefill``: the request parks with token #1 committed
    and never joins a decode batch; one that ends on that token is not
    held and frees its slot."""
    cfg, params = tiny
    eng = LLMEngine(params, cfg, max_batch=3, max_seq=64,
                    prefill_buckets=(8,))
    live = eng.add_request([5, 6, 7], SamplingParams(max_tokens=6))
    eng.step()                          # ``live`` decoding
    held = eng.add_request([9, 10, 11], SamplingParams(max_tokens=8),
                           hold_after_prefill=True)
    short = eng.add_request([12, 13], SamplingParams(max_tokens=1),
                            hold_after_prefill=True)
    eng.step()
    assert held in eng.held_requests() and not held.done
    assert held.generated == ref_greedy(params, cfg, [9, 10, 11], 1)
    assert held.t_first_token > 0 and len(held.logprobs) == 1
    assert held not in eng._active.values()
    assert short.done and short not in eng.held_requests()
    assert short.generated == ref_greedy(params, cfg, [12, 13], 1)
    while not live.done:
        eng.step()
    assert len(held.generated) == 1
    assert_greedy_consistent(params, cfg, live.prompt, live.generated)
    assert eng.sched.first_on_device == 1     # ``live`` alone rode a launch
    assert eng.export_held_kv(held)["first_token"] == held.generated[0]
    assert eng.release_held(held)
    assert sorted(eng._free) == [0, 1, 2]
