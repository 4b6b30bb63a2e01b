"""The Gated DeltaNet / gated attention / expert-share model
(models/qwen3_next.py) against its plain reference
(benchmarks/reference/qwen3_next.py), at a tiny size on the CPU: hidden 64,
two periods of (GDN, GDN, GDN, full), GDN 2 key / 4 value heads of 16,
attention 4 / 2 heads of 256 (8 rotary; K and V stored merged), 16 experts routed top-3 of which
this "chip" holds 4 (4-7), seeded weights with every norm weight non-zero.

Everything here computes in float32, so the tolerances are float32's: the
reference runs ``highest`` matmuls token by token (no chunked form, a dense
gate matrix, no cache), which moves logits of size ~4 by ~3e-5.
``LOGIT_TOL`` = 3e-4 leaves that ten times of room, and the same program in
bfloat16 misses it by a factor of thousands
(``test_bfloat16_fails_the_float32_tolerance``).
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import qwen3_next as q3
from kubeflow_tpu.ops import pallas_gdn
from kubeflow_tpu.serving import paged_kv
from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
from kubeflow_tpu.serving.scheduler import QuantConfig, SchedulerConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import qwen3_next as reference  # noqa: E402

LOGIT_TOL = 3e-4
CFG = q3.qwen3_next_tiny(dtype=jnp.float32)
# the reference reads a configuration file's keys
REF_CFG = {"rms_norm_eps": CFG.norm_eps, "full_attention_interval": 4,
           "num_hidden_layers": CFG.n_layers,
           "linear_num_key_heads": CFG.n_k_heads,
           "linear_num_value_heads": CFG.n_v_heads,
           "linear_key_head_dim": CFG.k_head_dim,
           "linear_value_head_dim": CFG.v_head_dim,
           "linear_conv_kernel_dim": CFG.conv_kernel,
           "rope_theta": CFG.rope_theta, "head_dim": CFG.head_dim,
           "partial_rotary_factor": CFG.rotary_dim / CFG.head_dim,
           "num_attention_heads": CFG.n_heads,
           "num_key_value_heads": CFG.n_kv_heads,
           "num_experts": CFG.n_experts_held,
           "first_expert_held": CFG.first_expert,
           "num_experts_per_tok": CFG.top_k}
CHUNK, BS, NBP = 16, 8, 8


@pytest.fixture(scope="module")
def params():
    p = q3.init_params(jax.random.key(3), CFG)
    # what a seeded model must not switch off: every zero-centred norm's
    # weight is off zero (``w`` for ``1 + w`` shows), the decay spreads
    for tree, key in (("linear", "in_norm"), ("full", "q_norm"),
                      ("full", "k_norm"), ("moe", "post_norm")):
        assert float(jnp.abs(p[tree][key]).min()) > 0, key
    decay = jnp.exp(-jnp.exp(p["linear"]["A_log"]) * jax.nn.softplus(0.0))
    assert 0.85 < float(decay.min()) and float(decay.max()) < 0.9995
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


# ---------------------------------------------------------------------------
# (a) the model against the reference
# ---------------------------------------------------------------------------

def test_forward_matches_the_reference(params):
    toks = _tokens(45, seed=1)
    got = np.asarray(q3.forward(params, jnp.asarray(toks)[None], CFG)[0])
    ref = reference.forward(params, toks, REF_CFG)
    assert ref["route_disagreements"] == 0
    assert np.abs(got - np.asarray(ref["logits"])).max() < LOGIT_TOL


def test_bfloat16_fails_the_float32_tolerance(params):
    cfg = q3.qwen3_next_tiny(dtype=jnp.bfloat16)
    toks = _tokens(45, seed=1)
    got = np.asarray(q3.forward(params, jnp.asarray(toks)[None], cfg)[0])
    ref = reference.forward(params, toks, REF_CFG)
    assert np.abs(got - np.asarray(ref["logits"])).max() > 100 * LOGIT_TOL


def _published_split(qkvz, qg, cfg):
    """The published model's split of the two products: reshaped into
    heads, split, and (u) put together again."""
    bsz, s, _ = qkvz.shape
    r = cfg.n_v_heads // cfg.n_k_heads
    dk, dv, hd = cfg.k_head_dim, cfg.v_head_dim, cfg.head_dim
    q, k, v, z = jnp.split(qkvz.reshape(bsz, s, cfg.n_k_heads, -1),
                           [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    u = jnp.concatenate([t.reshape(bsz, s, -1) for t in (q, k, v)], -1)
    qg = qg.reshape(bsz, s, cfg.n_heads, 2 * hd)
    return (u, z.reshape(bsz, s, cfg.n_v_heads, dv), qg[..., :hd],
            qg[..., hd:])


@pytest.mark.parametrize("widths", [
    {}, {"n_v_heads": 2},
    # the published GDN and attention widths (value heads twice key heads)
    {"dim": 2048, "n_k_heads": 16, "n_v_heads": 32, "k_head_dim": 128,
     "v_head_dim": 128, "n_heads": 16}])
def test_the_permuted_split_is_the_published_split(widths):
    """``gdn_inputs`` and ``attn_inputs`` take ``u``, ``z``, the query and
    the gate from the flat products by a constant permutation of columns:
    the same bfloat16 values as the published reshape-and-split, bit for
    bit."""
    cfg = q3.qwen3_next_tiny(dtype=jnp.bfloat16, n_layers=4, **widths)
    p = q3.init_params(jax.random.key(5), cfg, dtype=jnp.bfloat16)
    lp = {**jax.tree.map(lambda a: a[0], p["linear"]),
          **jax.tree.map(lambda a: a[0], p["full"])}
    x = jax.random.normal(jax.random.key(6), (2, 3, cfg.dim)).astype(
        jnp.bfloat16)
    h = q3.zc_norm(x, lp["in_norm"], cfg.norm_eps)
    want_u, want_z, want_q, want_gate = _published_split(
        q3._proj(h, lp["w_qkvz"], cfg.dtype), q3._proj(h, lp["w_q"],
                                                      cfg.dtype), cfg)
    u, z, _, _ = q3.gdn_inputs(lp, x, cfg)
    assert u.dtype == z.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(u), np.asarray(want_u))
    assert np.array_equal(np.asarray(z), np.asarray(want_z))
    positions = jnp.arange(3)[None]
    q, gate, _, _ = q3.attn_inputs(lp, x, positions, cfg)
    want_q = q3._rope(q3.zc_norm(want_q, lp["q_norm"], cfg.norm_eps),
                      positions, cfg)
    assert np.array_equal(np.asarray(q), np.asarray(want_q))
    assert np.array_equal(np.asarray(gate), np.asarray(want_gate))


@pytest.mark.parametrize("period,j", [(p, j) for p in range(2)
                                      for j in range(4)])
def test_a_layer_is_handed_its_published_weights(params, period, j):
    """What the paged programs hand layer ``j`` of period ``period``
    (``layer_stacks``, scanned as ``_scan_layers`` does, then
    ``period_layer``): published layer ``period * 4 + j``'s weights, the
    projections by one index into their whole stack, the experts' stacks
    whole with the layer's index among all."""
    ops = CFG.paged_ops()
    [(stack, whole)] = ops.layer_stacks(params)
    lp = dict(jax.tree.map(lambda a: a[period], {
        key: val for key, val in stack.items() if key not in whole}),
        **{key: stack[key] for key in whole}, stack_index=period)
    got = ops.period_layer(lp, j)
    layer = period * CFG.full_attention_interval + j
    tree, index = (("linear", period * 3 + j) if ops.period[j] == "recurrent"
                   else ("full", period))
    want = {key: val[index] for key, val in params[tree].items()}
    want.update({key: val[layer] for key, val in params["moe"].items()
                 if key not in q3.EXPERT_MATRICES})
    assert set(got) == set(want) | set(q3.EXPERT_MATRICES) | {"layer_index"}
    for key, val in want.items():
        assert np.array_equal(np.asarray(got[key]), np.asarray(val)), key
    for key in q3.EXPERT_MATRICES:
        assert got[key] is params["moe"][key]
    assert int(got["layer_index"]) == layer


def _token_by_token(q, k, v, g, beta, s):
    """The three lines of the recurrence, one token at a time, numpy."""
    out = []
    for t in range(q.shape[1]):
        s = np.exp(g[:, t])[:, None, None] * s
        ks = np.einsum("hkv,hk->hv", s, k[:, t])
        s = s + k[:, t, :, None] * (beta[:, t, None] * (v[:, t] - ks))[
            :, None]
        out.append(np.einsum("hkv,hk->hv", s, q[:, t]))
    return np.stack(out, 1), s


@pytest.mark.parametrize("t,n_true", [(64, 64), (128, 100), (192, 65)])
def test_the_chunkwise_scan_is_the_recurrence(t, n_true):
    """``chunk_gated_delta`` (the UT form over sub-chunks of 64, a scan
    across them) against the recurrence token by token, from a carried
    state; rows past ``n_true`` with ``beta = g = 0`` leave the state as
    the last true row left it."""
    rng = np.random.default_rng(t)
    h, dk, dv = 3, 16, 8
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(h, t, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(h, t, dk)))
    v = rng.normal(size=(h, t, dv))
    g = np.log(rng.uniform(0.85, 0.999, (h, t)))
    beta = rng.uniform(0.1, 0.9, (h, t))
    g[:, n_true:] = 0.0
    beta[:, n_true:] = 0.0
    s0 = rng.normal(size=(h, dk, dv))
    o, s = q3.chunk_gated_delta(*(jnp.asarray(x, jnp.float32)
                                  for x in (q, k, v, g, beta, s0)))
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
    _, s_true = _token_by_token(q[:, :n_true], k[:, :n_true], v[:, :n_true],
                                g[:, :n_true], beta[:, :n_true], s0)
    assert np.abs(np.asarray(o) - want_o).max() < 1e-4
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4
    assert np.abs(want_s - s_true).max() == 0.0


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0, 0, 0, 0, 0],
                                  [0, 1, 0, 0, 1]])
def test_the_decode_kernel_is_the_step_and_leaves_idle_slots(live):
    """``ops/pallas_gdn.py`` (interpreted) against the plain step: the live
    slots' state moves, every other slot's stays exactly as it was, none
    copied through anything (all idle: copied through unchanged)."""
    b, h, dk, dv, layers = 5, 4, 16, 16, 3
    ks = jax.random.split(jax.random.key(0), 6)
    q = jax.random.normal(ks[0], (b, h, dk))
    k = jax.random.normal(ks[1], (b, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, dv))
    decay = jax.random.uniform(ks[3], (b, h), minval=0.9, maxval=1.0)
    beta = jax.random.uniform(ks[4], (b, h))
    state = jax.random.normal(ks[5], (layers, b, h, dk, dv))
    live = jnp.asarray(live, bool)
    o, s = pallas_gdn.gdn_decode(q, k, v, decay, beta, state, 1, live,
                                 interpret=True)
    ro, rs = pallas_gdn.gdn_decode_reference(q, k, v, decay, beta, state, 1,
                                             live)
    assert np.abs(np.asarray(o - ro)).max() < 1e-5
    assert np.abs(np.asarray(s - rs)).max() < 1e-5
    untouched = ~np.asarray(live)
    assert np.array_equal(np.asarray(s[1])[untouched],
                          np.asarray(state[1])[untouched])
    assert np.array_equal(np.asarray(s)[[0, 2]], np.asarray(state)[[0, 2]])


# ---------------------------------------------------------------------------
# (b) prefill, then decode, through the pools and the state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def programs(params):
    """The chunk program and a decode chunk of 4 steps, jitted once."""
    def chunk(cache, tokens, tables, slot, offset, length):
        return paged_kv.paged_prefill_chunk(params, tokens, CFG, cache,
                                            tables, slot, offset, length)

    def decode(cache, tokens, tables, active, kernel):
        def step(cache, tok):
            logits, cache, _ = paged_kv.paged_decode_step(
                params, tok, CFG, cache, tables, kernel=kernel,
                active=active)
            cache["len"] = jnp.where(active, cache["len"], 0)
            return cache, logits
        return jax.lax.scan(step, cache, tokens)

    return jax.jit(chunk), jax.jit(decode, static_argnames=("kernel",))


def _paged_logits(params, programs, toks, n_prompt, kernel="gather", slot=1,
                  chunk_width=CHUNK):
    """Prefill ``toks[:n_prompt]`` in chunks into ``slot``, then one decode
    chunk over the rest: logits at rows n_prompt-1 .. and the cache."""
    chunk, decode = programs
    n_slots = 3
    cache = paged_kv.init_paged_cache(CFG, n_slots, NBP * BS, BS,
                                      n_slots * NBP + 1)
    # what another request left in the slot must not be seen
    cache = {key: (val + 3.0 if key.startswith("gdn") else val)
             for key, val in cache.items()}
    tables = np.zeros((n_slots, NBP), np.int32)
    tables[slot] = 1 + slot * NBP + np.arange(NBP)
    tables = jnp.asarray(tables)
    for off in range(0, n_prompt, chunk_width):
        piece = np.zeros((1, chunk_width), np.int32)
        part = toks[off:off + chunk_width][:n_prompt - off]
        piece[0, :len(part)] = part
        x_last, cache, _ = chunk(cache, jnp.asarray(piece), tables, slot,
                                 off, n_prompt)
    first = q3.lm_head(params, x_last, CFG)[0]
    cache["len"] = cache["len"].at[slot].set(n_prompt)
    steps = np.zeros((len(toks) - n_prompt, n_slots), np.int32)
    steps[:, slot] = toks[n_prompt:]
    cache, logits = decode(cache, jnp.asarray(steps), tables,
                           jnp.arange(n_slots) == slot, kernel=kernel)
    return np.concatenate([np.asarray(first)[None],
                           np.asarray(logits[:, slot])]), cache


@pytest.mark.parametrize("n_prompt,kernel,width", [
    (CHUNK - 1, "gather", CHUNK), (CHUNK + 1, "pallas", CHUNK),
    (2 * CHUNK + 9, "gather", CHUNK), (3 * CHUNK + 2, "pallas", CHUNK),
    (1, "pallas", CHUNK), (3, "gather", CHUNK), (23, "gather", 7),
    (31, "pallas", 10)])
def test_prefill_and_decode_through_the_pools_and_the_state(
        params, programs, n_prompt, kernel, width):
    """Prompts that end before and after a chunk boundary (a chunk of 16 is
    a quarter of a sub-chunk of 64: every chunk's pad rows split one;
    chunks of 7 and 10 start at every phase of the conv ring's 3 rows), of
    one token and of three (the ring's zero padding), then a decode chunk
    of 4 steps beside idle slots, against the reference's full forward, on
    logits."""
    toks = _tokens(n_prompt + 4, seed=n_prompt)
    got, cache = _paged_logits(params, programs, toks, n_prompt, kernel,
                               chunk_width=width)
    ref = reference.forward(params, toks, REF_CFG,
                            rows=range(n_prompt - 1, len(toks)))
    assert np.abs(got - np.asarray(ref["logits"])).max() < LOGIT_TOL
    # idle slots advanced nothing: their state is what it was
    for key in ("gdn_s", "gdn_conv"):
        assert np.all(np.asarray(cache[key][:, 0]) == 3.0)


def test_a_chunk_of_one_and_a_half_sub_chunks(params):
    """A chunk of 96 rows: the scan crosses a sub-chunk boundary inside the
    chunk and its second sub-chunk is half pad."""
    chunk = jax.jit(lambda cache, tokens, tables, slot, offset, length:
                    paged_kv.paged_prefill_chunk(params, tokens, CFG, cache,
                                                 tables, slot, offset,
                                                 length))
    toks = _tokens(130, seed=5)
    cache = paged_kv.init_paged_cache(CFG, 2, 2 * 96, BS, 2 * 24 + 1)
    tables = jnp.asarray(np.arange(2 * 24).reshape(2, 24) + 1, jnp.int32)
    for off in (0, 96):
        piece = np.zeros((1, 96), np.int32)
        part = toks[off:off + 96]
        piece[0, :len(part)] = part
        x_last, cache, _ = chunk(cache, jnp.asarray(piece), tables, 1, off,
                                 len(toks))
    got = np.asarray(q3.lm_head(params, x_last, CFG)[0])
    ref = reference.forward(params, toks, REF_CFG, rows=[len(toks) - 1])
    assert np.abs(got - np.asarray(ref["logits"])[0]).max() < LOGIT_TOL


def test_a_slot_that_is_not_active_keeps_its_state(params, programs):
    """A slot mid-prefill whose ``len`` is stale (its predecessor's, for one
    more step) is not in the dispatch: a decode chunk beside it must leave
    the state its first chunk wrote."""
    toks = _tokens(2 * CHUNK + 5, seed=8)
    chunk, decode = programs
    want, _ = _paged_logits(params, programs, toks, 2 * CHUNK + 1)
    cache = paged_kv.init_paged_cache(CFG, 3, NBP * BS, BS, 3 * NBP + 1)
    cache["len"] = cache["len"].at[1].set(7)           # stale, not active
    tables = np.zeros((3, NBP), np.int32)
    tables[1] = 1 + NBP + np.arange(NBP)
    x_last = None
    for off in range(0, 2 * CHUNK + 1, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        part = toks[off:off + CHUNK][:2 * CHUNK + 1 - off]
        piece[0, :len(part)] = part
        x_last, cache, _ = chunk(cache, jnp.asarray(piece),
                                 jnp.asarray(tables), 1, off, 2 * CHUNK + 1)
        # between its chunks the engine decodes the other slots, this
        # slot's table row zeroed
        cache, _ = decode(cache, jnp.ones((4, 3), jnp.int32),
                          jnp.zeros((3, NBP), jnp.int32),
                          jnp.zeros((3,), bool), kernel="pallas")
    got = np.asarray(q3.lm_head(params, x_last, CFG)[0])
    assert np.abs(got - want[0]).max() < 1e-5


# ---------------------------------------------------------------------------
# (c) the engine: a reused slot, a request beside others, the counters
# ---------------------------------------------------------------------------

def _engine(params, cfg=CFG, **kw):
    from kubeflow_tpu.obs.trace import SpanCollector

    kw.setdefault("max_batch", 4)
    return LLMEngine(params, cfg, max_seq=128, prefill_buckets=(CHUNK,),
                     kv_block_size=BS, decode_chunk=4,
                     scheduler=SchedulerConfig(radix_cache=False),
                     obs=SpanCollector(capacity=4096), **kw)


def _alone(params, prompt, n=8):
    (req,) = _engine(params, max_batch=1).generate(
        [prompt], SamplingParams(max_tokens=n))
    return req


@pytest.mark.parametrize("case", ["reused slot", "beside others"])
def test_a_request_gives_what_it_gives_alone(params, case):
    """The recurrent state neither leaks from a slot's previous request nor
    between the slots of a batch: tokens equal, logprobs to float32
    rounding (1e-5; a batch of another size is another program)."""
    prompts = [_tokens(n, seed=n).tolist() for n in (37, 5, 18)]
    eng = _engine(params, max_batch=1 if case == "reused slot" else 4)
    reqs = eng.generate(prompts, SamplingParams(max_tokens=8))
    if case == "reused slot":
        assert {r.slot for r in reqs} == {0}
    else:
        assert len({r.slot for r in reqs}) == 3
    for r, prompt in zip(reqs, prompts):
        want = _alone(params, prompt)
        assert r.generated == want.generated
        assert np.abs(np.asarray(r.logprobs)
                      - np.asarray(want.logprobs)).max() < 1e-5


def test_engine_serves_it_and_counts_what_it_adds(params):
    """add_request / step with chunked prefill and decode chunks of 4
    through the Pallas kernels: the served tokens are the reference's
    argmax; the spans and counters of ISSUE 38 (live GDN slots on a decode
    step, carried state on a chunk, tokens per HELD expert, picks routed to
    absent experts); state arrays counted as pools."""
    eng = _engine(params, kernel="pallas")
    prompts = [_tokens(n, seed=n).tolist() for n in (5, 37, 70, 1, 3, 17)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=10,
                                                record_routing=True))
    for r in reqs:
        seq = r.prompt + r.generated
        ref = reference.forward(
            params, np.asarray(seq), REF_CFG,
            rows=range(len(r.prompt) - 1, len(seq) - 1))
        assert np.array_equal(np.argmax(np.asarray(ref["logits"]), -1),
                              np.asarray(r.generated))
    spans = eng.obs.snapshot()
    steps = [s["attrs"] for s in spans if s["name"] == "decode.step"]
    assert steps and all(a["state_slots"] == a["batch"] for a in steps)
    assert all("absent_picks" in a for a in steps if "experts_hit" in a)
    chunks = [s["attrs"] for s in spans if s["name"] == "prefill.chunk"]
    assert {a["state_carried"] for a in chunks} == {True, False}
    assert eng.moe_tokens_per_expert.shape == (CFG.n_layers,
                                               CFG.n_experts_held)
    held = eng.moe_tokens_per_expert.sum()
    # 4 of 16 experts held: about three quarters of the picks go elsewhere
    share = eng.moe_absent_picks / (eng.moe_absent_picks + held)
    assert 0.55 < share < 0.95
    assert eng.slot_state_bytes == sum(
        eng.cache[key].nbytes for key in ("gdn_s", "gdn_conv"))


@pytest.mark.parametrize("mechanism,kwargs", [
    ("radix prefix cache", dict(scheduler=SchedulerConfig())),
    ("speculative decode", dict(scheduler=SchedulerConfig(
        radix_cache=False, spec_decode=True))),
    ("int8 weights", dict(quant=QuantConfig(weight_dtype="int8"))),
    ("quantized KV pool", dict(quant=QuantConfig(kv_dtype="int8"))),
    ("tensor mesh", dict(mesh="tensor")),
    ("disaggregated tiers", dict(tier="prefill")),
])
def test_what_the_model_cannot_be_served_with_is_refused(params, mechanism,
                                                         kwargs):
    if kwargs.get("mesh"):
        from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

        kwargs = dict(mesh=build_mesh(MeshConfig(tensor=1),
                                      devices=jax.devices()[:1]))
    assert mechanism in CFG.paged_ops().refuses
    with pytest.raises(ValueError, match=mechanism):
        if mechanism == "disaggregated tiers":
            LLMEngine(params, CFG, max_batch=2, max_seq=64,
                      prefill_buckets=(32,)).precompile(tier="prefill")
        else:
            LLMEngine(params, CFG, max_batch=2, max_seq=64,
                      prefill_buckets=(32,), **kwargs)


def test_the_verify_step_is_refused(params):
    eng = LLMEngine(params, CFG, max_batch=2, max_seq=64,
                    prefill_buckets=(32,))
    assert eng.paged.prefix_cache is False
    with pytest.raises(ValueError, match="per-slot rows"):
        paged_kv.paged_verify_step(
            params, jnp.zeros((2, 2), jnp.int32), CFG, eng.cache,
            jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# (d) the expert share
# ---------------------------------------------------------------------------

def test_the_expert_shares_sum_to_the_uncut_layer(params):
    """Eight chips' shares of 16 experts, 2 each, routed over all 16: the
    parts of the result the shares give, with the shared expert (which
    every chip computes alike) counted once, add up to the uncut layer."""
    cfg = q3.qwen3_next_tiny(dtype=jnp.float32, n_experts_held=16,
                             first_expert=0)
    lp = jax.tree.map(lambda a: a[2], params["moe"])
    ks = jax.random.split(jax.random.key(9), 3)
    lp.update({key: jax.random.normal(k, (16, *lp[key].shape[1:])) * 0.1
               for key, k in zip(q3.EXPERT_MATRICES, ks)})
    x = jax.random.normal(jax.random.key(4), (2, 7, CFG.dim))
    uncut, stats = q3.moe_block(lp, x, cfg)
    assert int(stats["absent_picks"]) == 0
    shared, _ = q3.moe_block(
        dict(lp, **{key: jnp.zeros_like(lp[key])
                    for key in q3.EXPERT_MATRICES}), x, cfg)
    total, absent = jnp.zeros_like(x), 0
    for chip in range(8):
        share = q3.qwen3_next_tiny(dtype=jnp.float32, n_experts_held=2,
                                   first_expert=2 * chip)
        part = dict(lp, **{key: lp[key][2 * chip:2 * chip + 2]
                           for key in q3.EXPERT_MATRICES})
        y, st = q3.moe_block(part, x, share)
        total = total + (y - shared)
        absent += int(st["absent_picks"])
    assert np.abs(np.asarray(total + shared - uncut)).max() < 1e-5
    # every pick is absent on all chips but one
    assert absent == 7 * 2 * 7 * cfg.top_k


# ---------------------------------------------------------------------------
# (e) the chip's check on programs broken on purpose
# ---------------------------------------------------------------------------

def _break(monkeypatch, broken):
    if broken == "float8":
        f8 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        for name in ("gdn_inputs", "attn_inputs", "moe_block"):
            fn = getattr(q3, name)
            monkeypatch.setattr(q3, name, lambda lp, x, *a, _fn=fn, **kw:
                                _fn(lp, f8(x), *a, **kw))
    elif broken in ("state not carried", "conv tail dropped"):
        chunk = q3.gdn_chunk
        keys = (("gdn_s", "gdn_conv") if broken == "state not carried"
                else ("gdn_conv",))

        def from_zeros(lp, x, positions, state, valid, cfg):
            return chunk(lp, x, positions, {
                key: jnp.zeros_like(val) if key in keys else val
                for key, val in state.items()}, valid, cfg)
        monkeypatch.setattr(q3, "gdn_chunk", from_zeros)
    elif broken == "w for 1 + w":
        def plain(x, w, eps):
            xf = x.astype(jnp.float32)
            xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1,
                                             keepdims=True) + eps)
            return (xf * w.astype(jnp.float32)).astype(x.dtype)
        monkeypatch.setattr(q3, "zc_norm", plain)
    elif broken == "attention gate left out":
        out = q3.attn_out
        monkeypatch.setattr(q3, "attn_out", lambda lp, o, gate, cfg: out(
            lp, o, jnp.full_like(gate, 30.0), cfg))


@pytest.mark.parametrize("broken", [None, "float8", "state not carried",
                                    "conv tail dropped", "w for 1 + w",
                                    "attention gate left out"])
def test_the_benchmarks_check_passes_the_program_and_fails_a_broken_one(
        params, broken, monkeypatch):
    """The functions that decide ``correct`` on the chip
    (``drivers/latent._serve_checked``, ``drivers/gdn._compare``), on this
    engine: the checked prompts (1 and 3 tokens, a chunk and one token, a
    chunk boundary inside a sub-chunk, three chunks) are served beside
    requests that hold other slots, teacher-forced through the reference
    with the engine's recorded top-3 at every row. The same check must fail
    a program whose residual stream a layer reads through float8_e4m3, one
    that starts every chunk from zero state, one that drops the conv tail
    at a chunk boundary, one with ``w`` for ``1 + w`` and one without the
    attention gate."""
    from drivers import gdn, latent

    _break(monkeypatch, broken)
    eng = _engine(params, max_batch=7, kernel="pallas")
    for n in (50, 9, 77):          # the backlog: they outlive the check
        eng.add_request(_tokens(n, seed=n).tolist(),
                        SamplingParams(max_tokens=100))
    # float32 against float32: near-ties of ~1e-6 only, so the allowance
    # here is 1e-3 (the chip's, for a bfloat16 program, is the traffic
    # file's)
    spec = {"prompt_lens": [1, 3, CHUNK + 1, CHUNK + 6, 2 * CHUNK + 9],
            "max_tokens": 16, "route_tol": 1e-3, "logit_steps": 8.0}
    reqs = latent._serve_checked(eng, CFG.vocab_size, spec, 11, print)
    assert eng.has_work()          # served beside live requests
    monkeypatch.undo()
    out = gdn._compare(reqs, params, dict(REF_CFG), spec, print)
    assert out["tokens_checked"] == 80
    # every row of the five requests: the prompt's too
    assert out["routed_rows_compared"] == CFG.n_layers * sum(
        n - 1 + 16 for n in spec["prompt_lens"])
    assert out["ok"] is (broken is None), out


# ---------------------------------------------------------------------------
# the benchmark's readers for this cell, on a made-up trace
# ---------------------------------------------------------------------------

def _spec(name):
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "metrics", name + ".json")) as f:
        return json.load(f)


def test_readers_of_the_longgen_cell():
    from lib import mla_moe, peaks, qwen3_next as lib
    from readers import decode_small_ops, device_time, gdn_decode

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "qwen3-next-80b-a3b-l8.json")) as f:
        cfg = json.load(f)
    assert lib.gdn_layers(cfg) == 6
    # S alone: the conv ring is XLA's, not the kernel's
    assert lib.gdn_kernel_bytes(cfg, 10) == 2 * 6 * 10 * 32 * 128 * 128 * 4
    # K and V of a token in the 2 full layers: 2 heads of 256, bf16
    assert lib.attn_kernel_bytes(cfg, 10) == 10 * 4_096
    dec = "jit__decode_impl(1)"
    ops = [
        # 5 decode steps: the GDN kernel 30 times over 6 layers
        {"program": dec, "seconds": 3.0, "count": 30.0,
         "name": "gdn_decode.7 custom-call f32[256,32,128]"},
        {"program": dec, "seconds": 1.0, "count": 10.0,
         "name": "closed_call.3 custom-call bf16[256,16,256]"},
        {"program": dec, "seconds": 1.0, "count": 40.0,
         "name": "gmm custom-call bf16[2560,512]"},
        {"program": dec, "seconds": 1.0, "count": 40.0,
         "name": "gmm.12 custom-call bf16[2560,2048]"},
        {"program": dec, "seconds": 0.5, "count": 40.0,
         "name": "fusion.2 fusion f32[256,18992]"},
        {"program": dec, "seconds": 0.2, "count": 40.0,
         "name": "fusion.3 fusion bf16[256,2048]"},
        # the chunk program: the GDN scan's operations and others
        {"program": "jit__lambda(2)", "seconds": 0.4, "count": 12.0,
         "name": "fusion.5 fusion f32[32,8,64,64]"},
        {"program": "jit__lambda(2)", "seconds": 0.2, "count": 12.0,
         "name": "fusion.6 fusion f32[1,1,32,128,128]"},
        {"program": "jit__lambda(2)", "seconds": 0.3, "count": 2.0,
         "name": "fusion.9 fusion f32[1,512,32,128]"},
        {"program": "jit__lambda(2)", "seconds": 0.7, "count": 2.0,
         "name": "gmm.4 custom-call bf16[5120,512]"},
        {"program": "jit__lambda(2)", "seconds": 0.1, "count": 2.0,
         "name": "fusion.11 fusion f32[1,2,8,512,6144]"}]
    run = types.SimpleNamespace(
        trace={"ops": ops, "busy_s": 10.0,
               "programs": {dec: {"count": 1, "seconds": 6.5}}},
        config=cfg, t_trace=(0.0, 3.0), device={"kind": "TPU v5 lite"},
        samples=[(0.5, 1000, 9), (2.5, 3000, 9), (5.0, 99_999, 9)],
        spans=[{"name": "decode.step", "t1": 1.0, "attrs": {
            "state_slots": 200, "device_steps": 2, "experts_hit": 1000}},
            {"name": "decode.step", "t1": 2.0, "attrs": {
                "state_slots": 250, "device_steps": 3,
                "experts_hit": 1500}},
            {"name": "decode.step", "t1": 9.0, "attrs": {
                "state_slots": 1, "device_steps": 8, "experts_hit": 8}}])
    bw = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    spec = _spec("gdn_decode_kernel_roofline.longgen")
    want = 100.0 * lib.gdn_kernel_bytes(cfg, 230.0) * 5 / bw / 3.0
    assert gdn_decode.read(run, **spec["args"]) == pytest.approx(want)
    assert gdn_decode.read(run, **_spec("model.decode_step_ms.longgen")[
        "args"]) == pytest.approx(1000.0 * 6.5 / 5)
    want = 100.0 * 5 * 500 * mla_moe.expert_bytes(cfg) / bw / 2.0
    assert gdn_decode.read(run, **_spec("moe.expert_ffn_roofline.longgen")[
        "args"]) == pytest.approx(want)
    assert device_time.read(run, **_spec(
        "gdn_decode_kernel.time_share_pct.longgen")["args"]) \
        == pytest.approx(30.0)
    assert device_time.read(run, **_spec(
        "gdn_prefill.time_share_pct.longgen")["args"]) == pytest.approx(6.0)
    assert device_time.read(run, **_spec(
        "moe.expert_ffn.time_share_pct.longgen")["args"]) \
        == pytest.approx(27.0)
    # the GQA kernel on the 2 full layers, live tokens from the samples
    want = 100.0 * lib.attn_kernel_bytes(cfg, 2000.0) * 5 / bw / 1.0
    assert gdn_decode.read(run, **_spec("gqa_decode_kernel_roofline.longgen")[
        "args"]) == pytest.approx(want)
    assert device_time.read(run, **_spec(
        "gqa_decode_kernel.time_share_pct.longgen")["args"]) \
        == pytest.approx(10.0)
    # neither kernel, nor the experts, nor the head: fusion.3 alone
    assert decode_small_ops.read(run, **_spec(
        "decode.small_ops.time_share_pct.longgen")["args"]) \
        == pytest.approx(2.0)
    # a program without the kernel (the parent): nothing is read
    run.trace = {"ops": ops[1:], "busy_s": 10.0, "programs": {}}
    for name in ("gdn_decode_kernel_roofline.longgen",
                 "gqa_decode_kernel_roofline.longgen",
                 "model.decode_step_ms.longgen",
                 "moe.expert_ffn_roofline.longgen"):
        assert gdn_decode.read(run, **_spec(name)["args"]) is None
