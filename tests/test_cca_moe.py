"""The compressed-convolutional-attention / top-1-expert model
(models/cca_moe.py) against its plain reference
(benchmarks/reference/cca_moe.py), at a tiny size on the CPU: hidden 64,
4 query / 2 KV heads of 16 (8 of them rotary), 4 experts and the skip choice
behind a router 16 wide, 3 layers, seeded weights with every bias NON-ZERO.

Everything here computes in float32, so the tolerances are float32's: the
reference runs ``highest`` matmuls in another order of operations (shifted
sums for the convolutions, a dense gate matrix, no cache), which moves logits
of size ~1 by ~1e-5. ``LOGIT_TOL`` = 2e-4 leaves that ten times of room, and
the same program computing in bfloat16 misses it by a factor of a hundred
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

from kubeflow_tpu.models import cca_moe
from kubeflow_tpu.parallel import moe
from kubeflow_tpu.serving import paged_kv
from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
from kubeflow_tpu.serving.scheduler import QuantConfig, SchedulerConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import cca_moe as reference  # noqa: E402

LOGIT_TOL = 2e-4
CFG = cca_moe.cca_moe_tiny(dtype=jnp.float32)
# the reference reads a configuration file's keys
REF_CFG = {"rms_norm_eps": CFG.norm_eps, "head_dim": CFG.head_dim,
           "num_attention_heads": CFG.n_heads,
           "num_key_value_heads": CFG.n_kv_heads,
           "num_experts": CFG.n_experts,
           "router_hidden_size": CFG.router_dim, "cca_time0": 2,
           "cca_time1": 2, "rope_parameters": {"hybrid": {
               "rope_theta": CFG.rope_theta, "partial_rotary_factor": 0.5}}}
CHUNK = 16


@pytest.fixture(scope="module")
def params():
    p = cca_moe.init_params(jax.random.key(3), CFG)
    lay = p["layers"]
    # what a seeded model must not switch off
    for key in ("conv0_b", "conv1_b", "router_down_b", "router_b1",
                "router_b2", "router_bias"):
        assert float(jnp.abs(lay[key]).min()) > 0, key
    for key in ("attn_alpha", "attn_gamma", "mlp_alpha", "mlp_gamma",
                "router_eta"):
        assert float(jnp.abs(lay[key] - 1).min()) > 0, key
    assert float(jnp.abs(lay["log_tau"]).min()) > 0
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n)


# ---------------------------------------------------------------------------
# (a) forward against the reference; (f) the reference against the letter
# ---------------------------------------------------------------------------

def test_forward_matches_the_reference(params):
    toks = _tokens(40)
    logits = cca_moe.forward(params, jnp.asarray(toks)[None], CFG)
    ref = reference.forward(params, toks, REF_CFG)
    assert np.abs(np.asarray(logits[0]) - ref["logits"]).max() < LOGIT_TOL
    # the skip choice and at least three experts are in play on 40 tokens
    assert len(set(ref["experts"].reshape(-1).tolist())) >= 4


def test_bfloat16_fails_the_float32_tolerance(params):
    toks = _tokens(40)
    cfg = cca_moe.cca_moe_tiny(dtype=jnp.bfloat16)
    logits = cca_moe.forward(params, jnp.asarray(toks)[None], cfg)
    ref = reference.forward(params, toks, REF_CFG)
    assert np.abs(np.asarray(logits[0]) - ref["logits"]).max() > 20 * LOGIT_TOL


def _by_the_letter(params, toks):
    """The layer equations of ISSUE 34 token by token in numpy float64,
    with explicit ``t - 1`` terms: what the benchmark's reference (shifted
    sums, blocks of queries) must equal."""
    from math import erf, sqrt

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    lay = p["layers"]
    h_n, kv, d = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    grp, rot, n_exp = h_n // kv, CFG.rotary_dim, CFG.n_experts
    gelu = np.vectorize(lambda z: 0.5 * z * (1 + erf(z / sqrt(2))))

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x) + CFG.norm_eps) * w

    def rope(x, t):                       # x [heads, d]
        half = rot // 2
        inv = 1.0 / CFG.rope_theta ** (np.arange(half) / half)
        cos, sin = np.cos(t * inv), np.sin(t * inv)
        a, b = x[:, :half], x[:, half:rot]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin,
                               x[:, rot:]], -1)

    n = len(toks)
    x = p["embed"][toks]
    carry = np.zeros((n, CFG.router_dim))
    for i in range(CFG.n_layers):
        w = {key: val[i] for key, val in lay.items()}
        zeros = np.zeros(CFG.mix_dim)
        u, a, hid, ks, vs, outs = [], [], [], [], [], []
        for t in range(n):
            hid.append(norm(x[t], w["attn_norm"]))
            q0 = (hid[t] @ w["w_q"]).reshape(h_n, d)
            k0 = (hid[t] @ w["w_k"]).reshape(kv, d)
            u.append(np.concatenate([q0.reshape(-1), k0.reshape(-1)]))
            a.append(w["conv0_w"][1] * u[t]
                     + w["conv0_w"][0] * (u[t - 1] if t else zeros)
                     + w["conv0_b"])
            a_now = a[t].reshape(h_n + kv, d)
            a_last = (a[t - 1] if t else zeros).reshape(h_n + kv, d)
            c = np.stack([a_now[g] @ w["conv1_w"][1, g]
                          + a_last[g] @ w["conv1_w"][0, g]
                          for g in range(h_n + kv)]) + w["conv1_b"]
            mean_q = np.stack([(q0[j] + k0[j // grp]) / 2
                               for j in range(h_n)])
            mean_k = np.stack([mean_q[j * grp:(j + 1) * grp].mean(0)
                               for j in range(kv)])
            q, k = c[:h_n] + mean_q, c[h_n:] + mean_k
            q = sqrt(d) * q / np.linalg.norm(q, axis=-1, keepdims=True)
            k = np.exp(w["log_tau"])[:, None] * sqrt(d) * k \
                / np.linalg.norm(k, axis=-1, keepdims=True)
            ks.append(rope(k, t))
            vs.append(np.stack([
                hid[t] @ w["w_v1"],
                hid[t - 1] @ w["w_v2"] if t else np.zeros(d)]))
            q = rope(q, t)
            o = np.zeros((h_n, d))
            for j in range(h_n):
                s = np.array([q[j] @ ks[r][j // grp] for r in range(t + 1)]) \
                    / sqrt(d)
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                o[j] = sum(pr[r] * vs[r][j // grp] for r in range(t + 1))
            outs.append(np.einsum("hk,hkd->d", o, w["wo"]))
        for t in range(n):
            x[t] = w["attn_alpha"] * x[t] + w["attn_gamma"] * outs[t]
            hm = norm(x[t], w["mlp_norm"])
            r = hm @ w["router_down"] + w["router_down_b"] \
                + w["router_eta"] * carry[t]
            carry[t] = r
            z = gelu(norm(r, w["router_norm"]) @ w["router_w1"]
                     + w["router_b1"])
            z = gelu(z @ w["router_w2"] + w["router_b2"]) @ w["router_w3"]
            pr = np.exp(z - z.max())
            pr /= pr.sum()
            e = int(np.argmax(pr + w["router_bias"]))
            y = np.zeros_like(hm)
            if e < n_exp:
                g = hm @ w["w_gate"][e]
                y = pr[e] * (((g / (1 + np.exp(-g))) * (hm @ w["w_up"][e]))
                             @ w["w_down"][e])
            x[t] = w["mlp_alpha"] * x[t] + w["mlp_gamma"] * y
    return np.stack([norm(x[t], p["final_norm"]) for t in range(n)]) \
        @ p["embed"].T


def test_the_benchmarks_reference_is_the_layer_equations(params):
    """The reference the chip's check uses IS the file the tests import,
    and it equals the equations written token by token (float64 against
    float32 ``highest``: 1e-5 on logits of size ~1)."""
    assert os.path.samefile(
        reference.__file__, os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "reference",
            "cca_moe.py"))
    toks = _tokens(12, seed=4)
    ref = reference.forward(params, toks, REF_CFG, q_block=5)
    assert np.abs(_by_the_letter(params, toks) - ref["logits"]).max() < 5e-5
    # padded at its end, asked for two rows: the same numbers
    part = reference.forward(params, toks, REF_CFG, rows=[3, 11], pad_to=24)
    assert np.abs(np.asarray(part["logits"])
                  - np.asarray(ref["logits"])[[3, 11]]).max() < 2e-5


# ---------------------------------------------------------------------------
# (b) the paged programs: chunk boundaries, then decode through cache + state
# ---------------------------------------------------------------------------

BS, NBP = 8, 8


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
            # as the engine does: an idle slot's length stays 0
            cache["len"] = jnp.where(active, cache["len"], 0)
            return cache, logits
        return jax.lax.scan(step, cache, tokens)

    return jax.jit(chunk), jax.jit(decode, static_argnames=("kernel",))


def _paged_logits(params, programs, toks, n_prompt, kernel="gather", slot=1,
                  cache=None):
    """Prefill ``toks[:n_prompt]`` in chunks of CHUNK into ``slot``, then
    one decode chunk over the rest: logits at rows n_prompt-1 .. and the
    cache."""
    chunk, decode = programs
    n_slots = 3
    if cache is None:
        cache = paged_kv.init_paged_cache(CFG, n_slots, NBP * BS, BS,
                                          n_slots * NBP + 1)
        # what another request left in the slot must not be seen
        cache["cca"] = cache["cca"] + 3.0
    tables = np.zeros((n_slots, NBP), np.int32)
    tables[slot] = 1 + slot * NBP + np.arange(NBP)
    tables = jnp.asarray(tables)
    for off in range(0, n_prompt, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        part = toks[off:off + CHUNK][:n_prompt - off]
        piece[0, :len(part)] = part
        x_last, cache, _ = chunk(cache, jnp.asarray(piece), tables, slot,
                                 off, n_prompt)
    first = cca_moe.lm_head(params, x_last, CFG)[0]
    cache["len"] = cache["len"].at[slot].set(n_prompt)
    steps = np.zeros((len(toks) - n_prompt, n_slots), np.int32)
    steps[:, slot] = toks[n_prompt:]
    cache, logits = decode(cache, jnp.asarray(steps), tables,
                           jnp.arange(n_slots) == slot, kernel=kernel)
    return np.concatenate([np.asarray(first)[None],
                           np.asarray(logits[:, slot])]), cache


@pytest.mark.parametrize("n_prompt,kernel", [
    (CHUNK - 1, "gather"), (CHUNK, "gather"), (CHUNK + 1, "gather"),
    (2 * CHUNK + 1, "pallas"), (1, "gather"), (2, "pallas")])
def test_prefill_and_decode_through_the_pool_and_the_state(
        params, programs, n_prompt, kernel):
    """Prompts that end before, on and after a chunk boundary, of one token
    and of two (the zero padding), then a decode chunk of 4 steps beside
    idle slots, against the reference's full forward, on logits."""
    toks = _tokens(n_prompt + 4, seed=n_prompt)
    got, cache = _paged_logits(params, programs, toks, n_prompt, kernel)
    ref = reference.forward(params, toks, REF_CFG,
                            rows=range(n_prompt - 1, len(toks)))
    assert np.abs(got - ref["logits"]).max() < LOGIT_TOL
    # idle slots advanced nothing: their rows are what they were
    assert np.array_equal(np.asarray(cache["cca"][:, 0]),
                          np.full((CFG.n_layers, CFG.state_dim), 3.0))


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
                          jnp.zeros((3,), bool), kernel="gather")
    got = np.asarray(cca_moe.lm_head(params, x_last, CFG)[0])
    assert np.abs(got - want[0]).max() < 1e-5


# ---------------------------------------------------------------------------
# (c) the engine: a reused slot, a request beside others
# ---------------------------------------------------------------------------

def _engine(params, cfg=CFG, **kw):
    from kubeflow_tpu.obs.trace import SpanCollector

    kw.setdefault("max_batch", 4)
    return LLMEngine(params, cfg, max_seq=128, prefill_buckets=(CHUNK,),
                     kv_block_size=BS, decode_chunk=4,
                     obs=SpanCollector(capacity=4096), **kw)


def _alone(params, prompt, n=8):
    (req,) = _engine(params, max_batch=1).generate(
        [prompt], SamplingParams(max_tokens=n))
    return req


@pytest.mark.parametrize("case", ["reused slot", "beside others"])
def test_a_request_gives_what_it_gives_alone(params, case):
    """Per-slot state neither leaks from a slot's previous request nor
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


def test_engine_serves_it_and_counts_the_routers_choices(params):
    """add_request / step with chunked prefill (chunks of 16) and decode
    chunks of 4: the served tokens are the reference's argmax, the recorded
    choices the reference's own; the spans and counters of ISSUE 34."""
    eng = _engine(params, kernel="pallas")
    prompts = [_tokens(n, seed=n).tolist() for n in (5, 37, 70, 1, 2, 17)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=10,
                                                record_routing=True))
    for r in reqs:
        seq = np.asarray(r.prompt + r.generated)
        rows = range(len(r.prompt) - 1, len(seq) - 1)
        ref = reference.forward(params, seq, REF_CFG, rows=rows)
        assert np.array_equal(np.argmax(ref["logits"], -1), r.generated)
        assert np.array_equal(np.stack(r.routing, 1)[..., 0], ref["experts"])
        # ... at every row of the prompt as well, over its chunks
        n = len(r.prompt)
        assert r.prompt_routing.shape == (CFG.n_layers, n, 1)
        own = reference.forward(params, seq[:n], REF_CFG)["experts"]
        assert np.array_equal(r.prompt_routing[..., 0], own)
    spans = eng.obs.snapshot()
    decode = [s["attrs"] for s in spans if s["name"] == "decode.step"]
    assert decode and all(
        {"batch", "chunk_len", "device_steps", "experts_hit", "skipped",
         "routed_assignments"} <= set(a) for a in decode)
    assert all(0 <= a["skipped"] <= a["routed_assignments"] for a in decode)
    chunks = [s["attrs"] for s in spans if s["name"] == "prefill.chunk"]
    assert all(a["state_carried"] == (a["chunk_index"] > 0)
               and a["chunk_index"] == a["offset"] // CHUNK for a in chunks)
    assert sum(a["state_carried"] for a in chunks) == 2 + 4 + 1
    # [layers, 17] at the published widths: the last column is the skip
    load = eng.moe_tokens_per_expert
    assert load.shape == (CFG.n_layers, CFG.n_experts + 1)
    assert load.sum() == sum(a["routed_assignments"]
                             for a in decode + chunks)
    assert load[:, -1].sum() >= sum(a["skipped"] for a in decode) > 0
    (build,) = [s["attrs"] for s in spans if s["name"] == "engine.build"]
    assert build["slot_state_bytes"] == eng.slot_state_bytes \
        == CFG.n_layers * 4 * CFG.state_dim * 4
    assert eng.kv_row_bytes() == CFG.n_layers * 2 * 2 * CFG.head_dim * 4


# ---------------------------------------------------------------------------
# (d) the router
# ---------------------------------------------------------------------------

def _layer(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def test_layer_0_has_no_carry_and_later_layers_do(params):
    toks = jnp.asarray(_tokens(24, seed=1))[None]
    base = cca_moe.forward(params, toks, CFG)

    def with_eta(layer, value):
        eta = params["layers"]["router_eta"].at[layer].set(value)
        return cca_moe.forward(dict(params, layers=dict(
            params["layers"], router_eta=eta)), toks, CFG)

    assert np.array_equal(np.asarray(with_eta(0, 5.0)), np.asarray(base))
    assert np.abs(np.asarray(with_eta(1, 5.0)) - np.asarray(base)).max() > 1e-3
    # and the carry handed on is r AFTER the addition
    hid = jax.random.normal(jax.random.key(0), (1, 6, CFG.dim))
    carry = jax.random.normal(jax.random.key(1), (1, 6, CFG.router_dim))
    lp = _layer(params, 1)
    _, r = cca_moe.router_logits(lp, hid, carry, CFG)
    _, r0 = cca_moe.router_logits(lp, hid, jnp.zeros_like(carry), CFG)
    assert np.allclose(r - r0, lp["router_eta"] * carry, atol=1e-6)


def test_the_bias_chooses_and_does_not_weigh(params):
    """A bias that favours expert 2 changes the choice to 2; the weight is
    softmax(z) at 2, the bias nowhere in it, and not renormalised."""
    lp = dict(_layer(params, 1))
    hid = jax.random.normal(jax.random.key(2), (1, 9, CFG.dim))
    carry = jnp.zeros((1, 9, CFG.router_dim))
    logits, _ = cca_moe.router_logits(lp, hid, carry, CFG)
    p = jax.nn.softmax(logits[0], -1)
    rc = CFG.router_config()
    own, w_own = moe.route(None, None, lp["router_bias"], rc,
                           logits=logits[0])
    assert np.array_equal(np.asarray(own[:, 0]), np.argmax(
        np.asarray(p) + np.asarray(lp["router_bias"]), -1))
    assert np.allclose(w_own[:, 0], np.take_along_axis(
        np.asarray(p), np.asarray(own), -1)[:, 0], atol=1e-7)
    bias = jnp.zeros((CFG.n_experts + 1,)).at[2].set(2.0)
    pick, w = moe.route(None, None, bias, rc, logits=logits[0])
    assert np.all(np.asarray(pick) == 2)
    assert np.allclose(w[:, 0], p[:, 2], atol=1e-7) and float(w.max()) < 1


def test_the_skip_choice_gives_a_delta_of_exactly_zero(params):
    lp = dict(_layer(params, 0))
    lp["router_bias"] = jnp.zeros((CFG.n_experts + 1,)).at[
        CFG.n_experts].set(2.0)                      # every token skips
    hid = jax.random.normal(jax.random.key(3), (2, 5, CFG.dim))
    y, _, stats = cca_moe.expert_half(
        lp, hid, jnp.zeros((2, 5, CFG.router_dim)), CFG)
    assert not np.asarray(y).any()
    assert np.asarray(stats["tokens_per_expert"]).tolist() == [0, 0, 0, 0, 10]
    assert int(stats["skipped"]) == 10 and int(stats["experts_hit"]) == 0
    # merged into the stream: alpha * x and nothing else
    x = jax.random.normal(jax.random.key(4), (2, 5, CFG.dim))
    assert np.array_equal(
        np.asarray(cca_moe.merge(x, y, lp["mlp_alpha"], lp["mlp_gamma"])),
        np.asarray(lp["mlp_alpha"] * x))


@pytest.mark.parametrize("idle", [False, True])
def test_routed_path_is_the_loop_over_all_experts(params, idle):
    """Top-1 with a skip through sorted rows and grouped products against
    the dense-gate loop (a gate of zero for the skip): the same sums in
    another order, no token dropped, pad rows and skips give zeros."""
    lp = _layer(params, 2)
    hid = jax.random.normal(jax.random.key(5), (1, 40, CFG.dim))
    mask = (jnp.arange(40) % 3 != 0)[None] if idle else None
    y, _, stats = jax.jit(
        lambda h: cca_moe.expert_half(
            lp, h, jnp.zeros((1, 40, CFG.router_dim)), CFG, mask))(hid)
    logits, _ = cca_moe.router_logits(
        lp, hid, jnp.zeros((1, 40, CFG.router_dim)), CFG)
    experts, weights = moe.route(None, None, lp["router_bias"],
                                 CFG.router_config(), logits=logits[0])
    pad = [jnp.concatenate([lp[key], jnp.zeros_like(lp[key][:1])])
           for key in cca_moe.EXPERT_MATRICES]        # expert 4: the skip
    want = moe.all_experts(hid[0], experts, weights, *pad,
                           valid=None if mask is None else mask[0])
    assert np.abs(np.asarray(y[0]) - np.asarray(want)).max() < 1e-5
    live = 40 if mask is None else int(mask.sum())
    assert int(stats["tokens_per_expert"].sum()) == live
    skipped = np.asarray(experts[:, 0]) == CFG.n_experts
    assert skipped.any() and not np.asarray(y[0])[skipped].any()
    if idle:
        assert not np.asarray(y[0])[::3].any()


def test_the_balancing_rule_levels_a_routers_load(params):
    """``balance_router_bias`` (what the benchmark's weights go through):
    the bias update of loss-free balancing on seeded sequences moves only
    ``router_bias``, and a router whose bias favours one expert (2.6x the
    mean of the 5 choices) is brought back towards a level load on tokens
    it has not seen (1.6x; at the published widths the seeded router's own
    3-5x the mean becomes 1.5-1.9x, PERF.md)."""
    lopsided = dict(params, layers=dict(
        params["layers"],
        router_bias=params["layers"]["router_bias"].at[:, 0].add(0.15)))
    balanced = jax.jit(lambda p, k: cca_moe.balance_router_bias(
        p, CFG, k, batch=8, seq=32))(lopsided, jax.random.key(7))
    for key, val in lopsided["layers"].items():
        same = np.array_equal(np.asarray(val),
                              np.asarray(balanced["layers"][key]))
        assert same == (key != "router_bias"), key
    fresh = jnp.asarray(_tokens(640, seed=12).reshape(20, 32))

    def busiest(p):
        load = np.asarray(cca_moe._layers(p, fresh, CFG)[1], np.float64)
        assert load.sum() == 640 * CFG.n_layers
        return (load.max(-1) / load.mean(-1)).max()

    assert busiest(balanced) < 1.9 < 2.3 < busiest(lopsided)   # 1.58, 2.59


# ---------------------------------------------------------------------------
# (e) what the engine refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mechanism,kwargs", [
    ("radix prefix cache", dict(scheduler=SchedulerConfig())),
    ("speculative decode", dict(scheduler=SchedulerConfig(
        radix_cache=False, spec_decode=True))),
    ("int8 weights", dict(quant=QuantConfig(weight_dtype="int8"))),
    ("quantized KV pool", dict(quant=QuantConfig(kv_dtype="int8"))),
    ("tensor mesh", dict(mesh="tensor")),
])
def test_what_the_model_cannot_be_served_with_is_refused(params, mechanism,
                                                         kwargs):
    if kwargs.get("mesh"):
        from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

        kwargs = dict(mesh=build_mesh(MeshConfig(tensor=1),
                                      devices=jax.devices()[:1]))
    assert mechanism in CFG.paged_ops().refuses
    with pytest.raises(ValueError, match=mechanism):
        LLMEngine(params, CFG, max_batch=2, max_seq=64,
                  prefill_buckets=(32,), **kwargs)


@pytest.mark.parametrize("way", ["hold_after_prefill", "inject_request",
                                 "precompile tier", "verify step"])
def test_no_other_way_into_a_slot(params, way):
    """Disaggregated tiers move blocks and not state, the verify step
    rewinds rows and not state: each entry is refused, and the default
    policy leaves the prefix cache off."""
    eng = LLMEngine(params, CFG, max_batch=2, max_seq=64,
                    prefill_buckets=(32,))
    assert eng.paged.prefix_cache is False
    if way == "verify step":
        with pytest.raises(ValueError, match="per-slot rows"):
            paged_kv.paged_verify_step(
                params, jnp.zeros((2, 2), jnp.int32), CFG, eng.cache,
                jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32))
        return
    with pytest.raises(ValueError, match="disaggregated tiers"):
        if way == "hold_after_prefill":
            eng.add_request([1, 2, 3], hold_after_prefill=True)
        elif way == "inject_request":
            eng.inject_request([1, 2, 3], SamplingParams(), first_token=1,
                               first_lp=0.0, blocks={}, n_blocks=1)
        else:
            eng.precompile(tier="prefill")


# ---------------------------------------------------------------------------
# (f) the chip's check on programs broken on purpose
# ---------------------------------------------------------------------------

def _break(monkeypatch, broken):
    mix = cca_moe.mix
    if broken == "state not carried":
        # a chunk starts from zeros wherever it starts
        monkeypatch.setattr(
            cca_moe, "mix", lambda lp, x, pos, state, cfg: mix(
                lp, x, pos, jnp.zeros_like(state) if x.shape[1] > 1
                else state, cfg))
    elif broken == "value shift dropped":
        def unshifted(lp, x, pos, state, cfg):
            q, k, v, left = mix(lp, x, pos, state, cfg)
            return q, k, v.at[:, :, 1].set(left[..., 2 * cfg.mix_dim:]), left
        monkeypatch.setattr(cca_moe, "mix", unshifted)
    elif broken == "float8":
        for name in ("mix", "attention_out_and_experts"):
            fn = getattr(cca_moe, name)
            monkeypatch.setattr(
                cca_moe, name, lambda lp, x, *a, _fn=fn, **kw: _fn(
                    lp, x.astype(jnp.float8_e4m3fn).astype(x.dtype), *a,
                    **kw))
    elif broken == "q-k mean left out":
        monkeypatch.setattr(cca_moe, "qk_mean", lambda q0, k0: (0.0, 0.0))


@pytest.mark.parametrize("broken", [None, "state not carried",
                                    "value shift dropped", "float8",
                                    "q-k mean left out"])
def test_the_benchmarks_check_passes_the_program_and_fails_a_broken_one(
        params, broken, monkeypatch):
    """The two functions that decide ``correct`` on the chip
    (``drivers/latent._serve_checked``, ``drivers/cca._compare``), on this
    engine: the checked prompts (1 and 2 tokens, a chunk and one token, three
    chunks) are served beside requests that hold other slots, teacher-forced
    through the reference with the engine's recorded choices. The same check
    must fail a program that starts every chunk from zeros, one that takes
    both value heads from the current token, one whose residual stream is
    rounded to float8_e4m3 where a layer reads it, one without the q-k mean."""
    from drivers import cca, latent

    _break(monkeypatch, broken)
    eng = _engine(params, max_batch=6, kernel="pallas")
    for n in (50, 9, 77):          # the backlog: they outlive the check
        eng.add_request(_tokens(n, seed=n).tolist(),
                        SamplingParams(max_tokens=100))
    # float32 against float32: a choice differs at ties of ~1e-6 only, so
    # the near-tie allowance here is 1e-3 (the chip's, for a bfloat16
    # program, is the traffic file's; the float8 program reads 0.012 here)
    spec = {"prompt_lens": [1, 2, CHUNK + 1, 2 * CHUNK + 9],
            "max_tokens": 16, "route_tol": 1e-3}
    reqs = latent._serve_checked(eng, CFG.vocab_size, spec, 11, print)
    assert eng.has_work()          # served beside live requests
    monkeypatch.undo()
    out = cca._compare(reqs, params, dict(REF_CFG), spec, print)
    assert out["tokens_checked"] == 64
    # every row of the four requests: the prompt's too
    assert out["routed_rows_compared"] == CFG.n_layers * sum(
        n - 1 + 16 for n in spec["prompt_lens"])
    assert out["ok"] is (broken is None), out


@pytest.mark.parametrize("row", [0, 7, 18])
def test_the_reference_follows_a_choice_forced_at_a_prompt_row(params, row):
    """The check hands the reference the program's choice at EVERY row
    (``forced_rows``), not only at the rows whose logits it compares: a row
    attends to all before it, so another expert at prompt row ``row`` moves
    the logits of the last row by far more than float32's rounding (and the
    later rows' own choices with it: more than one then differs from the
    ones handed in); the reference's own choices handed back change nothing."""
    toks = _tokens(20, seed=6)
    own = reference.forward(params, toks, REF_CFG, rows=[19])
    every = reference.forward(params, toks, REF_CFG)["experts"]
    assert np.array_equal(every[:, 19:], own["experts"])
    other = every.copy()
    other[0, row] = (other[0, row] + 1) % CFG.n_experts
    moved = reference.forward(params, toks, REF_CFG, rows=[19], forced=other,
                              forced_rows=range(20), route_tol=2.0)
    assert moved["route_disagreements"] >= 1
    assert moved["experts"].shape == own["experts"].shape
    assert np.abs(moved["logits"] - own["logits"]).max() > 1e-3
    same = reference.forward(params, toks, REF_CFG, rows=[19], forced=every,
                             forced_rows=range(20))
    assert same["route_disagreements"] == 0
    assert np.array_equal(same["logits"], own["logits"])


def test_a_choice_further_off_than_a_near_tie_is_a_violation(params):
    """``forced`` is taken within ``route_tol`` of the reference's largest
    ``p + beta`` and nowhere else."""
    toks = _tokens(20, seed=6)
    own = reference.forward(params, toks, REF_CFG, rows=[19])
    other = (own["experts"] + 1) % (CFG.n_experts + 1)
    forced = reference.forward(params, toks, REF_CFG, rows=[19],
                               forced=other, route_tol=0.0)
    assert forced["route_violations"] == CFG.n_layers
    assert np.array_equal(forced["experts"], own["experts"])
    taken = reference.forward(params, toks, REF_CFG, rows=[19],
                              forced=other, route_tol=2.0)
    assert taken["route_violations"] == 0 and taken["route_gap"] > 0
    assert taken["route_disagreements"] == CFG.n_layers
    assert np.array_equal(taken["experts"][0], other[0])


# ---------------------------------------------------------------------------
# the benchmark's readers for this cell, on a made-up trace
# ---------------------------------------------------------------------------

def test_readers_of_the_reasoning_cell():
    from lib import cca_moe as lib_cca, peaks
    from readers import cca_kernel_roofline, decode_small_ops

    cfg = {"num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 128,
           "vocab_size": 1000}
    assert lib_cca.decode_kernel_bytes(cfg, 10) == 2 * 10 * 512 * 2
    ops = [
        {"program": "jit__decode_impl(1)", "seconds": 2.0, "count": 8.0,
         "name": "closed_call.9 custom-call bf16[64,8,128]"},
        # the compiler numbers all but the first of a program's kernels of
        # one name: the plain ``gmm`` is a grouped product too
        {"program": "jit__decode_impl(1)", "seconds": 2.0, "count": 8.0,
         "name": "gmm.13 custom-call bf16[64,2048]"},
        {"program": "jit__decode_impl(1)", "seconds": 1.0, "count": 4.0,
         "name": "gmm custom-call bf16[64,2048]"},
        {"program": "jit__decode_impl(1)", "seconds": 1.0, "count": 4.0,
         "name": "fusion.7 fusion bf16[64,1000]"},
        {"program": "jit__decode_impl(1)", "seconds": 0.5, "count": 80.0,
         "name": "fusion.3 fusion bf16[64,1280]"},
        {"program": "jit__decode_impl(1)", "seconds": 0.25, "count": 80.0,
         "name": "while.2 while"},
        {"program": "jit__lambda(2)", "seconds": 9.0, "count": 1.0,
         "name": "fusion.5 fusion bf16[512,2048]"}]
    run = types.SimpleNamespace(
        trace={"ops": ops, "busy_s": 10.0}, config=cfg, t_trace=(0.0, 3.0),
        samples=[(1.0, 100, 7), (2.0, 300, 7), (5.0, 9999, 7)],
        device={"kind": "TPU v5 lite"})
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "metrics", "decode.small_ops.time_share_pct."
                           "reasoning.json")) as f:
        args = json.load(f)["args"]            # the cell's own patterns
    assert decode_small_ops.read(run, **args) == pytest.approx(7.5)
    # 4 decode steps (8 kernel calls over 2 layers), 200 live tokens
    want = 100.0 * 4 * lib_cca.decode_kernel_bytes(cfg, 200) \
        / peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] / 2.0
    assert cca_kernel_roofline.read(
        run, program=args["program"], op=args["kernel"]) \
        == pytest.approx(want)
    # a program without the kernel (the parent): nothing is read
    run.trace = {"ops": ops[-1:], "busy_s": 10.0}
    assert decode_small_ops.read(run, **args) is None
    assert cca_kernel_roofline.read(
        run, program=args["program"], op=args["kernel"]) is None
