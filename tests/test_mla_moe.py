"""The latent-attention / routed-expert model (models/mla_moe.py) against its
plain reference (benchmarks/reference/mla_moe.py), at a tiny size on the CPU:
hidden 64, 4 heads, q_lora 24, kv_lora 16, nope 8, rope 8, v 8, 8 experts
top-2 + a shared one, 1 dense + 4 expert layers + the prediction block,
seeded weights with a NON-ZERO correction bias.

Everything here computes in float32, so the tolerances are float32's: the
reference runs ``highest`` matmuls in another order of operations (in-place
interleaved RoPE, a dense gate matrix, no cache), which moves logits of size
~1 by ~1e-5. ``LOGIT_TOL`` = 2e-4 leaves that ten times of room, and the same
program computing in bfloat16 misses it by a factor of a hundred
(``test_bfloat16_fails_the_float32_tolerance``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import mla_moe
from kubeflow_tpu.ops.pallas_paged_attention import (
    _prefill_tiles, paged_latent_decode_attention,
    paged_latent_prefill_attention,
)
from kubeflow_tpu.parallel import moe
from kubeflow_tpu.serving import paged_kv
from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
from kubeflow_tpu.serving.scheduler import QuantConfig, SchedulerConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import mla_moe as reference  # noqa: E402

LOGIT_TOL = 2e-4
CFG = mla_moe.mla_moe_tiny(dtype=jnp.float32)
# the reference reads a configuration file's keys
REF_CFG = {"rms_norm_eps": CFG.norm_eps, "rope_theta": CFG.rope_theta,
           "qk_nope_head_dim": CFG.qk_nope_dim,
           "kv_lora_rank": CFG.kv_lora_rank,
           "num_experts_per_tok": CFG.moe_top_k,
           "n_routed_experts": CFG.n_experts, "norm_topk_prob": True,
           "routed_scaling_factor": CFG.routed_scale}


@pytest.fixture(scope="module")
def params():
    p = mla_moe.init_params(jax.random.key(3), CFG)
    # a bias large enough to decide choices among 8 experts: dropping it,
    # or weighing with it, then shows on these few tokens
    for stack in (p["moe_layers"], p["predict"]["block"]):
        stack["router_bias"] = 5.0 * stack["router_bias"]
    assert float(jnp.abs(p["moe_layers"]["router_bias"]).min()) > 0
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n)


def test_forward_matches_the_reference(params):
    toks = _tokens(40)
    logits, (predict,) = mla_moe.forward(params, jnp.asarray(toks)[None], CFG,
                                         return_predict=True)
    ref = reference.forward(params, toks, REF_CFG, predict=True)
    assert np.abs(np.asarray(logits[0]) - ref["logits"]).max() < LOGIT_TOL
    assert np.abs(np.asarray(predict[0])
                  - ref["predict_logits"]).max() < LOGIT_TOL
    # the prediction block is its own block: not the main head again
    assert np.abs(np.asarray(predict[0])
                  - np.asarray(logits[0, :-1])).max() > 0.1


def test_bfloat16_fails_the_float32_tolerance(params):
    toks = _tokens(40)
    cfg = mla_moe.mla_moe_tiny(dtype=jnp.bfloat16)
    logits = mla_moe.forward(params, jnp.asarray(toks)[None], cfg)
    ref = reference.forward(params, toks, REF_CFG)
    assert np.abs(np.asarray(logits[0]) - ref["logits"]).max() > 20 * LOGIT_TOL


@pytest.mark.parametrize("broken", ["bias", "scale", "sqrt", "kv_norm"])
def test_a_wrong_router_norm_or_scale_fails(params, broken, monkeypatch):
    """What the tolerance must catch: the bias dropped from the choice, the
    2.5 left out, sqrt(d_nope) for sqrt(d_nope + d_rope), a wrong N_kv."""
    toks = _tokens(40)
    cfg, p = CFG, dict(params)
    if broken == "bias":
        p["moe_layers"] = dict(p["moe_layers"], router_bias=jnp.zeros_like(
            p["moe_layers"]["router_bias"]))
    elif broken == "scale":
        cfg = mla_moe.mla_moe_tiny(dtype=jnp.float32, routed_scale=1.0)
    elif broken == "sqrt":
        monkeypatch.setattr(mla_moe, "_scale",
                            lambda c: float(c.qk_nope_dim) ** -0.5)
    elif broken == "kv_norm":
        p["moe_layers"] = dict(p["moe_layers"], kv_norm=2.0 * jnp.ones_like(
            p["moe_layers"]["kv_norm"]))
    wrong = mla_moe.forward(p, jnp.asarray(toks)[None], cfg)
    ref = reference.forward(params, toks, REF_CFG)
    assert np.abs(np.asarray(wrong[0]) - ref["logits"]).max() > 50 * LOGIT_TOL


def test_absorbed_attention_is_the_non_absorbed(params):
    """``q_lat . c`` for ``q_nope . (W_uk c)`` and ``W_uv (sum p c)`` for
    ``sum p (W_uv c)``: the same numbers in another order (float32: 1e-5)."""
    lp = jax.tree.map(lambda a: a[1], params["moe_layers"])
    x = jax.random.normal(jax.random.key(0), (1, 24, CFG.dim))
    q_nope, q_rope, rows = mla_moe.queries_and_row(
        lp, x, jnp.arange(24)[None], CFG)
    plain = mla_moe.causal_attention(lp, q_nope[0], q_rope[0], rows[0], CFG)
    q_abs = mla_moe.absorb_queries(lp, q_nope, q_rope, CFG)[0]    # [S, H, R]
    s = jnp.einsum("qhr,tr->hqt", q_abs, rows[0]) * mla_moe._scale(CFG)
    s = jnp.where(jnp.tril(jnp.ones((24, 24), bool))[None], s, -1e30)
    o_lat = jnp.einsum("hqt,tc->qhc", jax.nn.softmax(s, -1),
                       rows[0, :, :CFG.kv_lora_rank])
    absorbed = mla_moe.values_from_latent(lp, o_lat[None], CFG)[0]
    assert np.abs(np.asarray(plain) - np.asarray(absorbed)).max() < 2e-5


@pytest.mark.parametrize("idle", [False, True])
def test_routed_path_is_the_loop_over_all_experts(idle):
    """Sorted assignments through grouped products against the dense-gate
    loop, with pad rows masked: the same sums in another order."""
    rc = moe.RouterConfig(n_experts=8, top_k=2, score_func="sigmoid",
                          select_bias=True, norm_topk=True, scale=2.5)
    ks = jax.random.split(jax.random.key(5), 6)
    t, d, m = 13, 16, 8
    x = jax.random.normal(ks[0], (t, d))
    router = 0.3 * jax.random.normal(ks[1], (d, 8))
    bias = 0.1 * jax.random.normal(ks[2], (8,))
    w_gate, w_up = (0.3 * jax.random.normal(k, (8, d, m)) for k in ks[3:5])
    w_down = 0.3 * jax.random.normal(ks[5], (8, m, d))
    experts, weights = moe.route(x, router, bias, rc)
    # selection by s + b, weights from s: normalised and scaled
    s = jax.nn.sigmoid(x @ router)
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(
        np.asarray(jax.lax.top_k(s + bias, 2)[1]), -1))
    picked = np.take_along_axis(np.asarray(s), np.asarray(experts), -1)
    assert np.allclose(weights, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    valid = jnp.arange(t) % 3 != 0 if idle else None
    y, counts = jax.jit(moe.routed_experts)(x, experts, weights, w_gate, w_up,
                                            w_down, valid)
    want = moe.all_experts(x, experts, weights, w_gate, w_up, w_down, valid)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5
    live = t if valid is None else int(valid.sum())
    assert int(counts.sum()) == 2 * live            # no token dropped
    if idle:
        assert not np.asarray(y)[::3].any()         # pad rows give zeros


@pytest.mark.parametrize("lens", [(1, 8, 9), (16, 17, 64), (63, 5, 33)])
def test_decode_kernel_is_the_gather_path(lens):
    """Interpret mode against the gather oracle at ragged lengths, on and
    beside block boundaries (block 8), two blocks a grid step."""
    b, h, r, v, bs, nb, nbp = 3, 4, 24, 16, 8, 40, 8
    ks = jax.random.split(jax.random.key(1), 2)
    q = jax.random.normal(ks[0], (b, h, r))
    pool = jax.random.normal(ks[1], (3, nb, bs, r))
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, nb))[:b * nbp].reshape(b, nbp), jnp.int32)
    kv_len = jnp.asarray(lens, jnp.int32)
    out = paged_latent_decode_attention(q, pool, 2, tables, kv_len,
                                        value_dim=v, scale=0.25,
                                        interpret=True)
    view = pool[2][tables].reshape(b, -1, r)
    s = jnp.einsum("bhr,btr->bht", q, view) * 0.25
    s = jnp.where(jnp.arange(nbp * bs)[None, None] < kv_len[:, None, None],
                  s, -1e30)
    want = jnp.einsum("bht,btv->bhv", jax.nn.softmax(s, -1), view[..., :v])
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5


BS = 8
# the kernel's own tiles at this block size (1,024 queries x 1,024 rows)
Q_TILE, _TILE_BLOCKS = _prefill_tiles(1 << 20, BS, 1 << 20)
KV_TILE = _TILE_BLOCKS * BS


@pytest.mark.parametrize("case", [
    dict(name="from the first row", q_start=[0]),
    dict(name="mid-block", q_start=[13]),
    dict(name="several tiles deep", q_start=[2 * KV_TILE + 24]),
    dict(name="ragged final chunk, pad rows past the table", q_start=[200],
         n_tables=(200 + 2 * Q_TILE) // BS - 20),
    dict(name="narrower than a query sub-tile", c=40, q_start=[77]),
    dict(name="narrower than a sublane tile", c=5, q_start=[30]),
    dict(name="two slots, two offsets", q_start=[64, KV_TILE + 391]),
    dict(name="permuted table", q_start=[130], permute=True),
    dict(name="row padded past row_dim", q_start=[96], row=128),
], ids=lambda case: case["name"])
def test_prefill_kernel_is_the_plain_masked_softmax(case):
    """``paged_latent_prefill_attention`` in interpret mode against the plain
    form: gather the slot's rows by its table, up-project them, ONE masked
    float32 softmax over all of them."""
    h, latent, d_n, d_r, d_v, bs = 2, 16, 8, 8, 8, BS
    # two query sub-tiles unless the case says otherwise
    c = case.get("c", 2 * Q_TILE)
    q_start = jnp.asarray(case["q_start"], jnp.int32)
    b = len(case["q_start"])
    row = case.get("row", latent + d_r)
    # one table width, so the cases share a compile; a final chunk's pad
    # rows run past the last table entry
    n_tables = case.get("n_tables", (2 * KV_TILE + 2 * Q_TILE) // BS + 4)
    nb = b * n_tables + 1
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (b, c, h, d_n + d_r))
    pool = jax.random.normal(ks[1], (3, nb, bs, row))
    w_uk = 0.3 * jax.random.normal(ks[2], (latent, h, d_n))
    w_uv = 0.3 * jax.random.normal(ks[3], (latent, h, d_v))
    ids = np.arange(1, nb)
    if case.get("permute"):
        ids = np.random.default_rng(0).permutation(ids)
    tables = jnp.asarray(ids.reshape(b, n_tables), jnp.int32)
    out = paged_latent_prefill_attention(
        q, pool, w_uk, w_uv, 1, tables, q_start, rope_dim=d_r, scale=0.25,
        interpret=True)
    assert out.shape == (b, c, h, d_v)

    rows = pool[1][tables].reshape(b, n_tables * bs, row)
    k = jnp.concatenate([
        jnp.einsum("btc,chk->bthk", rows[..., :latent], w_uk),
        jnp.broadcast_to(rows[:, :, None, latent:latent + d_r],
                         (b, n_tables * bs, h, d_r))], -1)
    v = jnp.einsum("btc,chv->bthv", rows[..., :latent], w_uv)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) * 0.25
    q_pos = q_start[:, None] + jnp.arange(c)[None]
    seen = jnp.arange(n_tables * bs)[None, None] <= q_pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
    want = jnp.einsum("bhqt,bthv->bqhv", p, v)
    # rows at positions the table does not reach (a final chunk's padding)
    # give finite output that nobody reads: compared up to the table's end
    live = np.asarray(q_pos < n_tables * bs)
    assert np.isfinite(np.asarray(out)).all()
    assert (not live.all()) == ("pad rows" in case["name"])
    assert np.abs(np.asarray(out) - np.asarray(want))[live].max() < 2e-5


def test_prefill_roofline_counts_the_causal_pairs_of_the_chunks_that_ran():
    """The benchmark's count for the kernel (``lib/latent_prefill.py``) and
    its reader on a made-up trace: rows x (offset + (rows + 1) / 2) pairs a
    chunk, the chunks whose span ended inside the trace brought to the
    executions the device shows; nothing where the program has no such
    kernel (the parent)."""
    import types

    from lib import latent_prefill, peaks
    from readers import latent_prefill_roofline

    cfg = {"num_attention_heads": 3, "qk_head_dim": 5, "v_head_dim": 2,
           "num_hidden_layers": 2}
    # 4 rows at offset 10 attend 11 + 12 + 13 + 14 rows
    assert latent_prefill.chunk_attention_flops(cfg, 10, 4) \
        == 50 * 3 * 2 * 7 * 2

    def span(t1, offset, prompt):
        return {"name": "prefill.chunk", "t0": t1 - 0.01, "t1": t1,
                "attrs": {"offset": offset, "width": 8,
                          "prompt_tokens": prompt}}

    kernel = {"program": "jit__lambda(1)", "seconds": 1e-9, "count": 6.0,
              "name": "closed_call.26 custom-call bf16[1,3,8,2]"}
    other = [{"program": "jit__lambda(1)", "name": "gmm.13 custom-call",
              "seconds": 5.0, "count": 4.0},
             {"program": "jit__decode_impl(2)", "seconds": 7.0, "count": 9.0,
              "name": "closed_call.72 custom-call bf16[24,32,512]"}]
    run = types.SimpleNamespace(
        trace={"ops": [kernel] + other}, t_trace=(100.0, 103.0), config=cfg,
        device={"kind": "TPU v5 lite"},
        # a whole chunk, a final chunk of 3 true rows, one outside the trace
        spans=[span(100.5, 8, 40), span(101.0, 16, 19), span(99.0, 0, 40)])
    flops = latent_prefill.chunk_attention_flops(cfg, 8, 8) \
        + latent_prefill.chunk_attention_flops(cfg, 16, 3)
    # six executions over two layers: three chunks ran where two spans ended
    want = 100.0 * flops * 3 / 2 / peaks.peaks(
        "TPU v5 lite")["bf16_flops_per_s"] / 1e-9
    args = {"program": "^jit__lambda", "op": r"^closed_call\.\d+ custom-call"}
    assert latent_prefill_roofline.read(run, **args) == pytest.approx(want)
    run.trace = {"ops": other}
    assert latent_prefill_roofline.read(run, **args) is None


def _paged_logits(params, toks, n_prompt, chunk, kernel):
    """Prefill ``toks[:n_prompt]`` in chunks of ``chunk`` through the pool,
    then decode the rest one token a step: logits at rows n_prompt-1 .."""
    bs, nbp = 8, 16
    cache = paged_kv.init_paged_cache(CFG, 2, nbp * bs, bs, 2 * nbp + 1)
    tables = np.zeros((2, nbp), np.int32)
    tables[1] = np.arange(1, nbp + 1)
    tables = jnp.asarray(tables)
    for off in range(0, n_prompt, chunk):
        piece = np.zeros((1, chunk), np.int32)
        part = toks[off:off + chunk][:n_prompt - off]
        piece[0, :len(part)] = part
        x_last, cache, _ = paged_kv.paged_prefill_chunk(
            params, jnp.asarray(piece), CFG, cache, tables, 1, off, n_prompt)
    out = [mla_moe.lm_head(params, x_last, CFG)[0]]
    cache["len"] = cache["len"].at[1].set(n_prompt)
    for t in toks[n_prompt:]:
        logits, cache, _ = paged_kv.paged_decode_step(
            params, jnp.asarray([0, t], jnp.int32), CFG, cache, tables,
            kernel=kernel)
        cache["len"] = cache["len"].at[0].set(0)       # slot 0 stays idle
        out.append(logits[1])
    return np.stack([np.asarray(a) for a in out])


@pytest.mark.parametrize("chunk,kernel,n_prompt", [
    (64, "gather", 41), (16, "gather", 41), (16, "pallas", 41),
    # three chunks of the prefill kernel, the last one ragged, then the
    # decode kernel over what they wrote
    (32, "pallas", 90)])
def test_prefill_and_decode_through_the_pool(params, chunk, kernel, n_prompt):
    """One chunk and several (each through the prefill chunk kernel,
    interpreted), then decode beside an idle slot, against the reference's
    full forward, on logits."""
    toks = _tokens(n_prompt + 9, seed=2)
    got = _paged_logits(params, toks, n_prompt, chunk, kernel)
    ref = reference.forward(params, toks, REF_CFG,
                            rows=range(n_prompt - 1, len(toks)))
    assert np.abs(got[:-1] - ref["logits"][:-1]).max() < LOGIT_TOL


def test_engine_serves_it_and_records_the_routing(params):
    """add_request / step with chunked prefill (chunks of 32) and decode
    chunks of 4: the served tokens are the reference's argmax, their
    logprobs its log-softmax, the recorded experts the reference's own."""
    from kubeflow_tpu.obs.trace import SpanCollector

    obs = SpanCollector(capacity=4096)
    eng = LLMEngine(params, CFG, max_batch=4, max_seq=128,
                    prefill_buckets=(32,), kv_block_size=8, decode_chunk=4,
                    kernel="pallas", obs=obs)
    prompts = [_tokens(n, seed=n).tolist() for n in (5, 37, 70)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=10,
                                                record_routing=True))
    for r in reqs:
        seq = np.asarray(r.prompt + r.generated)
        rows = range(len(r.prompt) - 1, len(seq) - 1)
        ref = reference.forward(params, seq, REF_CFG, rows=rows)
        lp = jax.nn.log_softmax(ref["logits"], -1)
        assert np.array_equal(np.argmax(ref["logits"], -1), r.generated)
        assert np.abs(np.asarray(r.logprobs) - np.take_along_axis(
            np.asarray(lp), np.asarray(r.generated)[:, None], -1)[:, 0]
        ).max() < LOGIT_TOL
        got = np.sort(np.stack(r.routing, 1), -1)     # [layers, rows, k]
        assert np.array_equal(got, np.sort(ref["experts"], -1))
    spans = obs.snapshot()
    decode = [s for s in spans if s["name"] == "decode.step"]
    assert decode and all(
        s["attrs"]["routed_assignments"] > 0
        and 0 < s["attrs"]["experts_hit"] <= s["attrs"]["routed_assignments"]
        for s in decode)
    chunks = [s for s in spans if s["name"] == "prefill.chunk"]
    assert sum(s["attrs"]["routed_assignments"] for s in chunks) \
        == sum(len(p) for p in prompts) * CFG.moe_top_k * CFG.n_moe_layers
    # every routed assignment of every program is in the counter
    assert eng.moe_tokens_per_expert.shape == (CFG.n_moe_layers,
                                               CFG.n_experts)
    assert eng.moe_tokens_per_expert.sum() == sum(
        s["attrs"]["routed_assignments"] for s in decode + chunks)
    assert eng.kv_row_bytes() == CFG.n_layers * CFG.pool_row * 4


@pytest.mark.parametrize("broken", [None, "bias", "scale", "sqrt", "kv_norm",
                                    "float8"])
def test_the_benchmarks_check_passes_the_program_and_fails_a_broken_one(
        params, broken, monkeypatch):
    """The two functions of ``benchmarks/drivers/latent.py`` that decide
    ``correct`` on the chip, on this engine: the checked prompts are served
    beside requests that hold the other slots (one spans several chunks),
    teacher-forced through the reference with the engine's recorded routing.
    The control the chip runs of PR 29 made by hand, kept where it can be
    run again: the same check must fail a program with the bias left out of
    the choice, the 2.5 left out, sqrt(d_nope) in the scores, a wrong N_kv,
    the residual stream rounded to float8_e4m3 where a layer reads it."""
    from drivers import latent

    cfg, p = CFG, dict(params)
    if broken == "bias":
        p["moe_layers"] = dict(p["moe_layers"], router_bias=jnp.zeros_like(
            p["moe_layers"]["router_bias"]))
    elif broken == "scale":
        cfg = mla_moe.mla_moe_tiny(dtype=jnp.float32, routed_scale=1.0)
    elif broken == "sqrt":
        monkeypatch.setattr(mla_moe, "_scale",
                            lambda c: float(c.qk_nope_dim) ** -0.5)
    elif broken == "kv_norm":
        p["moe_layers"] = dict(p["moe_layers"], kv_norm=2.0 * jnp.ones_like(
            p["moe_layers"]["kv_norm"]))
    elif broken == "float8":
        for name in ("queries_and_row", "attention_out_and_ffn"):
            fn = getattr(mla_moe, name)
            monkeypatch.setattr(
                mla_moe, name, lambda lp, x, *a, _fn=fn, **kw: _fn(
                    lp, x.astype(jnp.float8_e4m3fn).astype(x.dtype), *a,
                    **kw))
    from kubeflow_tpu.obs.trace import SpanCollector

    eng = LLMEngine(p, cfg, max_batch=6, max_seq=192, prefill_buckets=(32,),
                    kv_block_size=8, decode_chunk=4, kernel="pallas",
                    obs=SpanCollector(capacity=4096))
    for n in (50, 9, 77):          # the backlog: they outlive the check
        eng.add_request(_tokens(n, seed=n).tolist(),
                        SamplingParams(max_tokens=100))
    spec = {"prompt_lens": [70, 33, 5], "max_tokens": 16, "route_tol": 0.03}
    reqs = latent._serve_checked(eng, CFG.vocab_size, spec, 11, print)
    assert eng.has_work()          # served beside live requests
    out = latent._compare(reqs, params, dict(REF_CFG), spec, print)
    assert out["tokens_checked"] == 48 and out["routed_rows_compared"] == 192
    assert out["ok"] is (broken is None), out


@pytest.mark.parametrize("mechanism,kwargs", [
    ("int8 weights", dict(quant=QuantConfig(weight_dtype="int8"))),
    ("quantized KV pool", dict(quant=QuantConfig(kv_dtype="int8"))),
    ("speculative decode",
     dict(scheduler=SchedulerConfig(spec_decode=True))),
    ("tensor mesh", dict(mesh="tensor")),
])
def test_what_the_model_cannot_be_served_with_is_refused(params, mechanism,
                                                         kwargs):
    if kwargs.get("mesh"):
        from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

        kwargs = dict(mesh=build_mesh(MeshConfig(tensor=1),
                                      devices=jax.devices()[:1]))
    with pytest.raises(ValueError, match=mechanism):
        LLMEngine(params, CFG, max_batch=2, max_seq=64,
                  prefill_buckets=(32,), **kwargs)


def test_quantize_weights_refuses_the_experts(params):
    from kubeflow_tpu.serving.quant import quantize_weights, resolve_quant

    _, downgrades = resolve_quant(QuantConfig(weight_dtype="int8"), cfg=CFG)
    assert downgrades and "expert" in downgrades[0][1]
    with pytest.raises(ValueError, match="MoE"):
        quantize_weights(params, CFG)


def test_reference_is_the_public_implementation_of_the_family():
    """The keys are DeepSeek-V3's: where ``transformers`` has that model,
    the reference's ``assumed`` (N_q, N_kv, interleaved RoPE, float32
    sigmoid router with the bias in the choice only) is checked against
    it, weights copied over. The prediction block is not in it."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV3")
    cfg = mla_moe.mla_moe_tiny(dtype=jnp.float32, n_predict_layers=0)
    p = mla_moe.init_params(jax.random.key(7), cfg)
    hf_cfg = transformers.DeepseekV3Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.dim,
        intermediate_size=cfg.mlp_dim, moe_intermediate_size=cfg.moe_mlp_dim,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_heads, n_shared_experts=1,
        n_routed_experts=cfg.n_experts, routed_scaling_factor=2.5,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_rope_head_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        qk_nope_head_dim=cfg.qk_nope_dim, n_group=1, topk_group=1,
        num_experts_per_tok=cfg.moe_top_k, first_k_dense_replace=1,
        norm_topk_prob=True, rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, rope_scaling=None, rope_interleave=True,
        max_position_embeddings=256, tie_word_embeddings=False,
        attention_bias=False, attn_implementation="eager")
    model = transformers.DeepseekV3ForCausalLM(hf_cfg).eval()

    def t(a):                      # ours [in, out] -> torch [out, in]
        return torch.tensor(np.asarray(a, np.float32).T.copy())

    sd = {"model.embed_tokens.weight": torch.tensor(np.asarray(p["embed"])),
          "model.norm.weight": torch.tensor(np.asarray(p["final_norm"])),
          "lm_head.weight": t(p["lm_head"])}
    n = 0
    for stack in (p["dense_layers"], p["moe_layers"]):
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            lp = jax.tree.map(lambda a: np.asarray(a[i], np.float32), stack)
            pre = f"model.layers.{n}."
            sd[pre + "input_layernorm.weight"] = torch.tensor(lp["attn_norm"])
            sd[pre + "post_attention_layernorm.weight"] = torch.tensor(
                lp["mlp_norm"])
            a = pre + "self_attn."
            sd[a + "q_a_proj.weight"] = t(lp["w_dq"])
            sd[a + "q_a_layernorm.weight"] = torch.tensor(lp["q_norm"])
            sd[a + "q_b_proj.weight"] = t(lp["w_uq"].reshape(
                cfg.q_lora_rank, -1))
            sd[a + "kv_a_proj_with_mqa.weight"] = t(lp["w_dkv"])
            sd[a + "kv_a_layernorm.weight"] = torch.tensor(lp["kv_norm"])
            sd[a + "kv_b_proj.weight"] = t(np.concatenate(
                [lp["w_uk"], lp["w_uv"]], -1).reshape(cfg.kv_lora_rank, -1))
            sd[a + "o_proj.weight"] = t(lp["wo"].reshape(-1, cfg.dim))
            m = pre + "mlp."
            if "router" in lp:
                sd[m + "gate.weight"] = t(lp["router"])
                sd[m + "gate.e_score_correction_bias"] = torch.tensor(
                    lp["router_bias"])
                for e in range(cfg.n_experts):
                    for ours, theirs in (("w_gate", "gate_proj"),
                                         ("w_up", "up_proj"),
                                         ("w_down", "down_proj")):
                        sd[f"{m}experts.{e}.{theirs}.weight"] = t(lp[ours][e])
                for ours, theirs in (("ws_gate", "gate_proj"),
                                     ("ws_up", "up_proj"),
                                     ("ws_down", "down_proj")):
                    sd[f"{m}shared_experts.{theirs}.weight"] = t(lp[ours])
            else:
                for ours, theirs in (("w_gate", "gate_proj"),
                                     ("w_up", "up_proj"),
                                     ("w_down", "down_proj")):
                    sd[f"{m}{theirs}.weight"] = t(lp[ours])
            n += 1
    missing = model.load_state_dict(sd, strict=False)
    assert not missing.unexpected_keys and all(
        "rotary" in k for k in missing.missing_keys), missing
    toks = _tokens(33, seed=9)
    with torch.no_grad():
        theirs = model(torch.tensor(toks[None])).logits[0].numpy()
    ours = reference.forward(p, toks, REF_CFG)["logits"]
    assert np.abs(theirs - np.asarray(ours)).max() < LOGIT_TOL
