"""Latent attention + many small routed experts: the JoyAI-LLM-Flash block
(the DeepSeek-V3 family's keys: ``kv_lora_rank``, ``q_lora_rank``,
``n_routed_experts``, ``scoring_func``, ``topk_method: noaux_tc``,
``num_nextn_predict_layers``).

The layer equations (``u`` is the normed input, every norm an RMSNorm):

    x = E[token];  h = x + Attn(N_1(x));  y = h + FFN(N_2(h))
    logits = N_f(y_last) W_head                      (untied head)

Attention (MLA), ``H`` heads, ``d_n`` = qk_nope_dim, ``d_r`` = qk_rope_dim:

    c_q = N_q(W_dq u)                      [q_lora_rank]
    q   = W_uq c_q                         H x (d_n + d_r): q_nope, q_rope
    [c_kv ; k_r] = W_dkv u                 [kv_lora_rank + d_r]
    c = N_kv(c_kv);  k_rope = RoPE(k_r)    ONE per token, shared by heads
    q_rope = RoPE(q_rope)
    k_nope,h = W_uk,h c;  v_h = W_uv,h c
    s = (q_nope . k_nope + q_rope . k_rope) / sqrt(d_n + d_r)
    o_h = sum softmax(s) v_h;  Attn = W_o [o_1 .. o_H]

The same, absorbed (decode): ``q_lat,h = W_uk,h^T q_nope,h``; ``s = (q_lat,h
. c + q_rope,h . k_rope) / sqrt(d_n + d_r)``; ``o_lat,h = sum p c``; ``o_h
= W_uv,h o_lat,h``. So the cache holds ONE row ``[c ; k_rope]`` a token a
layer (``row_dim`` = 576 values for 512 + 64), ``W_uk`` is applied before
the walk over it and ``W_uv`` after.

RoPE with ``rope_interleave``: the checkpoint's rotary pairs are (2i,
2i+1). They are de-interleaved into (first half, second half) and rotated
as halves, queries and the key alike, so every score is what rotating the
interleaved pairs gives; the rotated vectors stay in the half-split order
(what the family's public implementation does).

FFN: the first ``n_dense_layers`` layers a SwiGLU of width ``mlp_dim``; the
rest ``sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)`` of width ``moe_mlp_dim``:
``s = sigmoid(W_r u)`` in float32, the choice the plain top-k of ``s + b``
(``b``: the ``noaux_tc`` correction bias; ``n_group = topk_group = 1``),
the weights ``s`` at the chosen experts without ``b``, divided by their
sum, times ``routed_scale``. No token is dropped (``parallel/moe.py``,
the routed path).

Multi-token prediction (``n_predict_layers`` blocks after the last layer):
``h'_i = W_p [N_h(h_i) ; N_e(E[t_{i+1}])]`` with ``h_i`` the last layer's
output (before the final norm), one more block of the expert kind, its own
final norm and the shared head: logits for ``t_{i+2}``. ``forward`` can
return them; the engine does not draft with them yet.

Layout: the leading dense layers and the expert layers are two stacks
(``dense_layers``, ``moe_layers``), each scanned, so depth compiles once
and the serving programs carry the pool through both (``paged_ops``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.parallel import moe

NEG_INF = -1e30
EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
LANES = 128                      # the chip tiles a pool's last dimension


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 40               # dense + expert layers
    n_dense_layers: int = 1          # first_k_dense_replace
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 7168              # the dense layers' SwiGLU
    moe_mlp_dim: int = 768           # one expert's SwiGLU
    n_experts: int = 256             # routed
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scale: float = 2.5
    norm_topk: bool = True
    score_func: str = "sigmoid"
    n_predict_layers: int = 1        # multi-token-prediction blocks
    max_seq: int = 131072
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def row_dim(self) -> int:
        """Values one token caches per layer: the latent and the key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def pool_row(self) -> int:
        """The pool's row: ``row_dim`` padded with zeros to whole lane
        tiles. A 576-wide minor dimension is padded to 640 by the chip's
        tiling anyway, and left to itself XLA stores such an array in a
        compact transposed layout that it copies, whole, in front of every
        kernel call (PERF.md section 6, PR 29)."""
        return -(-self.row_dim // LANES) * LANES

    @property
    def n_kv_heads(self) -> int:
        return 1                     # one shared row: nothing to shard on

    def router_config(self) -> moe.RouterConfig:
        return moe.RouterConfig(
            n_experts=self.n_experts, top_k=self.moe_top_k,
            score_func=self.score_func, select_bias=True,
            norm_topk=self.norm_topk, scale=self.routed_scale)

    def paged_ops(self):
        """What ``serving/paged_kv.py`` writes its programs over."""
        return _paged_ops(self)


def mla_moe_tiny(**kw) -> MlaMoeConfig:
    """CI config: every mechanism present, runs on the CPU in seconds."""
    base = dict(vocab_size=256, dim=64, n_layers=5, n_dense_layers=1,
                n_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8,
                qk_rope_dim=8, v_head_dim=8, mlp_dim=128, moe_mlp_dim=32,
                n_experts=8, moe_top_k=2, max_seq=256, rope_theta=10000.0)
    base.update(kw)
    return MlaMoeConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _init_layers(rng, cfg: MlaMoeConfig, n: int, experts: bool, dtype):
    """``n`` stacked layers: attention, then the dense or the expert FFN."""
    d, h = cfg.dim, cfg.n_heads
    ks = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), (n, *shape), jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    lp = {
        "attn_norm": jnp.ones((n, d), dtype),
        "mlp_norm": jnp.ones((n, d), dtype),
        "w_dq": dense((d, cfg.q_lora_rank), d),
        "q_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
        "w_uq": dense((cfg.q_lora_rank, h, cfg.qk_head_dim),
                      cfg.q_lora_rank),
        "w_dkv": dense((d, cfg.row_dim), d),
        "kv_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
        "w_uk": dense((cfg.kv_lora_rank, h, cfg.qk_nope_dim),
                      cfg.kv_lora_rank),
        "w_uv": dense((cfg.kv_lora_rank, h, cfg.v_head_dim),
                      cfg.kv_lora_rank),
        "wo": dense((h, cfg.v_head_dim, d), h * cfg.v_head_dim),
    }
    if not experts:
        m = cfg.mlp_dim
        lp.update(w_gate=dense((d, m), d), w_up=dense((d, m), d),
                  w_down=dense((m, d), m))
        return lp
    e, m = cfg.n_experts, cfg.moe_mlp_dim
    ms = m * cfg.n_shared_experts
    lp.update(
        router=dense((d, e), d),
        # a trained checkpoint's correction bias is not zero: seeded, so
        # that a program which drops it or weighs with it is wrong, and
        # small, as one that has done its work of levelling the load is
        # (0.1 here makes the busiest expert ten times the mean's)
        router_bias=0.02 * jax.random.normal(next(ks), (n, e), jnp.float32),
        w_gate=dense((e, d, m), d), w_up=dense((e, d, m), d),
        w_down=dense((e, m, d), m),
        ws_gate=dense((d, ms), d), ws_up=dense((d, ms), d),
        ws_down=dense((ms, d), ms))
    return lp


def init_params(rng: jax.Array, cfg: MlaMoeConfig, dtype=jnp.float32):
    k_embed, k_dense, k_moe, k_head, k_mtp = jax.random.split(rng, 5)
    d = cfg.dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    params = {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "dense_layers": _init_layers(k_dense, cfg, cfg.n_dense_layers,
                                     False, dtype),
        "moe_layers": _init_layers(k_moe, cfg, cfg.n_moe_layers, True,
                                   dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }
    if cfg.n_predict_layers:
        p = cfg.n_predict_layers
        kp, kb = jax.random.split(k_mtp)
        params["predict"] = {
            "h_norm": jnp.ones((p, d), dtype),
            "e_norm": jnp.ones((p, d), dtype),
            "w_proj": dense(kp, (p, 2 * d, d), 2 * d),
            "block": _init_layers(kb, cfg, p, True, dtype),
            "final_norm": jnp.ones((p, d), dtype),
        }
    return params


def _layer_axes(experts: bool):
    ax = {
        "attn_norm": ("layers", "embed"), "mlp_norm": ("layers", "embed"),
        "w_dq": ("layers", "embed", None), "q_norm": ("layers", None),
        "w_uq": ("layers", None, "heads", "head_dim"),
        "w_dkv": ("layers", "embed", None), "kv_norm": ("layers", None),
        "w_uk": ("layers", None, "heads", "head_dim"),
        "w_uv": ("layers", None, "heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
    }
    if not experts:
        ax.update(w_gate=("layers", "embed", "mlp"),
                  w_up=("layers", "embed", "mlp"),
                  w_down=("layers", "mlp", "embed"))
        return ax
    ax.update(router=("layers", "embed", None), router_bias=("layers", None),
              w_gate=("layers", "expert", "embed", "mlp"),
              w_up=("layers", "expert", "embed", "mlp"),
              w_down=("layers", "expert", "mlp", "embed"),
              ws_gate=("layers", "embed", "mlp"),
              ws_up=("layers", "embed", "mlp"),
              ws_down=("layers", "mlp", "embed"))
    return ax


def param_logical_axes(cfg: MlaMoeConfig):
    """Logical axis names per param, mirroring ``init_params``."""
    axes = {"embed": ("vocab", "embed"), "dense_layers": _layer_axes(False),
            "moe_layers": _layer_axes(True), "final_norm": ("embed",),
            "lm_head": ("embed", "vocab")}
    if cfg.n_predict_layers:
        axes["predict"] = {
            "h_norm": ("layers", "embed"), "e_norm": ("layers", "embed"),
            "w_proj": ("layers", None, "embed"),
            "block": _layer_axes(True), "final_norm": ("layers", "embed")}
    return axes


# ---------------------------------------------------------------------------
# The layer's pieces (shared by forward and the paged programs)
# ---------------------------------------------------------------------------

def _inv_freq(cfg: MlaMoeConfig):
    return jnp.asarray(rope_frequencies(cfg.qk_rope_dim, cfg.rope_theta,
                                        scaling=None))


def _rope_interleaved(x, positions, inv_freq):
    """x [..., S, heads, d_r] with rotary pairs (2i, 2i+1): de-interleave
    to halves, rotate as halves (module docstring)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, inv_freq)


def queries_and_row(lp, x, positions, cfg: MlaMoeConfig):
    """The attention inputs of x [B, S, D] at ``positions`` [B|1, S]:
    (q_nope [B, S, H, d_n], q_rope [B, S, H, d_r] rotated, row [B, S,
    row_dim] = ``[c ; k_rope]``, what a token caches)."""
    dt = cfg.dtype
    inv_freq = _inv_freq(cfg)
    u = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = rms_norm(jnp.einsum("bsd,dr->bsr", u, lp["w_dq"].astype(dt)),
                   lp["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, lp["w_uq"].astype(dt))
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = _rope_interleaved(q_rope, positions, inv_freq)
    ckv = jnp.einsum("bsd,dr->bsr", u, lp["w_dkv"].astype(dt))
    c = rms_norm(ckv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = _rope_interleaved(ckv[..., None, cfg.kv_lora_rank:], positions,
                               inv_freq)[..., 0, :]
    return q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1)


def absorb_queries(lp, q_nope, q_rope, cfg: MlaMoeConfig, width=None):
    """[q_lat ; q_rope] per head, [B, S, H, width]: what is scored against
    a cached row (zeros beyond ``row_dim`` where the pool's row is
    padded)."""
    q_lat = jnp.einsum("bshk,chk->bshc", q_nope,
                       lp["w_uk"].astype(cfg.dtype))
    parts = [q_lat, q_rope]
    pad = (width or cfg.row_dim) - cfg.row_dim
    if pad:
        parts.append(jnp.zeros((*q_rope.shape[:-1], pad), q_rope.dtype))
    return jnp.concatenate(parts, axis=-1)


def values_from_latent(lp, o_lat, cfg: MlaMoeConfig):
    """o_lat [B, S, H, kv_lora_rank] -> o [B, S, H, v_head_dim]."""
    return jnp.einsum("bshc,chv->bshv", o_lat, lp["w_uv"].astype(cfg.dtype))


def keys_values_from_rows(lp, rows, cfg: MlaMoeConfig):
    """rows [T, >= row_dim] -> per-head (k_nope [T, H, d_n], k_rope [T,
    d_r], v [T, H, v_head_dim]): the non-absorbed form."""
    dt = cfg.dtype
    c = rows[:, :cfg.kv_lora_rank]
    k_nope = jnp.einsum("tc,chk->thk", c, lp["w_uk"].astype(dt))
    v = jnp.einsum("tc,chv->thv", c, lp["w_uv"].astype(dt))
    return k_nope, rows[:, cfg.kv_lora_rank:cfg.row_dim], v


def _scale(cfg: MlaMoeConfig) -> float:
    return float(cfg.qk_head_dim) ** -0.5


def _scores(q_nope, q_rope, k_nope, k_rope, cfg: MlaMoeConfig):
    """[H, Q, T] float32 scores of q_* [Q, H, *] against k_nope [T, H, d_n]
    and the shared k_rope [T, d_r], as ONE product over all d_n + d_r
    values: the shared key repeated per head (a few MB) instead of a
    second, head-less contraction (which the chip runs as a dilated
    convolution)."""
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], (*k_nope.shape[:2], k_rope.shape[-1]))], -1)
    return jnp.einsum("qhk,thk->hqt", jnp.concatenate([q_nope, q_rope], -1),
                      k, preferred_element_type=jnp.float32) * _scale(cfg)


def causal_attention(lp, q_nope, q_rope, rows, cfg: MlaMoeConfig):
    """Non-absorbed causal attention of one sequence over its own rows:
    q_* [S, H, *], rows [S, row_dim] -> o [S, H, v_head_dim]."""
    k_nope, k_rope, v = keys_values_from_rows(lp, rows, cfg)
    s = _scores(q_nope, q_rope, k_nope, k_rope, cfg)
    n = rows.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqt,thv->qhv", p.astype(v.dtype), v)


def routed_ffn(lp, u, cfg: MlaMoeConfig, token_mask=None):
    """The expert layer's FFN on normed u [B, S, D]: (delta [B, S, D],
    tokens_per_expert [E] int32, experts [B, S, k]). ``token_mask`` [B, S]
    keeps pad and idle rows out of the routed product (they multiply
    nothing)."""
    b, s, d = u.shape
    dt = cfg.dtype
    tokens = u.reshape(b * s, d)
    valid = None if token_mask is None else token_mask.reshape(b * s)
    experts, weights = moe.route(tokens, lp["router"], lp["router_bias"],
                                 cfg.router_config())
    w = [lp[key] for key in EXPERT_MATRICES]
    first = 0
    if w[0].ndim == 4:
        # the whole stack [layers, E, ...] and this layer's place in it
        # (``paged_kv._scan_layers``): never a slice of it
        w = [a.reshape(-1, *a.shape[2:]) for a in w]
        first = lp["stack_index"] * cfg.n_experts
    y, counts = moe.routed_experts(tokens, experts, weights, *w, valid=valid,
                                   n_experts=cfg.n_experts,
                                   first_group=first)
    shared = (jax.nn.silu(tokens @ lp["ws_gate"].astype(dt))
              * (tokens @ lp["ws_up"].astype(dt))) @ lp["ws_down"].astype(dt)
    return ((y + shared).reshape(b, s, d), counts,
            experts.reshape(b, s, -1))


def attention_out_and_ffn(lp, x, o, cfg: MlaMoeConfig, token_mask=None):
    """What follows attention: ``W_o``, the residual, the FFN of the
    layer's kind (``router`` among its weights: the expert layer).
    Returns (x, stats): the expert layer's counts, nothing for a dense
    one."""
    dt = cfg.dtype
    x = x + jnp.einsum("bshv,hvd->bsd", o, lp["wo"].astype(dt))
    u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "router" in lp:
        delta, counts, experts = routed_ffn(lp, u, cfg, token_mask)
        return x + delta, {"tokens_per_expert": counts,
                           "experts_hit": jnp.sum(counts > 0),
                           "experts": experts}
    ff = jax.nn.silu(jnp.einsum("bsd,dm->bsm", u, lp["w_gate"].astype(dt))) \
        * jnp.einsum("bsd,dm->bsm", u, lp["w_up"].astype(dt))
    return x + jnp.einsum("bsm,md->bsd", ff, lp["w_down"].astype(dt)), {}


def lm_head(params, x_last, cfg: MlaMoeConfig, final_norm=None):
    """x_last [B, D] before the final norm -> logits [B, V] float32."""
    x_last = rms_norm(x_last, params["final_norm"] if final_norm is None
                      else final_norm, cfg.norm_eps)
    return jnp.einsum("bd,dv->bv", x_last,
                      params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block(lp, x, positions, cfg: MlaMoeConfig):
    q_nope, q_rope, rows = queries_and_row(lp, x, positions, cfg)
    o = jax.vmap(lambda qn, qr, r: causal_attention(lp, qn, qr, r, cfg))(
        q_nope, q_rope, rows)
    return attention_out_and_ffn(lp, x, o, cfg)[0]


def forward(params, tokens, cfg: MlaMoeConfig, return_predict: bool = False):
    """Full-sequence forward, non-absorbed attention. tokens [B, S] ->
    logits [B, S, V] float32; with ``return_predict`` also the prediction
    blocks' logits [P, B, S-1, V] (row i of block 0 predicts token i+2)."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = params["embed"].astype(cfg.dtype)[tokens]
    for stack in (params["dense_layers"], params["moe_layers"]):
        x, _ = jax.lax.scan(
            lambda x, lp: (_block(lp, x, positions, cfg), None), x, stack)

    def head(x, final_norm):
        return lm_head(params, x.reshape(-1, cfg.dim), cfg,
                       final_norm).reshape(*x.shape[:2], -1)

    logits = head(x, params["final_norm"])
    if not return_predict:
        return logits
    out, h = [], x
    for k in range(cfg.n_predict_layers):
        pp = jax.tree.map(lambda a: a[k], params["predict"])
        # block k sees h_i and the token k+1 ahead: rows 0 .. S-2-k
        h = h[:, :-1]
        nxt = params["embed"].astype(cfg.dtype)[tokens[:, k + 1:]]
        h = jnp.einsum(
            "bsd,de->bse",
            jnp.concatenate([rms_norm(h, pp["h_norm"], cfg.norm_eps),
                             rms_norm(nxt, pp["e_norm"], cfg.norm_eps)], -1),
            pp["w_proj"].astype(cfg.dtype))
        h = _block(pp["block"], h, positions[:, :h.shape[1]], cfg)
        out.append(head(h, pp["final_norm"]))
    return logits, out


# ---------------------------------------------------------------------------
# The serving programs' view of the model (models/paged.PagedOps)
# ---------------------------------------------------------------------------

def _paged_ops(cfg: MlaMoeConfig):
    from kubeflow_tpu.models.paged import PagedOps

    def layer_stacks(params):
        # the routed experts' matrices are handed over whole: the grouped
        # products address them by (layer, expert)
        return [(params["dense_layers"], ()),
                (params["moe_layers"], EXPERT_MATRICES)]

    def embed(params, tokens):
        return params["embed"].astype(cfg.dtype)[tokens]

    def qkv(lp, x, positions, state):
        q_nope, q_rope, row = queries_and_row(lp, x, positions, cfg)
        pad = cfg.pool_row - cfg.row_dim
        if pad:
            row = jnp.concatenate(
                [row, jnp.zeros((*row.shape[:-1], pad), row.dtype)], -1)
        return (q_nope, q_rope), {"kv": row}, state    # no per-slot rows

    def decode_attention(lp, q, pools, layer, tables, kv_len, kernel, mesh,
                         interpret):
        del mesh                                  # refused by the engine
        q_abs = absorb_queries(lp, *q, cfg, width=cfg.pool_row)[:, 0]
        pool = pools["kv"]
        if kernel == "pallas":
            from kubeflow_tpu.ops.pallas_paged_attention import (
                paged_latent_decode_attention,
            )

            o_lat = paged_latent_decode_attention(
                q_abs, pool, layer, tables, kv_len,
                value_dim=cfg.kv_lora_rank, scale=_scale(cfg),
                interpret=interpret)
        else:
            view = pool[layer, tables].reshape(tables.shape[0], -1,
                                               pool.shape[-1])
            s = jnp.einsum("bhr,btr->bht", q_abs, view,
                           preferred_element_type=jnp.float32) * _scale(cfg)
            live = jnp.arange(view.shape[1])[None, :] < kv_len[:, None]
            p = jax.nn.softmax(jnp.where(live[:, None], s, NEG_INF), -1)
            o_lat = jnp.einsum("bht,btc->bhc", p.astype(view.dtype),
                               view[..., :cfg.kv_lora_rank])
        return values_from_latent(lp, o_lat[:, None], cfg)

    def out(lp, x, o, token_mask, carry):
        x, stats = attention_out_and_ffn(lp, x, o, cfg, token_mask)
        return x, carry, stats

    def chunk_attention(lp, q, pools, layer, tables, q_start):
        """q of [B, C] rows at positions ``q_start[b] + i`` over each
        slot's blocks (its own rows already scattered): the non-absorbed
        form as one flash kernel over the paged pool, interpreted where
        there is no TPU."""
        from kubeflow_tpu.ops.pallas_paged_attention import (
            paged_latent_prefill_attention,
        )

        return paged_latent_prefill_attention(
            jnp.concatenate(q, axis=-1), pools["kv"], lp["w_uk"], lp["w_uv"],
            layer, tables, q_start, rope_dim=cfg.qk_rope_dim,
            scale=_scale(cfg), interpret=jax.default_backend() != "tpu")

    return PagedOps(
        n_layers=cfg.n_layers,
        pool_rows={"kv": (cfg.pool_row,)},
        layer_stacks=layer_stacks, embed=embed, qkv=qkv,
        decode_attention=decode_attention, chunk_attention=chunk_attention,
        out=out,
        head=lambda params, x_last: lm_head(params, x_last, cfg),
        routed_per_token=cfg.moe_top_k * cfg.n_moe_layers,
        refuses={
            "quantized KV pool": "the latent row has no per-head scale "
                                 "table; a quantized latent pool is not "
                                 "written",
            "int8 weights": "the routed experts' grouped products are not "
                            "int8-lowered",
            "speculative decode": "paged_verify_step has no latent form; "
                                  "the prediction block is not driven as "
                                  "a drafter",
            "tensor mesh": "one cached row is shared by all heads: there "
                           "is no kv-head dimension to shard the pool on",
        })
