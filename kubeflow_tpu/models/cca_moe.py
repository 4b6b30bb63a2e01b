"""Compressed convolutional attention + top-1 experts behind an MLP router
that carries state from layer to layer: the ZAYA1 block (``model_type:
zaya``; keys ``cca_time0``, ``cca_time1``, ``router_hidden_size``,
``num_experts``, ``partial_rotary_factor``).

Every layer is an attention half and an expert half, each merged into the
residual stream by learned per-channel scales (no plain ``x + y``). Layer
``l``, token ``t``, ``H`` query heads over ``KV`` = 2 key/value heads of
width ``d``, ``g(i) = i // (H / KV)``, every norm an RMSNorm:

Attention half (CCA: queries and keys are mixed over time):

    h_t  = N_a(x_t)
    q~_t = h_t W_q   [H, d];   k~_t = h_t W_k   [KV, d]         (no bias)
    v_t  = [ h_t W_v1 ; h_{t-1} W_v2 ]      KV head 0 reads the current
           token, KV head 1 the previous one (the value shift); h_{-1} = 0
    u_t  = [q~_t ; k~_t]                     (H + KV) d = ``mix_dim`` values
    a_t  = w0[1] * u_t + w0[0] * u_{t-1} + b0          depthwise, kernel 2
    c_t  = W1[1] a_t + W1[0] a_{t-1} + b1              grouped: H + KV groups
           of d -> d, kernel 2;  u_{-1} = a_{-1} = 0   (left zero padding)
    m^q_{t,i} = (q~_{t,i} + k~_{t,g(i)}) / 2;  m^k_{t,j} = mean_{g(i)=j} m^q_{t,i}
    q_t  = c_t[q part] + m^q_t;   k_t = c_t[k part] + m^k_t
    q_{t,i} <- sqrt(d) q_{t,i} / |q_{t,i}|;  k_{t,j} <- tau_j sqrt(d) k_{t,j} / |k_{t,j}|
           tau_j = exp(``log_tau``_j) > 0, learned per KV head
    RoPE on the first ``rotary_dim`` values of each head, halves rotated
    o_{t,i} = sum_{s<=t} softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(d)) v_{s,g(i)}
    x_t <- alpha_a * x_t + gamma_a * (o_t W_o)

So a token caches ``k_t`` (mixed, normalised, rotated) and ``v_t`` in K and V
pools of ``[KV, d]`` rows, the GQA model's format, and after the mixing the
attention IS grouped-query attention (``ops/pallas_paged_attention.py``'s
decode kernel as it stands). What the mixing of token ``t`` reads of token
``t - 1`` is ``u_{t-1}``, ``a_{t-1}`` (which holds ``u_{t-2}``'s part: a
receptive field of three) and ``h_{t-1} W_v2``: ``state_dim`` = 2 (H + KV) d
+ d values a slot a layer, the model's one per-slot row (``cca``).
``mix`` is the one definition: a decode step hands it one token and the
slot's row, a prefill chunk C tokens and the row the previous chunk left
(zeros at offset 0), ``forward`` a whole sequence and zeros.

Expert half (top-1 of ``E`` experts, or none):

    h_t = N_m(x_t)
    r_t = h_t W_d + b_d                                   ``router_dim`` wide
    r_t <- r_t + eta_l * r^{(l-1)}_t;  r^{(l)}_t := r_t   the layer-to-layer
           carry (zeros into layer 0, so layer 0 has none)
    z_t = W_3 gelu(W_2 gelu(W_1 N_r(r_t) + b_1) + b_2)    E + 1 logits
    p_t = softmax(z_t) float32;  e_t = argmax_j (p_{t,j} + beta_j)
           beta: the balancing bias, which chooses and does not weigh
    e_t = E: y_t = 0 (the skip choice);  else y_t = p_{t,e_t} SwiGLU_{e_t}(h_t)
           the chosen probability itself, not renormalised
    x_t <- alpha_m * x_t + gamma_m * y_t

Head: ``logits = N_f(x) E^T`` with ``E`` the embedding (tied).

The router runs in float32 at full matmul precision (a bf16 product moves
near-ties of the 17 probabilities past each other); its carry is float32.
No token is dropped and none is coupled to another (``parallel/moe.py``, the
routed path; the skip choice is a row that belongs to no group).

Layout: one stack of layers, scanned; the experts' matrices are handed to
the grouped products whole (``[layers, E, ...]``), never sliced by layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.attention import attention, decode_attention
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.paged_pool import gather_views
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.parallel import moe

EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class CcaMoeConfig:
    vocab_size: int = 262272
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2              # head 0: this token's value, 1: the last
    head_dim: int = 128
    rotary_dim: int = 64             # partial_rotary_factor 0.5
    moe_mlp_dim: int = 2048          # one expert's SwiGLU
    n_experts: int = 16              # and one skip choice beside them
    router_dim: int = 256
    max_seq: int = 131072
    rope_theta: float = 5e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_kv_heads != 2 or self.n_heads % 2:
            raise ValueError("the value shift gives KV head 0 the current "
                             "token and KV head 1 the previous one: "
                             "n_kv_heads is 2 and divides n_heads")

    @property
    def mix_dim(self) -> int:
        """Values of ``u_t`` = [q~ ; k~]: what the two convolutions mix."""
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def state_dim(self) -> int:
        """Values a slot keeps per layer: ``u``, ``a`` and ``h W_v2`` of its
        last token."""
        return 2 * self.mix_dim + self.head_dim

    def router_config(self) -> moe.RouterConfig:
        return moe.RouterConfig(
            n_experts=self.n_experts + 1, top_k=1, score_func="softmax",
            select_bias=True, norm_topk=False)

    def paged_ops(self):
        """What ``serving/paged_kv.py`` writes its programs over."""
        return _paged_ops(self)


def cca_moe_tiny(**kw) -> CcaMoeConfig:
    """CI config: every mechanism present, runs on the CPU in seconds."""
    base = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
                head_dim=16, rotary_dim=8, moe_mlp_dim=32, n_experts=4,
                router_dim=16, max_seq=256, rope_theta=10000.0)
    base.update(kw)
    return CcaMoeConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: CcaMoeConfig, dtype=jnp.float32):
    """Seeded weights that switch no mechanism off: every bias and the
    balancing bias are non-zero, the merge scales, ``tau`` and ``eta`` are
    not one. The router is seeded for the widest spread of a decode batch
    over the experts that seeding gives (PERF.md section 6, PR 34): the two
    GELUs' positive mean puts the same offset on every token's logits, so
    the MLP's two inner matrices are at half the fan-in scale and its
    biases small (a nearly linear MLP has the least of that offset), and
    the last matrix is at eight times the fan-in scale, so that the 17
    probabilities differ by far more than the balancing bias adds."""
    n, d, hd = cfg.n_layers, cfg.dim, cfg.head_dim
    h, kv, e, m, r = (cfg.n_heads, cfg.n_kv_heads, cfg.n_experts,
                      cfg.moe_mlp_dim, cfg.router_dim)
    g = h + kv
    k_embed, *ks = jax.random.split(rng, 32)
    ks = iter(ks)

    def normal(shape, scale, mean=0.0, dt=dtype):
        return (mean + scale * jax.random.normal(
            next(ks), (n, *shape), jnp.float32)).astype(dt)

    def dense(shape, fan_in):
        return normal(shape, fan_in ** -0.5)

    layers = {
        "attn_norm": jnp.ones((n, d), dtype),
        # flat [D, heads x d]: 8 or 2 heads do not fill the chip's (16, 128)
        # tile, and a [D, heads, d] array is copied whole before its product
        "w_q": dense((d, h * hd), d), "w_k": dense((d, kv * hd), d),
        "w_v1": dense((d, hd), d), "w_v2": dense((d, hd), d),
        # kernel 2: tap 0 multiplies the previous token, tap 1 this one
        "conv0_w": normal((2, cfg.mix_dim), 0.5),
        "conv0_b": normal((cfg.mix_dim,), 0.1),
        "conv1_w": normal((2, g, hd, hd), (2 * hd) ** -0.5),
        "conv1_b": normal((g, hd), 0.1),
        # tau ~ e: unit-norm random q and k score N(0, tau^2), and at tau 1
        # attention over a thousand tokens is a plain average that no
        # query or key error moves (a trained model's heads select)
        "log_tau": normal((kv,), 0.1, 0.5),
        "wo": dense((h, hd, d), h * hd),
        "attn_alpha": normal((d,), 0.05, 1.0),
        "attn_gamma": normal((d,), 0.05, 1.0),
        "mlp_norm": jnp.ones((n, d), dtype),
        "router_down": dense((d, r), d), "router_down_b": normal((r,), 0.02),
        "router_eta": normal((), 0.1, 0.5),
        "router_norm": jnp.ones((n, r), dtype),
        "router_w1": normal((r, r), 0.5 * r ** -0.5),
        "router_b1": normal((r,), 0.02),
        "router_w2": normal((r, r), 0.5 * r ** -0.5),
        "router_b2": normal((r,), 0.02),
        "router_w3": normal((r, e + 1), 8.0 * r ** -0.5),
        "router_bias": normal((e + 1,), 0.02, dt=jnp.float32),
        "w_gate": dense((e, d, m), d), "w_up": dense((e, d, m), d),
        "w_down": dense((e, m, d), m),
        "mlp_alpha": normal((d,), 0.05, 1.0),
        "mlp_gamma": normal((d,), 0.05, 1.0),
    }
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  * d ** -0.5).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }


def param_logical_axes(cfg: CcaMoeConfig):
    """Logical axis names per param, mirroring ``init_params``."""
    del cfg
    lay = "layers"
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": (lay, "embed"),
            "w_q": (lay, "embed", "heads"), "w_k": (lay, "embed", None),
            "w_v1": (lay, "embed", "head_dim"),
            "w_v2": (lay, "embed", "head_dim"),
            "conv0_w": (lay, None, None), "conv0_b": (lay, None),
            "conv1_w": (lay, None, None, None, None),
            "conv1_b": (lay, None, None), "log_tau": (lay, None),
            "wo": (lay, "heads", "head_dim", "embed"),
            "attn_alpha": (lay, "embed"), "attn_gamma": (lay, "embed"),
            "mlp_norm": (lay, "embed"),
            "router_down": (lay, "embed", None),
            "router_down_b": (lay, None), "router_eta": (lay,),
            "router_norm": (lay, None),
            "router_w1": (lay, None, None), "router_b1": (lay, None),
            "router_w2": (lay, None, None), "router_b2": (lay, None),
            "router_w3": (lay, None, None), "router_bias": (lay, None),
            "w_gate": (lay, "expert", "embed", "mlp"),
            "w_up": (lay, "expert", "embed", "mlp"),
            "w_down": (lay, "expert", "mlp", "embed"),
            "mlp_alpha": (lay, "embed"), "mlp_gamma": (lay, "embed"),
        },
        "final_norm": ("embed",),
    }


# ---------------------------------------------------------------------------
# The layer's pieces (shared by forward and the paged programs)
# ---------------------------------------------------------------------------

def _inv_freq(cfg: CcaMoeConfig):
    return jnp.asarray(rope_frequencies(cfg.rotary_dim, cfg.rope_theta,
                                        scaling=None))


def _rope(x, positions, cfg: CcaMoeConfig):
    """Rotate the first ``rotary_dim`` values of each head as halves."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [apply_rope(x[..., :r], positions, _inv_freq(cfg)), x[..., r:]], -1)


def _after(x, first):
    """Row t holds x[t - 1]; row 0 holds ``first`` [B, ...]: what the token
    before the rows left."""
    return jnp.concatenate([first[:, None].astype(x.dtype), x[:, :-1]], 1)


def _unit(x, scale=None):
    """sqrt(d) x / |x| over the last axis, in float32 (times ``scale``)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True))
    if scale is not None:
        xf = xf * scale
    return xf.astype(x.dtype)


def qk_mean(q0, k0):
    """The q-k mean of q0 [B, S, H, d], k0 [B, S, KV, d] in float32: each
    query head with its group's key, each key with the mean of its
    group's."""
    b, s, h, hd = q0.shape
    kv = k0.shape[2]
    mean_q = (q0.astype(jnp.float32)
              + jnp.repeat(k0.astype(jnp.float32), h // kv, axis=2)) / 2
    return mean_q, mean_q.reshape(b, s, kv, h // kv, hd).mean(3)


def mix(lp, x, positions, state, cfg: CcaMoeConfig):
    """The attention inputs of x [B, S, D] at ``positions`` [B|1, S], with
    ``state`` [B, state_dim] what the token before row 0 left in this layer
    (zeros before a sequence's first token): (q [B, S, H, d], k and v
    [B, S, KV, d], both as cached, left [B, S, state_dim]: what each row
    leaves the next)."""
    dt = cfg.dtype
    b, s, _ = x.shape
    h, kv, hd, m = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mix_dim
    u_last, a_last, v_last = state[:, :m], state[:, m:2 * m], state[:, 2 * m:]
    hid = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    u = jnp.concatenate(
        [jnp.einsum("bsd,dk->bsk", hid, lp[key].astype(dt))
         for key in ("w_q", "w_k")], -1)
    q0 = u[..., :h * hd].reshape(b, s, h, hd)
    k0 = u[..., h * hd:].reshape(b, s, kv, hd)
    w0, w1 = lp["conv0_w"].astype(jnp.float32), lp["conv1_w"].astype(dt)
    a = (w0[1] * u + w0[0] * _after(u, u_last)
         + lp["conv0_b"].astype(jnp.float32)).astype(dt)
    # both taps as ONE product over [a_{t-1} ; a_t], 2 d values a group
    taps = jnp.concatenate([_after(a, a_last).reshape(b, s, h + kv, hd),
                            a.reshape(b, s, h + kv, hd)], -1)
    c = jnp.einsum("bsgi,gio->bsgo", taps,
                   jnp.concatenate([w1[0], w1[1]], 1)).astype(jnp.float32) \
        + lp["conv1_b"].astype(jnp.float32)
    mean_q, mean_k = qk_mean(q0, k0)
    q = _unit((c[:, :, :h] + mean_q).astype(dt))
    k = _unit((c[:, :, h:] + mean_k).astype(dt),
              jnp.exp(lp["log_tau"].astype(jnp.float32))[:, None])
    v2 = jnp.einsum("bsd,dk->bsk", hid, lp["w_v2"].astype(dt))
    v = jnp.stack([jnp.einsum("bsd,dk->bsk", hid, lp["w_v1"].astype(dt)),
                   _after(v2, v_last)], 2)
    return (_rope(q, positions, cfg), _rope(k, positions, cfg), v,
            jnp.concatenate([u, a, v2], -1))


def merge(x, y, alpha, gamma):
    """``alpha * x + gamma * y``: how a half-layer joins the residual."""
    out = (alpha.astype(jnp.float32) * x.astype(jnp.float32)
           + gamma.astype(jnp.float32) * y.astype(jnp.float32))
    return out.astype(x.dtype)


def router_logits(lp, hid, carry, cfg: CcaMoeConfig):
    """hid [B, S, D] normed, carry [B, S, router_dim] float32: the previous
    layer's router state (zeros into layer 0) -> (logits [B, S, E + 1]
    float32, this layer's state, handed on)."""
    f32 = jnp.float32

    def lin(z, w, bias=None):
        z = jnp.einsum("bsi,io->bso", z, lp[w].astype(f32),
                       precision=HIGHEST)
        return z if bias is None else z + lp[bias].astype(f32)

    r = lin(hid.astype(f32), "router_down", "router_down_b") \
        + lp["router_eta"].astype(f32) * carry
    z = rms_norm(r, lp["router_norm"], cfg.norm_eps)
    z = jax.nn.gelu(lin(z, "router_w1", "router_b1"), approximate=False)
    z = jax.nn.gelu(lin(z, "router_w2", "router_b2"), approximate=False)
    return lin(z, "router_w3"), r


def expert_half(lp, hid, carry, cfg: CcaMoeConfig, token_mask=None):
    """The expert half on normed hid [B, S, D]: (y [B, S, D], carry, stats:
    tokens per choice [E + 1] (column E: the skip), distinct experts hit,
    tokens that took the skip, the choices [B, S, 1]). ``token_mask``
    [B, S] keeps pad and idle rows out of the product and the counts."""
    b, s, d = hid.shape
    e = cfg.n_experts
    logits, carry = router_logits(lp, hid, carry, cfg)
    experts, weights = moe.route(None, None, lp["router_bias"],
                                 cfg.router_config(),
                                 logits=logits.reshape(b * s, e + 1))
    live = (jnp.ones((b * s,), bool) if token_mask is None
            else token_mask.reshape(b * s))
    skip = experts[:, 0] == e
    w = [lp[key] for key in EXPERT_MATRICES]
    first = 0
    if w[0].ndim == 4:
        # the whole stack [layers, E, ...] and this layer's place in it
        # (``paged_kv._scan_layers``): never a slice of it
        w = [a.reshape(-1, *a.shape[2:]) for a in w]
        first = lp["stack_index"] * e
    y, counts = moe.routed_experts(
        hid.reshape(b * s, d), experts, weights, *w, valid=live & ~skip,
        n_experts=e, first_group=first)
    skipped = jnp.sum(live & skip).astype(jnp.int32)
    return y.reshape(b, s, d), carry, {
        "tokens_per_expert": jnp.concatenate([counts, skipped[None]]),
        "experts_hit": jnp.sum(counts > 0), "skipped": skipped,
        "experts": experts.reshape(b, s, 1)}


def attention_out_and_experts(lp, x, o, carry, cfg: CcaMoeConfig,
                              token_mask=None):
    """What follows attention: ``W_o`` merged into the stream, then the
    expert half merged into it. Returns (x, carry, stats)."""
    x = merge(x, jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cfg.dtype)),
              lp["attn_alpha"], lp["attn_gamma"])
    hid = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y, carry, stats = expert_half(lp, hid, carry, cfg, token_mask)
    return merge(x, y, lp["mlp_alpha"], lp["mlp_gamma"]), carry, stats


def router_carry(x, cfg: CcaMoeConfig):
    """What enters layer 0 as the previous layer's router state."""
    return jnp.zeros((*x.shape[:2], cfg.router_dim), jnp.float32)


def embed_tokens(params, tokens, cfg: CcaMoeConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def lm_head(params, x_last, cfg: CcaMoeConfig):
    """x_last [B, D] before the final norm -> logits [B, V] float32, by the
    embedding (tied)."""
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bd,vd->bv", x_last,
                      params["embed"].astype(cfg.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layers(params, tokens, cfg: CcaMoeConfig):
    """Whole sequences through the layers: (x before the final norm,
    tokens per router choice [layers, E + 1])."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = embed_tokens(params, tokens, cfg)
    zeros = jnp.zeros((b, cfg.state_dim), cfg.dtype)

    def layer(carry, lp):
        x, r = carry
        q, k, v, _ = mix(lp, x, positions, zeros, cfg)
        o = attention(q, k, v, causal=True, impl="xla")
        x, r, stats = attention_out_and_experts(lp, x, o, r, cfg)
        return (x, r), stats["tokens_per_expert"]

    (x, _), load = jax.lax.scan(layer, (x, router_carry(x, cfg)),
                                params["layers"])
    return x, load


def forward(params, tokens, cfg: CcaMoeConfig):
    """Full-sequence forward. tokens [B, S] -> logits [B, S, V] float32."""
    b, s = tokens.shape
    x, _ = _layers(params, tokens, cfg)
    return lm_head(params, x.reshape(b * s, cfg.dim), cfg).reshape(b, s, -1)


def balance_router_bias(params, cfg: CcaMoeConfig, rng, batch=16, seq=64,
                        steps=48, first_step=0.02, decay=0.9):
    """The balancing bias as its own rule leaves it: ``steps`` rounds of
    ``beta_j += u * sign(mean load - load_j)`` (the bias update of
    loss-free balancing, every layer at once, ``u`` decaying from
    ``first_step``) on ``batch`` seeded sequences of ``seq`` random tokens.
    A seeded router's two GELUs put the same offset on every token's
    logits and a seeded bias does nothing about it (a batch of 64 then hits
    12 of 16 experts a layer, and which 12 changes with the seed); a
    trained model's bias has levelled the load, and this brings the seeded
    one there without a training run. Returns params with the new
    ``router_bias``; it still chooses and does not weigh."""
    tokens = jax.random.randint(rng, (batch, seq), 1, cfg.vocab_size)

    def with_bias(bias):
        return dict(params, layers=dict(params["layers"], router_bias=bias))

    def round_(bias, u):
        load = _layers(with_bias(bias), tokens, cfg)[1].astype(jnp.float32)
        return bias + u * jnp.sign(load.mean(-1, keepdims=True) - load), None

    bias, _ = jax.lax.scan(
        round_, params["layers"]["router_bias"].astype(jnp.float32),
        first_step * decay ** jnp.arange(steps, dtype=jnp.float32))
    return with_bias(bias)


# ---------------------------------------------------------------------------
# The serving programs' view of the model (models/paged.PagedOps)
# ---------------------------------------------------------------------------

def _paged_ops(cfg: CcaMoeConfig):
    from kubeflow_tpu.models.paged import PagedOps

    def qkv(lp, x, positions, state):
        q, k, v, left = mix(lp, x, positions, state["cca"], cfg)
        return q, {"k": k, "v": v}, {"cca": left}

    def decode_attn(lp, q, pools, layer, tables, kv_len, kernel, mesh,
                    interpret):
        del lp, mesh                              # refused by the engine
        if kernel == "pallas":
            # after the mixing this IS grouped-query attention over paged
            # K and V: the dense model's kernel, as it stands
            from kubeflow_tpu.ops.pallas_paged_attention import (
                paged_decode_attention,
            )

            return paged_decode_attention(
                q[:, 0], pools["k"], pools["v"], layer, tables, kv_len,
                interpret=interpret)[:, None]
        k_view, v_view = gather_views(pools, layer, tables, cfg)
        return decode_attention(q, k_view, v_view, kv_len)

    def chunk_attention(lp, q, pools, layer, tables, q_start):
        del lp
        k_view, v_view = gather_views(pools, layer, tables, cfg)
        return attention(q, k_view, v_view, causal=True, impl="xla",
                         q_offset=q_start)

    return PagedOps(
        n_layers=cfg.n_layers,
        pool_rows={"k": (cfg.n_kv_heads, cfg.head_dim),
                   "v": (cfg.n_kv_heads, cfg.head_dim)},
        slot_rows={"cca": (cfg.state_dim,)},
        layer_stacks=lambda params: [(params["layers"], EXPERT_MATRICES)],
        layer_carry=lambda x: router_carry(x, cfg),
        embed=lambda params, tokens: embed_tokens(params, tokens, cfg),
        qkv=qkv, decode_attention=decode_attn,
        chunk_attention=chunk_attention,
        out=lambda lp, x, o, token_mask, carry: attention_out_and_experts(
            lp, x, o, carry, cfg, token_mask),
        head=lambda params, x_last: lm_head(params, x_last, cfg),
        routed_per_token=cfg.n_layers,
        refuses={
            "radix prefix cache": "a shared chunk's compute is skipped, so "
                                  "there is no mixing state at the boundary "
                                  "where the private rows begin: state "
                                  "snapshots at block boundaries are not "
                                  "written",
            "disaggregated tiers": "export and import move pool blocks "
                                   "only; the slot's mixing state does not "
                                   "travel with them",
            "speculative decode": "the verify step rewinds rows past a "
                                  "rejected draft but not the slot's "
                                  "mixing state",
            "int8 weights": "the experts' grouped products and the two "
                            "convolutions are not int8-lowered",
            "quantized KV pool": "the normalised keys (|k| = tau sqrt(d)) "
                                 "have not been measured under a per-block "
                                 "scale",
            "tensor mesh": "two KV heads, one of them the previous token's "
                           "value: the per-slot state has no sharding rule",
        })
