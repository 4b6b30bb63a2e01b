"""Gated DeltaNet linear attention 3:1 with gated full attention, and 512
experts top-10 beside a gated shared expert: the Qwen3-Next block
(``model_type: qwen3_next``; keys ``full_attention_interval``,
``linear_num_key_heads``, ``linear_num_value_heads``, ``linear_*_head_dim``,
``linear_conv_kernel_dim``, ``num_experts``, ``shared_expert_intermediate_size``).

Layer ``i`` is full attention when ``(i + 1) % full_attention_interval ==
0`` and Gated DeltaNet (GDN) otherwise; each layer is

    x <- x + mixer(N_1(x));   x <- x + moe(N_2(x))

with every norm outside the GDN output the zero-centred RMSNorm ``x /
sqrt(mean(x^2) + eps) * (1 + w)`` in float32. Head: ``logits = N_f(x)
W_head`` (untied).

GDN layer (``n_k`` key heads, ``n_v`` value heads of ``d_k = d_v``, value
head ``j`` over key head ``j // (n_v / n_k)``, conv kernel ``K``):

    qkvz = h W_qkvz   per key head [q (d_k) | k (d_k) | v (r d_v) | z (r d_v)]
    ba   = h W_ba     per key head [b (r) | a (r)],   r = n_v / n_k
    u    = [q ; k ; v] (all heads of each)   -> depthwise causal conv of
           kernel K, no bias, then SiLU; the last K - 1 inputs are the
           slot's conv tail (zeros before the first token), kept as a ring
           of K - 1 rows, the input at position p in row p % (K - 1)
    q, k <- x / sqrt(sum x^2 + 1e-6) per head;  q <- q d_k^-1/2
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (float32)
    per token and value head, S [d_k, d_v] float32:
        S <- exp(g) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
    o  <- w * o / sqrt(mean(o^2) + eps) * silu(z)   per head, w plain
    out = o W_out

A prompt chunk runs the chunkwise-parallel form (``chunk_gated_delta``: the
WY / UT form over sub-chunks of 64, a scan across them carrying ``S``); a
decode token the step above, through the Pallas kernel of
``ops/pallas_gdn.py`` that reads and writes a live slot's state once.

Gated full attention: ``q_proj`` gives per head ``[query | gate]``; ``q`` and
``k`` through zero-centred norms, rotary on the first ``rotary_dim`` values
(halves rotated), causal softmax over ``n_kv_heads`` grouped heads, then
``o = (attn * sigmoid(gate)) W_o``. K and V are paged, at the published 256
values a head a block's (token, kv head) rows stored merged
(``models/paged.stored_merged``); the decode kernel is
``ops/pallas_paged_attention.py``'s.

MoE block: ``p = softmax(h W_router)`` over all ``n_experts`` in float32,
the top ``top_k`` renormalised to sum 1; each expert is a SwiGLU of
``moe_mlp_dim``; the shared expert a SwiGLU of ``shared_mlp_dim`` times
``sigmoid(h w_sg)``. This chip may hold a share of the experts
(``n_experts_held`` from ``first_expert`` on: expert parallelism): the router
scores all of them, and a pick outside the share multiplies nothing
(``parallel/moe.py::routed_experts``); nothing stands in for the absent
chips.

Layout: three stacks, one a kind (``linear``, ``full``) and one of the
expert blocks of every layer (``moe``), scanned as one stack of periods
(``PagedOps.period``); the experts' matrices are handed to the grouped
products whole (``[layers, E_held, ...]``), never sliced by layer, and each
projection matrix to its product as ONE dynamic index into its whole stack
(``WHOLE_OF``), in the layout it is stored in: a product whose columns come
per head is split as it is (``_by_part``), not reshaped into heads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.ops.attention import attention, decode_attention
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.parallel import moe

EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
# the matrices handed to every period whole, by the stack that holds them
WHOLE_OF = {"linear": ("w_qkvz", "w_out"),
            "full": ("w_q", "w_k", "w_v", "w_o"), "moe": EXPERT_MATRICES}
WHOLE = sum(WHOLE_OF.values(), ())
HIGHEST = jax.lax.Precision.HIGHEST
SUB_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64              # partial_rotary_factor 0.25
    rope_theta: float = 1e7
    n_k_heads: int = 16               # linear_num_key_heads
    n_v_heads: int = 32               # linear_num_value_heads
    k_head_dim: int = 128
    v_head_dim: int = 128
    conv_kernel: int = 4
    n_experts: int = 512              # routed over
    n_experts_held: int = 512         # held on this chip
    first_expert: int = 0             # the first of them
    top_k: int = 10
    moe_mlp_dim: int = 512
    shared_mlp_dim: int = 512
    norm_eps: float = 1e-6
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % self.full_attention_interval:
            raise ValueError("whole periods of full_attention_interval "
                             "layers only")
        if self.n_v_heads % self.n_k_heads:
            raise ValueError("value heads share key heads evenly")
        if not (0 <= self.first_expert and self.first_expert
                + self.n_experts_held <= self.n_experts):
            raise ValueError("the held experts lie among those routed over")

    @property
    def key_dim(self) -> int:
        return self.n_k_heads * self.k_head_dim

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.v_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``[q ; k ; v]``."""
        return 2 * self.key_dim + self.value_dim

    @property
    def period(self) -> tuple:
        return (("recurrent",) * (self.full_attention_interval - 1)
                + ("attention",))

    def router_config(self) -> moe.RouterConfig:
        return moe.RouterConfig(n_experts=self.n_experts, top_k=self.top_k,
                                score_func="softmax", norm_topk=True)

    def paged_ops(self):
        """What ``serving/paged_kv.py`` writes its programs over."""
        return _paged_ops(self)


def qwen3_next_tiny(**kw) -> Qwen3NextConfig:
    """CI config: every mechanism present, runs on the CPU in seconds: two
    periods, 16 experts of which 4 held, top-3, heads of the published 256
    (K and V stored merged)."""
    base = dict(vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
                head_dim=256, rotary_dim=8, rope_theta=10000.0, n_k_heads=2,
                n_v_heads=4, k_head_dim=16, v_head_dim=16, n_experts=16,
                n_experts_held=4, first_expert=4, top_k=3, moe_mlp_dim=32,
                shared_mlp_dim=24, max_seq=512)
    base.update(kw)
    return Qwen3NextConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Qwen3NextConfig, dtype=jnp.float32):
    """Seeded weights that switch no mechanism off. The zero-centred norms'
    weights are N(0, 0.1^2), not zero, so that ``w`` in place of ``1 + w``
    shows. The decay: ``A = exp(A_log)`` is log-uniform over [0.0015, 0.15]
    across heads and ``dt_bias`` N(0, 0.1^2), so that ``exp(g)`` at a
    median gate lies between about 0.9 and 0.999 (half-lives of 7 to 700
    tokens): HF's initialisation forgets nearly everything in a token, and
    a chunk that wrongly started from zeros would not show."""
    n_lin = cfg.n_layers // cfg.full_attention_interval \
        * (cfg.full_attention_interval - 1)
    n_full = cfg.n_layers - n_lin
    d, e, m = cfg.dim, cfg.n_experts_held, cfg.moe_mlp_dim
    k_embed, k_head, *ks = jax.random.split(rng, 40)
    ks = iter(ks)

    def normal(n, shape, scale, mean=0.0, dt=dtype):
        return (mean + scale * jax.random.normal(
            next(ks), (n, *shape), jnp.float32)).astype(dt)

    def dense(n, shape, fan_in):
        return normal(n, shape, fan_in ** -0.5)

    r = cfg.n_v_heads // cfg.n_k_heads
    per_key = 2 * cfg.k_head_dim + 2 * r * cfg.v_head_dim
    linear = {
        "in_norm": normal(n_lin, (d,), 0.1),
        "w_qkvz": dense(n_lin, (d, cfg.n_k_heads * per_key), d),
        "w_ba": dense(n_lin, (d, 2 * cfg.n_v_heads), d),
        # tap K - 1 multiplies the current token, tap 0 the oldest
        "conv_w": normal(n_lin, (cfg.conv_kernel, cfg.conv_dim),
                         cfg.conv_kernel ** -0.5),
        "A_log": jax.random.uniform(
            next(ks), (n_lin, cfg.n_v_heads), jnp.float32,
            jnp.log(0.0015), jnp.log(0.15)),
        "dt_bias": normal(n_lin, (cfg.n_v_heads,), 0.1, dt=jnp.float32),
        "out_norm": normal(n_lin, (cfg.v_head_dim,), 0.1, 1.0),
        "w_out": dense(n_lin, (cfg.value_dim, d), cfg.value_dim),
    }
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    full = {
        "in_norm": normal(n_full, (d,), 0.1),
        # per head [query | gate]
        "w_q": dense(n_full, (d, 2 * h * hd), d),
        "w_k": dense(n_full, (d, kv * hd), d),
        "w_v": dense(n_full, (d, kv * hd), d),
        "q_norm": normal(n_full, (hd,), 0.1),
        "k_norm": normal(n_full, (hd,), 0.1),
        "w_o": dense(n_full, (h * hd, d), h * hd),
    }
    n, ms = cfg.n_layers, cfg.shared_mlp_dim
    experts = {
        "post_norm": normal(n, (d,), 0.1),
        "router": dense(n, (d, cfg.n_experts), d),
        "ws_gate": dense(n, (d, ms), d), "ws_up": dense(n, (d, ms), d),
        "ws_down": dense(n, (ms, d), ms),
        "w_sg": dense(n, (d, 1), d),
        "w_gate": dense(n, (e, d, m), d), "w_up": dense(n, (e, d, m), d),
        "w_down": dense(n, (e, m, d), m),
    }
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  * d ** -0.5).astype(dtype),
        "lm_head": (jax.random.normal(k_head, (d, cfg.vocab_size),
                                      jnp.float32) * d ** -0.5).astype(dtype),
        "linear": linear, "full": full, "moe": experts,
        "final_norm": (0.1 * jax.random.normal(next(ks), (d,), jnp.float32)
                       ).astype(dtype),
    }


def param_logical_axes(cfg: Qwen3NextConfig):
    """Logical axis names per param, mirroring ``init_params``."""
    del cfg
    lay = "layers"
    return {
        "embed": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "linear": {
            "in_norm": (lay, "embed"), "w_qkvz": (lay, "embed", "heads"),
            "w_ba": (lay, "embed", None), "conv_w": (lay, None, None),
            "A_log": (lay, None), "dt_bias": (lay, None),
            "out_norm": (lay, None), "w_out": (lay, "heads", "embed"),
        },
        "full": {
            "in_norm": (lay, "embed"), "w_q": (lay, "embed", "heads"),
            "w_k": (lay, "embed", None), "w_v": (lay, "embed", None),
            "q_norm": (lay, None), "k_norm": (lay, None),
            "w_o": (lay, "heads", "embed"),
        },
        "moe": {
            "post_norm": (lay, "embed"), "router": (lay, "embed", None),
            "ws_gate": (lay, "embed", "mlp"), "ws_up": (lay, "embed", "mlp"),
            "ws_down": (lay, "mlp", "embed"), "w_sg": (lay, "embed", None),
            "w_gate": (lay, "expert", "embed", "mlp"),
            "w_up": (lay, "expert", "embed", "mlp"),
            "w_down": (lay, "expert", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }


# ---------------------------------------------------------------------------
# The layers' pieces (shared by forward and the paged programs)
# ---------------------------------------------------------------------------

def zc_norm(x, w, eps):
    """Zero-centred RMSNorm: ``x / sqrt(mean(x^2) + eps) * (1 + w)`` in
    float32, back in x's dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _proj(x, w, dt):
    return jnp.einsum("bsd,dk->bsk", x, w.astype(dt))


def _by_part(y, heads, widths):
    """A product whose columns come per head as ``[part 0 | part 1 | ...]``
    (``widths`` wide) -> ``[part 0 of every head | part 1 of every head |
    ...]``: static slices of the flat product put together (on a v5e a
    gather of its columns costs 0.7 ms more a decode step). The product is
    never reshaped into heads first: a reshape-and-split has the compiler
    store the weight transposed, a copy of the whole stack every call."""
    per = sum(widths)
    starts = np.cumsum((0,) + tuple(widths[:-1]))
    return jnp.concatenate([y[..., h * per + s:h * per + s + w]
                            for s, w in zip(starts, widths)
                            for h in range(heads)], -1)


def gdn_inputs(lp, x, cfg: Qwen3NextConfig):
    """x [B, S, D] -> (u [B, S, conv_dim] the convolution's input, z [B, S,
    n_v, d_v], b and a [B, S, n_v]), in the model dtype: the normed input
    and both projections rounded to it, as the published model computes
    them."""
    dt = cfg.dtype
    bsz, s, _ = x.shape
    r = cfg.n_v_heads // cfg.n_k_heads
    dk, dv = cfg.k_head_dim, cfg.v_head_dim
    h = zc_norm(x, lp["in_norm"], cfg.norm_eps)
    u, z = jnp.split(_by_part(_proj(h, lp["w_qkvz"], dt), cfg.n_k_heads,
                              (dk, dk, r * dv, r * dv)), [cfg.conv_dim], -1)
    ba = _proj(h, lp["w_ba"], dt).reshape(bsz, s, cfg.n_k_heads, 2 * r)
    b, a = ba[..., :r], ba[..., r:]
    return (u, z.reshape(bsz, s, cfg.n_v_heads, dv),
            b.reshape(bsz, s, -1), a.reshape(bsz, s, -1))


def gdn_qkv(c, b, a, lp, cfg: Qwen3NextConfig):
    """The convolution's output c [..., conv_dim] (after SiLU) and the two
    gate projections -> (q, k [..., n_v, d_k] normalised, q scaled, each
    value head's key head repeated; v [..., n_v, d_v]; decay = exp(g) and
    beta [..., n_v]), all float32."""
    lead = c.shape[:-1]
    r = cfg.n_v_heads // cfg.n_k_heads
    f32 = jnp.float32
    q, k, v = jnp.split(c.astype(f32), [cfg.key_dim, 2 * cfg.key_dim], -1)
    q = _l2(q.reshape(*lead, cfg.n_k_heads, cfg.k_head_dim)) \
        * cfg.k_head_dim ** -0.5
    k = _l2(k.reshape(*lead, cfg.n_k_heads, cfg.k_head_dim))
    q, k = (jnp.repeat(t, r, axis=-2) for t in (q, k))
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + lp["dt_bias"].astype(f32))
    return (q, k, v.reshape(*lead, cfg.n_v_heads, cfg.v_head_dim),
            g, jax.nn.sigmoid(b.astype(f32)))


def gdn_out(lp, o, z, cfg: Qwen3NextConfig):
    """o [B, S, n_v, d_v] float32 through the gated RMSNorm (a plain weight)
    and ``W_out`` -> [B, S, D]."""
    dt = cfg.dtype
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg.norm_eps)
    o = o * lp["out_norm"].astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    return _proj(o.astype(dt).reshape(*o.shape[:2], -1), lp["w_out"], dt)


@jax.named_scope("gdn_chunk_scan")
def chunk_gated_delta(q, k, v, g, beta, s0, sub=SUB_CHUNK):
    """The gated delta rule over T rows in its chunkwise-parallel form.

    q, k [H, T, d_k] (normalised, q scaled), v [H, T, d_v], g and beta
    [H, T] float32, s0 [H, d_k, d_v] the state before row 0; T a multiple of
    ``sub``. Returns (o [H, T, d_v], the state after row T - 1). A row with
    ``beta = g = 0`` leaves the state as it was.

    Within a sub-chunk of ``sub`` rows, with ``G`` the cumulative sum of g
    and ``Gamma_ij = exp(G_i - G_j)`` (i >= j), the UT transform gives the
    rows' new values as ``W (beta v) - W (beta exp(G) k) S`` for the state
    ``S`` before the sub-chunk, ``W = (I + tril_-1(diag(beta) K K^T *
    Gamma))^-1`` a unit lower-triangular inverse, here the product of ``(I
    + L^(2^i))`` for ``L = -tril_-1(...)`` (nilpotent of index ``sub``). A
    scan over the sub-chunks carries the state; every product is float32
    at full precision."""
    f32 = jnp.float32
    h, t, dk = k.shape
    dv = v.shape[-1]
    n = t // sub
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    q, k, v = (x.astype(f32).reshape(h, n, sub, -1) for x in (q, k, v))
    g = jnp.cumsum(g.astype(f32).reshape(h, n, sub), -1)
    beta = beta.astype(f32).reshape(h, n, sub)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    strict = jnp.tril(jnp.ones((sub, sub), bool), -1)
    gamma = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                  # [H, n, c, c]
    kb = k * beta[..., None]
    el = -jnp.where(strict, mm("hnik,hnjk->hnij", kb, k) * gamma, 0.0)
    w = jnp.eye(sub, dtype=f32) + el
    power = el
    for _ in range(max(0, (sub - 1).bit_length() - 1)):
        power = mm("hnij,hnjk->hnik", power, power)
        w = w + mm("hnij,hnjk->hnik", w, power)
    vb = mm("hnij,hnjv->hniv", w, v * beta[..., None])
    kcum = mm("hnij,hnjk->hnik", w, kb * jnp.exp(g)[..., None])
    attn = jnp.where(lower, mm("hnik,hnjk->hnij", q, k) * gamma, 0.0)
    qg = q * jnp.exp(g)[..., None]
    kg = k * jnp.exp(g[..., -1:] - g)[..., None]
    last = jnp.exp(g[..., -1])                              # [H, n]

    def step(s, xs):
        vb_i, kcum_i, attn_i, qg_i, kg_i, last_i = xs
        v_new = vb_i - mm("hik,hkv->hiv", kcum_i, s)
        o = mm("hik,hkv->hiv", qg_i, s) + mm("hij,hjv->hiv", attn_i, v_new)
        s = s * last_i[:, None, None] + mm("hik,hiv->hkv", kg_i, v_new)
        return s, o

    s, o = jax.lax.scan(step, s0.astype(f32), tuple(
        jnp.moveaxis(x, 1, 0) for x in (vb, kcum, attn, qg, kg, last)))
    return jnp.moveaxis(o, 0, 1).reshape(h, t, dv), s


def gdn_chunk(lp, x, positions, state, valid, cfg: Qwen3NextConfig):
    """A GDN layer over a chunk of rows: x [B, S, D] at ``positions`` [B|1,
    S], state ``{"gdn_s": [B, n_v, d_k, d_v] float32, "gdn_conv": [B, K - 1,
    conv_dim] float32}`` what the token before row 0 left (zeros before a
    sequence's first token; the conv tail a ring, the input at position p in
    row p % (K - 1)), valid [B, S] the true rows (a prefix of each row).
    Returns (out [B, S, D], the state at each slot's last true row)."""
    bsz, s, _ = x.shape
    keep = cfg.conv_kernel - 1
    u, z, b, a = gdn_inputs(lp, x, cfg)
    start = jnp.broadcast_to(positions[:, 0], (bsz,))
    # the ring in time order: the input at start - K + 1 + j in row j
    tail = jax.vmap(lambda ring, p: jnp.roll(ring, -(p % keep), axis=0))(
        state["gdn_conv"].astype(u.dtype), start)
    full = jnp.concatenate([tail, u], 1)
    w = lp["conv_w"].astype(jnp.float32)
    c = sum(w[j] * full[:, j:j + s].astype(jnp.float32)
            for j in range(cfg.conv_kernel))
    q, k, v, g, beta = gdn_qkv(jax.nn.silu(c), b, a, lp, cfg)
    # pad rows: beta = g = 0 leave the state as it was
    beta = jnp.where(valid[..., None], beta, 0.0)
    g = jnp.where(valid[..., None], g, 0.0)
    pad = -s % SUB_CHUNK

    def one(q, k, v, g, beta, s0):
        heads = lambda t: jnp.pad(jnp.moveaxis(t, 1, 0),
                                  [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        o, s1 = chunk_gated_delta(heads(q), heads(k), heads(v), heads(g),
                                  heads(beta), s0)
        return jnp.moveaxis(o[:, :s], 0, 1), s1

    o, s_last = jax.vmap(one)(q, k, v, g, beta, state["gdn_s"])
    n_true = jnp.sum(valid, 1)
    # the last K - 1 true inputs, back into their rows of the ring
    ring = jax.vmap(lambda f, n, p: jnp.roll(
        jax.lax.dynamic_slice_in_dim(f, n, keep), (p + n) % keep, axis=0))(
        full, n_true, start)
    return gdn_out(lp, o, z, cfg), {
        "gdn_s": s_last, "gdn_conv": ring.astype(state["gdn_conv"].dtype)}


def gdn_decode(lp, x, positions, states, layer, live, kernel, interpret,
               cfg: Qwen3NextConfig):
    """A GDN layer over one new row a slot, x [B, 1, D] at ``positions``
    [B, 1], ``states`` the WHOLE state arrays of all GDN layers, ``layer``
    this one's index among them, ``live`` [B] the slots whose state moves.
    Returns (out [B, 1, D], states updated in place for the live slots
    alone: ``S`` by the Pallas kernel (``kernel="pallas"``), the conv ring
    by a scatter of the step's one input a slot)."""
    from kubeflow_tpu.ops import pallas_gdn

    keep = cfg.conv_kernel - 1
    bsz = x.shape[0]
    pos = positions[:, 0]
    u, z, b, a = gdn_inputs(lp, x, cfg)
    w = lp["conv_w"].astype(jnp.float32)
    # ring row r holds the input at the position p < pos with p % (K - 1)
    # == r, which the conv multiplies by tap (r - pos) % (K - 1): one fused
    # reduction over the stored rows, no view of them (a slice a tap would
    # have XLA lay the whole array out anew, twice a call)
    tap = (jnp.arange(keep)[None, :] - pos[:, None]) % keep       # [B, K-1]
    coef = sum(jnp.where(tap[..., None] == t, w[t], 0.0) for t in range(keep))
    c = w[-1] * u[:, 0].astype(jnp.float32) + jnp.sum(
        states["gdn_conv"][layer].astype(jnp.float32) * coef, axis=1)
    q, k, v, g, beta = gdn_qkv(jax.nn.silu(c), b[:, 0], a[:, 0], lp, cfg)
    step = (functools.partial(pallas_gdn.gdn_decode, interpret=interpret)
            if kernel == "pallas" else pallas_gdn.gdn_decode_reference)
    o, s = step(q, k, v, jnp.exp(g), beta, states["gdn_s"], layer, live)
    # the step's input into row pos % (K - 1) of each live slot's ring, in
    # place; the other slots' rows out of range and dropped
    rows = jnp.where(live, jnp.arange(bsz), bsz)
    ring = states["gdn_conv"].at[layer, rows, pos % keep].set(
        u[:, 0].astype(states["gdn_conv"].dtype), mode="drop")
    return gdn_out(lp, o[:, None], z, cfg), {"gdn_s": s, "gdn_conv": ring}


def _inv_freq(cfg: Qwen3NextConfig):
    return jnp.asarray(rope_frequencies(cfg.rotary_dim, cfg.rope_theta,
                                        scaling=None))


def _rope(x, positions, cfg: Qwen3NextConfig):
    """Rotate the first ``rotary_dim`` values of each head as halves."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [apply_rope(x[..., :r], positions, _inv_freq(cfg)), x[..., r:]], -1)


def attn_inputs(lp, x, positions, cfg: Qwen3NextConfig):
    """x [B, S, D] at positions [B|1, S] -> (q [B, S, H, d] normed and
    rotated, gate [B, S, H, d], k [B, S, KV, d] normed and rotated, v)."""
    dt = cfg.dtype
    bsz, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hid = zc_norm(x, lp["in_norm"], cfg.norm_eps)
    q, gate = (t.reshape(bsz, s, h, hd) for t in jnp.split(
        _by_part(_proj(hid, lp["w_q"], dt), h, (hd, hd)), 2, -1))
    q = zc_norm(q, lp["q_norm"], cfg.norm_eps)
    k = zc_norm(_proj(hid, lp["w_k"], dt).reshape(bsz, s, kv, hd),
                lp["k_norm"], cfg.norm_eps)
    v = _proj(hid, lp["w_v"], dt).reshape(bsz, s, kv, hd)
    return _rope(q, positions, cfg), gate, _rope(k, positions, cfg), v


def attn_out(lp, o, gate, cfg: Qwen3NextConfig):
    """(attention [B, S, H, d] * sigmoid(gate)) W_o -> [B, S, D]."""
    dt = cfg.dtype
    y = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32)))
    return _proj(y.astype(dt).reshape(*o.shape[:2], -1), lp["w_o"], dt)


def moe_block(lp, x, cfg: Qwen3NextConfig, token_mask=None):
    """x [B, S, D] -> (x + the expert block's output, stats: tokens per
    HELD expert [n_held], distinct held experts hit, picks routed to absent
    experts, the choices [B, S, top_k] over all experts). ``token_mask``
    [B, S] keeps pad and idle rows out of the products and the counts."""
    b, s, d = x.shape
    dt = cfg.dtype
    hid = zc_norm(x, lp["post_norm"], cfg.norm_eps)
    flat = hid.reshape(b * s, d)
    experts, weights = moe.route(flat, lp["router"], None,
                                 cfg.router_config())
    live = (jnp.ones((b * s,), bool) if token_mask is None
            else token_mask.reshape(b * s))
    w = [lp[key] for key in EXPERT_MATRICES]
    first = 0
    if w[0].ndim == 4:
        # the whole stack [layers, E_held, ...] and this layer's place in it
        w = [a.reshape(-1, *a.shape[2:]) for a in w]
        first = lp["layer_index"] * cfg.n_experts_held
    y, counts = moe.routed_experts(
        flat, experts, weights, *w, valid=live,
        n_experts=cfg.n_experts_held, first_group=first,
        held_from=cfg.first_expert)
    shared = jax.nn.silu(flat @ lp["ws_gate"].astype(dt)) \
        * (flat @ lp["ws_up"].astype(dt))
    shared = (shared @ lp["ws_down"].astype(dt)).astype(jnp.float32) \
        * jax.nn.sigmoid((flat @ lp["w_sg"].astype(dt)).astype(jnp.float32))
    out = (x.reshape(b * s, d).astype(jnp.float32) + y.astype(jnp.float32)
           + shared).astype(x.dtype)
    held = (experts >= cfg.first_expert) \
        & (experts < cfg.first_expert + cfg.n_experts_held)
    absent = jnp.sum(live[:, None] & ~held).astype(jnp.int32)
    return out.reshape(b, s, d), {
        "tokens_per_expert": counts, "experts_hit": jnp.sum(counts > 0),
        "absent_picks": absent, "experts": experts.reshape(b, s, -1)}


def layer_out(lp, x, o, token_mask, cfg: Qwen3NextConfig):
    """What follows a mixer: its output into the stream, then the expert
    block. ``o``: a GDN layer's output [B, S, D], or an attention layer's
    (attention, gate)."""
    y = attn_out(lp, *o, cfg) if isinstance(o, tuple) else o
    x = (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)
    return moe_block(lp, x, cfg, token_mask)


def embed_tokens(params, tokens, cfg: Qwen3NextConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def lm_head(params, x_last, cfg: Qwen3NextConfig):
    """x_last [B, D] before the final norm -> logits [B, V] float32."""
    x_last = zc_norm(x_last, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bd,dv->bv", x_last,
                      params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def _stack(params, cfg: Qwen3NextConfig):
    """The three stacks as one stack of periods, the matrices of ``WHOLE``
    left whole beside it (``paged_kv._scan_layers``)."""
    per = cfg.full_attention_interval
    n = cfg.n_layers // per
    cut = lambda tree, k: {key: a.reshape(n, k, *a.shape[1:])
                           for key, a in params[tree].items()
                           if key not in WHOLE}
    return {"linear": cut("linear", per - 1), "full": cut("full", 1),
            "moe": cut("moe", per),
            **{key: params[tree][key] for tree, keys in WHOLE_OF.items()
               for key in keys}}


def period_layer(lp, j, cfg: Qwen3NextConfig):
    """Layer ``j`` of a period's weights (``_stack``, one step of the scan,
    with the stacks of ``WHOLE`` and the period's ``stack_index``): each
    projection matrix by ONE dynamic index into its stack, which the
    compiler fuses into the product (a period's slice of it, then the
    layer's, would be two copies), the experts' stacks whole and the
    layer's index among all."""
    per = cfg.full_attention_interval
    kind, i = ("linear", j) if j < per - 1 else ("full", 0)
    at = lp["stack_index"] * (per - 1 if kind == "linear" else 1) + i
    pick = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    return {**pick(lp[kind], i), **pick(lp["moe"], j),
            **{key: jax.lax.dynamic_index_in_dim(lp[key], at, keepdims=False)
               for key in WHOLE_OF[kind]},
            **{key: lp[key] for key in EXPERT_MATRICES},
            "layer_index": lp["stack_index"] * per + j}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: Qwen3NextConfig):
    """Full-sequence forward. tokens [B, S] -> logits [B, S, V] float32."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = embed_tokens(params, tokens, cfg)
    zeros = {"gdn_s": jnp.zeros((b, cfg.n_v_heads, cfg.k_head_dim,
                                 cfg.v_head_dim), jnp.float32),
             "gdn_conv": jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_dim),
                                   jnp.float32)}
    valid = jnp.ones((b, s), bool)
    stack = _stack(params, cfg)
    whole = {key: stack.pop(key) for key in WHOLE}

    def period(x, xs):
        lp = dict(xs[1], **whole, stack_index=xs[0])
        for j, kind in enumerate(cfg.period):
            lj = period_layer(lp, j, cfg)
            if kind == "recurrent":
                o, _ = gdn_chunk(lj, x, positions, zeros, valid, cfg)
            else:
                q, gate, k, v = attn_inputs(lj, x, positions, cfg)
                o = (attention(q, k, v, causal=True, impl="xla"), gate)
            x, _ = layer_out(lj, x, o, None, cfg)
        return x, None

    n = cfg.n_layers // cfg.full_attention_interval
    x, _ = jax.lax.scan(period, x, (jnp.arange(n), stack))
    return lm_head(params, x.reshape(b * s, cfg.dim), cfg).reshape(b, s, -1)


# ---------------------------------------------------------------------------
# The serving programs' view of the model (models/paged.PagedOps)
# ---------------------------------------------------------------------------

def _views(pools, layer, tables, cfg: Qwen3NextConfig):
    """Slot-logical K and V views [B, T, KV, d] of a merged pool's layer."""
    b = tables.shape[0]
    return tuple(pools[key][layer, tables].reshape(
        b, -1, cfg.n_kv_heads, cfg.head_dim) for key in ("k", "v"))


def _paged_ops(cfg: Qwen3NextConfig):
    from kubeflow_tpu.models.paged import PagedOps

    def qkv(lp, x, positions, state):
        del state
        q, gate, k, v = attn_inputs(lp, x, positions, cfg)
        return (q, gate), {"k": k, "v": v}, {}

    def decode_attn(lp, q, pools, layer, tables, kv_len, kernel, mesh,
                    interpret):
        del lp, mesh                              # refused by the engine
        q, gate = q
        if kernel == "pallas":
            from kubeflow_tpu.ops.pallas_paged_attention import (
                paged_decode_attention,
            )

            o = paged_decode_attention(
                q[:, 0], pools["k"], pools["v"], layer, tables, kv_len,
                interpret=interpret, kv_heads=cfg.n_kv_heads)[:, None]
        else:
            k_view, v_view = _views(pools, layer, tables, cfg)
            o = decode_attention(q, k_view, v_view, kv_len)
        return o, gate

    def chunk_attention(lp, q, pools, layer, tables, q_start):
        del lp
        q, gate = q
        k_view, v_view = _views(pools, layer, tables, cfg)
        return attention(q, k_view, v_view, causal=True, impl="xla",
                         q_offset=q_start), gate

    return PagedOps(
        n_layers=cfg.n_layers,
        pool_rows={"k": (cfg.n_kv_heads, cfg.head_dim),
                   "v": (cfg.n_kv_heads, cfg.head_dim)},
        period=cfg.period,
        period_layer=lambda lp, j: period_layer(lp, j, cfg),
        state_rows={"gdn_s": ((cfg.n_v_heads, cfg.k_head_dim,
                               cfg.v_head_dim), jnp.float32),
                    # float32: a bf16 ring is small enough for XLA to park
                    # the whole array in VMEM for a call and write it back
                    "gdn_conv": ((cfg.conv_kernel - 1, cfg.conv_dim),
                                 jnp.float32)},
        recurrent=lambda lp, x, positions, state, valid: gdn_chunk(
            lp, x, positions, state, valid, cfg),
        recurrent_decode=lambda lp, x, positions, states, layer, live,
        kernel, interpret: gdn_decode(lp, x, positions, states, layer, live,
                                      kernel, interpret, cfg),
        layer_stacks=lambda params: [(_stack(params, cfg), WHOLE)],
        embed=lambda params, tokens: embed_tokens(params, tokens, cfg),
        qkv=qkv, decode_attention=decode_attn,
        chunk_attention=chunk_attention,
        out=lambda lp, x, o, token_mask, carry: (
            lambda x, stats: (x, carry, stats))(
                *layer_out(lp, x, o, token_mask, cfg)),
        head=lambda params, x_last: lm_head(params, x_last, cfg),
        routed_per_token=cfg.n_layers * cfg.top_k,
        refuses={
            "radix prefix cache": "a shared chunk's compute is skipped, so "
                                  "there is no recurrent state at the block "
                                  "boundary where the private rows begin: "
                                  "no state snapshot is written there",
            "disaggregated tiers": "export and import move pool blocks "
                                   "only; the GDN layers' state does not "
                                   "travel with them",
            "speculative decode": "the verify step rewinds rows past a "
                                  "rejected draft but cannot rewind the "
                                  "GDN state S",
            "int8 weights": "the experts' grouped products and the GDN "
                            "projections are not int8-lowered",
            "quantized KV pool": "the full layers' normed keys have not "
                                 "been measured under a per-block scale",
            "tensor mesh": "two KV heads and a per-slot state with no "
                           "sharding rule",
        })
