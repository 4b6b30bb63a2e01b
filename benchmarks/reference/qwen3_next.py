"""Plain reference for ``qwen3-next-80b-a3b-l8``: Gated DeltaNet linear
attention 3:1 with gated full attention, 512 experts top-10 and a gated
shared expert, as Qwen3-Next-80B-A3B's ``config.json`` and its published
description give them (what neither states is listed under ``assumed`` in the
configuration file). Layer ``i`` is full attention when ``(i + 1) %
full_attention_interval == 0`` and GDN otherwise; ``N`` is the zero-centred
RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * (1 + w)``:

    x <- x + mixer(N(x));   x <- x + moe(N(x));   logits = N(x) W_head

    GDN (16 key heads, 32 value heads of 128, value head j over key head j//2)
      qkvz = h W_qkvz  per key head [q | k | v (2 heads) | z (2 heads)]
      ba   = h W_ba    per key head [b (2) | a (2)]
      [q ; k ; v] through a depthwise causal conv of kernel 4 (left zeros),
      then SiLU; q, k <- x / sqrt(sum x^2 + 1e-6) per head; q <- q / sqrt(128)
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
      per token t and value head, S [128, 128], S = 0 before the first token:
          S <- exp(g_t) S
          S <- S + k_t (beta_t (v_t - S^T k_t))^T
          o_t = S^T q_t
      o <- w * o / sqrt(mean(o^2) + 1e-6) * silu(z);   out = o W_out
    full attention (16 query heads over 2 KV heads of 256)
      [query | gate] = h W_q per head;  k = h W_k;  v = h W_v
      query, k through N per head; rotary on the first 64 values (halves)
      o = softmax(q k^T / 16, causal) v;   out = (o * sigmoid(gate)) W_o
    moe
      p = softmax(h W_router) over 512;  the top 10 divided by their sum
      y = sum over the picks HELD here of w_e W_down^e (silu(h W_gate^e) * h W_up^e)
          + sigmoid(h w_sg) W_down^s (silu(h W_gate^s) * h W_up^s)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a Python loop over the layers,
the convolution as explicit shifted sums, the recurrence token by token (a
``lax.scan`` of the three lines, no chunked form), a loop over the held
experts with a dense gate matrix, no cache, no kernel, nothing imported from
the program. One request at a time.

Departures, each as the program does it: the weights arrive in the program's
pytree (``linear``, ``full`` and ``moe`` stacks, [in, out] matrices, the
conv's tap ``K - 1`` the current token), cast to float32 a layer and an
expert at a time; the expert share: this chip holds ``num_experts`` of the
``num_experts_routed`` from ``first_expert_held`` on, and a pick outside the
share adds nothing, as the program's; the vocabulary is the chip's slice;
the multi-token-prediction block is left out.

Routing near a tie. With random weights the 10th and 11th of 512
probabilities can lie closer than the program's bf16 hidden state resolves.
``forced`` hands the reference the program's choices at ``forced_rows``
(every row of a request, the prompt's too: a row attends to all before it);
where they differ from the reference's own, the gap is the reference's 10th
probability less the lowest of the program's extra picks (``route_gap``,
``route_gaps``), and the program's choice is taken only within
``route_tol``; a choice further off stays the reference's own and counts in
``route_violations``.

``cfg`` is a configuration file's JSON object (the published keys).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS = ("w_gate", "w_up", "w_down")


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _rope_halves(x, positions, theta, rotary):
    half = rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "nk", "nv", "dk", "dv",
                                             "kernel"))
def _gdn(x, lp, *, eps, nk, nv, dk, dv, kernel):
    lp = _f32(lp)
    s_len = x.shape[0]
    r = nv // nk
    h = _norm(x, lp["in_norm"], eps)
    qkvz = (h @ lp["w_qkvz"]).reshape(s_len, nk, 2 * dk + 2 * r * dv)
    q = qkvz[..., :dk]
    k = qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(s_len, nv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(s_len, nv, dv)
    ba = (h @ lp["w_ba"]).reshape(s_len, nk, 2 * r)
    b = ba[..., :r].reshape(s_len, nv)
    a = ba[..., r:].reshape(s_len, nv)
    u = jnp.concatenate([q.reshape(s_len, -1), k.reshape(s_len, -1),
                         v.reshape(s_len, -1)], -1)
    conv = sum(lp["conv_w"][kernel - 1 - j]
               * jnp.concatenate([jnp.zeros((j, u.shape[1])), u[:s_len - j]])
               for j in range(kernel))
    c = jax.nn.silu(conv)
    key_dim = nk * dk
    q = _l2(c[:, :key_dim].reshape(s_len, nk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _l2(c[:, key_dim:2 * key_dim].reshape(s_len, nk, dk))
    v = c[:, 2 * key_dim:].reshape(s_len, nv, dv)
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    beta = jax.nn.sigmoid(b)

    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs            # [nv, d], [nv]
        s = jnp.exp(g_t)[:, None, None] * s
        s = s + k_t[:, :, None] * (beta_t[:, None] * (
            v_t - jnp.einsum("hkv,hk->hv", s, k_t)))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv)), (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * lp["out_norm"] * jax.nn.silu(z)
    return x + o.reshape(s_len, -1) @ lp["w_out"]


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rotary",
                                             "n_q", "n_kv", "hd"))
def _full(x, lp, positions, *, eps, theta, rotary, n_q, n_kv, hd):
    lp = _f32(lp)
    s_len = x.shape[0]
    h = _norm(x, lp["in_norm"], eps)
    qg = (h @ lp["w_q"]).reshape(s_len, n_q, 2 * hd)
    q = _rope_halves(_norm(qg[..., :hd], lp["q_norm"], eps), positions,
                     theta, rotary)
    gate = qg[..., hd:]
    k = _rope_halves(_norm((h @ lp["w_k"]).reshape(s_len, n_kv, hd),
                           lp["k_norm"], eps), positions, theta, rotary)
    v = (h @ lp["w_v"]).reshape(s_len, n_kv, hd)
    rep = n_q // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,thd->hqt", q, k) / jnp.sqrt(jnp.float32(hd))
    seen = jnp.arange(s_len)[None, :] <= jnp.arange(s_len)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thd->qhd", p, v) * jax.nn.sigmoid(gate)
    return x + o.reshape(s_len, -1) @ lp["w_o"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _router(x, lp, *, eps, top_k):
    """(h, p [S, E], the reference's own top-k, its k-th probability)."""
    lp = _f32(lp)
    h = _norm(x, lp["post_norm"], eps)
    p = jax.nn.softmax(h @ lp["router"], axis=-1)
    kth, own = jax.lax.top_k(p, top_k)
    return h, p, own, kth[:, -1]


@functools.partial(jax.jit, donate_argnums=(0,))
def _one_expert(y, h, gates, w_gate, w_up, w_down, layer, e):
    """y + gate_e * SwiGLU_e(h), held expert ``e`` of layer ``layer`` read
    out of the stacks [layers, held, ...] and cast to float32 here."""
    wg, wu, wd = _f32(tuple(a[layer, e] for a in (w_gate, w_up, w_down)))
    return y + gates[:, e, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd)


@jax.jit
def _shared(x, y, h, lp):
    lp = _f32(lp)
    s = (jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"])) @ lp["ws_down"]
    return x + y + jax.nn.sigmoid(h @ lp["w_sg"]) * s


def _moe(x, layers, i, cfg, rows, forced, route_tol, notes):
    eps = float(cfg["rms_norm_eps"])
    held, first = cfg["num_experts"], cfg["first_expert_held"]
    lp = {key: layers[key][i] for key in ("post_norm", "router")}
    h, p, own, kth = _router(x, lp, eps=eps,
                             top_k=cfg["num_experts_per_tok"])
    chosen = np.array(own)
    if forced is not None:
        p_np, kth_np = np.asarray(p), np.asarray(kth)
        for r, want in zip(rows, np.asarray(forced)):
            extra = sorted(set(want.tolist()) - set(chosen[r].tolist()))
            if not extra:
                continue
            gap = float(max(kth_np[r] - p_np[r, e] for e in extra))
            notes["route_disagreements"] += 1
            notes["route_gap"] = max(notes["route_gap"], gap)
            notes["route_gaps"].append(round(gap, 6))
            if gap <= route_tol:
                chosen[r] = want
            else:
                notes["route_violations"] += 1
    picked = jnp.asarray(chosen)
    w = jnp.take_along_axis(p, picked, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True)
    # only the experts this chip holds: the others add nothing here
    gates = (jax.nn.one_hot(picked - first, held, dtype=jnp.float32)
             * w[..., None]).sum(1)                           # [S, held]
    y = jnp.zeros_like(h)
    for e in range(held):
        y = _one_expert(y, h, gates, *(layers[key] for key in EXPERTS), i, e)
    shared = {key: layers[key][i] for key in ("ws_gate", "ws_up", "ws_down",
                                              "w_sg")}
    return _shared(x, y, h, shared), chosen


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, w_head, *, eps):
    return _norm(x, final_norm.astype(jnp.float32), eps) \
        @ w_head.astype(jnp.float32)


def forward(params, tokens, cfg: dict, rows=None, forced=None,
            route_tol: float = 0.0, pad_to: int = 0,
            forced_rows=None) -> dict:
    """One request. tokens [S] int -> ``logits`` [len(rows), V] float32 at
    ``rows`` (default: every row), ``experts`` [layers, len(rows), top_k] as
    used, and the routing notes of the module docstring. ``forced``
    [layers, len(forced_rows), top_k]: the program's choices (default rows:
    ``rows``). ``pad_to``: run at this many rows, the request padded at its
    end (causal attention, the conv and the recurrence look back only)."""
    eps = float(cfg["rms_norm_eps"])
    per = cfg["full_attention_interval"]
    tokens = np.asarray(tokens, np.int32)
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    forced_rows = rows if forced_rows is None else list(forced_rows)
    if pad_to > tokens.shape[0]:
        tokens = np.concatenate(
            [tokens, np.zeros(pad_to - tokens.shape[0], np.int32)])
    tokens = jnp.asarray(tokens)
    positions = jnp.arange(tokens.shape[0])
    notes = {"route_disagreements": 0, "route_violations": 0,
             "route_gap": 0.0, "route_gaps": []}
    layers = params["moe"]
    used = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            if (i + 1) % per:
                j = i // per * (per - 1) + i % per
                x = _gdn(x, jax.tree.map(lambda a: a[j], params["linear"]),
                         eps=eps, nk=cfg["linear_num_key_heads"],
                         nv=cfg["linear_num_value_heads"],
                         dk=cfg["linear_key_head_dim"],
                         dv=cfg["linear_value_head_dim"],
                         kernel=cfg["linear_conv_kernel_dim"])
            else:
                x = _full(x, jax.tree.map(lambda a: a[i // per],
                                          params["full"]),
                          positions, eps=eps, theta=float(cfg["rope_theta"]),
                          rotary=int(cfg["head_dim"]
                                     * cfg["partial_rotary_factor"]),
                          n_q=cfg["num_attention_heads"],
                          n_kv=cfg["num_key_value_heads"],
                          hd=cfg["head_dim"])
            x, chosen = _moe(x, layers, i, cfg, forced_rows,
                             None if forced is None else np.asarray(forced)[i],
                             route_tol, notes)
            used.append(chosen[rows])
        return dict(notes, experts=np.stack(used),
                    logits=_head(x[jnp.asarray(rows)], params["final_norm"],
                                 params["lm_head"], eps=eps))
