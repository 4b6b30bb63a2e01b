"""Plain reference for ``zaya1-8b-l20``: compressed convolutional attention
(CCA) and top-1 experts behind an MLP router that carries its state from
layer to layer, as ZAYA1-8B's ``config.json`` and the published descriptions
give them (CCA: arXiv:2510.04476; router, residual scaling: arXiv:2511.17127;
what neither states is listed under ``assumed`` in the configuration file).
Layer ``l``, token ``t``, ``g(i) = i // 4`` the KV head of query head ``i``,
every norm an RMSNorm with eps 1e-5:

    attention half
      h_t  = N(x_t)
      q~_t = h_t W_q  [8 x 128];  k~_t = h_t W_k  [2 x 128]        no bias
      v_t  = [h_t W_v1 ; h_{t-1} W_v2]     KV head 0: this token, 1: the last
      u_t  = [q~_t ; k~_t]                 1280 values
      a_t  = w0[1] * u_t + w0[0] * u_{t-1} + b0                   depthwise
      c_t  = W1[1] a_t + W1[0] a_{t-1} + b1    10 groups of 128 -> 128
             u_{-1} = a_{-1} = h_{-1} = 0
      m^q_{t,i} = (q~_{t,i} + k~_{t,g(i)}) / 2;  m^k_{t,j} = mean_{g(i)=j} m^q_{t,i}
      q_t = c_t[q] + m^q_t;  k_t = c_t[k] + m^k_t
      q_{t,i} <- sqrt(128) q_{t,i} / |q_{t,i}|;  k_{t,j} <- tau_j sqrt(128) k_{t,j} / |k_{t,j}|
      RoPE on the first 64 values of each head, halves rotated, theta 5e6
      o_{t,i} = sum_{s<=t} softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(128)) v_{s,g(i)}
      x_t <- alpha_a * x_t + gamma_a * (o_t W_o)
    expert half
      h_t = N(x_t);  r_t = h_t W_d + b_d  [256]
      r_t <- r_t + eta_l r^{(l-1)}_t (zeros into layer 0);  r^{(l)}_t := r_t
      z_t = W_3 gelu(W_2 gelu(W_1 N(r_t) + b_1) + b_2)             17 logits
      p_t = softmax(z_t);  e_t = argmax_j (p_{t,j} + beta_j)
      e_t = 16: y_t = 0;  else y_t = p_{t,e_t} W_down^e (silu(h_t W_gate^e) * h_t W_up^e)
      x_t <- alpha_m * x_t + gamma_m * y_t
    head: logits = N(x) E^T, E the embedding

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a Python loop over the layers,
the convolutions as explicit shifted sums, a loop over ALL experts with a
dense gate matrix, no cache, no kernel, nothing imported from the program.
The weights arrive in the program's pytree (one stack of layers, [in, out]
matrices) and are cast to float32 a layer, and an expert, at a time, so that
the 9.4 GB model fits beside the served copy; one request at a time; scores
in blocks of queries; the head in blocks of the vocabulary and only at the
rows asked for.

Routing near a tie. With random weights the two largest of 17 ``p + beta``
can lie closer than the program's bf16 hidden state resolves. ``forced`` hands
the reference the program's choice at ``forced_rows``: where it
differs from the reference's own, ``forward`` records by how much the
program's pick falls short of the reference's largest ``p + beta``
(``route_gap``; ``route_gaps`` lists every one) and takes the program's
choice only if that is within ``route_tol``; a choice further off stays the
reference's own and is counted in ``route_violations``. Rows whose choice is
not handed in keep the reference's own routing; the check hands in every row
(``forced_rows``), because a row attends to all before it and a prompt row
routed the other way at a near-tie carries another expert's output.

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


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _shifted(x):
    """Row t holds x[t - 1], row 0 zeros: the left zero padding."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _rope_halves(x, positions, theta, rotary):
    """x [S, heads, d]: the first ``rotary`` values of each head rotated as
    halves (i, i + rotary / 2), the rest left as they are."""
    half = rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv      # [S,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rotary", "n_q",
                                             "n_kv"))
def _attention_inputs(x, lp, positions, *, eps, theta, rotary, n_q, n_kv):
    lp = _f32(lp)
    h = _rmsnorm(x, lp["attn_norm"], eps)
    q0 = (h @ lp["w_q"]).reshape(x.shape[0], n_q, -1)           # [S, H, d]
    k0 = (h @ lp["w_k"]).reshape(x.shape[0], n_kv, -1)          # [S, KV, d]
    d = q0.shape[2]
    u = jnp.concatenate([q0, k0], axis=1).reshape(x.shape[0], -1)
    a = lp["conv0_w"][1] * u + lp["conv0_w"][0] * _shifted(u) + lp["conv0_b"]
    grouped = a.reshape(-1, n_q + n_kv, d)
    c = (jnp.einsum("sgi,gio->sgo", grouped, lp["conv1_w"][1])
         + jnp.einsum("sgi,gio->sgo", _shifted(grouped), lp["conv1_w"][0])
         + lp["conv1_b"])
    mean_q = (q0 + jnp.repeat(k0, n_q // n_kv, axis=1)) / 2
    mean_k = mean_q.reshape(-1, n_kv, n_q // n_kv, d).mean(2)
    q = c[:, :n_q] + mean_q
    k = c[:, n_q:] + mean_k
    root = jnp.sqrt(jnp.float32(d))
    q = root * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = jnp.exp(lp["log_tau"])[:, None] * root * k \
        / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.stack([h @ lp["w_v1"], _shifted(h @ lp["w_v2"])], axis=1)
    return (_rope_halves(q, positions, theta, rotary),
            _rope_halves(k, positions, theta, rotary), v)


@jax.jit
def _attend(q, k, v, q0):
    """One block of queries (rows q0 ..) against all keys, causal, each
    query head over its group's KV head."""
    d = q.shape[-1]
    rep = q.shape[1] // k.shape[1]
    s = jnp.einsum("qhk,thk->hqt", q, jnp.repeat(k, rep, axis=1)) \
        / jnp.sqrt(jnp.float32(d))
    q_pos = q0 + jnp.arange(q.shape[0])
    seen = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thv->qhv", p, jnp.repeat(v, rep, axis=1))


@functools.partial(jax.jit, static_argnames=("eps",))
def _after_attention(x, o, lp, *, eps):
    lp = _f32(lp)
    x = lp["attn_alpha"] * x \
        + lp["attn_gamma"] * jnp.einsum("shk,hkd->sd", o, lp["wo"])
    return x, _rmsnorm(x, lp["mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _router(h, carry, lp, *, eps):
    """(p [S, 17], p + beta, the reference's own choice, this layer's router
    state)."""
    lp = _f32(lp)
    r = h @ lp["router_down"] + lp["router_down_b"] \
        + lp["router_eta"] * carry
    z = _rmsnorm(r, lp["router_norm"], eps)
    z = jax.nn.gelu(z @ lp["router_w1"] + lp["router_b1"], approximate=False)
    z = jax.nn.gelu(z @ lp["router_w2"] + lp["router_b2"], approximate=False)
    p = jax.nn.softmax(z @ lp["router_w3"], axis=-1)
    choice = p + lp["router_bias"]
    return p, choice, jnp.argmax(choice, axis=-1), r


@functools.partial(jax.jit, donate_argnums=(0,))
def _one_expert(y, h, gates, w_gate, w_up, w_down, layer, e):
    """y + gate_e * SwiGLU_e(h), expert ``e`` of layer ``layer`` read out of
    the stacks [layers, experts, ...] and cast to float32 here."""
    wg, wu, wd = _f32(tuple(a[layer, e] for a in (w_gate, w_up, w_down)))
    return y + gates[:, e, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd)


@jax.jit
def _merge(x, y, alpha, gamma):
    return alpha.astype(jnp.float32) * x + gamma.astype(jnp.float32) * y


def _expert_half(x, h, carry, layers, i, cfg, rows, forced, route_tol,
                 notes):
    n_exp = cfg["num_experts"]
    lp = {key: layers[key][i] for key in layers
          if key.startswith("router_")}
    p, choice, own, carry = _router(h, carry, lp,
                                    eps=float(cfg["rms_norm_eps"]))
    chosen = np.array(own)
    if forced is not None:
        choice_np = np.asarray(choice)
        rows, forced = np.asarray(rows), np.asarray(forced).reshape(-1)
        differ = np.nonzero(forced != chosen[rows])[0]
        for r, want in zip(rows[differ], forced[differ]):
            gap = float(choice_np[r, chosen[r]] - choice_np[r, want])
            notes["route_disagreements"] += 1
            notes["route_gap"] = max(notes["route_gap"], gap)
            notes["route_gaps"].append(round(gap, 4))
            if gap <= route_tol:
                chosen[r] = want
            else:
                notes["route_violations"] += 1
    picked = jnp.asarray(chosen)
    w = jnp.take_along_axis(p, picked[:, None], axis=-1)[:, 0]
    # column 16 is the skip: no expert, a delta of exactly zero
    gates = jax.nn.one_hot(picked, n_exp + 1, dtype=jnp.float32)[:, :n_exp] \
        * w[:, None]
    y = jnp.zeros_like(h)
    for e in range(n_exp):
        y = _one_expert(y, h, gates, *(layers[key] for key in EXPERTS), i, e)
    return _merge(x, y, layers["mlp_alpha"][i], layers["mlp_gamma"][i]), \
        carry, chosen


@functools.partial(jax.jit, static_argnames=("eps", "width"))
def _head_block(x, final_norm, embed, start, *, eps, width):
    w = jax.lax.dynamic_slice_in_dim(embed, start, width, axis=0)
    return _rmsnorm(x, final_norm.astype(jnp.float32), eps) \
        @ w.astype(jnp.float32).T


def _head_in_blocks(x, final_norm, embed, eps):
    """The tied head an eighth of the vocabulary at a time, each cut out of
    the table inside its own program: 262,272 rows in float32 are 2.1 GB."""
    vocab = embed.shape[0]
    width = vocab // 8 if vocab % 8 == 0 else vocab
    return jnp.concatenate([
        _head_block(x, final_norm, embed, a, eps=eps, width=width)
        for a in range(0, vocab, width)], axis=-1)


def forward(params, tokens, cfg: dict, rows=None, forced=None,
            route_tol: float = 0.0, q_block: int = 256,
            pad_to: int = 0, forced_rows=None) -> dict:
    """One request. tokens [S] int -> ``logits`` [len(rows), V] float32 at
    ``rows`` (default: every row), ``experts`` [layers, len(rows)] as used
    (``num_experts``: the skip), and the routing notes of the module
    docstring. ``forced`` [layers, len(forced_rows)]: the program's choice
    at ``forced_rows`` (default: ``rows``; the check hands in every row of
    the request, since a compared row attends to all before it).
    ``pad_to``: run at this many rows, the request padded at its end."""
    if cfg["cca_time0"] != 2 or cfg["cca_time1"] != 2:
        raise ValueError("both convolutions are written for kernel 2")
    eps = float(cfg["rms_norm_eps"])
    rope = cfg["rope_parameters"]["hybrid"]
    rotary = int(cfg["head_dim"] * rope["partial_rotary_factor"])
    tokens = np.asarray(tokens, np.int32)
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    forced_rows = rows if forced_rows is None else list(forced_rows)
    if pad_to > tokens.shape[0]:
        # rows after the request's own: causal attention, the value shift
        # and both convolutions look back only, so no compared row sees them
        tokens = np.concatenate(
            [tokens, np.zeros(pad_to - tokens.shape[0], np.int32)])
    tokens = jnp.asarray(tokens)
    positions = jnp.arange(tokens.shape[0])
    notes = {"route_disagreements": 0, "route_violations": 0,
             "route_gap": 0.0, "route_gaps": []}
    layers = params["layers"]
    attn_keys = ("attn_norm", "w_q", "w_k", "w_v1", "w_v2", "conv0_w",
                 "conv0_b", "conv1_w", "conv1_b", "log_tau")
    used = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        carry = jnp.zeros((x.shape[0], cfg["router_hidden_size"]),
                          jnp.float32)
        for i in range(layers["attn_norm"].shape[0]):
            q, k, v = _attention_inputs(
                x, {key: layers[key][i] for key in attn_keys}, positions,
                eps=eps, theta=float(rope["rope_theta"]), rotary=rotary,
                n_q=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"])
            o = jnp.concatenate([
                _attend(q[a:a + q_block], k, v, a)
                for a in range(0, x.shape[0], q_block)])
            x, h = _after_attention(
                x, o, {key: layers[key][i] for key in (
                    "wo", "attn_alpha", "attn_gamma", "mlp_norm")}, eps=eps)
            x, carry, chosen = _expert_half(
                x, h, carry, layers, i, cfg, forced_rows,
                None if forced is None else np.asarray(forced)[i],
                route_tol, notes)
            used.append(chosen[rows])
        return dict(notes, experts=np.stack(used),
                    logits=_head_in_blocks(x[jnp.asarray(rows)],
                                           params["final_norm"],
                                           params["embed"], eps))
