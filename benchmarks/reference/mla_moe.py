"""Plain reference for ``joyai-llm-flash-l5``: latent attention (MLA), one
leading dense layer, then layers of 256 sigmoid-routed experts (top 8) beside
a shared expert, and the multi-token-prediction block, as JoyAI-LLM-Flash's
``config.json`` and the public implementation of its family (DeepSeek-V3 in
``transformers``) give them. ``u`` is the normed input, every norm an RMSNorm:

    x = E[token];  h = x + Attn(N_1(x));  y = h + FFN(N_2(h)); final norm; head
    Attn:  c_q = N_q(W_dq u);  q = W_uq c_q  (H x 192 = q_nope 128 | q_rope 64)
           [c_kv ; k_r] = W_dkv u;  c = N_kv(c_kv);  k_rope = RoPE(k_r), one a
           token for all heads;  q_rope = RoPE(q_rope)
           k_nope,h = W_uk,h c;  v_h = W_uv,h c
           s = (q_nope . k_nope + q_rope . k_rope) / sqrt(192), causal softmax,
           o_h = sum p v_h;  Attn = W_o [o_1 .. o_H]
    FFN, dense layer:  SwiGLU of width 7168
    FFN, expert layer: s = sigmoid(W_r u) in float32; the chosen experts: the
           top 8 of s + b (b: the noaux_tc correction bias); weights are s at
           the chosen experts (without b) over their sum, times 2.5;
           FFN = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)
    MTP:   h' = W_p [N_h(h_i) ; N_e(E[t_{i+1}])], one more expert block, its
           own final norm, the shared head: logits for t_{i+2}

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a Python loop over the layers and
a loop over ALL experts with a dense gate matrix, the non-absorbed attention,
no cache, no kernel, nothing imported from the program. Departures from the
published code, all of layout or of size: RoPE's interleaved pairs (2i, 2i+1)
are rotated in place (the public code de-interleaves first; every score is the
same); the weights arrive in the program's pytree (two stacks of layers, W_uk
and W_uv apart, [in, out] matrices) and are cast to float32 a layer, and an
expert, at a time, so that the 11 GB model fits beside the served copy; one
request at a time; scores in blocks of queries; the head only at the rows
asked for.

Routing near a tie. With random weights the 8th and 9th of 256 scores lie
close, and the program's bf16 hidden state picks the other one now and then.
``forced`` hands the reference the program's choice at the rows that are
compared: where it differs from the reference's own, ``forward`` records by
how much the program's pick falls short of the reference's 8th score
(``route_gap``) and takes the program's choice only if that is under
``route_tol``; a choice further off stays the reference's own and is counted
in ``route_violations``. Rows that are not compared keep the reference's own
routing.

``cfg`` is a configuration file's JSON object (the published keys).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


class _Layer:
    """Layer ``i`` of a stack of layers, read piece by piece: a layer's 256
    experts are 2.4 GB that must never be sliced out of the stack whole."""

    def __init__(self, stack, i):
        self.stack, self.i = stack, i

    def __contains__(self, key):
        return key in self.stack

    def __getitem__(self, key):
        if key in ("w_gate", "w_up", "w_down") and "router" in self.stack:
            raise KeyError(f"{key}: one expert at a time (expert_stacks)")
        return self.stack[key][self.i]

    def expert_stacks(self):
        """The three stacks of expert matrices, whole, and this layer's
        place in them: ``_one_expert`` picks its expert out inside its own
        program. (Slices made out here are buffers the host queues far
        ahead of their use: 2.4 GB a layer.)"""
        return (*(self.stack[key] for key in ("w_gate", "w_up", "w_down")),
                self.i)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope_pairs(x, positions, theta):
    """x [S, ..., D] with rotary pairs (2i, 2i+1), rotated in place."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv             # [S, D/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


@jax.jit
def _ffn(u, w_gate, w_up, w_down):
    """SwiGLU with the weights cast to float32 here, as one program: op by
    op the dense layer's four [rows, 7168] intermediates are 1.9 GB at
    16,896 rows."""
    return _swiglu(u, *_f32((w_gate, w_up, w_down)))


@functools.partial(jax.jit, static_argnames=("eps", "theta", "nope", "rank"))
def _attention_inputs(x, lp, positions, *, eps, theta, nope, rank):
    lp = _f32(lp)
    u = _rmsnorm(x, lp["attn_norm"], eps)
    c_q = _rmsnorm(u @ lp["w_dq"], lp["q_norm"], eps)
    q = jnp.einsum("sr,rhk->shk", c_q, lp["w_uq"])
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], positions,
                                                theta)
    ckv = u @ lp["w_dkv"]
    c = _rmsnorm(ckv[:, :rank], lp["kv_norm"], eps)
    k_rope = _rope_pairs(ckv[:, rank:], positions, theta)
    k_nope = jnp.einsum("sc,chk->shk", c, lp["w_uk"])
    v = jnp.einsum("sc,chv->shv", c, lp["w_uv"])
    return q_nope, q_rope, k_nope, k_rope, v


@jax.jit
def _attend(q_nope, q_rope, k_nope, k_rope, v, q0):
    """One block of queries (rows q0 ..) against all keys, causal."""
    d = q_nope.shape[-1] + q_rope.shape[-1]
    s = (jnp.einsum("qhk,thk->hqt", q_nope, k_nope)
         + jnp.einsum("qhr,tr->hqt", q_rope, k_rope)) / jnp.sqrt(
             jnp.float32(d))
    q_pos = q0 + jnp.arange(q_nope.shape[0])
    seen = jnp.arange(k_nope.shape[0])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thv->qhv", p, v)


@functools.partial(jax.jit, static_argnames=("eps",))
def _after_attention(x, o, wo, mlp_norm, *, eps):
    h = x + jnp.einsum("shv,hvd->sd", o, wo.astype(jnp.float32))
    return h, _rmsnorm(h, mlp_norm.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _scores(u, router, bias, *, top_k):
    s = jax.nn.sigmoid(u @ router.astype(jnp.float32))
    choice = s + bias.astype(jnp.float32)
    kth, own = jax.lax.top_k(choice, top_k)
    return s, choice, own, kth[:, -1]


@functools.partial(jax.jit, donate_argnums=(0,))
def _one_expert(y, u, gates, w_gate, w_up, w_down, layer, e):
    """y + gate_e * SwiGLU_e(u), expert ``e`` of layer ``layer`` read out of
    the stacks [layers, experts, ...] and cast to float32 here. ``y`` is
    donated: the host queues these calls far ahead, and every one would
    otherwise hold a new sum (36 MB at 4,352 rows) until it had run."""
    w = _f32(tuple(a[layer, e] for a in (w_gate, w_up, w_down)))
    return y + gates[:, e, None] * _swiglu(u, *w)


def _expert_ffn(u, lp, cfg, rows, forced, route_tol, notes):
    """sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u), by a loop over ALL experts."""
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    s, choice, own, kth = _scores(u, lp["router"], lp["router_bias"], top_k=k)
    chosen = np.array(own)
    if forced is not None:
        choice_np, kth_np = np.asarray(choice), np.asarray(kth)
        for r, want in zip(rows, np.asarray(forced)):
            extra = sorted(set(want.tolist()) - set(chosen[r].tolist()))
            if not extra:
                continue
            gap = float(max(kth_np[r] - choice_np[r, x] for x in extra))
            notes["route_disagreements"] += 1
            notes["route_gap"] = max(notes["route_gap"], gap)
            if gap <= route_tol:
                chosen[r] = want
            else:
                notes["route_violations"] += 1
    chosen = jnp.asarray(chosen)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    gates = (jax.nn.one_hot(chosen, e, dtype=jnp.float32)
             * w[..., None]).sum(1)                                # [S, E]
    y = jnp.zeros_like(u)
    for i in range(e):
        y = _one_expert(y, u, gates, *lp.expert_stacks(), i)
    shared = _ffn(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y + shared, np.asarray(chosen)


def _layer(x, lp, positions, cfg, q_block, rows, forced, route_tol, notes):
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    attn = {key: lp[key] for key in ("attn_norm", "w_dq", "q_norm", "w_uq",
                                     "w_dkv", "kv_norm", "w_uk", "w_uv")}
    q_nope, q_rope, k_nope, k_rope, v = _attention_inputs(
        x, attn, positions, eps=eps, theta=theta,
        nope=cfg["qk_nope_head_dim"], rank=cfg["kv_lora_rank"])
    o = jnp.concatenate([
        _attend(q_nope[a:a + q_block], q_rope[a:a + q_block], k_nope, k_rope,
                v, a) for a in range(0, x.shape[0], q_block)])
    h, u = _after_attention(x, o, lp["wo"], lp["mlp_norm"], eps=eps)
    del q_nope, q_rope, k_nope, k_rope, v, o     # 1.2 GB at 16,896 rows
    if "router" not in lp:
        return h + _ffn(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    delta, chosen = _expert_ffn(u, lp, cfg, rows, forced, route_tol, notes)
    return h + delta, chosen


@functools.partial(jax.jit, static_argnames=("eps", "width"))
def _head_block(x, final_norm, w_head, start, *, eps, width):
    w = jax.lax.dynamic_slice_in_dim(w_head, start, width, axis=1)
    return _rmsnorm(x, final_norm.astype(jnp.float32), eps) \
        @ w.astype(jnp.float32)


def _head_in_blocks(x, final_norm, w_head, eps):
    """The head an eighth of the vocabulary at a time, each cut out of the
    matrix inside its own program: 129,280 columns in float32 are 1 GB that
    the chip does not have to spare, and eight slices made out here are
    buffers the host queues ahead of their use."""
    vocab = w_head.shape[1]
    width = vocab // 8 if vocab % 8 == 0 else vocab
    return jnp.concatenate([
        _head_block(x, final_norm, w_head, a, eps=eps, width=width)
        for a in range(0, vocab, width)], axis=-1)


def forward(params, tokens, cfg: dict, rows=None, forced=None,
            route_tol: float = 0.0, predict: bool = False,
            q_block: int = 256, pad_to: int = 0) -> dict:
    """One request. tokens [S] int -> ``logits`` [len(rows), V] float32 at
    ``rows`` (default: every row), ``experts`` [expert layers, len(rows), 8]
    as used, and the routing notes of the module docstring. ``forced``
    [expert layers, len(rows), 8]: the program's choice at ``rows``.
    ``predict``: also ``predict_logits`` [S-1, V], the prediction block's
    (row i predicts token i+2; its own routing, never forced). ``pad_to``:
    run at this many rows, the request padded at its end."""
    eps = float(cfg["rms_norm_eps"])
    tokens = np.asarray(tokens, np.int32)
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    if pad_to > tokens.shape[0]:
        # rows after the request's own: causal attention never shows them
        # to a row that is compared, and one length compiles once
        assert not predict
        tokens = np.concatenate(
            [tokens, np.zeros(pad_to - tokens.shape[0], np.int32)])
    tokens = jnp.asarray(tokens)
    positions = jnp.arange(tokens.shape[0])
    notes = {"route_disagreements": 0, "route_violations": 0,
             "route_gap": 0.0}
    used = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        n_moe = 0
        for stack in (params["dense_layers"], params["moe_layers"]):
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = _Layer(stack, i)
                want = None
                if forced is not None and "router" in lp:
                    want = np.asarray(forced)[n_moe]
                x, chosen = _layer(x, lp, positions, cfg, q_block, rows,
                                   want, route_tol, notes)
                if chosen is not None:
                    used.append(chosen[rows])
                    n_moe += 1
        out = dict(notes, experts=np.stack(used) if used else None,
                   logits=_head_in_blocks(x[jnp.asarray(rows)],
                                          params["final_norm"],
                                          params["lm_head"], eps))
        if predict:
            pp = {key: val[0] for key, val in params["predict"].items()
                  if key != "block"}
            nxt = params["embed"][tokens[1:]].astype(jnp.float32)
            h = jnp.concatenate(
                [_rmsnorm(x[:-1], pp["h_norm"].astype(jnp.float32), eps),
                 _rmsnorm(nxt, pp["e_norm"].astype(jnp.float32), eps)], -1
            ) @ pp["w_proj"].astype(jnp.float32)
            h, _ = _layer(h, _Layer(params["predict"]["block"], 0),
                          positions[:-1], cfg, q_block, [], None, 0.0,
                          dict(notes))
            out["predict_logits"] = _head_in_blocks(
                h, pp["final_norm"], params["lm_head"], eps)
    return out
