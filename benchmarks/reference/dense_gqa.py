"""Plain reference for both configurations: a dense decoder with grouped-query
attention, as Mistral-7B-v0.3 and InternLM2 publish it.

    x   = embed[tokens]
    for each layer:
        h = rmsnorm(x) ; q,k,v = h Wq, h Wk, h Wv ; rope(q), rope(k)
        x = x + softmax(causal(q k^T / sqrt(head_dim))) v  Wo     (GQA: each KV
                                          head serves heads/kv_heads queries)
        h = rmsnorm(x) ; x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = rmsnorm(x) Whead

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no batching
tricks, and nothing imported from the program. RoPE rotates the two halves of
a head (the Hugging Face convention both checkpoints use), no scaling.
Departures from the published code: none in the equations; the weights arrive
in the layout the program stores them in (a pytree with a leading ``layers``
axis, q/k/v as three matrices) and are cast to float32 one layer at a time, so
that a 7 B-wide model fits beside the served copy.

``cfg`` is a configuration file's JSON object (the published keys).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    # x: [B, S, H, D]; rotate (first half, second half) pairs
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, :, None].astype(jnp.float32) * inv          # [B,S,D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(x, lp, positions, *, eps, theta):
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    b, s, _ = x.shape
    heads, kv_heads = lp["wq"].shape[1], lp["wk"].shape[1]
    hd = lp["wq"].shape[2]
    h = _rmsnorm(x, lp["attn_norm"], eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), positions, theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), positions, theta)
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    h = _rmsnorm(x, lp["mlp_norm"], eps)
    ff = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + ff @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, w_head, *, eps):
    return _rmsnorm(x, final_norm.astype(jnp.float32), eps) \
        @ w_head.astype(jnp.float32)


def forward_logits(params, tokens, cfg: dict):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                 tokens.shape)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(x, lp, positions, eps=eps, theta=theta)
        head = (params["embed"].T if cfg.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=eps)


def lm_loss(params, tokens, cfg: dict, z_loss: float = 0.0) -> float:
    """Mean next-token cross-entropy of ``tokens`` [B, S+1], one sequence at
    a time (the logits of one 4096-token sequence at a 92,544-word vocabulary
    are 1.5 GB); ``z_loss`` adds ``z * logsumexp(logits)^2`` per token, as
    the trainer's loss does."""
    total, count = 0.0, 0
    for row in tokens:
        logits = forward_logits(params, row[None, :-1], cfg)[0]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.asarray(row[1:], jnp.int32)[:, None], axis=-1)[:, 0]
        loss = logz - picked + z_loss * jnp.square(logz)
        total += float(jnp.sum(loss))
        count += int(loss.shape[0])
    return total / count
