"""Llama-3 model family, TPU-first.

Design (deliberately not a torch translation — SURVEY.md §7 design stance):

- Pure-functional: params are a pytree of arrays; `forward` is a jittable
  function. No module framework in the hot path.
- **Scan over layers**: all transformer blocks are stacked along a leading
  `layers` axis and executed with `jax.lax.scan`, so XLA compiles ONE block
  regardless of depth (compile time O(1) in n_layers) and remat policy applies
  uniformly.
- **Logical axes everywhere**: every param/activation carries logical axis
  names resolved against a mesh by `parallel.sharding` rules — the same model
  runs DP/FSDP/TP/SP by swapping the rule table.
- bf16 compute, f32 params (casting at the boundary), f32 softmax/norms.

Reference parity: the reference (Kubeflow) ships no model code — models live
in user containers. This module is the first-party data plane SURVEY.md §7
requires, sized for the BASELINE.json configs (Llama-3-8B serving, 70B FSDP).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.models.paged import PagedOps
from kubeflow_tpu.ops.attention import (
    _xla_attention, attention, decode_attention,
)
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.paged_pool import gather_views
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.parallel.sharding import constrain


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    rope_scaling: str | None = "llama3"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    attn_impl: str = "xla"            # "xla" | "flash" | "pallas"
    attn_block: int = 512             # flash-kernel tile (VMEM budget knob)
    remat: str = "full"               # "none" | "full" | "dots"
    z_loss: float = 1e-4
    # MoE (0 experts = dense MLP). Mixtral-style: the FFN becomes a routed
    # mixture; attention/embeddings unchanged (SURVEY.md §2.7 'EP').
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def flops_per_token(self, seq: int | None = None) -> float:
        """Approx model FLOPs per token (fwd+bwd = 3x fwd matmul FLOPs).
        With ``seq`` the causal attention-score FLOPs (QK^T and PV, avg
        context seq/2) are included — the MFU-honest accounting. Remat
        recompute is deliberately NOT counted (it lowers reported MFU).
        For MoE only the top-k experts' FFN FLOPs are active per token."""
        d, m, v = self.dim, self.mlp_dim, self.vocab_size
        attn_proj = 2 * d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim
        attn_out = 2 * self.n_heads * self.head_dim * d
        attn_score = (2 * seq * self.n_heads * self.head_dim) if seq else 0
        active_ffns = self.moe_top_k if self.n_experts else 1
        mlp = 2 * 3 * d * m * active_ffns
        per_layer = attn_proj + attn_out + attn_score + mlp
        return 3 * (self.n_layers * per_layer + 2 * d * v)

    def moe_config(self):
        from kubeflow_tpu.parallel.moe import MoEConfig

        return MoEConfig(
            dim=self.dim, mlp_dim=self.mlp_dim, n_experts=self.n_experts,
            top_k=self.moe_top_k, capacity_factor=self.moe_capacity_factor,
            dtype=self.dtype)

    def paged_ops(self) -> PagedOps:
        """The model as the serving programs see it (``_paged_ops``)."""
        return _paged_ops(self)


def llama3_8b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama3_70b(**kw) -> LlamaConfig:
    return LlamaConfig(
        dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, mlp_dim=28672, **kw
    )


def llama_1b(**kw) -> LlamaConfig:
    """Single-v5e-chip benchmark config (16G HBM)."""
    return LlamaConfig(
        vocab_size=32768, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        mlp_dim=5632, max_seq=2048, tie_embeddings=True, **kw
    )


def llama_tiny(**kw) -> LlamaConfig:
    """CI config: runs on CPU in seconds."""
    return LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq=128, rope_scaling=None, tie_embeddings=True, **kw
    )


def llama_moe_8x(base: LlamaConfig | None = None, n_experts: int = 8,
                 **kw) -> LlamaConfig:
    """Mixtral-style MoE variant of any base config (default 8 experts)."""
    base = base or llama3_8b()
    return dataclasses.replace(base, n_experts=n_experts, **kw)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LlamaConfig, dtype=jnp.float32):
    """Initialize parameters (stacked along a leading `layers` axis)."""
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d, h, kv, hd, m, L = (
        cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim,
        cfg.n_layers,
    )

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "mlp_norm": jnp.ones((L, d), dtype),
        "wq": dense(ks[0], (L, d, h, hd), d),
        "wk": dense(ks[1], (L, d, kv, hd), d),
        "wv": dense(ks[2], (L, d, kv, hd), d),
        "wo": dense(ks[3], (L, h, hd, d), h * hd),
    }
    if cfg.n_experts:
        E = cfg.n_experts
        layers.update({
            "moe_router": dense(ks[7], (L, d, E), d),
            "w_gate": dense(ks[4], (L, E, d, m), d),
            "w_up": dense(ks[5], (L, E, d, m), d),
            "w_down": dense(ks[6], (L, E, m, d), m),
        })
    else:
        layers.update({
            "w_gate": dense(ks[4], (L, d, m), d),
            "w_up": dense(ks[5], (L, d, m), d),
            "w_down": dense(ks[6], (L, m, d), m),
        })
    params = {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (d, cfg.vocab_size), d)
    return params


def param_logical_axes(cfg: LlamaConfig):
    """Logical axis names per param, mirroring init_params' structure."""
    layer_axes = {
        "attn_norm": ("layers", "embed"),
        "mlp_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
    }
    if cfg.n_experts:
        layer_axes.update({
            "moe_router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    else:
        layer_axes.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer_axes,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# The layer's pieces (shared by forward, the pipeline stages and the paged
# serving programs). Each handles the int8 weight tree (serving/quant.py:
# ``name_q`` int8 + ``name_s`` f32 per-output-channel scales) by KEY
# PRESENCE, once; the quant-off expression is the plain einsum, so an
# unquantized tree traces the program it always did. ``constrain`` is the
# trainer's activation-sharding hook (``parallel.sharding.constrain`` from
# ``_block``); the serving programs pass none.
# ---------------------------------------------------------------------------

def _unconstrained(x, names):
    return x


def qmm(spec, x, tree, name, cfg: LlamaConfig):
    """Matmul over an int8-quantized weight ``name``: the HBM read is one
    byte per param, the tile upcasts to the compute dtype inside the fused
    einsum, and the scales multiply the OUTPUT tile — a dense dequantized
    weight never exists."""
    out = jnp.einsum(spec, x, tree[name + "_q"].astype(cfg.dtype))
    return out * tree[name + "_s"].astype(cfg.dtype)


def rope_inv_freq(cfg: LlamaConfig):
    return jnp.asarray(rope_frequencies(
        cfg.head_dim, cfg.rope_theta, cfg.rope_scaling,
        original_max_seq=cfg.max_seq,
    ))


def embed_tokens(params, tokens, cfg: LlamaConfig, constrain=_unconstrained):
    """Embedding lookup; int8 tables dequant the gathered rows with their
    per-vocab-row scale."""
    if "embed_q" in params:
        rows = params["embed_q"].astype(cfg.dtype)[tokens]
        return rows * params["embed_s"].astype(cfg.dtype)[tokens][..., None]
    # SPMD-clean under the trainer's mesh: a row gather from the
    # (vocab=tensor, embed=fsdp)-sharded table makes the partitioner emit
    # an "involuntary full rematerialization" of the [B,S,D] activation (it
    # can't reshard gather output efficiently). Explicitly replicating the
    # bf16-cast table first makes the gather local and the batch/seq
    # partition a free slice — the same table all-gather XLA's fallback
    # pays, minus the (much larger) activation replication.
    table = constrain(params["embed"].astype(cfg.dtype), (None, None))
    return table[tokens]


def attention_inputs(lp, x, positions, cfg: LlamaConfig, inv_freq,
                     constrain=_unconstrained):
    """Norm, the three projections, rope: x [B, S, D] at ``positions``
    [B|1, S] -> (q [B, S, H, hd] rotated, k [B, S, KV, hd] rotated, v)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if "wq_q" in lp:
        q = qmm("bsd,dhk->bshk", h, lp, "wq", cfg)
        k = qmm("bsd,dhk->bshk", h, lp, "wk", cfg)
        v = qmm("bsd,dhk->bshk", h, lp, "wv", cfg)
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(cfg.dtype))
    q = constrain(q, ("batch", "seq", "act_heads", None))
    k = constrain(k, ("batch", "seq", None, None))
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    return q, k, v


def _ffn(h, lp, cfg: LlamaConfig, token_mask=None):
    """FFN half of a block on the normed input h: (delta, aux_loss_scalar).
    Dense SwiGLU, or the routed MoE mixture when cfg.n_experts > 0.
    ``token_mask`` [B, S]: serving paths exclude pad/idle rows from MoE
    routing (they would steal expert capacity from real tokens)."""
    if cfg.n_experts:
        from kubeflow_tpu.parallel.moe import moe_aux_total, moe_layer

        moe_params = {"router": lp["moe_router"], "w_gate": lp["w_gate"],
                      "w_up": lp["w_up"], "w_down": lp["w_down"]}
        y, aux = moe_layer(moe_params, h, cfg.moe_config(),
                           token_mask=token_mask)
        return y, moe_aux_total(aux)
    if "w_gate_q" in lp:
        gate = qmm("bsd,dm->bsm", h, lp, "w_gate", cfg)
        up = qmm("bsd,dm->bsm", h, lp, "w_up", cfg)
        ff = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "act_mlp"))
        down = qmm("bsm,md->bsd", ff, lp, "w_down", cfg)
        return down, jnp.zeros((), jnp.float32)
    gate = jnp.einsum("bsd,dm->bsm", h, lp["w_gate"].astype(cfg.dtype))
    up = jnp.einsum("bsd,dm->bsm", h, lp["w_up"].astype(cfg.dtype))
    ff = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "act_mlp"))
    down = jnp.einsum("bsm,md->bsd", ff, lp["w_down"].astype(cfg.dtype))
    return down, jnp.zeros((), jnp.float32)


def attention_out_and_ffn(lp, x, o, cfg: LlamaConfig, token_mask=None,
                          constrain=_unconstrained):
    """What follows attention: ``W_o``, the residual, the FFN and its
    residual. o: [B, S, H, hd]. Returns (x, aux_loss_scalar)."""
    if "wo_q" in lp:
        o = qmm("bshk,hkd->bsd", o, lp, "wo", cfg)
    else:
        o = jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cfg.dtype))
    x = x + constrain(o, ("batch", "seq", "act_embed"))
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    down, aux = _ffn(h, lp, cfg, token_mask=token_mask)
    return x + constrain(down, ("batch", "seq", "act_embed")), aux


def head_logits(params, x, cfg: LlamaConfig):
    """Final norm and the LM head: x [..., D] before the norm -> logits
    [..., V] in the compute dtype. Tied embeddings reuse the table; in the
    int8 tree its per-vocab-ROW scales become per-output-channel scales of
    the transposed head, and an untied head carries its own."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    name = "embed" if cfg.tie_embeddings else "lm_head"
    quantized = "embed_q" in params
    head = params[name + "_q"] if quantized else params[name]
    if cfg.tie_embeddings:
        head = head.T
    logits = jnp.einsum("...d,dv->...v", x, head.astype(cfg.dtype))
    if quantized:
        logits = logits * params[name + "_s"].astype(cfg.dtype)
    return logits


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block(x, lp, inv_freq, positions, cfg: LlamaConfig, mesh=None):
    """One transformer block as the trainer runs it: the pieces with the
    activation constraints and the configured attention between them.
    x: [B,S,D] in compute dtype. Returns (x, aux_loss_scalar)."""
    q, k, v = attention_inputs(lp, x, positions, cfg, inv_freq, constrain)
    if cfg.attn_impl in ("ring", "ulysses"):
        from kubeflow_tpu.parallel.ring_attention import (
            ring_attention, ulysses_attention,
        )

        if mesh is None:
            raise ValueError(f"attn_impl={cfg.attn_impl!r} requires mesh=")
        attn_fn = ring_attention if cfg.attn_impl == "ring" else ulysses_attention
        o = attn_fn(q, k, v, mesh, causal=True)
    else:
        o = attention(q, k, v, causal=True, impl=cfg.attn_impl,
                      block_q=cfg.attn_block, block_kv=cfg.attn_block)
    return attention_out_and_ffn(lp, x, o, cfg, constrain=constrain)


def _remat_wrap(fn, cfg: LlamaConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, tokens, cfg: LlamaConfig, positions=None, mesh=None,
            return_aux: bool = False):
    """Full-sequence forward. tokens: [B,S] int32 -> logits [B,S,V] (f32).

    `mesh` is only needed for the context-parallel attention impls
    ("ring"/"ulysses"), which run shard_map collectives over it.
    With ``return_aux`` returns (logits, aux) where aux carries the summed
    MoE penalties (zero for dense configs) — add it to the training loss.
    """
    if positions is None:
        positions = jnp.arange(tokens.shape[1])[None, :]
    inv_freq = rope_inv_freq(cfg)
    x = embed_tokens(params, tokens, cfg, constrain)
    x = constrain(x, ("batch", "seq", "act_embed"))

    block = _remat_wrap(
        lambda x, lp: _block(x, lp, inv_freq, positions, cfg, mesh), cfg
    )
    x, aux_per_layer = jax.lax.scan(block, x, params["layers"])

    logits = constrain(head_logits(params, x, cfg), ("batch", "seq", None))
    logits = logits.astype(jnp.float32)
    if return_aux:
        return logits, {"moe_aux": jnp.sum(aux_per_layer)}
    return logits


# ---------------------------------------------------------------------------
# The serving programs' view of the model (models/paged.PagedOps)
# ---------------------------------------------------------------------------

def bucket_prefill(params, tokens, lengths, cfg: LlamaConfig):
    """A whole bucket of prompts in one causal pass. tokens: [B, S]
    left-aligned, right-padded; ``lengths`` [B] int32 each prompt's true
    length. Returns (logits [B, V] f32 at position ``lengths - 1``, the
    layers' rows ``{"k", "v"}: [L, B, S, KV, hd]`` for
    ``paged_insert_batch``). Rows beyond a prompt's length hold garbage
    and are never attended (the insert skips or masks them, decode masks
    to the slot's length and overwrites them one position at a time)."""
    positions = jnp.arange(tokens.shape[1])[None, :]
    inv_freq = rope_inv_freq(cfg)
    token_mask = positions < lengths[:, None]
    # "ring"/"ulysses" are training-only context-parallel paths; prefill
    # falls back to the first-party pallas kernel for those — O(S) memory,
    # CPU-interpretable
    impl = cfg.attn_impl if cfg.attn_impl in ("xla", "flash", "pallas") \
        else "pallas"

    def layer(x, lp):
        q, k, v = attention_inputs(lp, x, positions, cfg, inv_freq)
        o = attention(q, k, v, causal=True, impl=impl,
                      block_q=cfg.attn_block, block_kv=cfg.attn_block)
        x, _ = attention_out_and_ffn(lp, x, o, cfg, token_mask=token_mask)
        return x, (k, v)

    x, (k, v) = jax.lax.scan(layer, embed_tokens(params, tokens, cfg),
                             params["layers"])
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1,
    )[:, 0]
    return head_logits(params, last, cfg).astype(jnp.float32), \
        {"k": k, "v": v}


def _paged_ops(cfg: LlamaConfig) -> PagedOps:
    """Dense GQA (optionally the capacity-buffer expert FFN) as the paged
    programs see it."""
    inv_freq = rope_inv_freq(cfg)

    def qkv(lp, x, positions, state):
        q, k, v = attention_inputs(lp, x, positions, cfg, inv_freq)
        return q, {"k": k, "v": v}, state      # no per-slot rows: {}

    def decode_attn(lp, q, pools, layer, tables, kv_len, kernel, mesh,
                    interpret):
        if kernel == "pallas":
            # block-resident kernel over the carried pool, addressed by
            # (layer, block): per slot, only the live blocks named by its
            # table row move HBM->VMEM; no [max_seq] view and no slice of
            # the pool exists. Under a mesh the call shard_maps over the
            # heads/KV axis — per-shard pool blocks, replicated tables, no
            # collectives (quantized scale tables shard on kv-heads with
            # the pool).
            from kubeflow_tpu.ops.pallas_paged_attention import (
                paged_decode_attention_sharded,
            )

            return paged_decode_attention_sharded(
                q[:, 0], pools["k"], pools["v"], layer, tables, kv_len,
                mesh=mesh, interpret=interpret,
                k_scale=pools.get("k_scale"),
                v_scale=pools.get("v_scale"))[:, None]
        k_view, v_view = gather_views(pools, layer, tables, cfg)
        return decode_attention(q, k_view, v_view, kv_len)

    def chunk_attention(lp, q, pools, layer, tables, q_start):
        # the shared GQA causal kernel with traced query offsets: row i
        # of slot b (absolute position q_start[b]+i) attends kv rows <= it
        k_view, v_view = gather_views(pools, layer, tables, cfg)
        return _xla_attention(q, k_view, v_view, causal=True,
                              q_offset=q_start)

    return PagedOps(
        n_layers=cfg.n_layers,
        pool_rows={"k": (cfg.n_kv_heads, cfg.head_dim),
                   "v": (cfg.n_kv_heads, cfg.head_dim)},
        layer_stacks=lambda params: [(params["layers"], ())],
        embed=lambda params, tokens: embed_tokens(params, tokens, cfg),
        qkv=qkv, decode_attention=decode_attn,
        chunk_attention=chunk_attention,
        out=lambda lp, x, o, token_mask, carry: (
            attention_out_and_ffn(lp, x, o, cfg, token_mask=token_mask)[0],
            carry, {}),
        head=lambda params, x_last: head_logits(
            params, x_last, cfg).astype(jnp.float32),
        bucket_prefill=lambda params, tokens, lengths: bucket_prefill(
            params, tokens, lengths, cfg))
