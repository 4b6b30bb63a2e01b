"""Pipeline-parallel Llama: PP composed with EP/DP/TP in one jitted program.

The reference runs pipeline stages as separate pods wired by a launcher
(SURVEY.md §2.7 'PP' — Megatron/DeepSpeed inside user containers). The
TPU-native composition keeps the whole pipelined model a single SPMD
program: transformer layers are re-stacked ``[n_stages, L/n_stages, ...]``
and sharded over the ``pipeline`` mesh axis; inside each stage the usual
scan-over-layers runs, and because only the pipeline axis is *manual* in the
shard_map (``partial_manual=True``), the MoE expert all-to-alls and any
TP/DP layouts still resolve over the remaining (auto) mesh axes. Embedding
and the LM head run outside the pipeline body, replicated over the pipeline
axis (their FLOPs are marginal; shared-embedding PP schemes do the same).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.losses import softmax_cross_entropy
from kubeflow_tpu.parallel.pipeline import pipeline_apply
from kubeflow_tpu.parallel.sharding import constrain

# NOTE: kubeflow_tpu.models.llama imports parallel.sharding, so importing it
# at module scope from inside the parallel package would be circular; the
# llama symbols are imported lazily inside the functions below.


def to_pipeline_params(params, n_stages: int):
    """Re-stack layer params [L, ...] -> stages [n_stages, L/n_stages, ...].

    Embedding / final norm / head stay top-level (replicated over the
    pipeline axis by their logical-axis rules)."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if L % n_stages:
        raise ValueError(f"n_layers={L} not divisible by n_stages={n_stages}")
    stages = jax.tree_util.tree_map(
        lambda a: a.reshape((n_stages, L // n_stages) + a.shape[1:]),
        params["layers"])
    out = {k: v for k, v in params.items() if k != "layers"}
    out["stages"] = stages
    return out


def init_pipeline_params(rng, cfg, n_stages: int, dtype=jnp.float32):
    from kubeflow_tpu.models.llama import init_params

    return to_pipeline_params(init_params(rng, cfg, dtype), n_stages)


def pipeline_param_logical_axes(cfg):
    """Logical axes for the pipeline-arranged param tree: each layer leaf
    gains a leading 'pipe_stage' axis (rule: the pipeline mesh axis)."""
    from kubeflow_tpu.models.llama import param_logical_axes

    base = param_logical_axes(cfg)
    stages = jax.tree_util.tree_map(
        lambda names: ("pipe_stage",) + tuple(names),
        base["layers"], is_leaf=lambda x: isinstance(x, tuple))
    out = {k: v for k, v in base.items() if k != "layers"}
    out["stages"] = stages
    return out


def pipeline_forward(params, tokens, cfg, mesh, *,
                     microbatches: int, axis: str = "pipeline"):
    """Pipelined full-sequence forward: tokens [B,S] -> (logits [B,S,V] f32,
    aux dict). B must divide by ``microbatches``."""
    from kubeflow_tpu.models import llama

    positions = jnp.arange(tokens.shape[1])[None, :]
    inv_freq = llama.rope_inv_freq(cfg)

    block = llama._remat_wrap(
        lambda x, lp: llama._block(x, lp, inv_freq, positions, cfg), cfg)

    def stage_fn(stage_layers, x):
        x, aux_per_layer = jax.lax.scan(block, x, stage_layers)
        return x, jnp.sum(aux_per_layer)

    fwd = pipeline_apply(
        stage_fn, mesh, axis=axis, microbatches=microbatches,
        partial_manual=True, stage_aux=True)

    x = llama.embed_tokens(params, tokens, cfg)
    x = constrain(x, ("batch", "seq", "act_embed"))
    x, moe_aux = fwd(params["stages"], x)

    logits = constrain(llama.head_logits(params, x, cfg),
                       ("batch", "seq", None))
    return logits.astype(jnp.float32), {"moe_aux": moe_aux}


# ------------------------------------------------------- MPMD chunk spec --
#
# The SPMD pipeline above keeps all stages in ONE program; the MPMD
# runner (parallel/mpmd.py) runs each model CHUNK as its own jitted
# program on its own worker, joined by the host-staged transport.
# MpmdLlamaSpec is the model plug that drives REAL transformer blocks
# through that runner: the token embedding is folded into chunk 0 (its
# input is int32 tokens, so its backward is params-only), interior
# chunks are pure scan-over-blocks [R,S,D] -> [R,S,D], and the LM head
# (final norm + projection + CE loss) rides the head worker. All chunks
# slice ONE full-model init, so a plain (V=1) and an interleaved (V=2)
# run over the same total_stages partition train bitwise-identical
# models — the bench's schedule-invariance gate.


def mpmd_model_config(run_cfg, env=None):
    """Derive the LlamaConfig an MPMD run trains from the run config +
    KFT_MPMD_* env knobs. Untied embeddings are forced: the MPMD head
    worker owns the LM head while chunk 0 owns the embedding — a tied
    table would silently train as two independent copies."""
    import os

    from kubeflow_tpu.models.llama import LlamaConfig

    env = os.environ if env is None else env
    g = lambda k, d: env.get(f"KFT_MPMD_{k}", d)
    dim = run_cfg.dim
    seq = int(g("SEQ", "64"))
    return LlamaConfig(
        vocab_size=int(g("VOCAB", "256")),
        dim=dim,
        n_layers=run_cfg.layers_per_stage * run_cfg.total_stages,
        n_heads=int(g("HEADS", "4")),
        n_kv_heads=int(g("KV_HEADS", "2")),
        mlp_dim=int(g("MLP", str(4 * dim))),
        max_seq=seq,
        rope_scaling=None,
        tie_embeddings=False,
        dtype=jnp.float32,       # CPU rig + bitwise parity gates
        remat="none",            # value-identical; skip recompute on CPU
        z_loss=0.0,              # per-token mean only: decomposes per-mb
    )


def _mpmd_block(mcfg, seq: int):
    """The one block builder both the MPMD chunks and the SPMD oracle
    trace — identical math is the parity contract."""
    from kubeflow_tpu.models import llama

    positions = jnp.arange(seq)[None, :]
    inv_freq = llama.rope_inv_freq(mcfg)
    return llama._remat_wrap(
        lambda x, lp: llama._block(x, lp, inv_freq, positions, mcfg), mcfg)


class MpmdLlamaSpec:
    """parallel/mpmd.MLPSpec's contract, implemented by a real Llama.

    Per GLOBAL chunk c of total_stages: params are layer slice
    [c*per, (c+1)*per) of one full-model init (chunk 0 adds the
    embedding table); the chunk fn scans those blocks (chunk 0 embeds
    its int32 token input first). The head worker owns final_norm +
    lm_head and computes per-microbatch CE/M so the per-step sum equals
    the full-batch mean — the decomposition 1F1B needs."""

    name = "llama"
    first_chunk_needs_dx = False      # tokens are int: params-only VJP

    def __init__(self, model_cfg, seq: int):
        self.mcfg = model_cfg
        self.seq = seq
        self._full = None

    def full_params(self, cfg):
        if self._full is None:
            from kubeflow_tpu.models.llama import init_params

            self._full = init_params(
                jax.random.key(cfg.seed), self.mcfg, jnp.float32)
        return self._full

    def _layer_slice(self, cfg, chunk: int):
        full = self.full_params(cfg)
        per = self.mcfg.n_layers // cfg.total_stages
        return jax.tree_util.tree_map(
            lambda a: a[chunk * per:(chunk + 1) * per], full["layers"])

    def chunk_params(self, cfg, chunk: int):
        p = {"layers": self._layer_slice(cfg, chunk)}
        if chunk == 0:
            p["embed"] = self.full_params(cfg)["embed"]
        return p

    def head_params(self, cfg):
        full = self.full_params(cfg)
        return {"final_norm": full["final_norm"],
                "lm_head": full["lm_head"]}

    def chunk_fn(self, cfg, chunk: int):
        from kubeflow_tpu.models import llama

        mcfg = self.mcfg
        block = _mpmd_block(mcfg, self.seq)

        if chunk == 0:
            def fn(p, tokens):
                x = llama.embed_tokens(p, tokens, mcfg)
                x, _ = jax.lax.scan(block, x, p["layers"])
                return x
        else:
            def fn(p, x):
                x, _ = jax.lax.scan(block, x, p["layers"])
                return x
        return fn

    def head_fn(self, cfg):
        from kubeflow_tpu.models import llama

        mcfg, M = self.mcfg, cfg.microbatches

        def fn(hp, y, t):
            loss, _ = softmax_cross_entropy(
                llama.head_logits(hp, y, mcfg).astype(jnp.float32), t,
                z_loss=mcfg.z_loss)
            return loss / M
        return fn

    def example_x(self, cfg, chunk: int):
        R = cfg.mb_rows
        if chunk == 0:
            return jnp.zeros((R, self.seq), jnp.int32)
        return jnp.zeros((R, self.seq, self.mcfg.dim), jnp.float32)

    def example_y(self, cfg):
        return jnp.zeros((cfg.mb_rows, self.seq, self.mcfg.dim),
                         jnp.float32)

    def example_t(self, cfg):
        return jnp.zeros((cfg.mb_rows, self.seq), jnp.int32)

    def batch(self, cfg, step: int):
        """(inputs [M,R,seq] int32, targets [M,R,seq] int32): next-token
        pairs from a deterministic (seed, step) token stream — worker 0
        and the head worker derive the same values with no data channel."""
        import numpy as np

        M, R = cfg.microbatches, cfg.mb_rows
        k = jax.random.fold_in(jax.random.key(cfg.seed + 20011), step)
        toks = jax.random.randint(
            k, (cfg.global_batch, self.seq + 1), 0, self.mcfg.vocab_size,
            jnp.int32)
        toks = np.asarray(toks)
        return (toks[:, :-1].reshape(M, R, self.seq),
                toks[:, 1:].reshape(M, R, self.seq))

    def snapshot_meta(self, cfg) -> dict:
        """Spec identity folded into the elastic snapshot fingerprint
        (mpmd.run_fingerprint): everything that changes the llama param
        SHAPES or token stream — a llama snapshot must never restore
        into an MLP run, nor into a llama run with different dims."""
        m = self.mcfg
        return {"spec": self.name, "vocab": m.vocab_size, "dim": m.dim,
                "n_layers": m.n_layers, "heads": m.n_heads,
                "kv_heads": m.n_kv_heads, "mlp": m.mlp_dim,
                "seq": self.seq}


def mpmd_llama_spec(run_cfg, env=None) -> MpmdLlamaSpec:
    mcfg = mpmd_model_config(run_cfg, env)
    return MpmdLlamaSpec(mcfg, mcfg.max_seq)


def run_mpmd_llama_oracle(cfg, spec: MpmdLlamaSpec) -> list:
    """SPMD oracle for the MPMD llama run: the SAME full-model params,
    block math, chunk partition (total_stages deep), microbatching and
    per-microbatch CE head through ``pipeline_apply`` in one program —
    same SGD. Needs >= total_stages local devices."""
    import numpy as np
    from jax.sharding import Mesh

    cfg.validate()
    T = cfg.total_stages
    devs = jax.devices()
    if len(devs) < T:
        raise RuntimeError(
            f"llama oracle needs {T} devices, have {len(devs)} "
            "(set --xla_force_host_platform_device_count)")
    mesh = Mesh(np.array(devs[:T]), ("pipeline",))
    mcfg = spec.mcfg
    block = _mpmd_block(mcfg, spec.seq)

    def stage_fn(stage_layers, x):
        x, _ = jax.lax.scan(block, x, stage_layers)
        return x

    fwd = pipeline_apply(stage_fn, mesh, microbatches=cfg.microbatches)
    head_fn = spec.head_fn(cfg)
    M, R = cfg.microbatches, cfg.mb_rows

    def loss_fn(stages, embed, hp, tokens, targets):
        x = embed.astype(mcfg.dtype)[tokens]
        y = fwd(stages, x)
        ymb = y.reshape(M, R, spec.seq, mcfg.dim)
        tmb = targets.reshape(M, R, spec.seq)
        per_mb = jax.vmap(lambda ym, tm: head_fn(hp, ym, tm))(ymb, tmb)
        return jnp.sum(per_mb)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
    full = spec.full_params(cfg)
    stages = to_pipeline_params(full, T)["stages"]
    embed = full["embed"]
    hp = spec.head_params(cfg)
    sgd = lambda p, g: jax.tree_util.tree_map(
        lambda a, b: a - cfg.lr * b, p, g)
    losses = []
    for k in range(cfg.steps):
        x_mb, t_mb = spec.batch(cfg, k)
        tokens = x_mb.reshape(cfg.global_batch, spec.seq)
        targets = t_mb.reshape(cfg.global_batch, spec.seq)
        loss, (gs, ge, gh) = grad_fn(stages, embed, hp, tokens, targets)
        losses.append(float(loss))
        stages, embed, hp = sgd(stages, gs), sgd(embed, ge), sgd(hp, gh)
    return losses


def pipeline_lm_loss_fn(cfg, mesh, *, microbatches: int,
                        axis: str = "pipeline"):
    """Next-token LM loss through the pipelined forward (Trainer-compatible:
    loss_fn(params, batch) -> (loss, metrics))."""

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits, fwd_aux = pipeline_forward(
            params, inputs, cfg, mesh, microbatches=microbatches, axis=axis)
        mask = batch.get("mask")
        mask = mask[:, 1:] if mask is not None else None
        loss, aux = softmax_cross_entropy(
            logits, targets, mask, z_loss=getattr(cfg, "z_loss", 0.0))
        metrics = {"tokens": aux["total_weight"]}
        if cfg.n_experts:
            loss = loss + fwd_aux["moe_aux"]
            metrics["moe_aux"] = fwd_aux["moe_aux"]
        return loss, metrics

    return loss_fn
