"""Mixture-of-Experts with expert parallelism (SURVEY.md §2.7 'EP').

TPU-first design: Switch/GShard-style *dense dispatch* — tokens are routed
into a per-expert capacity buffer with einsum one-hots, the expert FFN runs
batched over the expert dim, and sharding constraints put the expert dim on
the ``expert`` mesh axis so XLA emits the all-to-all. Static shapes
throughout (capacity buffers, no ragged ops), which is exactly what the MXU
and the XLA scheduler want; overflow tokens are dropped by capacity like the
reference implementations.

Aux objectives: Switch load-balancing loss + router z-loss.

The SERVED path of a model with many small experts is the second half of
this file (``RouterConfig``, ``route``, ``routed_experts``): no capacity
buffers and no dropped token. The ``T x k`` assignments are sorted by
expert and the three expert matrices are applied as grouped products over
the sorted rows, so a step reads the weights of the experts that were hit
and no others, and a one-hot dispatch tensor (``[T, E, C]``, as costly as
the experts themselves at 256 experts) never exists. ``all_experts`` is
the plain loop the tests hold it against.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from kubeflow_tpu.parallel.sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    mlp_dim: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3
    load_balance_coef: float = 1e-2
    dtype: jnp.dtype = jnp.float32


def init_moe_params(rng: jax.Array, cfg: MoEConfig):
    kr, kg, ku, kd = jax.random.split(rng, 4)
    d, m, e = cfg.dim, cfg.mlp_dim, cfg.n_experts
    scale = 1.0 / math.sqrt(d)
    return {
        "router": jax.random.normal(kr, (d, e)) * scale,
        "w_gate": jax.random.normal(kg, (e, d, m)) * scale,
        "w_up": jax.random.normal(ku, (e, d, m)) * scale,
        "w_down": jax.random.normal(kd, (e, m, d)) * (1.0 / math.sqrt(m)),
    }


def moe_param_logical_axes(cfg: MoEConfig):
    return {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def moe_layer(params, x, cfg: MoEConfig, *, capacity: Optional[int] = None,
              token_mask=None):
    """Apply the MoE FFN. x: [B, S, D] -> (y [B, S, D], aux_losses dict).

    Dense dispatch: combine/dispatch tensors [G, E, C] (G = B*S tokens)
    contract tokens into per-expert capacity buffers and back. Sharding
    constraints place E on the `expert` mesh axis (all-to-all emitted by
    XLA) and tokens on the data axes.

    ``token_mask`` [B, S] bool marks REAL tokens: padding rows (prefill
    buckets, idle decode slots) must not route — garbage rows would
    compete for expert capacity and displace real tokens' assignments,
    changing real outputs (the serving-correctness failure mode).
    """
    b, s, d = x.shape
    g = b * s
    e, k = cfg.n_experts, cfg.top_k
    if capacity is None:
        capacity = max(1, int(math.ceil(g * k / e * cfg.capacity_factor)))

    tokens = x.reshape(g, d)
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [G, E]
    valid = (jnp.ones((g,), bool) if token_mask is None
             else token_mask.reshape(g))

    # top-k expert choice per token
    topk_probs, topk_idx = jax.lax.top_k(probs, k)             # [G, k]
    # renormalize the chosen experts' weights
    topk_probs = topk_probs / jnp.maximum(
        topk_probs.sum(-1, keepdims=True), 1e-9)

    # aux losses (float32, REAL tokens only) — ONE formula for both
    # dispatch paths; each path adds only its own dropped fraction
    vf = valid.astype(jnp.float32)
    denom = jnp.maximum(vf.sum(), 1.0)
    top1 = jax.nn.one_hot(topk_idx[:, 0], e, dtype=jnp.float32)
    aux = {
        # load balance: E * sum_e fraction_tokens_e * mean_router_prob_e
        "moe_load_balance": cfg.load_balance_coef * e * jnp.sum(
            ((top1 * vf[:, None]).sum(0) / denom)
            * ((probs * vf[:, None]).sum(0) / denom)),
        "moe_router_z": cfg.router_z_coef * jnp.sum(
            jax.nn.logsumexp(logits, axis=-1) ** 2 * vf) / denom,
    }

    if cfg.capacity_factor <= 0:
        # dropless-EXACT path (capacity_factor <= 0): every token's output
        # is its true top-k mixture, independent of batch composition.
        # Capacity buffers couple tokens ACROSS the batch (a garbage or
        # neighbor row can displace a real token's assignment), which is
        # fine as a training regularizer but wrong for serving, where the
        # same prompt must decode identically at any batch size. Costs
        # E/k x the routed FFN FLOPs (scan over experts, peak [G, m]).
        gates = jnp.zeros((g, e), cfg.dtype)
        for j in range(k):                     # static k
            gates = gates + jax.nn.one_hot(
                topk_idx[:, j], e, dtype=cfg.dtype) \
                * topk_probs[:, j, None].astype(cfg.dtype)
        gates = gates * valid[:, None].astype(cfg.dtype)
        tk = tokens.astype(cfg.dtype)

        def one_expert(y, xs):
            wg, wu, wd, gate_e = xs
            h = jax.nn.silu(tk @ wg.astype(cfg.dtype)) \
                * (tk @ wu.astype(cfg.dtype))
            return y + gate_e[:, None] * (h @ wd.astype(cfg.dtype)), None

        y, _ = jax.lax.scan(
            one_expert, jnp.zeros((g, d), cfg.dtype),
            (params["w_gate"], params["w_up"], params["w_down"], gates.T))
        aux["moe_dropped_fraction"] = jnp.zeros((), jnp.float32)
        return y.reshape(b, s, d).astype(x.dtype), aux

    # position of each (token, choice) in its expert's capacity buffer:
    # cumulative count of prior assignments to the same expert. Flatten
    # choices in priority order (choice 0 of every token first).
    flat_idx = topk_idx.T.reshape(-1)                          # [k*G]
    flat_valid = jnp.tile(valid, k)                            # [k*G]
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32) \
        * flat_valid[:, None].astype(jnp.int32)                # [k*G, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [k*G, E]
    pos = pos_in_expert.sum(-1)                                # [k*G]
    keep = (pos < capacity) & flat_valid
    pos = jnp.where(keep, pos, 0)

    # dispatch/combine tensors
    disp = (jax.nn.one_hot(flat_idx, e, dtype=cfg.dtype)[:, :, None]
            * jax.nn.one_hot(pos, capacity, dtype=cfg.dtype)[:, None, :]
            * keep[:, None, None])                             # [k*G, E, C]
    disp = disp.reshape(k, g, e, capacity)
    weights = topk_probs.T.reshape(k, g).astype(cfg.dtype)     # [k, G]
    combine = (disp * weights[:, :, None, None]).sum(0)        # [G, E, C]
    dispatch = disp.sum(0)                                     # [G, E, C]

    # expert-parallel compute: [E, C, D] buffers, E on the expert mesh axis
    expert_in = jnp.einsum("gec,gd->ecd", dispatch,
                           tokens.astype(cfg.dtype))
    expert_in = constrain(expert_in, ("expert", None, "act_embed"))
    h = jax.nn.silu(jnp.einsum("ecd,edm->ecm", expert_in,
                               params["w_gate"].astype(cfg.dtype)))
    h = h * jnp.einsum("ecd,edm->ecm", expert_in,
                       params["w_up"].astype(cfg.dtype))
    expert_out = jnp.einsum("ecm,emd->ecd", h,
                            params["w_down"].astype(cfg.dtype))
    expert_out = constrain(expert_out, ("expert", None, "act_embed"))

    y = jnp.einsum("gec,ecd->gd", combine, expert_out)

    aux["moe_dropped_fraction"] = ((~keep) & flat_valid).astype(
        jnp.float32).sum() / jnp.maximum(
        flat_valid.astype(jnp.float32).sum(), 1.0)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_aux_total(aux: dict) -> jax.Array:
    """Sum of the differentiable aux penalties (exclude diagnostics)."""
    return aux["moe_load_balance"] + aux["moe_router_z"]


# ---------------------------------------------------------------------------
# The served path: scores -> top-k -> sort by expert -> grouped products
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """How a layer turns router logits into ``top_k`` experts and their
    weights. ``score_func``: ``softmax`` | ``sigmoid`` over all experts.
    ``select_bias``: the choice is the top-k of ``score + bias`` (a
    per-expert correction that gradients do not reach) while the weights
    are the scores WITHOUT it. ``norm_topk``: divide the chosen scores by
    their sum; ``scale`` multiplies them afterwards."""

    n_experts: int
    top_k: int
    score_func: str = "softmax"
    select_bias: bool = False
    norm_topk: bool = True
    scale: float = 1.0


def route(tokens, router_w, bias, rc: RouterConfig, logits=None):
    """tokens [T, D] -> (experts [T, k] int32, weights [T, k] f32).
    Logits, scores and the choice are float32 at full matmul precision: a
    bf16 product here moves the 8th and 9th score past each other.
    ``logits`` [T, E] float32: a router that is more than one matrix hands
    its own in (``tokens`` and ``router_w`` are then not read)."""
    if logits is None:
        logits = jnp.dot(tokens.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    if rc.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif rc.score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score_func={rc.score_func!r} "
                         "(want softmax|sigmoid)")
    choice = scores + bias.astype(jnp.float32) if rc.select_bias else scores
    _, experts = jax.lax.top_k(choice, rc.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if rc.norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * rc.scale


def grouped_matmul(rows, weights, group_sizes):
    """``rows[sorted by group] @ weights[group]``: rows [M, K] whose first
    ``group_sizes[0]`` rows belong to group 0 and so on, weights
    [G, K, N] -> [M, N]. Rows past ``sum(group_sizes)`` belong to no group
    and their output is unspecified.

    The Pallas grouped matmul that ships with JAX (megablox ``gmm``): it
    visits a group's weight tile once per row tile that touches the group
    and skips empty groups, so a step of a few rows reads the experts that
    were hit and nothing else. Chosen over ``jax.lax.ragged_dot`` on the
    chip (PERF.md section 6, PR 29: 0.66 against 1.06 ms for 192 rows over
    139 experts, 1.6 against 4.2 ms for 16,384 rows). Row tiles of 32 for
    a decode step's tens of rows, 128 for a prefill chunk's thousands,
    weight tiles of up to 2,048 x 1,024; on the CPU the same kernel runs
    under the Pallas interpreter."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    n = weights.shape[-1]
    tm = 128 if m >= 1024 else 32
    pad = -m % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    tk, tn = min(k, 2048), min(n, 2048)
    if tk * tn > 2048 * 1024:
        # a weight tile is double-buffered: two of 2,048 x 2,048 bf16 are
        # the whole 16 MiB of scoped VMEM (experts as wide as the model)
        tn = 1024
    out = gmm(rows, weights, group_sizes, preferred_element_type=rows.dtype,
              tiling=(tm, tk, tn),
              interpret=jax.default_backend() == "cpu")
    return out[:m]


def routed_experts(tokens, experts, weights, w_gate, w_up, w_down,
                   valid=None, n_experts=None, first_group=0, held_from=None):
    """Sum over each token's chosen experts of ``w * SwiGLU_e(token)``.

    tokens [T, D]; experts/weights [T, k] over ``n_experts`` experts;
    w_gate/w_up [G, D, M], w_down [G, M, D] with expert ``e`` at group
    ``first_group + e``: a layer's own matrices (G = n_experts, the
    default), or several layers' experts in one array (``[L * E, ...]``, a
    free reshape of a stack) with ``first_group = layer * E`` traced.
    Handing the grouped product the whole stack and an offset is what
    keeps a layer loop from slicing a layer's experts out of the stack: a
    copy of all of them, every layer of every step. ``valid`` [T] bool
    marks real tokens (pad and idle rows are sorted behind every group,
    multiply nothing and give zeros). Returns (y [T, D] in tokens.dtype,
    tokens_per_expert [n_experts] int32). No token is dropped and none is
    coupled to another: a row's output depends on its own experts alone,
    whatever else is in the batch.

    ``held_from``: this chip holds only a share of the layer's experts
    (expert parallelism), the ``n_experts`` from ``held_from`` on, and
    ``experts`` name experts of the whole layer. A pick outside the share
    multiplies nothing and adds nothing (its row sorts behind every group,
    as an invalid token's does); what the absent chips would add is not
    stood in for. Counts are of the held experts."""
    t, k = experts.shape
    groups = w_gate.shape[0]
    e = groups if n_experts is None else n_experts
    flat = experts.reshape(t * k)
    live_pick = None
    if held_from is not None:
        flat = flat - held_from
        live_pick = (flat >= 0) & (flat < e)
        if valid is not None:
            live_pick = live_pick & jnp.repeat(valid, k)
        flat = jnp.where(live_pick, flat, e)
    elif valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)    # behind all groups
    order = jnp.argsort(flat, stable=True)                 # [T*k] by expert
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    sizes = counts if groups == e else jax.lax.dynamic_update_slice(
        jnp.zeros((groups,), jnp.int32), counts, (first_group,))
    rows = tokens[order // k]                              # [T*k, D]
    h = jax.nn.silu(grouped_matmul(rows, w_gate.astype(rows.dtype), sizes)) \
        * grouped_matmul(rows, w_up.astype(rows.dtype), sizes)
    out = grouped_matmul(h, w_down.astype(rows.dtype), sizes)    # [T*k, D]
    # back to (token, choice) order (the permutation inverted by a scatter,
    # not a second sort); rows of no group may hold anything
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    out = out[back].reshape(t, k, -1).astype(jnp.float32)
    if live_pick is not None:
        out = jnp.where(live_pick.reshape(t, k, 1), out * weights[..., None],
                        0.0)
        return out.sum(1).astype(tokens.dtype), counts
    live = jnp.ones((t,), bool) if valid is None else valid
    out = jnp.where(live[:, None, None], out * weights[..., None], 0.0)
    return out.sum(1).astype(tokens.dtype), counts


def all_experts(tokens, experts, weights, w_gate, w_up, w_down, valid=None):
    """What ``routed_experts`` computes, by a loop over ALL experts with a
    dense gate matrix: the oracle of the CPU tests, E / k times the work."""
    e = w_gate.shape[0]
    gates = (jax.nn.one_hot(experts, e, dtype=jnp.float32)
             * weights[..., None]).sum(1)                  # [T, E]
    if valid is not None:
        gates = gates * valid[:, None]
    y = jnp.zeros(tokens.shape, jnp.float32)
    for i in range(e):
        h = jax.nn.silu(tokens @ w_gate[i].astype(tokens.dtype)) \
            * (tokens @ w_up[i].astype(tokens.dtype))
        y = y + gates[:, i:i + 1] * (h @ w_down[i].astype(tokens.dtype))
    return y.astype(tokens.dtype)
