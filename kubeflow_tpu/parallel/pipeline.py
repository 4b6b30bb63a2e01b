"""Pipeline parallelism — GPipe-style microbatch pipeline over a mesh axis.

The reference delegates PP to user containers (Megatron/DeepSpeed stages
across pods, SURVEY.md §2.7 'PP'). The TPU-native design keeps every stage
in ONE jitted SPMD program: stage parameters are sharded over the
``pipeline`` mesh axis (stacked on a leading stage dim), and microbatch
activations stream between stages with ``jax.lax.ppermute`` inside
``shard_map`` — XLA overlaps the permute (small p2p transfer, DCN-tolerant)
with the next microbatch's compute. No MPMD launcher, no per-stage process
groups.

Schedule: GPipe fill-drain. For S stages and M microbatches each device
ticks S+M-1 times; stage s is idle for s ticks at fill and S-1-s at drain
(the usual bubble; 1F1B would need per-stage weight gradients resident,
same comms pattern).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack per-stage parameter pytrees on a leading 'stage' dim: the result
    is a PYTREE of the same structure (one stacked array per leaf), sharded
    over the pipeline axis so each device holds its stage only."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipeline_apply(
    stage_fn: Callable,
    mesh: Mesh,
    *,
    axis: str = "pipeline",
    microbatches: int,
    batch_spec: P = P(),
    partial_manual: bool = False,
    stage_aux: bool = False,
) -> Callable:
    """Build ``fn(stacked_params, x) -> y`` running stage_fn as a pipeline.

    - ``stage_fn(stage_params, x) -> y``: one stage's computation; x/y have
      identical shapes (the inter-stage activation contract). With
      ``stage_aux`` it returns ``(y, aux_scalar)`` — e.g. MoE load-balance
      penalties — and the pipelined fn returns ``(y, aux_total)`` where
      aux_total averages the per-microbatch stage penalties (bubble ticks on
      zero-injected activations are masked out).
    - ``stacked_params``: pytree with leading stage dim (see
      stack_stage_params), sharded P(axis) on dim 0.
    - ``x``: [batch, ...] global batch; split into ``microbatches`` equal
      microbatches along dim 0.
    - ``partial_manual``: only the pipeline axis is manual in the shard_map;
      every other mesh axis stays auto, so stage_fn may contain its own
      sharding constraints (expert all-to-alls, TP splits) which XLA places
      over the remaining axes. This is how PP composes with EP/DP/TP in one
      jitted program.

    Returns the pipelined function (jit-able; grads flow through ppermute).
    """
    n_stages = mesh.shape[axis]

    def impl(stacked_params, x):
        # inside shard_map: stacked_params has stage dim 1 (this device's
        # stage); x is the full per-shard batch
        local_params = jax.tree_util.tree_map(
            lambda p: p[0], stacked_params)
        stage = jax.lax.axis_index(axis)
        mb = jnp.reshape(
            x, (microbatches, x.shape[0] // microbatches, *x.shape[1:]))
        mb_shape = mb.shape[1:]

        total = microbatches + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out, aux_acc = carry
            # stage 0 ingests microbatch t (zeros once input is exhausted)
            inject = mb[jnp.minimum(t, microbatches - 1)]
            inject = jnp.where(t < microbatches, inject,
                               jnp.zeros_like(inject))
            state_in = jnp.where(stage == 0, inject, buf)
            if stage_aux:
                y, aux = stage_fn(local_params, state_in)
                # stage s holds real data for microbatch t-s only while
                # s <= t < s+M; bubble ticks run on zeros and are masked
                valid = ((t >= stage) & (t - stage < microbatches))
                aux_acc = aux_acc + jnp.where(
                    valid, aux.astype(jnp.float32), 0.0)
            else:
                y = stage_fn(local_params, state_in)
            # the LAST stage's output for microbatch t-(S-1) is ready now
            out_idx = t - (n_stages - 1)
            out = jnp.where(
                (stage == n_stages - 1) & (out_idx >= 0),
                out.at[jnp.maximum(out_idx, 0)].set(y),
                out)
            # stream activations to the next stage (ring; last->0 ignored)
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf, out, aux_acc), None

        buf0 = jnp.zeros(mb_shape, x.dtype)
        out0 = jnp.zeros((microbatches, *mb_shape), x.dtype)
        (_, out, aux_acc), _ = jax.lax.scan(
            tick, (buf0, out0, jnp.zeros((), jnp.float32)),
            jnp.arange(total))
        # collected on the last stage; psum-broadcast so the result is
        # replicated over the pipeline axis (loss computed everywhere)
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        out = jnp.reshape(out, (x.shape[0], *mb_shape[1:]))
        if stage_aux:
            # sum every stage's penalty, average over microbatches (each
            # microbatch's aux is already a per-token mean)
            return out, jax.lax.psum(aux_acc, axis) / microbatches
        return out

    out_specs = (batch_spec, P()) if stage_aux else batch_spec
    kwargs = dict(mesh=mesh, in_specs=(P(axis), batch_spec),
                  out_specs=out_specs)
    if partial_manual:
        # axis_names = the manual subset; the rest stays auto
        return shard_map(impl, axis_names=frozenset({axis}),
                         check_vma=False, **kwargs)
    return shard_map(impl, check_vma=False, **kwargs)


def pipeline_loss_fn(
    stage_fn: Callable,
    loss_head: Callable,
    mesh: Mesh,
    *,
    axis: str = "pipeline",
    microbatches: int,
):
    """Compose pipeline_apply with a loss head: returns
    ``loss(stacked_params, head_params, x, targets) -> scalar``."""
    fwd = pipeline_apply(stage_fn, mesh, axis=axis, microbatches=microbatches)

    def loss(stacked_params, head_params, x, targets):
        y = fwd(stacked_params, x)
        return loss_head(head_params, y, targets)

    return loss
