"""Gated DeltaNet decode step as one Pallas kernel over the slots.

A Gated DeltaNet (GDN) layer keeps, per slot, a state ``S`` of ``Hv`` value
heads x ``[dk, dv]`` in float32 (2 MiB a slot at 32 x 128 x 128). One decode
token, per value
head, with ``q`` and ``k`` already convolved, L2-normalised (``q`` scaled by
``dk ** -0.5``), ``decay = exp(g)`` and ``beta = sigmoid(b)``:

    S <- decay * S
    S <- S + k (beta (v - S^T k))^T
    o  = S^T q

The state is most of what a decode step of such a model moves, so the kernel
reads each live slot's ``S`` once and writes it once, in place: the whole
state array of all GDN layers rides in HBM, aliased from input to output,
with the layer as a scalar-prefetch operand; the grid runs over the slots
and the pipeline copies a slot's ``[Hv, dk, dv]`` block in and out. A slot
that is idle or not active starts no copy: its grid step maps to the block
of the nearest live slot (``src``), which the pipeline then neither fetches
again nor writes back until the block changes, and the step leaves the
block alone. When no slot is live at all, every step maps to slot 0's block
and the first copies it through unchanged.

Everything is float32 on the vector unit: ``k`` and ``q`` arrive as
columns (``[dk, Hv]`` a slot), ``v`` and the two gates as rows, so that
both products are a broadcast and a sum over the sublanes, exact to float32
rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 48 * 2 ** 20


def _kernel(layer_ref, src_ref, mode_ref, q_ref, k_ref, v_ref, g_ref,
            s_in, o_ref, s_out, *, heads):
    del layer_ref, src_ref
    b = pl.program_id(0)
    mode = mode_ref[b]

    @pl.when(mode == 1)
    def _live():
        for j in range(heads):
            s = s_in[0, 0, j]                            # [dk, dv] f32
            kc = k_ref[0, :, j:j + 1]                    # [dk, 1]
            qc = q_ref[0, :, j:j + 1]
            s = s * g_ref[0, 0, j:j + 1, :]
            ks = jnp.sum(kc * s, axis=0, keepdims=True)  # [1, dv]
            delta = g_ref[0, 1, j:j + 1, :] * (v_ref[0, j:j + 1, :] - ks)
            s = s + kc * delta
            s_out[0, 0, j] = s
            o_ref[0, j:j + 1, :] = jnp.sum(qc * s, axis=0, keepdims=True)

    @pl.when(mode != 1)
    def _idle():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(mode == 2)
    def _through():
        s_out[...] = s_in[...]


def gdn_decode(q, k, v, decay, beta, state, layer, live, *,
               interpret: bool = False):
    """One decode token of every slot through layer ``layer`` of a GDN
    state array, in place.

    q, k: [B, Hv, dk] (each value head's query and key: a key head repeated
    for the value heads that share it), L2-normalised, q scaled; v: [B, Hv,
    dv]; decay, beta: [B, Hv] float32; state: [L, B, Hv, dk, dv] float32;
    layer: int32 scalar; live: [B] bool, the slots to update. Returns (o [B,
    Hv, dv] float32, zeros for a slot that is not live; state, the array
    that came in, updated for the live slots alone)."""
    b, hv, dk = q.shape
    dv = v.shape[-1]
    live = live.astype(bool)
    idx = jnp.arange(b)
    # the block each grid step maps to: its own if live, else the last
    # live slot before it, else the first live slot after it
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    after = jax.lax.cummin(jnp.where(live, idx, b), reverse=True)
    src = jnp.where(before >= 0, before, jnp.where(after < b, after, 0))
    mode = jnp.where(live, 1, 0)
    mode = mode.at[0].set(jnp.where(live.any(), mode[0], 2))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    f32 = jnp.float32
    cols = lambda x: jnp.swapaxes(x.astype(f32), 1, 2)      # [B, dk, Hv]
    # the two gates as rows (a scalar broadcast over sublanes AND lanes is
    # one Mosaic does not lower): [B, 2, Hv, dv]
    gates = jnp.broadcast_to(jnp.stack([decay, beta], 1).astype(f32)[..., None],
                             (b, 2, hv, dv))

    def own(bi, *_):
        return (bi, 0, 0)

    def shared(bi, layer_ref, src_ref, mode_ref):
        return (layer_ref[0], src_ref[bi], 0, 0, 0)

    s_spec = pl.BlockSpec((1, 1, hv, dk, dv), shared)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, dk, hv), own),
                  pl.BlockSpec((1, dk, hv), own),
                  pl.BlockSpec((1, hv, dv), own),
                  pl.BlockSpec((1, 2, hv, dv), lambda bi, *_: (bi, 0, 0, 0)),
                  s_spec],
        out_specs=[pl.BlockSpec((1, hv, dv), own), s_spec],
    )
    return pl.pallas_call(
        functools.partial(_kernel, heads=hv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hv, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand indices count the three scalar-prefetch ones
        input_output_aliases={7: 1},
        # a block is revisited by consecutive steps: slots in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="gdn_decode",
    )(layer, src.astype(jnp.int32), mode.astype(jnp.int32), cols(q), cols(k),
      v.astype(f32), gates, state)


def gdn_decode_reference(q, k, v, decay, beta, state, layer, live):
    """What ``gdn_decode`` computes, in plain ``jax.numpy``: the oracle of
    the tests (and the path of ``kernel="gather"``): a scatter of the live
    slots' new rows, dropped for the others."""
    f32 = jnp.float32
    s = state[layer] * decay[..., None, None]
    ks = jnp.einsum("bhk,bhkv->bhv", k.astype(f32), s,
                    precision=jax.lax.Precision.HIGHEST)
    delta = beta[..., None] * (v.astype(f32) - ks)
    s = s + k.astype(f32)[..., :, None] * delta[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q.astype(f32), s,
                   precision=jax.lax.Precision.HIGHEST)
    rows = jnp.where(live, jnp.arange(q.shape[0]), q.shape[0])
    return (jnp.where(live[:, None, None], o, 0.0),
            state.at[layer, rows].set(s, mode="drop"))
