"""Context parallelism: ring attention and Ulysses-style all-to-all attention.

The reference has no long-context machinery at all (SURVEY.md §5 — sequence
length is invisible to Kubeflow; users run Megatron-CP/DeepSpeed-Ulysses in
their containers over NCCL P2P). Here it is a framework feature over the
``context`` mesh axis:

- **Ring attention** (`ring_attention`): sequence-sharded Q/K/V; KV blocks
  rotate around the ring via `jax.lax.ppermute` while each device accumulates
  blockwise-softmax partial results (log-sum-exp streaming, f32). Comm rides
  the ICI neighbor links and overlaps with the per-block attention matmuls.
  O(S/c) memory per device. This is the arbitrarily-long-sequence path.

- **Ulysses all-to-all** (`ulysses_attention`): `all_to_all` swaps the shard
  axis from sequence to heads around the attention op, so each device runs
  full-sequence attention for H/c heads. Cheaper comm volume for moderate
  context degree; requires n_kv_heads % context == 0.

Both are written as per-shard functions applied under `jax.shard_map` and
agree numerically with full attention (tests/test_ring_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn_update(q, k, v, q_pos, k_pos, o, m, l, causal):
    """One blockwise-softmax accumulation step (all f32).

    q: [B,Sq,KV,G,D]; k,v: [B,Sk,KV,D]; o: like q; m,l: [B,KV,G,Sq].
    Returns updated (o, m, l).
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q * scale, k)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # fully-masked-so-far rows keep m=-inf; guard the exp against inf-inf
    safe = m_new > NEG_INF / 2
    corr = jnp.where(safe, jnp.exp(m - m_new), 0.0)
    p = jnp.exp(s - jnp.where(safe, m_new, 0.0)[..., None])
    p = jnp.where(safe[..., None], p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    # o layout [B,Sq,KV,G,D]; corr layout [B,KV,G,Sq] -> [B,Sq,KV,G,1]
    corr_o = corr.transpose(0, 3, 1, 2)[..., None]
    o_new = o * corr_o + jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o_new, m_new, l_new


def _ring_attention_shard(q, k, v, axis_name: str, causal: bool):
    """Per-shard ring attention. q:[B,Sl,H,D] k,v:[B,Sl,KV,D] (local blocks)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh

    qf = q.astype(jnp.float32).reshape(b, sl, kvh, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    q_pos = idx * sl + jnp.arange(sl)
    o = jnp.zeros_like(qf)
    m = jnp.full((b, kvh, g, sl), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, g, sl), jnp.float32)
    # constant-initialized carries must be marked device-varying for scan
    # under shard_map's varying-manual-axes checks; match qf's varying set
    # so carry-in and carry-out types agree.
    vma = set(jax.typeof(qf).vma)

    def _match_vma(x):
        missing = tuple(vma - set(jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    o, m, l = (_match_vma(x) for x in (o, m, l))

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, step):
        o, m, l, k_cur, v_cur = carry
        src = (idx - step) % n          # whose block we currently hold
        k_pos = src * sl + jnp.arange(sl)
        o, m, l = _block_attn_update(qf, k_cur, v_cur, q_pos, k_pos, o, m, l, causal)
        # rotate AFTER use; XLA overlaps the ppermute with the next block's
        # compute since there is no data dependency until the following step.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o, m, l, kf, vf), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, sl, h, d).astype(q.dtype)


def ring_attention(
    q, k, v, mesh: Mesh, *, axis: str = "context", causal: bool = True,
    batch_axes=("data", "fsdp"), head_axis: str | None = "tensor",
):
    """Sequence-sharded ring attention over `axis`.

    q: [B,S,H,D], k/v: [B,S,KV,D] with S sharded over `axis`. Batch stays
    sharded over `batch_axes`, heads over `head_axis` (composes with TP).
    """
    qspec = P(batch_axes, axis, head_axis, None)
    kspec = P(batch_axes, axis, head_axis, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_shard, axis_name=axis,
                          causal=causal),
        mesh=mesh,
        in_specs=(qspec, kspec, kspec),
        out_specs=qspec,
    )
    return fn(q, k, v)


def _ulysses_shard(q, k, v, axis_name: str, causal: bool):
    """Per-shard Ulysses: all_to_all seq-shard -> head-shard, full attention,
    reverse. q:[B,Sl,H,D] k,v:[B,Sl,KV,D]; requires KV % axis_size == 0."""
    from kubeflow_tpu.ops.attention import _xla_attention

    # [B,Sl,H,D] -> gather seq, scatter heads -> [B,S,H/n,D]
    qg = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    o = _xla_attention(qg, kg, vg, causal=causal)
    # reverse: scatter seq, gather heads
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q, k, v, mesh: Mesh, *, axis: str = "context", causal: bool = True,
    batch_axes=("data", "fsdp"), head_axis: str | None = "tensor",
):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention."""
    if mesh.shape[axis] > 1 and k.shape[2] % mesh.shape[axis] != 0:
        raise ValueError(
            f"ulysses needs n_kv_heads ({k.shape[2]}) divisible by "
            f"mesh axis {axis!r} ({mesh.shape[axis]}); use ring_attention"
        )
    qspec = P(batch_axes, axis, head_axis, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_shard, axis_name=axis,
                          causal=causal),
        mesh=mesh,
        in_specs=(qspec, qspec, qspec),
        out_specs=qspec,
    )
    return fn(q, k, v)
