"""Causal grouped-query attention for TPU.

Two execution paths, selected by `impl`:

- "xla": plain einsum attention. XLA fuses softmax chains well on TPU and this
  is the correct baseline + CPU-test path.
- "flash": Pallas TPU flash-attention kernel (blockwise, O(S) memory). Uses
  the stock `jax.experimental.pallas.ops.tpu.flash_attention` kernel; a
  first-party splash-style kernel lives in ops/pallas_attention.py and can be
  selected with "pallas".

All paths take q:[B,S,H,D] k/v:[B,S,KV,D] and return [B,S,H,D]. GQA is
handled by repeating KV heads logically (einsum grouping), never materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _xla_attention(q, k, v, *, causal: bool, q_offset=0, bias=None):
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    groups = h // kvh
    qf = q.astype(jnp.float32).reshape(b, sq, kvh, groups, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf * scale, kf)
    if bias is not None:
        logits = logits + bias
    if causal:
        # q_offset may be a scalar (all rows share one offset — prefill /
        # chunked prefill) or a [B] array (per-slot offsets — the batched
        # speculative-decode verify step); either broadcasts to [B?, Sq]
        q_pos = jnp.arange(sq)[None, :] + jnp.atleast_1d(
            jnp.asarray(q_offset))[:, None]
        kv_pos = jnp.arange(skv)
        mask = q_pos[:, :, None] >= kv_pos[None, None, :]   # [B|1, Sq, Skv]
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    impl: str = "xla",
    q_offset: int | jax.Array = 0,
    block_q: int = 512,
    block_kv: int = 512,
):
    """Multi-head / grouped-query attention.

    q: [B, Sq, H, D]; k, v: [B, Skv, KV_H, D] with H % KV_H == 0.
    `q_offset` shifts query positions for causal masking during decode.

    Validation happens out here, unjitted: under jit an explicitly-passed
    q_offset=0 would trace to a Tracer and defeat the isinstance check.
    """
    if impl in ("flash", "pallas") and not (
            isinstance(q_offset, int) and q_offset == 0):
        raise ValueError(
            f"impl={impl!r} does not support q_offset; use impl='xla' "
            "(decode paths use decode_attention)")
    return _attention_jit(q, k, v, causal=causal, impl=impl,
                          q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv)


@functools.partial(
    jax.jit, static_argnames=("causal", "impl", "block_q", "block_kv")
)
def _attention_jit(
    q,
    k,
    v,
    *,
    causal: bool,
    impl: str,
    q_offset,
    block_q: int,
    block_kv: int,
):
    platform = jax.default_backend()
    if impl == "flash":
        if platform != "tpu":
            # the stock kernel has no interpreter path; xla is the
            # numerics-identical CPU/GPU stand-in
            return _xla_attention(q, k, v, causal=causal)
        return _flash_attention(q, k, v, causal=causal, block_q=block_q, block_kv=block_kv)
    if impl == "pallas":
        if platform not in ("tpu", "cpu"):
            return _xla_attention(q, k, v, causal=causal)
        from kubeflow_tpu.ops.pallas_attention import flash_attention as own_flash

        kernel = functools.partial(
            own_flash, causal=causal, block_q=block_q,
            block_kv=block_kv, interpret=platform == "cpu")
        return _shard_mapped(kernel, q, k, v)
    return _xla_attention(q, k, v, causal=causal, q_offset=q_offset)


def _ambient_mesh():
    """The mesh in context at trace time: ``with mesh:`` (how Trainer and
    the AOT proofs enter one) populates the thread-resource env, which is
    also what with_sharding_constraint resolves against."""
    from jax._src.mesh import thread_resources

    physical = thread_resources.env.physical_mesh
    return physical if physical.axis_names else None


def _shard_mapped(kernel, q, k, v):
    """Partition a Mosaic kernel over the ambient mesh.

    XLA auto-partitions plain HLO, but Mosaic (Pallas) calls must be
    wrapped in shard_map. Per the model's logical rules the flash kernel
    parallelizes over batch (data/fsdp axes) and heads (tensor); sequence
    stays local — context parallelism is ring/Ulysses attention's job
    (parallel/ring_attention.py), never this kernel's."""
    mesh = _ambient_mesh()
    if mesh is None:
        return kernel(q, k, v)
    have = set(mesh.axis_names)
    batch_axes = tuple(a for a in ("data", "fsdp")
                       if a in have and mesh.shape[a] > 1)
    head_axis = "tensor" if "tensor" in have and mesh.shape["tensor"] > 1 \
        else None
    if not batch_axes and head_axis is None:
        return kernel(q, k, v)
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes or None, None, head_axis, None)
    # check_vma=False: pallas_call's out_shape ShapeDtypeStructs carry no
    # varying-mesh-axes annotation, which strict vma checking rejects
    wrapped = jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    return wrapped(q, k, v)


def _flash_attention(q, k, v, *, causal, block_q, block_kv):
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    b, s, h, d = q.shape
    kvh = k.shape[2]
    if h != kvh:
        # stock kernel wants matching head counts; expand KV (still O(S) mem)
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    # kernel layout is [B, H, S, D]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    sizes = fa.BlockSizes(
        block_q=min(block_q, s),
        block_k_major=min(block_kv, s),
        block_k=min(block_kv, s),
        block_b=1,
        block_q_major_dkv=min(block_q, s),
        block_k_major_dkv=min(block_kv, s),
        block_k_dkv=min(block_kv, s),
        block_q_dkv=min(block_q, s),
        block_k_major_dq=min(block_kv, s),
        block_k_dq=min(block_kv, s),
        block_q_dq=min(block_q, s),
    )
    out = fa.flash_attention(
        qt, kt, vt, causal=causal,
        sm_scale=1.0 / (d ** 0.5),
        block_sizes=sizes,
    )
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len):
    """Single-step decode attention against a KV cache.

    q: [B, 1, H, D]; k_cache/v_cache: [B, S_max, KV, D]; cache_len: [B] int32
    (number of valid cache entries per sequence, including this step).
    """
    b, _, h, d = q.shape
    kvh = k_cache.shape[2]
    groups = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, groups, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf * scale, k_cache.astype(jnp.float32))
    mask = jnp.arange(k_cache.shape[1])[None, :] < cache_len[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)
