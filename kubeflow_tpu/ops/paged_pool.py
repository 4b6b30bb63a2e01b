"""Array helpers over a block-paged KV pool ``[L, NB, bs, KV, D]``: the
slot-logical views a model's attention reads, and the quantized pool's
storage format (int8 / fp8_e4m3 values under per-block per-kv-head f32
scales ``[L, NB, KV]``), written and read in one place. The paged programs
(``serving/paged_kv.py``) and the models' ``paged_ops()`` import these; the
Pallas kernel (``ops/pallas_paged_attention.py``) fuses the same dequant
into its inner loop.
"""

from __future__ import annotations

import jax.numpy as jnp


def kv_store(x, store_dtype):
    """f32 values -> pool storage dtype: round+clip for int8, a plain
    cast (round-to-nearest) for the fp8 emulation."""
    if jnp.issubdtype(store_dtype, jnp.integer):
        return jnp.clip(jnp.round(x), -127, 127).astype(store_dtype)
    return x.astype(store_dtype)


def kv_qmax(store_dtype) -> float:
    return 127.0 if jnp.issubdtype(store_dtype, jnp.integer) else 448.0


def quant_scatter_rows(pool, scale, layer, blk, off, rows):
    """Quantize-on-write for the per-step KV scatters (decode, chunked
    prefill, spec verify): write ``rows`` into layer ``layer`` of the
    quantized ``pool`` [L, NB, bs, KV, D] at (blk, off) under the
    per-block per-kv-head ``scale`` [L, NB, KV], growing scales
    monotonically (scatter-max) and requantizing each touched block's
    resident rows when its scale grows — so earlier rows stay decodable
    under the one scale the read path (kernel and oracle alike) applies.
    When the scale does NOT grow the requant ratio is exactly 1.0 and
    int8 content round-trips unchanged. Both arrays are updated by
    scatters at ``(layer, blk)``: in a loop that carries them nothing
    pool-sized is copied.

    blk/off: int32, any common shape; rows: [..., KV, D]. Duplicate blk
    entries (verify writing several rows of one slot's block) are
    benign: the scatter-max folds all their amaxes first, every
    duplicate then computes the identical grown scale and requantized
    resident content, and the new rows land at distinct offsets. Rows
    routed to the scratch block 0 only ever pollute scratch scales,
    which nothing meaningful reads."""
    blk = blk.reshape(-1)
    off = off.reshape(-1)
    rows = rows.reshape(blk.shape[0], *rows.shape[-2:]).astype(jnp.float32)
    qmax = kv_qmax(pool.dtype)
    amax = jnp.max(jnp.abs(rows), axis=-1)               # [N, KV]
    old = scale[layer, blk]                              # [N, KV]
    scale = scale.at[layer, blk].max(amax / qmax)
    new = scale[layer, blk]
    safe = jnp.maximum(new, 1e-30)
    ratio = jnp.where(new > 0, old / safe, 0.0)          # <= 1.0 always
    resident = (pool[layer, blk].astype(jnp.float32)
                * ratio[:, None, :, None])
    pool = pool.at[layer, blk].set(kv_store(resident, pool.dtype))
    q = jnp.where(new[:, :, None] > 0, rows / safe[:, :, None], 0.0)
    pool = pool.at[layer, blk, off].set(kv_store(q, pool.dtype))
    return pool, scale


def dequant_gather_view(pool, scale, layer, tables, cfg):
    """Slot-logical [B, T, KV, D] view of layer ``layer`` of a QUANTIZED
    pool: gather the table's blocks, upcast, multiply each block's
    per-kv-head scale, cast to the compute dtype — element-for-element
    the pipeline the Pallas kernel fuses into its inner loop, which is
    what keeps the kernel-vs-oracle parity tests exact under
    quantization."""
    b = tables.shape[0]
    v = (pool[layer, tables].astype(jnp.float32)
         * scale[layer, tables][:, :, None, :, None]).astype(cfg.dtype)
    return v.reshape(b, -1, *pool.shape[3:])


def gather_views(pools, layer, tables, cfg):
    """Slot-logical K and V views [B, T, KV, D] of layer ``layer``: block
    j of a table row holds logical positions [j*bs, (j+1)*bs) — table
    order IS sequence order. A quantized pool dequants on the way (the
    quantized gather oracle)."""
    if "k_scale" in pools:
        return (dequant_gather_view(pools["k"], pools["k_scale"], layer,
                                    tables, cfg),
                dequant_gather_view(pools["v"], pools["v_scale"], layer,
                                    tables, cfg))
    b = tables.shape[0]
    return tuple(pools[key][layer, tables].reshape(
        b, -1, *pools[key].shape[3:]) for key in ("k", "v"))
