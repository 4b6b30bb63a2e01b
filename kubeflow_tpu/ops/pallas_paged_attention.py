"""First-party Pallas TPU paged-attention DECODE kernel.

The serving bottleneck this kernel removes (VERDICT r5 "What's weak" #4):
the gather decode path materializes each slot's full logical ``[max_seq]``
KV view every layer (``k_pool[tables]``), so per-step HBM traffic scales
with the ARENA, not with the tokens actually live — measured 7.44 ms/step
against a 2.01 ms param-read bandwidth bound at batch 32. The stock
``jax.experimental.pallas.ops.tpu.paged_attention`` kernel does not lower
at our proxy shapes (small query groups / non-256 head_dim), so this is
the first-party replacement, the same way ``ops/pallas_attention.py`` is
the first-party training flash kernel.

Design (decode only — one query token per slot):

- The kernel is given the WHOLE pool ``[L, num_blocks, block, KV_H, D]``
  and a layer index, never a slice of it: the decode program carries the
  pool through its layer loop and updates it in place
  (``serving/paged_kv.py``), and ``pool[layer]`` handed to a custom call
  would be a copy of a layer's pool, every layer.
- Grid ``(batch,)``: one grid step a slot. The layer, the live lengths
  and the block-table rows ride in as **scalar-prefetch** operands; the
  pools stay in HBM (``memory_space=pl.ANY``) and the kernel walks the
  slot's table itself: a ``fori_loop`` over the slot's LIVE chunks,
  ``cdiv(cdiv(kv_len, block), G)`` of them, each ``G`` pool blocks copied
  by ``make_async_copy(pool.at[layer, tables[b, j]], ...)`` into a
  double-buffered VMEM scratch. The next chunk's copies are started
  before the current one is waited on, and under a slot's LAST chunk the
  first chunk of the next live slot, so a slot does not begin with an
  exposed copy.
- What is not live costs nothing: an idle slot (``kv_len`` 0) starts no
  copy and multiplies nothing, a short slot's loop ends at its tail, and
  no copy is started for a block past it — per-step HBM traffic is
  O(live tokens), the paged-attention property. (As a grid over
  ``(batch, max_blocks_per_seq)`` with dead steps pinned to the last live
  block, 1,180 of 1,280 steps of a call were dead on the chat cell, a
  sixth of a live step's time each: PERF.md section 6, PR 30.)
- ``G`` comes from the shapes alone (``_chunk_blocks``: a chunk's K and
  V as f32 matrices of at most 2 MiB each and at most 1,024 tokens: 8
  blocks of 64 x 8 x 128, 16 of 64 x 2 x 128): the chip pays a fixed cost
  a softmax update, and two products over one block are too small to hide
  it; the products also run over a chunk's dead rows, so the longest
  chunk that fits is not the fastest.
- A block is read as the ``[block * KV_H, D]`` matrix of (token, kv head)
  rows it is in HBM, and lands in VMEM dense. VMEM tiles a buffer's last
  two dims into 8 sublane rows of 128 lanes, so which VIEW of the pool the
  copies address depends on ``KV_H``. Whole tiles of kv heads (``KV_H`` a
  multiple of 8: the dense cells) arrive as the stored ``(block, KV_H,
  D)`` and merging the leading dims of full tiles is free. Any other
  count (2 kv heads: the CCA model, or 8 split over ``tensor`` = 4) would
  leave every token a tile that is three quarters padding, and ``reshape(rows, D)`` a
  compaction of the whole chunk on the vector unit between the copy and
  the products (29.7% of the roofline where 8 heads read 79.6%, PERF.md
  section 6, PR 37); there the wrapper hands the kernel the pool as
  ``[L, NB, block * KV_H, D]``, which is the same bytes in HBM (a bitcast
  in the compiled program), and a copy brings a block in as the matrix
  the products take. A merged ``[..., KV_H * D]`` view, whose per-head
  tiles would be lane slices, is NOT a free reshape of the pool on the
  chip: it is a relayout of everything reshaped (PR 27: 2 of the 8
  whole-pool operations a step).
- GQA in-kernel, without per-head tiles: ONE ``[H, D] x [D, G*block*KV_H]``
  product scores every query head against every (token, kv head) row of
  the chunk and a mask keeps each head's own kv head (column ``c`` is kv
  head ``c % KV_H``, query head ``h`` belongs to ``h // groups``) and the
  live tokens; the masked probabilities are exactly 0, so ONE ``[H,
  G*block*KV_H] x [G*block*KV_H, D]`` product sums each head's own rows.
  That is KV_H times the useful MXU work, and free: the kernel moves 1
  byte per ~32 flop against a ridge of 240, and on the chip it is as fast
  as the per-head lane-slice form it replaces and 15% faster than
  per-head strided sublane reads of the same block (PERF.md §6, PR 27).
  Each pool block is fetched ONCE per slot; KV heads are never repeated,
  and no ``[max_seq]`` view ever exists. The value rows past a slot's
  live tail are zeroed as well as their scores masked: the buffer holds
  whatever an earlier slot left there, and ``0 x NaN`` is NaN.
- Online softmax across a slot's chunks (running max / sum / weighted
  accumulator carried by the loop, f32), exactly the flash recurrence the
  training kernel uses.

``interpret=True`` runs the identical kernel logic on CPU (tier-1 tests);
the gather path in ``serving/paged_kv.py`` stays available as the
reference oracle behind the same ``kernel=`` switch.

Mesh partitioning: grouped-query attention is embarrassingly parallel
over KV heads — every query-head group attends ONLY its own KV head, and
the online-softmax state never crosses groups. So a tensor-sharded
engine (KV pool sharded on the kv-head dim, q sharded on heads by the
same factor) runs the kernel under ``shard_map``
(``paged_decode_attention_sharded``): each shard streams its LOCAL pool
blocks through VMEM against its local query heads, block tables and
lengths replicated, zero collectives. XLA cannot auto-partition a Mosaic
call, which is why the gather oracle used to be the only sharded path;
the shard_map wrapper removes that downgrade.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30      # same mask value as the gather path (decode_attention)


def _softmax_accumulate(s, values, m_ref, l_ref, acc_ref):
    """One block of the online-softmax recurrence (the GQA kernel below keeps
    its own copy inline: folding it into this helper moved where Mosaic loads
    the V tile and cost that kernel 4% on the chip, PERF.md section 6, PR
    29): masked scores ``s`` [H, T] (f32) and the block's ``values`` [T, D]
    fold into the running max / sum / weighted values in VMEM scratch.
    m/l scratch is lane-replicated so the [H, 128] tiles stay aligned
    (only lane 0 is meaningful). Returns (acc, l_new)."""
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])              # masked columns: exactly 0
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [H, D]
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc
    return acc, l_new


def _live_block(block_size, n_tables, kvlen_ref, tables_ref, bi, j):
    """The pool block iteration ``j`` of slot ``bi`` reads. Past the live
    tail it stays the slot's LAST live block: an unchanged block index
    elides the DMA, so dead iterations move nothing (an idle slot pins to
    block 0, fetched once)."""
    n_live = pl.cdiv(kvlen_ref[bi], block_size)
    jc = jnp.clip(jnp.minimum(j, n_live - 1), 0, n_tables - 1)
    return tables_ref[bi, jc]


def _decode_kernel(layer_ref, kvlen_ref, tables_ref, q_ref, k_hbm, v_hbm,
                   *rest, scale, block_size, kv_heads, groups, head_dim,
                   chunk_blocks, quantized):
    """One grid step = one slot: walk its live blocks ``chunk_blocks`` at a
    time through a double-buffered VMEM scratch, one online-softmax update
    a chunk. The pools stay in HBM and are addressed by (layer,
    tables[b, j]) in the copies; a quantized pool's scale tables come as
    this layer's [1, NB, KV_H], whole in VMEM."""
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems, state = rest
    else:
        o_ref, k_buf, v_buf, sems, state = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_heads = q_ref.shape[1]
    layer = layer_ref[0]
    chunk_rows = chunk_blocks * block_size * kv_heads

    def copies(slot, chunk, buf, g):
        """The copies that bring block ``g`` of a slot's chunk into
        buffer ``buf`` (built alike to start and to wait for them)."""
        blk = tables_ref[slot, chunk * chunk_blocks + g]
        return [pltpu.make_async_copy(k_hbm.at[layer, blk], k_buf.at[buf, g],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], v_buf.at[buf, g],
                                      sems.at[1, buf])]

    def for_live_blocks(slot, chunk, buf, act):
        """``act`` on the copies of the chunk's LIVE blocks only: nothing
        past a slot's tail is ever fetched."""
        n_blocks = pl.cdiv(kvlen_ref[slot], block_size)
        for g in range(chunk_blocks):
            @pl.when(chunk * chunk_blocks + g < n_blocks)
            def _():
                for copy in copies(slot, chunk, buf, g):
                    act(copy)

    def start(slot, chunk, buf):
        for_live_blocks(slot, chunk, buf, lambda copy: copy.start())

    def wait(slot, chunk, buf):
        for_live_blocks(slot, chunk, buf, lambda copy: copy.wait())

    @pl.when(b == 0)
    def _reset():
        state[0] = 0        # the buffer the next chunk computed lies in
        state[1] = -1       # the slot whose first chunk is already in flight

    kv_len = kvlen_ref[b]
    n_blocks = pl.cdiv(kv_len, block_size)
    n_chunks = pl.cdiv(n_blocks, chunk_blocks)

    @pl.when(kv_len == 0)
    def _idle():
        # kv_len >= 1 for every slot the engine reads (the decode step just
        # wrote this step's row); an idle slot costs no copy and no product
        # and still leaves defined output
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(kv_len > 0)
    def _live():
        buf0 = state[0]

        @pl.when(state[1] != b)
        def _first():               # nobody before this slot fetched for it
            start(b, 0, buf0)

        # the next slot with anything to read: its first chunk is started
        # under this slot's last, or every slot would begin with an exposed
        # copy (a call 116 -> 159 us at the offline cell's occupancy,
        # PERF.md section 6, PR 30)
        nxt_slot = jax.lax.while_loop(
            lambda i: (i < n_slots) & (
                kvlen_ref[jnp.minimum(i, n_slots - 1)] == 0),
            lambda i: i + 1, b + 1)

        q = q_ref[0].astype(jnp.float32) * scale             # [H, D]
        # column c of a chunk's scores is token c // KV_H of the chunk, kv
        # head c % KV_H; query head h belongs to kv head h // groups. A
        # foreign head's column gets a token no length reaches.
        shape = (n_heads, chunk_rows)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        tok = jnp.where((col % kv_heads) == (head // groups),
                        col // kv_heads, jnp.int32(2 ** 30))

        def tile(buf_ref, sc_ref, buf, c):
            """A chunk as the [G * block * KV_H, D] f32 matrix it is in
            memory (row t * KV_H + g: token t, kv head g), dequantized."""
            if sc_ref is None:
                return buf_ref[buf].astype(jnp.float32).reshape(
                    chunk_rows, head_dim)
            # dequant between the copy and the MXU, per element as the
            # gather oracle does it (bit-for-bit parity in f32), each block
            # by its own row of the layer's scale table
            blocks = []
            for g in range(chunk_blocks):
                j = jnp.minimum(c * chunk_blocks + g, tables_ref.shape[1] - 1)
                sc = sc_ref[0, pl.ds(tables_ref[b, j], 1), :]    # [1, KV_H]
                x = buf_ref[buf, g].astype(jnp.float32).reshape(
                    block_size, kv_heads, head_dim) * sc[0][None, :, None]
                blocks.append(x.reshape(block_size * kv_heads, head_dim))
            return jnp.concatenate(blocks, axis=0)

        def chunk_step(c, carry):
            m_prev, l_prev, acc = carry
            buf = (buf0 + c) % 2
            last = c + 1 == n_chunks

            @pl.when(jnp.logical_not(last))
            def _():
                start(b, c + 1, 1 - buf)

            @pl.when(last & (nxt_slot < n_slots))
            def _():
                start(nxt_slot, 0, 1 - buf)

            wait(b, c, buf)
            left = kv_len - c * (chunk_blocks * block_size)  # live tokens
            # every query head against every (token, kv head) row of the
            # chunk in ONE product, masked to the head's own kv head and
            # the live tokens: the blocks stay in the layout they are
            # stored in, and the KV_H-fold of redundant MXU work is free
            # beside the bytes (32 flop/byte against a ridge of 240)
            s = jax.lax.dot_general(
                q, tile(k_buf, ks_ref, buf, c), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [H, rows]
            s = jnp.where(tok < left, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)             # masked columns: exactly 0
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # rows past the live tail are whatever the buffer held (no copy
            # of this slot wrote them): their scores are masked above, but
            # 0 x NaN is NaN in the second product, so their VALUES are
            # zeroed too (free beside the copies, measured)
            v = tile(v_buf, vs_ref, buf, c)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < left * kv_heads, v, 0.0)
            acc = acc * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [H, D]
            return m_new, l_new, acc

        _, l_fin, acc = jax.lax.fori_loop(
            0, n_chunks, chunk_step,
            (jnp.full((n_heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, head_dim), jnp.float32)))
        o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        state[0] = (buf0 + n_chunks) % 2
        state[1] = nxt_slot


def _chunk_blocks(block_size, kvh, head_dim, n_tables):
    """Pool blocks a softmax update, by two limits. The chunk's K and V as
    f32 matrices (the products' operands) are held to 2 MiB each, beside
    which the two double-buffered copies in the pool's dtype and the
    [H, rows] scores fit the default scoped VMEM; a block counts as it lies
    in VMEM, and it lies there dense, ``block * KV_H`` rows whatever
    ``KV_H`` is, because a kv-head dim that would pad a tile is read merged
    (``paged_decode_attention``). And a chunk is at most 1,024 tokens: the
    products run over a whole chunk whatever part of it is live, half a
    chunk of dead rows a slot on average, against a fixed cost a chunk of
    about 12 blocks' worth at 2 kv heads. At 64 x 2 x 128, slots of 4-70
    live blocks: 8 / 16 / 32 blocks a chunk read 68.3 / 70.7 / 64.4% of the
    roofline in the cell, the kernel alone 67.3 / 69.6 / 62.6 and 71.3 at
    12, 70.9 at 24, 54.7 at 48 (PERF.md section 6, PR 37). 8 kv heads
    reach the first limit at 8 blocks, 512 tokens."""
    block_f32 = block_size * kvh * head_dim * 4
    return max(1, min(n_tables, (2 * 2 ** 20) // block_f32,
                      1024 // block_size))


def paged_decode_attention(q, k_pool, v_pool, layer, tables, kv_len, *,
                           interpret: bool = False,
                           k_scale=None, v_scale=None, kv_heads=None):
    """Block-resident paged GQA decode attention over ONE layer of the
    whole pool, addressed by (layer, block).

    q: [B, H, D] (this step's query rows); k_pool/v_pool:
    [L, num_blocks, block_size, KV_H, D] (the engine's paged pools as
    stored, current step's KV row already scattered in); layer: int32
    scalar, the layer to read; tables: [B, max_blocks_per_seq] int32 pool
    block ids in logical order; kv_len: [B] int32 live rows per slot
    INCLUDING this step. Returns [B, H, D] in q.dtype.

    The caller never slices ``pool[layer]`` (a copy of a layer's pool,
    every layer): the pools are handed over whole and stay in HBM, the
    layer rides as a scalar-prefetch operand beside the lengths and the
    tables, and the kernel copies ``pool[layer, tables[b, j]]`` itself.

    k_scale/v_scale: [L, num_blocks, KV_H] f32 per-block per-kv-head
    scales of an int8/fp8-quantized pool (both or neither). When given,
    the layer's scale table rides whole in VMEM and each copied block
    dequants by its own row of it (upcast * scale) between the copy and
    the products.

    A pool stored merged (``models/paged.stored_merged``) comes as ``[L,
    num_blocks, block_size * KV_H, D]`` with ``kv_heads`` = KV_H: the matrix
    the kernel reads, so no view of it is taken. Heads wider than one lane
    tile (D = 256) need it: their 5-D tiles interleave the heads.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    b, h, d = q.shape
    if k_pool.ndim == 4:
        if kv_heads is None:
            raise ValueError("a pool stored merged needs kv_heads")
        n_layers, num_blocks, rows, d_k = k_pool.shape
        kvh = kv_heads
        block_size = rows // kvh
    else:
        n_layers, num_blocks, block_size, kvh, d_k = k_pool.shape
    if d != d_k:
        raise ValueError(f"head_dim mismatch: q has {d}, pool has {d_k}")
    if h % kvh:
        raise ValueError(f"H={h} not a multiple of KV_H={kvh}")
    n_tables = tables.shape[1]
    chunk = _chunk_blocks(block_size, kvh, d, n_tables)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kv_len = kv_len.astype(jnp.int32)
    tables = tables.astype(jnp.int32)

    if kvh % 8 and k_pool.ndim == 5:
        # a kv-head dim that does not fill whole sublane tiles: the block's
        # (token, kv head) rows merged, so that it lands in VMEM as the
        # dense matrix the products take (module docstring). What the v5e
        # compile makes of the reshape (tests/test_pallas_tpu_lowering.py):
        # for bf16 at 2 heads a bitcast, the stored bytes of a T(2,128)(2,1)
        # pool being the merged matrix already; for int8 at 2 heads (one
        # chip, or 8 over tensor = 4) XLA keeps the pool token-minor
        # ({4,1,3,2,0:T(8,128)(4,1)}) and two copies of each pool a call
        # bring it to the row-major merged form (a pool STORED merged would
        # need neither), where unmerged a copy could not name the padding
        # of a kv-head dim under 32 bits at all
        k_pool, v_pool = (p.reshape(n_layers, num_blocks, block_size * kvh, d)
                          for p in (k_pool, v_pool))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, h, d), lambda bi, *_: (bi, 0, 0)),
                whole, whole]
    args = (layer, kv_len, tables, q, k_pool, v_pool)
    scratch = [
        pltpu.VMEM((2, chunk) + k_pool.shape[2:], k_pool.dtype),
        pltpu.VMEM((2, chunk) + v_pool.shape[2:], v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),         # [K | V, buffer]
        pltpu.SMEM((2,), jnp.int32),             # carried from slot to slot
    ]
    if k_scale is not None:
        if k_scale.shape != (n_layers, num_blocks, kvh):
            raise ValueError(f"k_scale shape {k_scale.shape} != "
                             f"{(n_layers, num_blocks, kvh)}")
        # the layer's scale table rides whole in VMEM (fetched once a call:
        # its block index never changes); a block's row is read from it
        in_specs += [pl.BlockSpec(
            (1, num_blocks, kvh),
            lambda bi, layer_ref, *_: (layer_ref[0], 0, 0))] * 2
        args += (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _decode_kernel, scale=1.0 / (d ** 0.5), block_size=block_size,
        kv_heads=kvh, groups=h // kvh, head_dim=d, chunk_blocks=chunk,
        quantized=k_scale is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # slots in order: a slot starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Latent (MLA) decode: every query head over ONE shared row per token
# ---------------------------------------------------------------------------

def _latent_decode_kernel(layer_ref, kvlen_ref, tables_ref, q_ref, *rest,
                          scale, block_size, value_dim, blocks_per_step):
    del layer_ref, tables_ref           # consumed by the index maps
    row_refs = rest[:blocks_per_step]
    o_ref, m_ref, l_ref, acc_ref, rows_ref = rest[blocks_per_step:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = kvlen_ref[b]
    first = j * blocks_per_step * block_size     # this step's first token

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first < kv_len)
    def _contribute():
        # the step's blocks side by side as ONE [G * bs, R] matrix: two
        # products of a useful width and one softmax update a grid step,
        # not two small products and an update a block (3.3 times the
        # time on the chip, PERF.md section 6, PR 29). Blocks past the
        # slot's live tail repeat its last live block and are masked.
        for g, ref in enumerate(row_refs):
            rows_ref[g * block_size:(g + 1) * block_size, :] = ref[0, 0]
        rows = rows_ref[...]
        # operands as stored (bf16 on the chip), f32 accumulation: at 61
        # operations a cached byte a float32 product would make the MXU
        # the bound (32 heads fill a quarter of it as it is)
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, G * bs]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(first + col < kv_len, s, NEG_INF)
        # the values are the rows' first ``value_dim`` entries: a
        # lane-aligned slice of what is already in VMEM
        _softmax_accumulate(s, rows[:, :value_dim], m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # kv_len >= 1 always (the decode step just wrote this step's row);
        # an all-dead slot still leaves defined output (zeros)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def paged_latent_decode_attention(q, pool, layer, tables, kv_len, *,
                                  value_dim: int, scale: float,
                                  interpret: bool = False):
    """Absorbed latent-attention decode over ONE layer of the whole pool.

    q: [B, H, R] absorbed queries (``W_uk^T q_nope`` beside ``q_rope``);
    pool: [L, num_blocks, block_size, R], one row per token shared by all
    heads (the normed latent, then the rotated key); tables / kv_len /
    layer as ``paged_decode_attention``. Scores are ``q . row * scale``
    over all R entries, the output ``sum p row[:value_dim]``: [B, H,
    value_dim] in q.dtype. ``W_uk`` is applied before and ``W_uv`` after,
    outside.

    The GQA kernel's operands (layer, lengths and tables by scalar
    prefetch) and online softmax, over a grid of (slot, table entries)
    with the dead tail pinned to the slot's last live block (the walk
    the GQA kernel had until it took the loop inside), with two
    differences the shape asks for. A block is the ``[bs, R]``
    matrix it is in memory, used whole by every head. And one grid step
    reads up to 32 blocks, each through its own BlockSpec on the same
    pool, and multiplies them as one matrix: a block is bs x R x 2 bytes
    (80 KiB at 64 x 640), which moves in a quarter of a grid step's fixed
    cost, and the long contexts this serves are hundreds of blocks a
    slot."""
    b, h, r = q.shape
    n_layers, num_blocks, block_size, r_pool = pool.shape
    if r != r_pool:
        raise ValueError(f"row mismatch: q has {r}, pool has {r_pool}")
    n_tables = tables.shape[1]
    per_step = next(g for g in (32, 16, 8, 4, 2, 1) if n_tables % g == 0)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kv_len = kv_len.astype(jnp.int32)
    tables = tables.astype(jnp.int32)

    def rows_map(g):
        def index(bi, j, layer_ref, kvlen_ref, tables_ref):
            return (layer_ref[0],
                    _live_block(block_size, n_tables, kvlen_ref, tables_ref,
                                bi, j * per_step + g), 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_tables // per_step),
        in_specs=[pl.BlockSpec((1, h, r), lambda bi, j, *_: (bi, 0, 0))]
        + [pl.BlockSpec((1, 1, block_size, r), rows_map(g))
           for g in range(per_step)],
        out_specs=pl.BlockSpec((1, h, value_dim),
                               lambda bi, j, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
            pltpu.VMEM((per_step * block_size, r), pool.dtype),
        ],
    )
    kernel = functools.partial(
        _latent_decode_kernel, scale=scale, block_size=block_size,
        value_dim=value_dim, blocks_per_step=per_step)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        interpret=interpret,
    )(layer, kv_len, tables, q, *([pool] * per_step))


def shard_unsupported_reason(mesh, n_kv_heads: int,
                             axis: str = "tensor"):
    """Why ``paged_decode_attention_sharded`` cannot run on this mesh, or
    None when it can. The one hard constraint is the engine's own pool
    constraint: the KV-head dim must split evenly over ``axis``. Mesh
    axes the specs don't mention (data/fsdp in a mixed topology) are
    fine — shard_map treats them as replication, which the serving
    engine's tensor-only pool sharding already guarantees."""
    if mesh is None:
        return None
    sizes = dict(getattr(mesh, "shape", {}) or {})
    tp = int(sizes.get(axis, 1))
    if tp > 1 and n_kv_heads % tp:
        return (f"n_kv_heads={n_kv_heads} not divisible by "
                f"{axis}={tp}")
    return None


def _shard_map(f, mesh, in_specs, out_specs):
    """Mosaic calls have no varying-mesh-axes rule, so the check must be
    off (the specs here are correct by construction — per-KV-head groups
    are independent)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def paged_decode_attention_sharded(q, k_pool, v_pool, layer, tables, kv_len,
                                   *, mesh, axis: str = "tensor",
                                   interpret: bool = False,
                                   k_scale=None, v_scale=None):
    """``paged_decode_attention`` partitioned over the mesh's heads/KV
    axis with shard_map: q [B, H, D] shards on H, pools
    [L, NB, bs, KV_H, D] on KV_H, the layer, block tables and lengths
    replicated — each shard's table row names the same pool blocks, but
    only the local kv-head slice of them is resident per chip. No
    collectives: softmax state is private to each query-head group.

    Falls back to the unwrapped kernel when there is no mesh or it doesn't
    shard ``axis`` (a 1-sized axis needs no partitioning); raises for
    topologies the kernel cannot shard (see shard_unsupported_reason) —
    callers decide the gather downgrade, not this function.

    Quantized pools: the [L, NB, KV_H] scale tables shard on their
    kv-head dim with the pools (``P(None, None, axis)``) — each shard
    dequants its local kv-head slice with its local scales, still zero
    collectives."""
    kvh = k_pool.shape[3]
    reason = shard_unsupported_reason(mesh, kvh, axis)
    if reason is not None:
        raise ValueError(f"cannot shard paged attention: {reason}")
    if mesh is None or int(dict(mesh.shape).get(axis, 1)) <= 1:
        return paged_decode_attention(q, k_pool, v_pool, layer, tables,
                                      kv_len, interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)
    pool_spec = P(None, None, None, axis, None)
    in_specs = (P(None, axis, None), pool_spec, pool_spec, P(),
                P(None, None), P(None))
    args = (q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), tables, kv_len)
    if k_scale is not None:
        in_specs += (P(None, None, axis),) * 2
        args += (k_scale, v_scale)

    def kern(qs, kp, vp, lay, t, kl, ks=None, vs=None):
        return paged_decode_attention(qs, kp, vp, lay, t, kl,
                                      interpret=interpret,
                                      k_scale=ks, v_scale=vs)

    return _shard_map(kern, mesh, in_specs=in_specs,
                      out_specs=P(None, axis, None))(*args)


# ---------------------------------------------------------------------------
# Latent (MLA) prefill chunk: a chunk's queries over the slot's rows, flash
# ---------------------------------------------------------------------------

def _latent_prefill_kernel(layer_ref, qstart_ref, tables_ref, q_ref, wk_ref,
                           wv_ref, pool_hbm, o_ref, rows_buf, k_buf, m_ref,
                           l_ref, acc_ref, sems, *, scale, block_size,
                           latent_dim, rope_dim, q_tile, tile_blocks):
    """One grid step = one (slot, head): walk the slot's rows a KV tile of
    ``tile_blocks`` pool blocks at a time, copied by the table through a
    double buffer, up to the tile that holds the last query's position.
    A tile is up-projected once with this head's ``W_uk`` / ``W_uv`` and
    then met by the chunk's query sub-tiles; the score tile of a (query
    sub-tile, KV tile) pair lives only between its product and the value
    product. m / l / acc of ALL the chunk's rows stay in VMEM across the
    walk."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    q_start = qstart_ref[b]
    chunk = q_ref.shape[2]
    nope_dim = wk_ref.shape[1]
    n_tables = tables_ref.shape[1]
    kv_tile = tile_blocks * block_size
    n_q = chunk // q_tile
    # the walk ends at the tile that holds the last query's own position
    n_tiles = (q_start + chunk - 1) // kv_tile + 1

    def copies(t, buf):
        # a table entry past the slot's last block names the scratch block
        # (finite rows, every one of them masked): the tile is always whole
        for g in range(tile_blocks):
            blk = tables_ref[b, jnp.minimum(t * tile_blocks + g,
                                            n_tables - 1)]
            yield pltpu.make_async_copy(
                pool_hbm.at[layer, blk],
                rows_buf.at[buf, pl.ds(g * block_size, block_size)],
                sems.at[buf])

    def start(t, buf):
        for copy in copies(t, buf):
            copy.start()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start(0, 0)

    def kv_step(t, _):
        buf = t % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            start(t + 1, 1 - buf)

        for copy in copies(t, buf):
            copy.wait()
        rows = rows_buf[buf]                                  # [tk, R]
        latent = rows[:, :latent_dim]
        # this head's keys and values of the tile, once: operands as
        # stored, f32 accumulation, rounded to the pool's dtype as the
        # plain form's einsum rounds them. The shared rotary key is the
        # [tk, d_r] matrix it is, beside the head's own keys.
        k_buf[:, :nope_dim] = jnp.dot(
            latent, wk_ref[...],
            preferred_element_type=jnp.float32).astype(k_buf.dtype)
        k_buf[:, nope_dim:] = rows[:, latent_dim:latent_dim + rope_dim]
        keys = k_buf[...]                                     # [tk, d_n+d_r]
        values = jnp.dot(latent, wv_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(rows.dtype)                 # [tk, d_v]
        kv0 = t * kv_tile

        def pair(i, masked):
            sub = pl.ds(pl.multiple_of(i * q_tile, q_tile), q_tile)
            s = jax.lax.dot_general(
                q_ref[0, 0, sub, :], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [tq, tk]
            if masked:
                q_pos = q_start + i * q_tile + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                kv_pos = kv0 + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
            _softmax_accumulate(s, values, m_ref.at[sub], l_ref.at[sub],
                                acc_ref.at[sub])

        # query sub-tile i holds positions q_start + [i, i + 1) * q_tile:
        # one wholly before the tile multiplies nothing, one the diagonal
        # crosses builds a mask, one wholly past the tile needs none
        first = jnp.clip((kv0 - q_start) // q_tile, 0, n_q)
        clear = jnp.clip(pl.cdiv(kv0 + kv_tile - 1 - q_start, q_tile),
                         first, n_q)
        jax.lax.fori_loop(first, clear, lambda i, _: pair(i, True), None)
        jax.lax.fori_loop(clear, n_q, lambda i, _: pair(i, False), None)

    jax.lax.fori_loop(0, n_tiles, kv_step, None)
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
                   ).astype(o_ref.dtype)


def _prefill_tiles(chunk: int, block_size: int, n_tables: int):
    """(query sub-tile rows, pool blocks a KV tile) from the shapes: score
    tiles of 1,024 x 1,024 (4 MiB in f32; on the chip 4.70 ms a call at
    2,048 queries over 12k rows where 512 x 512 takes 6.82, PERF.md section
    6, PR 32), a narrower chunk one sub-tile of its own width in whole bf16
    sublane tiles."""
    q_tile = min(1024, -(-chunk // 16) * 16)
    return q_tile, max(1, min(n_tables, 1024 // block_size))


def paged_latent_prefill_attention(q, pool, w_uk, w_uv, layer, tables,
                                   q_start, *, rope_dim: int, scale: float,
                                   interpret: bool = False):
    """Non-absorbed causal latent attention of a chunk of queries over ONE
    layer of the whole pool: the chunked prefill's attention as one flash
    kernel.

    q: [B, C, H, d_n + d_r] (``q_nope`` beside the rotated ``q_rope``), row
    i of slot b at position ``q_start[b] + i``; pool: [L, num_blocks,
    block_size, R] with a token's row ``[c ; k_rope ; padding]`` (the
    chunk's own rows already scattered in); w_uk: [latent, H, d_n], w_uv:
    [latent, H, d_v]; layer / tables as ``paged_latent_decode_attention``;
    q_start: [B] int32. Returns o [B, C, H, d_v] in q.dtype: ``softmax((q .
    [W_uk c ; k_rope]) * scale) (W_uv c)`` over the rows at positions <= the
    query's own, scores, exponentials, sums and the accumulator in float32,
    the probabilities in the pool's dtype for the value product.

    Grid (slot, head): a head's queries [C, d_n + d_r], its slices of
    ``W_uk`` / ``W_uv`` and its m / l / acc stay in VMEM while the kernel
    walks the slot's rows (module docstring's idiom: the whole pool in HBM,
    blocks copied by the table, double-buffered). A context's keys and
    values never exist anywhere: a tile's are made in VMEM from the rows,
    once a (head, tile). Nothing masked is multiplied beyond the sub-tiles
    the diagonal crosses (``_latent_prefill_kernel``). Rows past the last
    true one (a final chunk's padding) attend like any other and give
    finite output nobody reads."""
    b, c, h, qk_dim = q.shape
    block_size = pool.shape[2]
    latent_dim, _, nope_dim = w_uk.shape
    v_dim = w_uv.shape[2]
    if qk_dim != nope_dim + rope_dim:
        raise ValueError(f"q has {qk_dim} values a head, the keys "
                         f"{nope_dim} + {rope_dim}")
    q_tile, tile_blocks = _prefill_tiles(c, block_size, tables.shape[1])
    padded = -(-c // q_tile) * q_tile
    q = jnp.transpose(q, (0, 2, 1, 3))                      # [B, H, C, qk]
    if padded != c:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, padded - c), (0, 0)))
    kv_tile = tile_blocks * block_size
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, padded, qk_dim),
                         lambda bi, hi, *_: (bi, hi, 0, 0)),
            # a head's slice of the up-projections: [latent, H * d] is the
            # weights' own memory, head hi its hi-th block of columns
            pl.BlockSpec((latent_dim, nope_dim), lambda bi, hi, *_: (0, hi)),
            pl.BlockSpec((latent_dim, v_dim), lambda bi, hi, *_: (0, hi)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, padded, v_dim),
                               lambda bi, hi, *_: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kv_tile, pool.shape[3]), pool.dtype),
            pltpu.VMEM((kv_tile, qk_dim), pool.dtype),
            pltpu.VMEM((padded, 128), jnp.float32),
            pltpu.VMEM((padded, 128), jnp.float32),
            pltpu.VMEM((padded, v_dim), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _latent_prefill_kernel, scale=scale, block_size=block_size,
        latent_dim=latent_dim, rope_dim=rope_dim, q_tile=q_tile,
        tile_blocks=tile_blocks)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, padded, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # beside the queries, the accumulators and the double buffer,
            # the f32 score and probability tiles: over the 16 MiB default
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(layer, q_start.astype(jnp.int32), tables.astype(jnp.int32), q,
      w_uk.reshape(latent_dim, h * nope_dim).astype(pool.dtype),
      w_uv.reshape(latent_dim, h * v_dim).astype(pool.dtype), pool)
    return jnp.transpose(o[:, :, :c], (0, 2, 1, 3))
