"""Block-paged KV cache for the LLM engine (vLLM's PagedAttention role,
SURVEY.md §2.4 LLM row), XLA-first.

A dense arena ([L, max_batch, max_seq, KV, D]) would charge every slot
for the worst-case sequence length. Here KV lives in a pool of fixed-size
blocks ([L, num_blocks, block_size, KV, D]) and each slot owns a *block
table* — the ordered block ids backing its logical sequence — so arena
memory scales with tokens actually resident, and a pool holding
``num_blocks * block_size`` tokens can serve far more concurrent short
requests than a dense arena of equal bytes.

What a model is to these programs is ``models/paged.py::PagedOps``, which
every servable config builds in its own ``paged_ops()`` from the layer
pieces its ``forward`` uses; this module names no model. The pool's array
format (views, quantized rows and scales) is ``ops/paged_pool.py``.

Everything stays static-shape for XLA: the pool and the [max_batch,
max_blocks_per_seq] table array never change shape; tables are
host-managed numpy (the scheduler allocates blocks at admission — enough
for prompt + max_tokens, so decode can never run out mid-flight) and ride
into the jitted step as a plain traced argument.

The pool is updated IN PLACE. Every program that writes it (decode,
chunked prefill, speculative verify) walks the layers with the layer index
and that layer's weights as the scanned part and the whole pools in the
scan's CARRY (``_scan_layers``): a step's rows land with a scatter at
``(layer, block, offset)`` and every reader addresses the pool by
``(layer, block)``. No program slices a layer's pool out (``xs``) or stacks
updated layers back (``ys``) — XLA lowers those to copies of the whole pool,
several a step — and with the cache donated the carry aliases through the
engine's chunk loop too, so a step costs O(rows written + blocks read), not
O(pool). ``pool_shaped_ops`` reads a compiled program's text and lists what
would break that.

Decode attention has two execution paths over that carry, selected by
``paged_decode_step(..., kernel=)``:

- ``"gather"`` — materialize each slot's logical [max_seq] view
  (``k_pool[layer, tables]``) and run dense GQA attention over it. Per-step
  HBM traffic scales with the ARENA (r5 ablation: view cost follows
  max_seq, not live length). Retained as the reference oracle, the CPU
  default, and the path for meshes the kernel cannot be sharded over.
- ``"pallas"`` — the first-party block-resident kernel
  (``ops/pallas_paged_attention.py``), given the whole pool and the layer:
  per slot, stream only the live blocks named by its table row through
  VMEM and run grouped-query attention with an online-softmax accumulator
  in-kernel. HBM traffic is O(live tokens); no view is ever materialized.
  On CPU the SAME kernel logic runs under the Pallas interpreter
  (``interpret=True``), so tier-1 tests exercise the exact code path that
  compiles for TPU.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.paged import PagedOps, stored_merged
from kubeflow_tpu.ops.paged_pool import (
    kv_qmax, kv_store, quant_scatter_rows,
)
from kubeflow_tpu.serving.quant import kv_store_dtype


def paged_ops(cfg) -> PagedOps:
    """The model's pieces, from the config's own ``paged_ops()``."""
    return cfg.paged_ops()


def init_paged_cache(cfg, max_batch: int, max_seq: int,
                     block_size: int, num_blocks: int, dtype=None,
                     kv_sharding=None, len_sharding=None,
                     quant_kv: str = "none",
                     scale_sharding=None) -> dict:
    """Pool + per-slot lengths. ``num_blocks`` bounds total resident tokens
    (num_blocks * block_size), independent of max_batch * max_seq. The
    pools and their rows are the model's (``PagedOps.pool_rows``): K and V
    of ``[KV, D]`` rows for a GQA model, one ``kv`` pool of latent rows for
    a latent-attention one. What a model's layers keep per SLOT and not per
    token (``PagedOps.slot_rows``) lies beside the pools, ``[n_layers,
    max_batch, *row]``: a second kind of state in the one cache, carried,
    donated and updated in place with the pools. In a model of two layer
    kinds (``PagedOps.period``) each array has rows for the layers of the
    kind that owns it only: the pools and ``slot_rows`` for the attention
    layers, ``state_rows`` (in their own dtype) for the recurrent ones.
    ``kv_sharding`` allocates the pool DIRECTLY with that sharding — a
    pod-sized pool must never transit one chip unsharded.

    ``quant_kv`` != "none" stores the pools in the quantized dtype
    ("int8" | "fp8_e4m3") and adds per-block per-kv-head f32 scale
    tables ``k_scale``/``v_scale`` [L, num_blocks, KV] beside them (the
    quantized-pool marker every dispatch path keys on is the presence of
    those keys). ``scale_sharding`` shards the scale tables on the
    kv-head dim alongside the pool's."""
    if max_seq % block_size:
        raise ValueError(f"max_seq={max_seq} not a multiple of "
                         f"block_size={block_size}")
    ops = paged_ops(cfg)
    dtype = dtype or cfg.dtype
    quantized = bool(quant_kv) and quant_kv != "none"
    if quantized and "quantized KV pool" in ops.refuses:
        raise ValueError(f"{type(cfg).__name__} has no quantized KV pool: "
                         + ops.refuses["quantized KV pool"])
    cache = {}
    n_attn = ops.layers_of("attention")
    for name, row in ops.pool_rows.items():
        if stored_merged(row):     # a token's kv heads: consecutive rows
            row = (block_size * row[0], *row[1:])
        else:
            row = (block_size, *row)
        cache[name] = jnp.zeros(
            (n_attn, num_blocks, *row),
            kv_store_dtype(quant_kv) if quantized else dtype,
            device=kv_sharding)
    if quantized:
        for name, row in ops.pool_rows.items():
            cache[name + "_scale"] = jnp.zeros(
                (n_attn, num_blocks, row[0]), jnp.float32,
                device=scale_sharding)
    for name, row in ops.slot_rows.items():
        cache[name] = jnp.zeros((n_attn, max_batch, *row), dtype)
    for name, (row, state_dtype) in ops.state_rows.items():
        cache[name] = jnp.zeros(
            (ops.layers_of("recurrent"), max_batch, *row), state_dtype)
    cache["len"] = jnp.zeros((max_batch,), jnp.int32, device=len_sharding)
    return cache


class BlockAllocator:
    """Host-side free list over the pool's block ids.

    Block 0 is never handed out: idle slots' table rows are all-zero and
    the decode scatter still writes their (masked, garbage) row somewhere —
    block 0 is that scratch target, so it must never back live data."""

    def __init__(self, num_blocks: int):
        self._free = list(range(1, num_blocks))

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[list[int]]:
        if n > len(self._free):
            return None
        out = self._free[:n]
        del self._free[:n]
        return out

    def free(self, ids) -> None:
        self._free.extend(int(i) for i in ids)


def blocks_for(tokens: int, block_size: int) -> int:
    return -(-tokens // block_size)


class _RadixNode:
    """One cached KV block: the edge from its parent is the block's token
    tuple, so a root-path spells a block-aligned prompt prefix."""

    __slots__ = ("parent", "key", "children", "block", "tick")

    def __init__(self, parent, key, block, tick):
        self.parent = parent
        self.key = key
        self.children: dict[tuple, "_RadixNode"] = {}
        self.block = block
        self.tick = tick


class RadixPrefixCache:
    """Refcount-aware radix tree over FULL KV blocks (the vLLM/SGLang
    radix-attention role). Each node owns one pool block whose KV is a
    pure function of (tokens, positions, params); matching walks token
    tuples from the root, so only identical prefixes at identical
    positions share. Eviction is LRU over unpinned LEAVES — a node with
    live descendants (or a nonzero refcount, tracked by the owner) can
    never be unlinked, which makes stale partial chains structurally
    impossible (the flaw the old flat hash map had to heal by hand)."""

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._root = _RadixNode(None, None, None, 0)
        self._by_block: dict[int, _RadixNode] = {}
        self._tick = 0
        self.evictions = 0

    def _keys(self, prompt) -> list[tuple]:
        bs = self.block_size
        return [tuple(int(t) for t in prompt[k * bs:(k + 1) * bs])
                for k in range(len(prompt) // bs)]

    def __len__(self) -> int:
        return len(self._by_block)

    def __contains__(self, block: int) -> bool:
        return block in self._by_block

    def blocks(self) -> set:
        return set(self._by_block)

    def match(self, prompt) -> list[int]:
        """Block ids of the longest cached block-aligned prefix of
        ``prompt`` (LRU-touching the whole path)."""
        node, out = self._root, []
        for key in self._keys(prompt):
            child = node.children.get(key)
            if child is None:
                break
            self._tick += 1
            child.tick = self._tick
            out.append(child.block)
            node = child
        return out

    def insert(self, prompt, blocks, n_blocks: Optional[int] = None) -> list:
        """Publish ``blocks[k]`` as the cached KV for prompt block k, for
        every FULL block (or the first ``n_blocks``). Existing nodes are
        walked through unchanged — a concurrent publisher keeps the first
        registration and the caller's copy stays private. Returns the
        block ids actually registered."""
        keys = self._keys(prompt)
        if n_blocks is not None:
            keys = keys[:n_blocks]
        node, registered = self._root, []
        for k, key in enumerate(keys):
            if k >= len(blocks):
                break
            child = node.children.get(key)
            if child is None:
                blk = int(blocks[k])
                if blk in self._by_block:
                    break          # one node per block, ever
                self._tick += 1
                child = _RadixNode(node, key, blk, self._tick)
                node.children[key] = child
                self._by_block[blk] = child
                registered.append(blk)
            node = child
        return registered

    def evictable_count(self, refs: dict) -> int:
        """Nodes reclaimable under ``refs`` pins: a node counts iff its
        whole subtree is unpinned (leaves-first eviction can reach it)."""
        def rec(node):
            cnt, ok_all = 0, True
            for c in node.children.values():
                c_cnt, c_ok = rec(c)
                cnt += c_cnt
                ok_all = ok_all and c_ok
            if node is self._root:
                return cnt, True
            ok = ok_all and refs.get(node.block, 0) == 0
            return cnt + (1 if ok else 0), ok
        return rec(self._root)[0]

    def evict_lru(self, n: int, refs: dict) -> list[int]:
        """Unlink up to ``n`` unpinned leaves, LRU-first (evicting a leaf
        may expose its parent as the next candidate). Pinned blocks and
        interior nodes are untouchable. One scan seeds a tick-ordered
        heap; exposed parents push locally — O(N log N) per call, not
        O(n*N) rescans in the admission hot path."""
        import heapq

        heap = [(node.tick, blk) for blk, node in self._by_block.items()
                if not node.children and refs.get(blk, 0) == 0]
        heapq.heapify(heap)
        freed: list[int] = []
        while heap and len(freed) < n:
            tick, blk = heapq.heappop(heap)
            node = self._by_block.get(blk)
            if (node is None or node.children or node.tick != tick
                    or refs.get(blk, 0) > 0):
                continue                       # stale heap entry
            parent = node.parent
            del parent.children[node.key]
            del self._by_block[blk]
            freed.append(blk)
            self.evictions += 1
            if (parent is not self._root and not parent.children
                    and refs.get(parent.block, 0) == 0):
                heapq.heappush(heap, (parent.tick, parent.block))
        return freed


@dataclasses.dataclass
class PagedKV:
    """The engine-facing bundle: pool dict + host block tables/allocator,
    with automatic prefix caching (the vLLM APC role) through a
    refcounted RADIX tree: full prompt blocks are keyed by their token
    tuples along the root path (position-dependence from tree depth) and
    shared across requests by refcount. Shared blocks are never rewritten
    — the KV inside is a pure function of (tokens, positions, params).
    When a block's refcount hits zero it stays cached and LRU-evictable
    (leaves first) until the pool needs it back. Chunked prefills
    participate too: they share cached prefixes at reserve time (with
    ``defer_publish=True``) and publish completed read-only blocks chunk
    by chunk via ``publish_prompt_blocks``."""

    cfg: Any                         # a config with ``paged_ops()``
    max_batch: int
    max_seq: int
    block_size: int
    num_blocks: int
    prefix_cache: bool = True
    kv_sharding: object = None       # NamedSharding for the pool k/v
    len_sharding: object = None
    quant_kv: str = "none"           # "none" | "int8" | "fp8_e4m3"
    scale_sharding: object = None    # NamedSharding for k_scale/v_scale

    def __post_init__(self):
        self.cache = init_paged_cache(
            self.cfg, self.max_batch, self.max_seq, self.block_size,
            self.num_blocks, kv_sharding=self.kv_sharding,
            len_sharding=self.len_sharding, quant_kv=self.quant_kv,
            scale_sharding=self.scale_sharding)
        self.max_blocks_per_seq = self.max_seq // self.block_size
        self.tables = np.zeros(
            (self.max_batch, self.max_blocks_per_seq), np.int32)
        self.allocator = BlockAllocator(self.num_blocks)
        self._slot_blocks: dict[int, list[int]] = {}
        # prefix cache state
        self._ref: dict[int, int] = {}              # block -> live users
        self.radix = RadixPrefixCache(self.block_size)
        self.prefix_hits = 0                        # blocks shared
        self.prefix_queries = 0                     # full blocks looked up

    def _alloc_evicting(self, n: int):
        """Allocator alloc with LRU eviction of unpinned cached blocks.
        A doomed allocation (free + evictable < n) returns None WITHOUT
        evicting: a head-of-line request retrying every step must not
        flush everyone else's prefix cache for nothing."""
        ids = self.allocator.alloc(n)
        if ids is not None:
            return ids
        if (self.allocator.free_blocks
                + self.radix.evictable_count(self._ref)) < n:
            return None
        self.allocator.free(self.radix.evict_lru(
            n - self.allocator.free_blocks, self._ref))
        return self.allocator.alloc(n)

    # ---- host-side scheduling ----

    def reserve(self, slot: int, prompt_len: int, max_tokens: int,
                min_blocks: int = 0, prompt=None,
                defer_publish: bool = False) -> Optional[int]:
        """Reserve every block the request can ever touch (prompt + all
        generated tokens) so decode never exhausts the pool mid-flight.
        With ``prompt`` tokens and prefix caching on, the longest cached
        block-aligned prefix is SHARED (refcounted) instead of
        reallocated. Returns the number of shared prefix blocks, or None
        if the pool cannot satisfy the reservation. ``min_blocks`` lets
        prefill demand bucket-coverage. ``defer_publish`` (chunked
        prefill) skips registering the private full-prompt blocks — their
        content lands over FUTURE steps, so the engine publishes them
        chunk by chunk instead (a premature match would read garbage)."""
        need = max(blocks_for(prompt_len + max_tokens, self.block_size),
                   min_blocks)
        need = min(need, self.max_blocks_per_seq)
        shared: list[int] = []
        n_full = 0
        if self.prefix_cache and prompt is not None:
            n_full = len(prompt) // self.block_size
            self.prefix_queries += n_full
            shared = self.radix.match(prompt)
            for blk in shared:
                # refcount BEFORE any allocation below: eviction skips
                # referenced blocks, so the allocator can never hand a
                # shared block back out as someone's private block
                self._ref[blk] = self._ref.get(blk, 0) + 1
        private = self._alloc_evicting(need - len(shared))
        if private is None:
            for blk in shared:          # roll the refcounts back
                self._ref[blk] -= 1
                if self._ref[blk] <= 0:
                    self._ref.pop(blk, None)
            return None
        self.prefix_hits += len(shared)
        for blk in private:
            self._ref[blk] = self._ref.get(blk, 0) + 1
        ids = shared + private
        if (self.prefix_cache and prompt is not None
                and not defer_publish):
            # private blocks holding FULL prompt blocks become cacheable:
            # after this step's prefill-insert they contain exactly the
            # keyed content, ordered before any later sharer's reads
            self.radix.insert(prompt, ids, n_blocks=n_full)
        self._slot_blocks[slot] = ids
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        row[:len(ids)] = ids
        self.tables[slot] = row
        return len(shared)

    def publish_prompt_blocks(self, slot: int, prompt,
                              upto_tokens: int) -> int:
        """Chunked-prefill publication: register this slot's blocks whose
        content is complete (every position < ``upto_tokens`` written and
        dispatched) as shareable read-only radix nodes. Safe mid-prefill
        and after an abort — the published KV is already valid."""
        if not self.prefix_cache:
            return 0
        ids = self._slot_blocks.get(slot)
        if not ids:
            return 0
        n = min(int(upto_tokens), len(prompt)) // self.block_size
        return len(self.radix.insert(prompt, ids, n_blocks=n))

    def release(self, slot: int) -> None:
        ids = self._slot_blocks.pop(slot, None)
        for blk in ids or []:
            self._ref[blk] = self._ref.get(blk, 1) - 1
            if self._ref[blk] <= 0:
                self._ref.pop(blk, None)
                if blk in self.radix:
                    continue    # stays cached + evictable, not free-listed
                self.allocator.free([blk])
        self.tables[slot] = 0

    @property
    def reclaimable_blocks(self) -> int:
        """Free-list blocks plus cached blocks eviction could reach."""
        return (self.allocator.free_blocks
                + self.radix.evictable_count(self._ref))

    def cached_block_ids(self) -> set:
        return self.radix.blocks()

    def slot_blocks(self, slot: int) -> list[int]:
        return list(self._slot_blocks.get(slot, []))


# -------------------------------------------------- KV block migration ----
# Device<->host movers for disaggregated serving (serving/disagg.py): a
# finished prefill's pool blocks leave the prefill pod as host numpy and
# land in a (different) decode pod's pool. Both sides pad the id list to
# the next power of two so the compile count stays log-bounded in blocks
# per request; pad ids are block 0 — the scratch block whose content is
# garbage by contract — so the extra gather rows are discarded on the
# host and the extra scatter writes land where writes are already allowed.

def _pool_keys(cache: dict) -> tuple:
    """The pools and their scale tables: everything but the lengths."""
    return tuple(k for k in cache if k != "len")


@functools.partial(jax.jit, static_argnames=("keys",))
def _gather_pools(cache, idx, keys):
    return {key: jnp.take(cache[key], idx, axis=1) for key in keys}


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("keys",))
def _scatter_pools(cache, idx, blocks, keys):
    new = dict(cache)
    for key in keys:
        new[key] = cache[key].at[:, idx].set(
            blocks[key].astype(cache[key].dtype))
    return new


def _pad_pow2(ids) -> tuple:
    n = len(ids)
    m = 1 << max(0, (n - 1).bit_length())
    idx = np.zeros((max(1, m),), np.int32)
    idx[:n] = ids
    return idx, n


def gather_kv_blocks(cache: dict, ids) -> dict:
    """Fetch pool blocks ``ids`` to host numpy — [L, n, bs, KV, D] per
    pool (plus [L, n, KV] scale tables when the pool is quantized: the
    payload migrates at the pool's stored bytes, int8 KV ships as
    int8)."""
    idx, n = _pad_pow2(ids)
    keys = _pool_keys(cache)
    out = jax.device_get(_gather_pools(cache, jnp.asarray(idx), keys))
    return {key: np.asarray(v)[:, :n] for key, v in out.items()}


def scatter_kv_blocks(cache: dict, ids, blocks: dict) -> dict:
    """Write migrated block payloads into pool blocks ``ids`` and return
    the new cache dict (pools are donated — no full-pool copy survives).
    ``blocks`` is ``gather_kv_blocks`` output, possibly sliced on axis 1
    to drop radix-shared prefix blocks the destination already holds."""
    if not len(ids):
        return cache
    idx, n = _pad_pow2(ids)
    keys = tuple(k for k in _pool_keys(cache) if k in blocks)
    pay = {}
    for key in keys:
        b = np.asarray(blocks[key])
        if len(idx) > n:
            pad = np.zeros((b.shape[0], len(idx) - n) + b.shape[2:],
                           b.dtype)
            b = np.concatenate([b, pad], axis=1)
        pay[key] = b
    return _scatter_pools(cache, jnp.asarray(idx), pay, keys)


# ------------------------------------------- the program's own text ----
# What says that the pool is updated in place is the compiled program: an
# operation whose result is as large as the pool (or as one layer of it)
# moves that many bytes every step, whatever the requests need.

# "%name = type[dims]{layout} opcode(%operand, ...), attributes"; results
# of tuple type (while, tuple) match nothing and are no buffers of their own
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[([\d,]*)\]\S*) ([\w\-]+)\(([^)]*)\)(.*)$")
# names for, or views of, a buffer that exists
_HLO_NO_DATA = ("parameter", "get-tuple-element", "bitcast")
_HLO_WRITES = ("scatter", "dynamic-update-slice")


def pool_shaped_ops(hlo_text: str, pool_shapes) -> list:
    """The instructions of a compiled program (``compiled.as_text()``)
    that produce a buffer as large as a KV pool or as one layer of it:
    [(name, opcode, result type)], empty for a program that updates the
    pool in place. ``pool_shapes``: the pools' shapes as one device holds
    them, layers first, e.g. ``[cache["k"].shape]``.

    Not listed: parameters, tuples and their elements, ``while``,
    ``bitcast`` (names for a buffer that exists); a ``scatter`` or
    ``dynamic-update-slice`` (or a fusion around one) whose ONLY
    pool-sized operand is the buffer it updates — XLA runs those in
    place, and one that takes a second pool-sized operand is writing a
    layer's pool into a stacked copy; what is inside a fusion (it never
    reaches memory). A kernel's custom call reads the pool as an operand
    and returns a step's rows, so it is never pool-shaped either. Sizes
    are compared as element counts, whatever the dimensions are merged
    or split into."""
    sizes = set()
    for shape in pool_shapes:
        n = int(np.prod(shape))
        sizes.update((n, n // int(shape[0])))
    # every pool-sized instruction by computation; the fusions' bodies
    comps: dict[str, list] = {}
    fused: dict[str, str] = {}              # fusion instruction -> its body
    pool_sized, cur = set(), None
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and " = " not in line:
            cur = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            comps[cur] = []
        m = _HLO_INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        name, rtype, dims, opcode, operands, attrs = m.groups()
        if opcode == "fusion":
            fused[name] = re.search(r"calls=%([\w.\-]+)", attrs).group(1)
        if int(np.prod([int(d) for d in dims.split(",") if d])) in sizes:
            pool_sized.add(name)
            comps[cur].append(
                (name, rtype, opcode, re.findall(r"%([\w.\-]+)", operands)))
    bodies = set(fused.values())
    found = []
    for cname, body in comps.items():
        if cname in bodies:
            continue
        for name, rtype, opcode, operands in body:
            if opcode in _HLO_NO_DATA:
                continue
            writes = opcode in _HLO_WRITES or any(
                i[2] in _HLO_WRITES for i in comps.get(fused.get(name), ()))
            if writes and sum(o in pool_sized for o in operands) == 1:
                continue
            found.append((name, opcode, rtype))
    return found


# ------------------------------------------------------------ jitted bodies

def _block_size(ops: PagedOps, cache) -> int:
    """Tokens a pool block holds."""
    name, row = next(iter(ops.pool_rows.items()))
    return cache[name].shape[2] // (row[0] if stored_merged(row) else 1)


def _scatter_rows(pools, layer, blk, off, rows):
    """This step's rows (``{pool: [..., *row]}``) into layer ``layer`` of
    the carried pools at (blk, off); quantize-on-write where the pool is
    quantized (``k_scale`` present). A pool stored merged
    (``models/paged.stored_merged``) takes a token's kv heads as
    consecutive rows of its block. Returns the updated pools dict."""
    if "k_scale" in pools:
        k_pool, k_sc = quant_scatter_rows(pools["k"], pools["k_scale"],
                                          layer, blk, off, rows["k"])
        v_pool, v_sc = quant_scatter_rows(pools["v"], pools["v_scale"],
                                          layer, blk, off, rows["v"])
        return {"k": k_pool, "v": v_pool, "k_scale": k_sc, "v_scale": v_sc}

    def put(pool, val):
        if not stored_merged(val.shape[blk.ndim:]):
            return pool.at[layer, blk, off].set(val.astype(pool.dtype))
        kvh = val.shape[-2]
        row = off[..., None] * kvh + jnp.arange(kvh)
        return pool.at[layer, jnp.broadcast_to(blk[..., None], row.shape),
                       row].set(val.astype(pool.dtype))
    return {**pools, **{key: put(pools[key], val)
                        for key, val in rows.items()}}


def _scan_layers(ops: PagedOps, params, x, cache, layer_fn,
                 recurrent_fn=None):
    """Run ``layer_fn(lp, x, extra, pools, layer) -> (x, extra, pools,
    stats)`` over the layers with the layer index and the layer's weights
    scanned and the pools (the scale tables of a quantized pool and a
    model's per-slot rows with them) in the CARRY, so each layer's rows are
    scattered into the one buffer that came in. ``extra`` is what the
    model's layers hand one another beside ``x``
    (``PagedOps.layer_carry(x)`` as it enters the first layer, ``{}`` for a
    model that has none: an empty carry adds nothing to the loop). A model
    whose layers are of several kinds has one stack per kind
    (``ops.layer_stacks``): the stacks are scanned one after the other,
    the layer index running on, the same carry through all of them.
    Returns (x, extra, pools, stats) with each stat stacked over the
    layers that report it.

    A stack comes as ``(stacked tree, whole)``: the weights named in
    ``whole`` are NOT scanned but handed to every layer as the stack they
    are, with the layer's place in it as ``stack_index`` — for weights a
    kernel addresses by (layer, group) itself, which a scan would slice
    out, a copy, layer by layer.

    A model of two layer kinds (``PagedOps.period``) has one stack of
    periods: a step of the scan runs the period's layers in published
    order, each by the function of its kind (``layer_fn`` for attention,
    ``recurrent_fn`` for a recurrent layer) and with its index among the
    layers of that kind, the index of its rows in the arrays that kind
    owns. Its stats come back in the order of all layers."""
    pools = {key: cache[key] for key in _pool_keys(cache)}
    extra = ops.layer_carry(x) if ops.layer_carry else {}
    first, stats = 0, {}
    fns = {"attention": layer_fn, "recurrent": recurrent_fn}
    for stack, whole in ops.layer_stacks(params):
        kept = {key: stack[key] for key in whole}
        scanned = {key: val for key, val in stack.items() if key not in whole}
        n = jax.tree.leaves(scanned)[0].shape[0]
        xs = (first + jnp.arange(n), scanned)
        if kept:
            xs += (jnp.arange(n),)

        def body(carry, xs, kept=kept):
            layer, lp = xs[:2]
            if kept:
                lp = dict(lp, **kept, stack_index=xs[2])
            if ops.period:
                return _period(ops, fns, lp, carry, layer)
            *carry, ys = layer_fn(lp, *carry, layer)
            return tuple(carry), ys

        (x, extra, pools), ys = jax.lax.scan(body, (x, extra, pools), xs)
        first += n
        for key, val in ys.items():
            if ops.period:        # [periods, layers a period, ...]
                val = val.reshape(-1, *val.shape[2:])
            stats[key] = (val if key not in stats
                          else jnp.concatenate([stats[key], val]))
    return x, extra, pools, stats


def _period(ops: PagedOps, fns, lp, carry, p):
    """One period of layers (``_scan_layers``): ``p`` is the period's
    index, a layer's index among those of its kind is ``p`` times the kind's
    count a period plus the kind's layers before it in the period."""
    per = {kind: ops.period.count(kind) for kind in ops.period}
    before = dict.fromkeys(per, 0)
    ys = []
    for j, kind in enumerate(ops.period):
        *carry, y = fns[kind](ops.period_layer(lp, j), *carry,
                              p * per[kind] + before[kind])
        before[kind] += 1
        ys.append(y)
    return tuple(carry), {key: jnp.stack([y[key] for y in ys])
                          for key in ys[0]}


def paged_insert_batch(cache, k_new, v_new, blk_ids, lengths, slots):
    """Batched prefill insert: all admitted requests' KV lands in ONE
    scatter.

    k_new/v_new: [L, B, T, KV, D] with T == blk_ids.shape[1] * block_size;
    blk_ids: [B, nb] pool destinations where id 0 means "skip this block"
    (already-resident shared prefix blocks and pad regions — the scratch
    block absorbs those writes); lengths/slots: [B] with slot < 0 marking
    an inert pad row (its length write is redirected harmlessly).

    Quantized pools (``k_scale`` in cache) quantize-on-insert: per-block
    per-kv-head amax over the incoming rows -> scale, values round/clip
    into the storage dtype, scales scatter beside the pool. Rows past
    each request's ``lengths`` are zeroed FIRST so pad garbage can never
    inflate a final block's scale (pad rows are never attended)."""
    L = cache["k"].shape[0]
    bs = cache["k"].shape[2]
    b, nb = blk_ids.shape
    if "k_scale" in cache:
        qmax = kv_qmax(cache["k"].dtype)
        t = k_new.shape[2]
        live = (jnp.arange(t)[None, :]
                < lengths[:, None])[None, :, :, None, None]
        kb = jnp.where(live, k_new, 0).astype(jnp.float32).reshape(
            L, b, nb, bs, *k_new.shape[3:])
        vb = jnp.where(live, v_new, 0).astype(jnp.float32).reshape(
            L, b, nb, bs, *v_new.shape[3:])
        ks = jnp.max(jnp.abs(kb), axis=(3, 5)) / qmax    # [L, B, nb, KV]
        vs = jnp.max(jnp.abs(vb), axis=(3, 5)) / qmax
        ksafe = jnp.maximum(ks, 1e-30)[:, :, :, None, :, None]
        vsafe = jnp.maximum(vs, 1e-30)[:, :, :, None, :, None]
        kq = kv_store(jnp.where(ksafe > 1e-30, kb / ksafe, 0.0),
                      cache["k"].dtype)
        vq = kv_store(jnp.where(vsafe > 1e-30, vb / vsafe, 0.0),
                      cache["v"].dtype)
        k = cache["k"].at[:, blk_ids].set(kq)
        v = cache["v"].at[:, blk_ids].set(vq)
        k_scale = cache["k_scale"].at[:, blk_ids].set(ks)
        v_scale = cache["v_scale"].at[:, blk_ids].set(vs)
        slots_drop = jnp.where(slots >= 0, slots, cache["len"].shape[0])
        ln = cache["len"].at[slots_drop].set(lengths, mode="drop")
        return {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale,
                "len": ln}
    kb = k_new.reshape(L, b, nb, bs, *k_new.shape[3:]).astype(
        cache["k"].dtype)
    vb = v_new.reshape(L, b, nb, bs, *v_new.shape[3:]).astype(
        cache["v"].dtype)
    k = cache["k"].at[:, blk_ids].set(kb)
    v = cache["v"].at[:, blk_ids].set(vb)
    # pad rows: redirect to an out-of-range index and drop the write (a
    # "safe" in-range redirect could collide with a real row's slot)
    slots_drop = jnp.where(slots >= 0, slots, cache["len"].shape[0])
    ln = cache["len"].at[slots_drop].set(lengths, mode="drop")
    return {"k": k, "v": v, "len": ln}


def _resolve_decode_kernel(kernel: str) -> str:
    """Map the ``kernel=`` switch to an executable path on this backend.
    "auto": pallas on TPU, gather elsewhere. An explicit "pallas" request
    holds on TPU and CPU (interpret mode); other platforms (gpu) fall
    back to gather, mirroring ops/attention.py's impl dispatch."""
    return resolve_decode_kernel(kernel)[0]


def resolve_decode_kernel(kernel: str, mesh=None,
                          n_kv_heads: Optional[int] = None,
                          platform: Optional[str] = None):
    """Full kernel resolution -> (resolved, downgrade_reason).

    "auto": pallas on TPU — INCLUDING under a mesh, via the shard_map'd
    kernel (paged_decode_attention_sharded) — gather elsewhere. An
    explicit "pallas" holds on TPU and CPU (interpret mode). A downgrade
    the caller did not ask for (gpu platform, or a mesh topology the
    shard_map wrapper can't partition) returns the reason so the engine
    can COUNT and log it (kft_model_kernel_downgrades_total) instead of
    silently losing the block-resident path's bandwidth."""
    from kubeflow_tpu.ops.pallas_paged_attention import (
        shard_unsupported_reason,
    )

    if kernel not in ("auto", "pallas", "gather"):
        raise ValueError(f"kernel={kernel!r} (want auto|pallas|gather)")
    platform = platform or jax.default_backend()
    if kernel == "gather":
        return "gather", None
    if kernel == "auto":
        resolved = "pallas" if platform == "tpu" else "gather"
    else:
        if platform not in ("tpu", "cpu"):
            return "gather", (f"kernel='pallas' has no {platform} path "
                              "(mosaic is TPU-only; CPU runs interpret "
                              "mode)")
        resolved = "pallas"
    if resolved == "pallas" and mesh is not None:
        reason = shard_unsupported_reason(
            mesh, n_kv_heads if n_kv_heads is not None else 0)
        if reason is not None:
            return "gather", reason
    return resolved, None


def paged_decode_step(params, token, cfg, cache, tables,
                      kernel: str = "gather", mesh=None, active=None):
    """One decode step over the paged pool. token: [B] int32; tables:
    [B, max_blocks_per_seq] int32 -> (logits [B, V], cache, stats: the
    layers' counts from ``PagedOps.out``, ``{}`` for a dense model). The
    pools ride the layer loop as a carry and are updated in place (module
    docstring): donate ``cache`` and nothing pool-sized moves. ``kernel``
    picks the attention path: "gather" | "pallas" | "auto"; with ``mesh``
    the pallas path runs shard_map'd over the heads/KV tensor axis
    (per-shard pool blocks, replicated tables). ``cfg`` is any config
    with a ``paged_ops()`` method.

    Per-slot rows (``PagedOps.slot_rows``): a layer reads what the slot's
    previous token left and the step's one token leaves its own, in
    place. A slot that holds no sequence (``len`` 0) or is not ``active``
    ([B] bool: the engine's dispatch mask; a slot freed or mid-prefill
    whose ``len`` is stale for one more step) writes nothing: its row must
    stay what its next chunk or step expects. The same holds for a
    recurrent layer's state (``PagedOps.state_rows``), which the model's
    ``recurrent_decode`` updates in place for the live slots alone."""
    ops = paged_ops(cfg)
    kernel, _ = resolve_decode_kernel(kernel, mesh=mesh,
                                      n_kv_heads=cfg.n_kv_heads)
    interpret = jax.default_backend() == "cpu"
    b = token.shape[0]
    bs = _block_size(ops, cache)
    pos = cache["len"]                                   # [B]
    positions = pos[:, None]
    x = ops.embed(params, token[:, None])

    batch = jnp.arange(b)
    blk = tables[batch, pos // bs]                       # [B] dest block
    off = pos % bs                                       # [B] row in block
    # idle slots hold len 0: keep their garbage rows out of expert routing
    token_mask = (pos > 0)[:, None]
    live = token_mask
    if (ops.slot_rows or ops.state_rows) and active is not None:
        live = token_mask & active[:, None]

    def layer_fn(lp, x, extra, pools, layer):
        state = {name: pools[name][layer] for name in ops.slot_rows}
        q, rows, left = ops.qkv(lp, x, positions, state)
        # scatter this step's row into each slot's current block
        pools = _scatter_rows(pools, layer, blk, off,
                              {key: r[:, 0] for key, r in rows.items()})
        for name, val in left.items():
            pools[name] = pools[name].at[layer].set(
                jnp.where(live, val[:, 0], state[name]))
        o = ops.decode_attention(lp, q, pools, layer, tables, pos + 1,
                                 kernel, mesh, interpret)
        x, extra, stats = ops.out(lp, x, o, token_mask, extra)
        return x, extra, pools, stats

    def recurrent_fn(lp, x, extra, pools, layer):
        o, states = ops.recurrent_decode(
            lp, x, positions, {name: pools[name] for name in ops.state_rows},
            layer, live[:, 0], kernel, interpret)
        x, extra, stats = ops.out(lp, x, o, token_mask, extra)
        return x, extra, {**pools, **states}, stats

    x, _, pools, stats = _scan_layers(ops, params, x, cache, layer_fn,
                                      recurrent_fn)
    logits = ops.head(params, x[:, 0])
    cache = {**pools, "len": cache["len"] + 1}
    return logits, cache, stats


def paged_prefill_chunk(params, tokens, cfg, cache,
                        tables, slot, offset, length, share_len=0):
    """Chunked prefill straight into the paged pool (vLLM chunked-prefill
    role): processes `tokens` [1, C] as positions offset..offset+C-1 of
    `slot`'s sequence, attending to everything the slot's blocks already
    hold. Prompts longer than any prefill bucket (up to max_seq), and every
    prompt of a model without ``PagedOps.bucket_prefill``, stream through
    in fixed-size chunks, so the compile count stays O(1) in prompt length
    (offset/length are traced).

    Rows at positions >= `length` (the final chunk's padding) scatter to
    block 0 — the pool's scratch block — never into live data; so do rows
    at positions < `share_len` (a radix-shared prefix): their KV is
    ALREADY resident in shared read-only blocks, which must never be
    rewritten while other slots read them (the re-computed values are
    bit-identical, so attention over the view stays exact either way).
    Returns (x_last [1, D]: the PRE-final-norm hidden state at the
    chunk's last TRUE row — the head applies final_norm; the caller runs
    it ONCE on the final chunk's value rather than paying a full-vocab
    matmul per chunk — the updated cache, and the layers' counts as in
    ``paged_decode_step``, an expert model's choices ``experts`` at every
    row of the chunk [layers, C, k]: a pad row's mean nothing).
    cache["len"] for the slot is NOT advanced
    here; the engine sets it once after the last chunk (decode masks by
    len, so partial writes stay invisible).

    Per-slot rows (``PagedOps.slot_rows``): the chunk's first token reads
    what the slot's previous chunk left, and ZEROS at ``offset`` 0, inside
    the program, so admission resets nothing on the host and a reused slot
    never sees its predecessor; the chunk's last TRUE row leaves the slot's
    new rows, pad rows past ``length`` nothing. A recurrent layer's state
    (``PagedOps.state_rows``) is read and left the same way: the model's
    ``recurrent`` returns it at the chunk's last true row."""
    ops = paged_ops(cfg)
    _, c = tokens.shape
    bs = _block_size(ops, cache)
    pos = offset + jnp.arange(c)                          # [C] absolute
    valid = pos < length
    # destination rows: real rows land in the slot's table blocks; pad
    # rows and shared-prefix rows land in scratch block 0 (row p % bs —
    # garbage / duplicate values, never read)
    blk = jnp.where(
        valid & (pos >= share_len),
        tables[slot, jnp.clip(pos // bs, 0, tables.shape[1] - 1)],
        0)
    off = pos % bs
    positions = pos[None, :]
    x = ops.embed(params, tokens)
    q_start = jnp.reshape(offset, (1,))
    last_row = jnp.clip(length - offset - 1, 0, c - 1)

    def layer_fn(lp, x, extra, pools, layer):
        state = {name: jnp.where(offset > 0, pools[name][layer, slot], 0)[None]
                 for name in ops.slot_rows}
        q, rows, left = ops.qkv(lp, x, positions, state)
        pools = _scatter_rows(pools, layer, blk, off,
                              {key: r[0] for key, r in rows.items()})
        for name, val in left.items():
            pools[name] = pools[name].at[layer, slot].set(val[0, last_row])
        o = ops.chunk_attention(lp, q, pools, layer, tables[slot][None],
                                q_start)
        x, extra, stats = ops.out(lp, x, o, valid[None, :], extra)
        return x, extra, pools, stats

    def recurrent_fn(lp, x, extra, pools, layer):
        # the slot's state gathered and scattered one leading row a window,
        # as the decode step writes it: a [rows, width] window at once has
        # XLA lay an array of few wide rows out anew, twice a call
        def at(name):
            n = ops.state_rows[name][0][0]
            return layer, jnp.full((n,), slot), jnp.arange(n)

        state = {name: jnp.where(offset > 0, pools[name][at(name)], 0)[None]
                 for name in ops.state_rows}
        o, left = ops.recurrent(lp, x, positions, state, valid[None, :])
        pools = {**pools, **{name: pools[name].at[at(name)].set(val[0])
                             for name, val in left.items()}}
        x, extra, stats = ops.out(lp, x, o, valid[None, :], extra)
        return x, extra, pools, stats

    x, _, pools, stats = _scan_layers(ops, params, x, cache, layer_fn,
                                      recurrent_fn)
    cache = {**pools, "len": cache["len"]}
    if "experts" in stats:       # [layers, 1, C, k] -> every row's choice
        stats["experts"] = stats["experts"][:, 0]
    return x[:, last_row], cache, stats


def paged_verify_step(params, tokens, cfg, cache, tables, limit):
    """Batched multi-token target step for speculative decoding: ONE
    dispatch scores ``S`` candidate positions per slot (vLLM/Medusa
    verify role). tokens: [B, S] int32 where column 0 is the slot's last
    committed token and columns 1.. are drafter proposals; row s of slot
    b lands at position ``cache['len'][b] + s`` (the same "input token's
    KV is written this step" convention the decode step uses), and
    logits[b, s] predicts position len+s+1. limit: [B] int32 — tokens
    the slot's reserved blocks can hold; rows at/after it (a draft tail
    running past the allocation, or an idle/mid-prefill slot with
    limit 0) scatter to the scratch block exactly like mid-prefill pad
    rows, never into live data.

    Rejected-tail KV rows need no cleanup: the NEXT dispatch (verify or
    plain decode) starts at the committed length and rewrites every
    rejected position before attention can see it — its queries attend
    kv positions <= their own, and all its writes cover [len, len+S).
    cache["len"] is NOT advanced here; the engine commits the accepted
    length host-side after comparing drafts against the argmax chain.

    Attention uses the model's chunk form with per-slot causal offsets
    (the only multi-query-row path; S is tiny, so this step is compute-
    shaped like a short prefill, not the bandwidth-bound single-row
    decode the pallas kernel exists for) — under a mesh XLA
    auto-partitions it like the chunked-prefill program.

    A model with per-slot rows has no verify: a rejected draft's rows sit
    beyond ``len`` and are rewritten, but what it left per slot would have
    to be rewound.

    Returns (logits [B, S, V] f32, cache)."""
    ops = paged_ops(cfg)
    if ops.slot_rows or ops.state_rows:
        raise ValueError(f"{type(cfg).__name__} keeps per-slot rows "
                         f"{sorted({**ops.slot_rows, **ops.state_rows})}: "
                         "the verify step cannot rewind them past a "
                         "rejected draft")
    b, s = tokens.shape
    bs = _block_size(ops, cache)
    start = cache["len"]                                   # [B]
    pos = start[:, None] + jnp.arange(s)[None, :]          # [B, S] absolute
    valid = pos < limit[:, None]
    batch = jnp.arange(b)
    blk = jnp.where(
        valid,
        tables[batch[:, None],
               jnp.clip(pos // bs, 0, tables.shape[1] - 1)],
        0)
    off = pos % bs
    x = ops.embed(params, tokens)                          # [B, S, D]

    def layer_fn(lp, x, extra, pools, layer):
        q, rows, _ = ops.qkv(lp, x, pos, {})
        # duplicate blk entries (several rows of one slot's block in a
        # single verify) are safe in a quantized pool: quant_scatter_rows
        # folds their amaxes via scatter-max before any content write
        pools = _scatter_rows(pools, layer, blk, off, rows)
        # per-slot query offsets: row s (position start[b]+s) attends kv
        # rows <= start[b]+s — this step's own earlier rows included,
        # every stale/rejected row beyond them masked
        o = ops.chunk_attention(lp, q, pools, layer, tables, start)
        x, extra, stats = ops.out(lp, x, o, valid, extra)
        return x, extra, pools, stats

    x, _, pools, _ = _scan_layers(ops, params, x, cache, layer_fn)
    logits = ops.head(params, x.reshape(b * s, x.shape[-1])).reshape(b, s, -1)
    return logits, {**pools, "len": cache["len"]}
