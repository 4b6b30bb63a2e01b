"""What a model hands the paged serving programs: ``PagedOps``.

The contract lives with the models so that a model file imports nothing
of the serving layer: ``serving/paged_kv.py`` writes its three programs
(decode, chunked prefill, speculative verify) once over these pieces, and
a config class builds its own in a ``paged_ops()`` method from the layer
pieces its ``forward`` uses (``models/llama.py``, ``models/mla_moe.py``).
The pool array helpers a model's attention may need are in
``ops/paged_pool.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class PagedOps:
    """A model as the paged programs and the engine see it: the pieces of
    one layer around attention, what a token caches, and what the model
    cannot be served with.

    ``pool_rows``: pool name -> the shape of ONE token's row in it; a pool
    is ``[n_layers, num_blocks, block_size, *row]``. ``layer_stacks(params)``:
    the stacked layer trees in order (layers of one kind per stack), each
    with the names of the weights it wants whole (``_scan_layers``); each
    is scanned with the pools in the carry. ``qkv(lp, x, positions)`` ->
    ``(q, {pool: rows [B, S, *row]})``; ``decode_attention(lp, q, pools,
    layer, tables, kv_len, kernel, mesh, interpret)`` -> o of the one new
    row per slot; ``chunk_attention(lp, q, pools, layer, tables,
    q_start)`` -> o of [B, C] rows at positions ``q_start[b] + i``, causal
    over what the slot's blocks hold; ``out(lp, x, o, token_mask)`` ->
    ``(x, stats)`` with ``stats`` a dict of small per-layer counts (empty
    for a dense layer); ``head(params, x_last)`` -> float32 logits from
    the hidden state before the final norm. ``bucket_prefill(params,
    tokens, lengths)`` -> ``(logits [B, V] at each row's last true token,
    {pool: rows [n_layers, B, S, *row]})``: a whole bucket of left-aligned
    prompts in one causal pass, its rows handed to ``paged_insert_batch``;
    None where every prompt streams through ``paged_prefill_chunk``.
    ``routed_per_token``: expert assignments one token makes over all
    layers (0: no experts). ``refuses``: mechanism -> why the engine must
    not be built with it."""

    n_layers: int
    pool_rows: dict
    layer_stacks: Callable
    embed: Callable
    qkv: Callable
    decode_attention: Callable
    chunk_attention: Callable
    out: Callable
    head: Callable
    bucket_prefill: Optional[Callable] = None
    routed_per_token: int = 0
    refuses: dict = dataclasses.field(default_factory=dict)
