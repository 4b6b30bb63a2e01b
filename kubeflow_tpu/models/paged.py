"""What a model hands the paged serving programs: ``PagedOps``.

The contract lives with the models so that a model file imports nothing
of the serving layer: ``serving/paged_kv.py`` writes its three programs
(decode, chunked prefill, speculative verify) once over these pieces, and
a config class builds its own in a ``paged_ops()`` method from the layer
pieces its ``forward`` uses (``models/llama.py``, ``models/mla_moe.py``,
``models/cca_moe.py``).
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
    is scanned with the pools in the carry. ``slot_rows``: name -> the
    shape of ONE slot's row in ONE layer, for what a layer keeps per SLOT
    and not per token (the last token's values that the next token's
    mixing reads); the cache holds ``[n_layers, max_batch, *row]`` beside
    the pools, updated in place like them; empty for a model whose queries
    and keys are functions of one token. ``qkv(lp, x, positions, state)``
    -> ``(q, {pool: rows [B, S, *row]}, {name: [B, S, *row]})``: ``state``
    is ``{name: [B, *row]}``, what the token before row 0 left in this
    layer (zeros before a sequence's first token), and the third result is
    what EACH row would leave; the program keeps the last true row's.
    ``layer_carry(x)`` -> what the layers hand one another beside ``x``
    (a pytree of ``[B, S, ...]`` arrays that no pool holds, as it enters
    the first layer); None: nothing. ``decode_attention(lp, q, pools,
    layer, tables, kv_len, kernel, mesh, interpret)`` -> o of the one new
    row per slot; ``chunk_attention(lp, q, pools, layer, tables,
    q_start)`` -> o of [B, C] rows at positions ``q_start[b] + i``, causal
    over what the slot's blocks hold; ``out(lp, x, o, token_mask, carry)``
    -> ``(x, carry, stats)`` with ``carry`` the layer-to-layer carry (``{}``
    without ``layer_carry``) and ``stats`` a dict of small per-layer counts
    (empty for a dense layer); ``head(params, x_last)`` -> float32 logits from
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
    slot_rows: dict = dataclasses.field(default_factory=dict)
    layer_carry: Optional[Callable] = None
    refuses: dict = dataclasses.field(default_factory=dict)
