"""What a model hands the paged serving programs: ``PagedOps``.

The contract lives with the models so that a model file imports nothing
of the serving layer: ``serving/paged_kv.py`` writes its three programs
(decode, chunked prefill, speculative verify) once over these pieces, and
a config class builds its own in a ``paged_ops()`` method from the layer
pieces its ``forward`` uses (``models/llama.py``, ``models/mla_moe.py``,
``models/cca_moe.py``, ``models/qwen3_next.py``).
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
    not be built with it.

    Layers of two kinds. ``period`` (empty for a model whose every layer
    attends over the pools): the kinds of the layers of ONE step of the
    scan, in published order, each ``"attention"`` or ``"recurrent"``;
    ``layer_stacks`` then gives one stack of periods and
    ``period_layer(lp, j)`` cuts the weights of the period's ``j``-th layer
    out of a step's. Each pool and each state array belongs to the layers
    of one kind and holds a row for those only: ``pool_rows`` (and
    ``slot_rows``) to the attention layers, ``[layers_of("attention"), ...]``,
    the attention layer ``i`` of its kind at index ``i``; ``state_rows``
    (name -> (the shape of one slot's state in one layer, dtype)) to the
    recurrent layers, ``[layers_of("recurrent"), max_batch, *row]``. A
    recurrent layer owns no pool row: its pieces are ``recurrent(lp, x,
    positions, state, valid)`` -> ``(o, left)`` over [B, S] rows from
    ``state`` (``{name: [B, *row]}``, zeros before a sequence's first
    token), ``left`` the state at each slot's last TRUE row (pad rows leave
    it as it was), and ``recurrent_decode(lp, x, positions, states, layer,
    live, kernel, interpret)`` -> ``(o, states)`` for one new row a slot,
    ``states`` the WHOLE arrays, updated in place for the ``live`` [B] slots
    only. ``out`` takes either kind's ``o``. A pool whose row holds several
    kv heads wider than a lane tile is stored merged (``stored_merged``)."""

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
    period: tuple = ()
    period_layer: Optional[Callable] = None
    state_rows: dict = dataclasses.field(default_factory=dict)
    recurrent: Optional[Callable] = None
    recurrent_decode: Optional[Callable] = None

    def layers_of(self, kind: str) -> int:
        """How many layers of ``kind`` the model has: the first index of
        the arrays that belong to that kind."""
        if not self.period:
            return self.n_layers if kind == "attention" else 0
        return self.n_layers // len(self.period) * self.period.count(kind)


def stored_merged(row) -> bool:
    """Whether a pool of token rows ``row`` = (kv heads, head_dim) keeps a
    block's (token, kv head) rows merged, ``[layers, num_blocks, block_size
    * row[0], row[1]]``, token ``t``'s head ``g`` at row ``t * row[0] + g``:
    the matrix the paged decode kernel reads. The 5-D form is that matrix in
    HBM only while a head is one lane tile wide (128 values); a wider head's
    stored tiles interleave the heads, and the kernel's view of it would be
    a copy of the whole pool every call."""
    return len(row) == 2 and row[0] > 1 and row[1] > 128
