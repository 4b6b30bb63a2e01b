"""Operations of the latent model's prefill chunk attention, from shapes.

Kept with the benchmark, so that a change to the program cannot move a share
of a peak. It reads LOW, never high: only the products a causal attention
must make are counted, each (query, key) pair at or under the diagonal once,
a score over ``qk_head_dim`` values and a sum over ``v_head_dim``. The
up-projection of a tile's rows to a head's keys and values (a sixth more, made
once a (head, tile)), the masked halves of the sub-tiles the diagonal crosses
and the final chunk's pad rows are work the kernel does and this leaves out.
"""

from __future__ import annotations


def chunk_attention_flops(cfg: dict, offset: int, valid: int) -> float:
    """One prefill chunk of ``valid`` true rows at positions ``offset`` ..
    over all layers: row i attends ``offset + i + 1`` rows."""
    pairs = valid * (offset + (valid + 1) / 2.0)
    return float(pairs * cfg["num_attention_heads"] * 2
                 * (cfg["qk_head_dim"] + cfg["v_head_dim"])
                 * cfg["num_hidden_layers"])
