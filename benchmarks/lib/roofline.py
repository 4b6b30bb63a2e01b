"""Operations and bytes computed from shapes: the yardstick's arithmetic.

Kept with the benchmark so that a change to the program cannot move a share
of a peak. ``cfg`` is a configuration file's JSON object (published keys).
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3x the forward's matmuls), causal attention scores
    at an average context of seq/2 included, recomputation not counted.
    Copied from ``LlamaConfig.flops_per_token(seq)`` (dense case)."""
    d, h, kv, hd, m, v, layers = _dims(cfg)
    attn_proj = 2 * d * (h + 2 * kv) * hd
    attn_out = 2 * h * hd * d
    attn_score = 2 * seq * h * hd
    mlp = 2 * 3 * d * m
    return 3.0 * (layers * (attn_proj + attn_out + attn_score + mlp)
                  + 2 * d * v)


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """Bytes of weights one decode step has to read (the embedding table is
    gathered by row and not counted; the untied head is read whole)."""
    d, h, kv, hd, m, v, layers = _dims(cfg)
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * m + 2 * d
    return float(bytes_per_param * (layers * per_layer + d * v + d))


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    """K and V of one token over all layers."""
    d, h, kv, hd, m, v, layers = _dims(cfg)
    return float(2 * layers * kv * hd * bytes_per_value)


def decode_kernel_bytes(cfg: dict, live_tokens: float) -> float:
    """Least bytes the paged-attention kernel moves in ONE decode step over
    all layers: K and V of every live token once. Queries, outputs and block
    tables are left out, so the share reads slightly low, never high."""
    return kv_bytes_per_token(cfg) * live_tokens


def decode_step_bytes(cfg: dict, live_tokens: float) -> float:
    """Least bytes of one whole decode step: weights once plus live KV."""
    return weight_bytes(cfg) + decode_kernel_bytes(cfg, live_tokens)
