"""From a configuration file to the program's own model objects.

The file holds the published keys; this maps them onto ``LlamaConfig`` (the
repo's one dense GQA + SwiGLU + RoPE block) and makes the weights on the
device from the seed, in one jitted call.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# rehearsal on the CPU (selftest.py): every width cut so that a cell's whole
# control flow runs in seconds. Never used on the chip.
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "num_hidden_layers": 2}


def load_config(name: str, tiny: bool = False) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    if tiny:
        cfg.update(TINY)
    return cfg


def llama_config(cfg: dict, **overrides):
    from kubeflow_tpu.models.llama import LlamaConfig

    head_dim = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    if head_dim * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as dim / n_heads")
    if cfg.get("rope_scaling") is not None or cfg.get("sliding_window"):
        raise ValueError("rope scaling and sliding windows are not modelled")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), **overrides)


def serving_params(lcfg, seed: int):
    """bf16 weights on the device, one jitted call, nothing on the host."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import llama

    return jax.jit(lambda key: llama.init_params(key, lcfg, jnp.bfloat16))(
        jax.random.key(seed % (1 << 31)))
