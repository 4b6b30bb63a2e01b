"""``zaya1-8b-l20``: from the configuration file to the program's model
objects, and the bytes of the paged GQA decode kernel on this model.

The file holds the published keys (ZAYA1-8B's ``config.json``); this maps them
onto ``CcaMoeConfig`` and makes the weights on the device from the seed. The
count is kept here, with the benchmark, so that a change to the program cannot
move a share of a peak. It reads LOW, never high: only the keys and values of
the live tokens, with queries, outputs and tables left out. An expert's bytes
are ``lib/mla_moe.expert_bytes`` (the same three matrices by the same keys).
"""

from __future__ import annotations

# rehearsal on the CPU (selftest.py): ``lib/model.py`` cuts the dense keys,
# these are the expert and router keys. Never used on the chip.
TINY = {"moe_intermediate_size": 32, "num_experts": 4,
        "router_hidden_size": 16, "num_hidden_layers": 3,
        "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 10000,
                                       "rope_type": "default"}}}


def model_config(cfg: dict):
    from kubeflow_tpu.models.cca_moe import CcaMoeConfig

    rope = cfg["rope_parameters"]["hybrid"]
    if cfg["cca_time0"] != 2 or cfg["cca_time1"] != 2:
        raise ValueError("both convolutions are written for kernel 2")
    if cfg["num_experts_per_tok"] != 1 or not cfg["tie_word_embeddings"]:
        raise ValueError("top-1 experts and a tied head")
    if cfg["sliding_window"] or rope["rope_type"] != "default" or set(
            cfg["layer_types"][:cfg["num_hidden_layers"]]) != {"hybrid"}:
        raise ValueError("every layer attends over the whole context with "
                         "unscaled rotary frequencies")
    return CcaMoeConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * rope["partial_rotary_factor"]),
        moe_mlp_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"], router_dim=cfg["router_hidden_size"],
        max_seq=cfg["max_position_embeddings"],
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def serving_params(mcfg, seed: int):
    """bf16 weights on the device, one jitted call, nothing on the host:
    seeded, then the router's balancing bias brought to where its own rule
    leaves it (``cca_moe.balance_router_bias``, on sequences from the same
    seed), as a trained checkpoint's is."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import cca_moe

    def make(key):
        k_init, k_balance = jax.random.split(key)
        return cca_moe.balance_router_bias(
            cca_moe.init_params(k_init, mcfg, jnp.bfloat16), mcfg, k_balance)

    return jax.jit(make)(jax.random.key(seed % (1 << 31)))


def kv_values_per_token(cfg: dict) -> int:
    """K and V of one token in one layer: 2 x KV heads x head size."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_kernel_bytes(cfg: dict, live_tokens: float,
                        bytes_per_value: int = 2) -> float:
    """Least bytes the paged GQA decode kernel moves in ONE decode step over
    all layers: K and V of every live token once."""
    return float(cfg["num_hidden_layers"] * live_tokens
                 * kv_values_per_token(cfg) * bytes_per_value)
