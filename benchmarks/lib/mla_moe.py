"""``joyai-llm-flash-l5``: from the configuration file to the program's model
objects, and the operations and bytes of its two new kernels.

The file holds the published keys (DeepSeek-V3's); this maps them onto
``MlaMoeConfig`` and makes the weights on the device from the seed. The counts
are kept here, with the benchmark, so that a change to the program cannot move
a share of a peak. Each reads LOW, never high: only what the algorithm must
move or compute is counted, at the 576 useful values of a cached row (the pool
stores 640) and with queries, outputs, tables and the router left out.
"""

from __future__ import annotations

# rehearsal on the CPU (selftest.py): ``lib/model.py`` cuts the dense keys,
# these are the latent ranks and the expert keys. Never used on the chip.
TINY = {"q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "qk_head_dim": 16, "v_head_dim": 8,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "rope_theta": 10000}


def model_config(cfg: dict):
    from kubeflow_tpu.models.mla_moe import MlaMoeConfig

    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope scaling is not modelled")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("grouped expert choice is not modelled")
    if cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"]:
        raise ValueError("every layer after the dense ones is an expert "
                         "layer and the head is untied")
    return MlaMoeConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        mlp_dim=cfg["intermediate_size"],
        moe_mlp_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        score_func=cfg["scoring_func"],
        n_predict_layers=cfg["num_nextn_predict_layers"],
        max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def serving_params(mcfg, seed: int):
    """bf16 weights on the device, one jitted call, nothing on the host."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import mla_moe

    return jax.jit(lambda key: mla_moe.init_params(key, mcfg, jnp.bfloat16))(
        jax.random.key(seed % (1 << 31)))


def latent_row_values(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_kernel_bytes(cfg: dict, live_tokens: float,
                        bytes_per_value: int = 2) -> float:
    """Least bytes the latent decode kernel moves in ONE decode step over all
    layers: the cached row of every live token once."""
    return float(cfg["num_hidden_layers"] * live_tokens
                 * latent_row_values(cfg) * bytes_per_value)


def decode_kernel_flops(cfg: dict, live_tokens: float) -> float:
    """Operations of the same step: every head scores a token over its whole
    row and sums over its latent part."""
    per_token_head = 2 * latent_row_values(cfg) + 2 * cfg["kv_lora_rank"]
    return float(cfg["num_hidden_layers"] * live_tokens
                 * cfg["num_attention_heads"] * per_token_head)


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """The three matrices of ONE routed expert."""
    return float(3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
                 * bytes_per_param)
