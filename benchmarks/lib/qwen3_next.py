"""``qwen3-next-80b-a3b-l8``: from the configuration file to the program's
model objects, and the bytes of the GDN and GQA decode kernels its readers
divide by (an expert's are ``lib/mla_moe.expert_bytes``, the same three
matrices by the same keys).

The file holds the published keys (Qwen3-Next-80B-A3B's ``config.json``, with
``num_experts`` the experts HELD here and ``num_experts_routed`` the 512 the
router scores); this maps them onto ``Qwen3NextConfig`` and makes the weights
on the device from the seed. The counts are kept here, with the benchmark, so
that a change to the program cannot move a share of a peak. Each reads LOW,
never high: only what the algorithm must move.
"""

from __future__ import annotations

# rehearsal on the CPU (selftest.py): ``lib/model.py`` cuts the dense keys,
# these are the GDN and expert keys. Never used on the chip.
TINY = {"head_dim": 32, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 16,
        "linear_value_head_dim": 16, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 24, "num_experts": 4,
        "num_experts_routed": 16, "first_expert_held": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 8, "rope_theta": 10000}


def model_config(cfg: dict):
    from kubeflow_tpu.models.qwen3_next import Qwen3NextConfig

    if cfg.get("rope_scaling") is not None or cfg["use_sliding_window"]:
        raise ValueError("rope scaling and sliding windows are not modelled")
    if cfg["tie_word_embeddings"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("every layer an expert layer, top-k renormalised, "
                         "the head untied")
    return Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        n_k_heads=cfg["linear_num_key_heads"],
        n_v_heads=cfg["linear_num_value_heads"],
        k_head_dim=cfg["linear_key_head_dim"],
        v_head_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        n_experts=cfg["num_experts_routed"],
        n_experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert_held"],
        top_k=cfg["num_experts_per_tok"],
        moe_mlp_dim=cfg["moe_intermediate_size"],
        shared_mlp_dim=cfg["shared_expert_intermediate_size"],
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq=cfg["max_position_embeddings"])


def serving_params(mcfg, seed: int):
    """bf16 weights on the device, one jitted call, nothing on the host (the
    decay's two vectors are float32, as ``init_params`` makes them)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import qwen3_next

    return jax.jit(lambda key: qwen3_next.init_params(key, mcfg, jnp.bfloat16))(
        jax.random.key(seed % (1 << 31)))


def gdn_layers(cfg: dict) -> int:
    per = cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] // per * (per - 1)


def gdn_kernel_bytes(cfg: dict, live_slots: float) -> float:
    """Least bytes the GDN decode kernel moves in ONE decode step over all
    GDN layers: each live slot's ``S`` (float32) read once and written once.
    The conv ring is not the kernel's: XLA reads and scatters it outside."""
    s = cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * 4
    return float(2 * gdn_layers(cfg) * live_slots * s)


def attn_kernel_bytes(cfg: dict, live_tokens: float) -> float:
    """Least bytes the paged GQA decode kernel moves in ONE decode step over
    the full-attention layers: K and V of every live token once, bf16."""
    full = cfg["num_hidden_layers"] - gdn_layers(cfg)
    return float(full * live_tokens * 2 * cfg["num_key_value_heads"]
                 * cfg["head_dim"] * 2)
