"""HF-layout Llama checkpoint loader/saver (safetensors <-> param pytree).

Parity: SURVEY.md §2.4 'Runtime servers' — the reference's
huggingfaceserver loads HF-hub-layout checkpoints (config.json +
model*.safetensors [+ index] + tokenizer.json) straight into its runtime
([U] kserve:python/huggingfaceserver). This module is the TPU-native
equivalent: it maps the HF Llama tensor layout onto this repo's
scan-stacked pytree (models/llama.py) with

- torch Linear [out, in] -> einsum [in, out] transposition, and head-dim
  splitting for the attention projections;
- per-tensor lazy reads (safetensors mmap) so peak host memory is one
  tensor, not the whole checkpoint;
- dtype casting at load (bf16 params by default for serving);
- optional *sharded* materialization: given a Mesh, every param is
  device_put with the NamedSharding derived from
  llama.param_logical_axes — so an 8B/70B checkpoint is never resident
  unsharded on one device.

The RoPE convention matches: HF Llama uses the rotate-half (split-half)
layout, exactly what ops/rotary.py implements, so no weight permutation is
needed.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel import sharding as shd

try:  # safetensors ships with transformers in this environment
    from safetensors import safe_open
    from safetensors.flax import save_file as _st_save
except ImportError:  # pragma: no cover - env always has it; keep import soft
    safe_open = None
    _st_save = None

# save_pretrained's default cap on one weights file (HF: max_shard_size)
MAX_SHARD_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# config.json <-> LlamaConfig
# ---------------------------------------------------------------------------

def config_from_hf(d: dict[str, Any], **overrides) -> llama.LlamaConfig:
    """Translate an HF LlamaConfig dict into this repo's LlamaConfig."""
    rope_scaling = d.get("rope_scaling") or {}
    scaling_type = rope_scaling.get("rope_type") or rope_scaling.get("type")
    kw: dict[str, Any] = dict(
        vocab_size=d["vocab_size"],
        dim=d["hidden_size"],
        n_layers=d["num_hidden_layers"],
        n_heads=d["num_attention_heads"],
        n_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
        mlp_dim=d["intermediate_size"],
        max_seq=d.get("max_position_embeddings", 8192),
        rope_theta=float(d.get("rope_theta", 500000.0)),
        rope_scaling="llama3" if scaling_type == "llama3" else None,
        norm_eps=float(d.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(d.get("tie_word_embeddings", False)),
    )
    if d.get("num_local_experts"):
        # Mixtral layout: routed FFN mixture, attention unchanged
        kw["n_experts"] = int(d["num_local_experts"])
        kw["moe_top_k"] = int(d.get("num_experts_per_tok", 2))
    kw.update(overrides)
    return llama.LlamaConfig(**kw)


def config_to_hf(cfg: llama.LlamaConfig) -> dict[str, Any]:
    d: dict[str, Any] = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.mlp_dim,
        "max_position_embeddings": cfg.max_seq,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": "bfloat16",
    }
    if cfg.rope_scaling == "llama3":
        d["rope_scaling"] = {
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": cfg.max_seq,
        }
    if cfg.n_experts:
        d["architectures"] = ["MixtralForCausalLM"]
        d["model_type"] = "mixtral"
        d["num_local_experts"] = cfg.n_experts
        d["num_experts_per_tok"] = cfg.moe_top_k
    return d


def load_config(model_dir: str, **overrides) -> llama.LlamaConfig:
    with open(os.path.join(model_dir, "config.json")) as f:
        return config_from_hf(json.load(f), **overrides)


# ---------------------------------------------------------------------------
# weight-name mapping
# ---------------------------------------------------------------------------

class _TensorIndex:
    """name -> (file, lazy reader) over model.safetensors or the sharded
    model-0000x-of-0000y.safetensors + model.safetensors.index.json form."""

    def __init__(self, model_dir: str):
        if safe_open is None:  # pragma: no cover
            raise RuntimeError("safetensors is required to load HF checkpoints")
        self.model_dir = model_dir
        index_path = os.path.join(model_dir, "model.safetensors.index.json")
        if os.path.exists(index_path):
            with open(index_path) as f:
                weight_map: dict[str, str] = json.load(f)["weight_map"]
            self._files = sorted(set(weight_map.values()))
            self._where = weight_map
        else:
            single = os.path.join(model_dir, "model.safetensors")
            if not os.path.exists(single):
                raise FileNotFoundError(
                    f"no model.safetensors[.index.json] in {model_dir}")
            self._files = ["model.safetensors"]
            self._where = None
        self._open: dict[str, Any] = {}

    def _handle(self, fname: str):
        if fname not in self._open:
            self._open[fname] = safe_open(
                os.path.join(self.model_dir, fname), framework="flax")
        return self._open[fname]

    def names(self) -> set[str]:
        if self._where is not None:
            return set(self._where)
        return set(self._handle(self._files[0]).keys())

    def get(self, name: str) -> jax.Array:
        fname = self._where[name] if self._where else self._files[0]
        return self._handle(fname).get_tensor(name)

    def close(self) -> None:
        self._open.clear()


def _linear(w: jax.Array) -> jax.Array:
    """torch Linear weight [out, in] -> einsum layout [in, out]."""
    return w.T


def load_params(model_dir: str, cfg: Optional[llama.LlamaConfig] = None, *,
                dtype=jnp.bfloat16, mesh=None, rules=None):
    """Read an HF-layout Llama checkpoint into the scan-stacked pytree.

    With ``mesh``, each param is placed with the NamedSharding from
    llama.param_logical_axes + the rule table — the sharded-load path, so
    nothing bigger than one tensor is ever host-resident and nothing bigger
    than its shard is device-resident per chip.
    """
    cfg = cfg or load_config(model_dir, dtype=dtype)
    idx = _TensorIndex(model_dir)
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dim

    put = _placer(cfg, mesh, rules, dtype)
    stack_shardings = None
    if mesh is not None:
        stack_shardings = shd.tree_shardings(
            mesh, llama.param_logical_axes(cfg), rules)["layers"]

    def layer_stack(fmt: str, transform, key: str = "") -> jax.Array:
        """Stack per-layer tensors. With a mesh, the stack materializes
        SHARD BY SHARD (jax.make_array_from_callback reading one layer
        tensor at a time) — a 70B/Mixtral FFN stack never exists as one
        host allocation; without a mesh, plain host stacking."""
        if stack_shardings is None:
            return jnp.stack([transform(idx.get(fmt.format(i)))
                              for i in range(cfg.n_layers)])
        sample = np.asarray(transform(idx.get(fmt.format(0))))
        gshape = (cfg.n_layers,) + sample.shape

        def cb(index):
            li = index[0]
            return np.stack([
                np.asarray(transform(idx.get(fmt.format(i))))[
                    tuple(index[1:])].astype(dtype)
                for i in range(*li.indices(cfg.n_layers))])

        return jax.make_array_from_callback(
            gshape, stack_shardings[key], cb)

    layers = {
        "attn_norm": layer_stack(
            "model.layers.{}.input_layernorm.weight", lambda w: w,
            "attn_norm"),
        "mlp_norm": layer_stack(
            "model.layers.{}.post_attention_layernorm.weight", lambda w: w,
            "mlp_norm"),
        "wq": layer_stack(
            "model.layers.{}.self_attn.q_proj.weight",
            lambda w: _linear(w).reshape(d, h, hd), "wq"),
        "wk": layer_stack(
            "model.layers.{}.self_attn.k_proj.weight",
            lambda w: _linear(w).reshape(d, kv, hd), "wk"),
        "wv": layer_stack(
            "model.layers.{}.self_attn.v_proj.weight",
            lambda w: _linear(w).reshape(d, kv, hd), "wv"),
        "wo": layer_stack(
            "model.layers.{}.self_attn.o_proj.weight",
            lambda w: _linear(w).reshape(h, hd, d), "wo"),
    }
    if cfg.n_experts:
        # Mixtral block_sparse_moe: router gate [E, d] -> [d, E]; per-expert
        # w1(gate)/w3(up) [m, d] -> [d, m]; w2(down) [d, m] -> [m, d];
        # experts stack on a leading E dim matching llama.init_params
        E = cfg.n_experts

        def expert_stack(fmt: str, key: str) -> jax.Array:
            if stack_shardings is None:
                return jnp.stack([
                    jnp.stack([_linear(idx.get(fmt.format(i, e)))
                               for e in range(E)])
                    for i in range(cfg.n_layers)])
            sample = np.asarray(_linear(idx.get(fmt.format(0, 0))))
            gshape = (cfg.n_layers, E) + sample.shape

            def cb(index):
                li, ei = index[0], index[1]
                return np.stack([
                    np.stack([
                        np.asarray(_linear(idx.get(fmt.format(i, e))))[
                            tuple(index[2:])].astype(dtype)
                        for e in range(*ei.indices(E))])
                    for i in range(*li.indices(cfg.n_layers))])

            return jax.make_array_from_callback(
                gshape, stack_shardings[key], cb)

        layers["moe_router"] = layer_stack(
            "model.layers.{}.block_sparse_moe.gate.weight", _linear,
            "moe_router")
        layers["w_gate"] = expert_stack(
            "model.layers.{}.block_sparse_moe.experts.{}.w1.weight",
            "w_gate")
        layers["w_up"] = expert_stack(
            "model.layers.{}.block_sparse_moe.experts.{}.w3.weight",
            "w_up")
        layers["w_down"] = expert_stack(
            "model.layers.{}.block_sparse_moe.experts.{}.w2.weight",
            "w_down")
    else:
        layers["w_gate"] = layer_stack(
            "model.layers.{}.mlp.gate_proj.weight", _linear, "w_gate")
        layers["w_up"] = layer_stack(
            "model.layers.{}.mlp.up_proj.weight", _linear, "w_up")
        layers["w_down"] = layer_stack(
            "model.layers.{}.mlp.down_proj.weight", _linear, "w_down")
    params = {
        "embed": idx.get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": idx.get("model.norm.weight"),
    }
    if not cfg.tie_embeddings:
        name = ("lm_head.weight" if "lm_head.weight" in idx.names()
                else "model.embed_tokens.weight")
        params["lm_head"] = _linear(idx.get(name))
    params = put(params)
    idx.close()
    return cfg, params


def _placer(cfg, mesh, rules, dtype):
    axes = llama.param_logical_axes(cfg)

    def put(params):
        if mesh is None:
            return jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
        shardings = shd.tree_shardings(mesh, axes, rules)
        return jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x, dtype), s),
            params, shardings)

    return put


def save_pretrained(model_dir: str, cfg: llama.LlamaConfig, params, *,
                    max_shard_bytes: int = MAX_SHARD_BYTES) -> None:
    """Write the pytree back out in HF layout — the export path, and the
    fixture-maker for tests. What fits ``max_shard_bytes`` is one
    model.safetensors; more is split the HF way, whole tensors into
    model-0000x-of-0000y.safetensors + model.safetensors.index.json (a
    tensor larger than the cap gets a file of its own)."""
    if _st_save is None:  # pragma: no cover
        raise RuntimeError("safetensors is required to save HF checkpoints")
    os.makedirs(model_dir, exist_ok=True)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(config_to_hf(cfg), f, indent=1)
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dim
    lp = params["layers"]
    flat: dict[str, jax.Array] = {
        "model.embed_tokens.weight": params["embed"],
        "model.norm.weight": params["final_norm"],
    }
    if not cfg.tie_embeddings:
        flat["lm_head.weight"] = params["lm_head"].T
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        flat[p + "input_layernorm.weight"] = lp["attn_norm"][i]
        flat[p + "post_attention_layernorm.weight"] = lp["mlp_norm"][i]
        flat[p + "self_attn.q_proj.weight"] = lp["wq"][i].reshape(d, h * hd).T
        flat[p + "self_attn.k_proj.weight"] = lp["wk"][i].reshape(d, kv * hd).T
        flat[p + "self_attn.v_proj.weight"] = lp["wv"][i].reshape(d, kv * hd).T
        flat[p + "self_attn.o_proj.weight"] = lp["wo"][i].reshape(h * hd, d).T
        if cfg.n_experts:
            flat[p + "block_sparse_moe.gate.weight"] = lp["moe_router"][i].T
            for e in range(cfg.n_experts):
                ep = p + f"block_sparse_moe.experts.{e}."
                flat[ep + "w1.weight"] = lp["w_gate"][i, e].T
                flat[ep + "w3.weight"] = lp["w_up"][i, e].T
                flat[ep + "w2.weight"] = lp["w_down"][i, e].T
        else:
            flat[p + "mlp.gate_proj.weight"] = lp["w_gate"][i].T
            flat[p + "mlp.up_proj.weight"] = lp["w_up"][i].T
            flat[p + "mlp.down_proj.weight"] = lp["w_down"][i].T
    flat = {k: jnp.asarray(v) for k, v in flat.items()}
    shards: list[dict[str, jax.Array]] = [{}]
    room = max_shard_bytes
    for name, x in flat.items():
        if shards[-1] and x.nbytes > room:
            shards.append({})
            room = max_shard_bytes
        shards[-1][name] = x
        room -= x.nbytes
    if len(shards) == 1:
        _st_save(flat, os.path.join(model_dir, "model.safetensors"))
        return
    weight_map: dict[str, str] = {}
    for i, shard in enumerate(shards, 1):
        fname = f"model-{i:05d}-of-{len(shards):05d}.safetensors"
        _st_save(shard, os.path.join(model_dir, fname))
        weight_map.update(dict.fromkeys(shard, fname))
    with open(os.path.join(model_dir,
                           "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": sum(
            x.nbytes for x in flat.values())},
            "weight_map": weight_map}, f, indent=1)


def load_pretrained(model_dir: str, *, dtype=jnp.bfloat16, mesh=None,
                    rules=None, **config_overrides):
    """One call: (LlamaConfig, params) from an HF checkpoint directory.
    The param ``dtype`` doubles as the config's compute dtype unless a
    ``dtype`` config override says otherwise."""
    config_overrides.setdefault("dtype", dtype)
    cfg = load_config(model_dir, **config_overrides)
    return load_params(model_dir, cfg, dtype=dtype, mesh=mesh, rules=rules)
