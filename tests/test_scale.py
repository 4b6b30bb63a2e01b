"""Scale-push tests: pipeline parallelism, MoE/expert parallelism, the MoE
Llama variant training end-to-end on the virtual mesh, and the hybrid
multi-slice mesh construction (SURVEY.md §2.7 PP/EP/multi-slice rows)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel import (
    MeshConfig, MoEConfig, build_mesh, init_moe_params, moe_layer,
    pipeline_apply, stack_stage_params,
)

from conftest import paged_session


# ---------------------------------------------------------------- pipeline

@pytest.fixture(scope="module")
def pipe_mesh():
    return build_mesh(MeshConfig(pipeline=4))      # fsdp absorbs the rest


def _mlp_stages(n_stages, dim, key):
    stages = []
    for _ in range(n_stages):
        k1, k2, key = jax.random.split(key, 3)
        stages.append({"w": jax.random.normal(k1, (dim, dim)) * 0.5,
                       "b": jax.random.normal(k2, (dim,)) * 0.1})
    return stages


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_pipeline_matches_sequential(pipe_mesh):
    stages = _mlp_stages(4, 16, jax.random.key(0))
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.key(1), (8, 16))
    fwd = jax.jit(pipeline_apply(_stage_fn, pipe_mesh, microbatches=4))
    y = fwd(stacked, x)
    ref = x
    for p in stages:
        ref = _stage_fn(p, ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_grads_reach_every_stage(pipe_mesh):
    stages = _mlp_stages(4, 16, jax.random.key(2))
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.key(3), (8, 16))
    fwd = pipeline_apply(_stage_fn, pipe_mesh, microbatches=2)
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(fwd(p, x) ** 2)))(stacked, x)
    per_stage = np.asarray(jnp.abs(g["w"]).sum(axis=(1, 2)))
    assert (per_stage > 0).all(), per_stage


def test_pipeline_microbatch_count_must_divide(pipe_mesh):
    stages = _mlp_stages(4, 8, jax.random.key(4))
    stacked = stack_stage_params(stages)
    x = jnp.zeros((6, 8))
    fwd = pipeline_apply(_stage_fn, pipe_mesh, microbatches=4)
    with pytest.raises(Exception):
        jax.jit(fwd)(stacked, x)      # 6 % 4 != 0


# ---------------------------------------------------------------- moe

def test_moe_matches_per_token_reference():
    cfg = MoEConfig(dim=16, mlp_dim=32, n_experts=4, top_k=2,
                    capacity_factor=8.0)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 6, 16))
    y, aux = jax.jit(lambda p, x: moe_layer(p, x, cfg))(params, x)
    assert float(aux["moe_dropped_fraction"]) == 0.0

    tokens = np.asarray(x.reshape(-1, 16), np.float32)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(tokens @ np.asarray(params["router"], np.float32)), -1))
    ref = np.zeros_like(tokens)
    for t in range(tokens.shape[0]):
        idx = np.argsort(-probs[t])[:2]
        w = probs[t][idx] / probs[t][idx].sum()
        for wi, ei in zip(w, idx):
            h = np.asarray(jax.nn.silu(jnp.asarray(
                tokens[t] @ np.asarray(params["w_gate"][ei]))))
            h = h * (tokens[t] @ np.asarray(params["w_up"][ei]))
            ref[t] += wi * (h @ np.asarray(params["w_down"][ei]))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), ref,
                               rtol=1e-4, atol=1e-4)


def test_moe_sharded_matches_unsharded():
    cfg = MoEConfig(dim=16, mlp_dim=32, n_experts=4, top_k=2,
                    capacity_factor=8.0)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 6, 16))
    y, _ = jax.jit(lambda p, x: moe_layer(p, x, cfg))(params, x)
    mesh = build_mesh(MeshConfig(expert=4, fsdp=1, data=2))
    with mesh:
        y2, _ = jax.jit(lambda p, x: moe_layer(p, x, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_moe_capacity_drops_overflow():
    cfg = MoEConfig(dim=16, mlp_dim=32, n_experts=4, top_k=1,
                    capacity_factor=0.26)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 6, 16))
    _, aux = jax.jit(lambda p, x: moe_layer(p, x, cfg))(params, x)
    assert float(aux["moe_dropped_fraction"]) > 0


def test_moe_aux_losses_differentiable():
    cfg = MoEConfig(dim=8, mlp_dim=16, n_experts=4, top_k=2)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 8, 8))

    def loss(p):
        y, aux = moe_layer(p, x, cfg)
        return jnp.sum(y ** 2) + aux["moe_load_balance"] + aux["moe_router_z"]

    g = jax.jit(jax.grad(loss))(params)
    assert float(jnp.abs(g["router"]).sum()) > 0    # router learns


# ---------------------------------------------------------------- moe llama

def test_llama_moe_trains(mesh8):
    cfg = llama.llama_tiny(n_experts=4, moe_top_k=2,
                           moe_capacity_factor=4.0, dtype=jnp.float32)
    from kubeflow_tpu.training import (
        Trainer, TrainerConfig, lm_loss_fn, put_batch, synthetic_lm_batches,
    )

    trainer = Trainer(
        mesh=mesh8,
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        params_logical_axes=llama.param_logical_axes(cfg),
        loss_fn=lm_loss_fn(llama.forward, cfg),
        config=TrainerConfig(learning_rate=3e-3, warmup_steps=2,
                             total_steps=50),
    )
    trainer.init_state(jax.random.key(0))
    batch = put_batch(mesh8, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))
    first = None
    for _ in range(12):
        m = trainer.train_step(batch)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first          # MoE model actually learns
    assert "moe_aux" in m


def test_llama_moe_decode_matches_forward():
    cfg = llama.llama_tiny(n_experts=4, moe_top_k=2,
                           moe_capacity_factor=8.0, dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    prompt = [5, 6, 7, 8]
    logits, step = paged_session(cfg, params, [prompt])
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        toks.append(int(jnp.argmax(step(toks[-1:])[0])))

    ref = list(prompt)
    for _ in range(4):
        full = llama.forward(params, jnp.asarray([ref], jnp.int32), cfg)
        ref.append(int(jnp.argmax(full[0, -1])))
    assert toks == ref[len(prompt):]


def test_moe_expert_sharded_training(mesh_expert):
    cfg = llama.llama_tiny(n_experts=4, moe_top_k=2,
                           moe_capacity_factor=4.0, dtype=jnp.float32)
    from kubeflow_tpu.training import (
        Trainer, TrainerConfig, lm_loss_fn, put_batch, synthetic_lm_batches,
    )

    trainer = Trainer(
        mesh=mesh_expert,
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        params_logical_axes=llama.param_logical_axes(cfg),
        loss_fn=lm_loss_fn(llama.forward, cfg),
        config=TrainerConfig(learning_rate=3e-3, warmup_steps=2,
                             total_steps=20),
    )
    trainer.init_state(jax.random.key(0))
    batch = put_batch(mesh_expert, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))
    m = trainer.train_step(batch)
    assert float(m["loss"]) > 0


# ------------------------------------------------------- pipelined llama

def test_pipeline_llama_matches_forward():
    """Pipelined dense Llama (partial-manual shard_map, PP axis only)
    reproduces the sequential forward exactly."""
    from kubeflow_tpu.parallel import pipeline_forward, to_pipeline_params

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    ref = llama.forward(params, tokens, cfg)

    mesh = build_mesh(MeshConfig(pipeline=2, data=2, fsdp=2))
    pp = to_pipeline_params(params, 2)
    with mesh:
        out, _ = jax.jit(lambda p, t: pipeline_forward(
            p, t, cfg, mesh, microbatches=2))(pp, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_llama_moe_trains_pp_ep_dp():
    """MoE Llama trains through pipeline_apply on a {pipeline:2, expert:2,
    data:2} mesh — PP composed with EP and pure DP in one jitted step (the
    driver-dryrun mesh 2 shape)."""
    from kubeflow_tpu.parallel import (
        init_pipeline_params, pipeline_lm_loss_fn, pipeline_param_logical_axes,
    )
    from kubeflow_tpu.training import (
        Trainer, TrainerConfig, put_batch, synthetic_lm_batches,
    )

    cfg = llama.llama_tiny(n_experts=4, moe_top_k=2,
                           moe_capacity_factor=4.0, dtype=jnp.float32)
    mesh = build_mesh(MeshConfig(pipeline=2, expert=2, data=2))
    trainer = Trainer(
        mesh=mesh,
        init_params_fn=lambda rng: init_pipeline_params(rng, cfg, 2),
        params_logical_axes=pipeline_param_logical_axes(cfg),
        loss_fn=pipeline_lm_loss_fn(cfg, mesh, microbatches=2),
        config=TrainerConfig(learning_rate=3e-3, warmup_steps=2,
                             total_steps=20),
    )
    trainer.init_state(jax.random.key(0))
    batch = put_batch(mesh, next(iter(
        synthetic_lm_batches(cfg.vocab_size, 8, 32))))
    first = None
    for _ in range(8):
        m = trainer.train_step(batch)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first
    assert "moe_aux" in m


def test_pipeline_llama_stage_param_split():
    from kubeflow_tpu.parallel import (
        pipeline_param_logical_axes, to_pipeline_params,
    )

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    pp = to_pipeline_params(params, 2)
    assert pp["stages"]["wq"].shape[:2] == (2, cfg.n_layers // 2)
    axes = pipeline_param_logical_axes(cfg)
    assert axes["stages"]["wq"][0] == "pipe_stage"
    with pytest.raises(ValueError):
        to_pipeline_params(params, 3)      # 2 layers % 3 != 0


# ---------------------------------------------------------------- mesh

def test_hybrid_multislice_mesh_shapes():
    """2 slices of 4 devices: DCN data outer, ICI inner axes."""
    cfg = MeshConfig(data=1, fsdp=2, tensor=2, dcn_data=2)
    mesh = build_mesh(cfg)
    assert dict(mesh.shape)["data"] == 2        # dcn * ici data merged
    assert dict(mesh.shape)["fsdp"] == 2
    assert dict(mesh.shape)["tensor"] == 2


def test_mesh_rejects_bad_pipeline_factor():
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(pipeline=3, fsdp=1))


def test_aot_scale_proof_8b_serving_v5p8():
    """BASELINE.md row 4 cannot run on single-chip CI, but the REAL
    XLA:TPU compiler can prove it: AOT-compile the tensor-parallel 8B
    serving hot path against a compile-only v5p-8 topology and assert the
    per-chip HBM requirement fits. (The 70B/v5p-128 twin runs in
    `make scale-proof` — its compile is too slow for the unit suite.)"""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel.aot import aot_serve_proof

    proof = aot_serve_proof(
        llama.llama3_8b(), "v5p:2x2x1", tensor=4,
        batch=8, max_seq=8192, name="llama3_8b-serve-v5p8")
    assert proof.n_devices == 4
    assert proof.mesh_axes == {"tensor": 4}
    # bf16 8B params / 4 chips ~ 4G + KV pool: sane, and far under budget
    assert 3.0 < proof.argument_gb < 20.0
    assert proof.fits, proof.to_dict()


# ------------------------------------------------- aot roofline inputs

def test_measured_mfu_tracks_latest_bench_artifact(tmp_path, monkeypatch):
    """The projection's MFU input comes from the NEWEST readable
    BENCH_r*.json (parsed copy or truncated tail), not the baked
    constant; the constant is only the no-artifact fallback."""
    import json as _json

    from kubeflow_tpu.parallel.aot import (
        MEASURED_SINGLE_CHIP_MFU, measured_single_chip_mfu,
    )

    assert measured_single_chip_mfu(root=str(tmp_path)) == (
        MEASURED_SINGLE_CHIP_MFU, "baked-in fallback (no bench artifact)")

    (tmp_path / "BENCH_r07.json").write_text(
        _json.dumps({"parsed": {"extra": {"mfu": 0.61}}}))
    assert measured_single_chip_mfu(root=str(tmp_path)) == (
        0.61, "BENCH_r07.json")

    # a newer round whose parsed copy is gone but whose tail still
    # carries the number (the real r05 artifact shape) wins
    (tmp_path / "BENCH_r08.json").write_text(_json.dumps(
        {"parsed": None, "tail": '..., "mfu": 0.63, "device": "v5e"'}))
    assert measured_single_chip_mfu(root=str(tmp_path)) == (
        0.63, "BENCH_r08.json")

    # garbage newest falls through to the newest readable
    (tmp_path / "BENCH_r09.json").write_text("{not json")
    assert measured_single_chip_mfu(root=str(tmp_path))[1] == \
        "BENCH_r08.json"

    monkeypatch.setenv("KFT_BENCH_DIR", str(tmp_path))
    assert measured_single_chip_mfu()[0] == 0.63


def test_hlo_collective_bytes_split_by_fabric():
    """Wire-byte accounting: group size + op type set the per-chip bytes,
    replica groups spanning slices ride DCN."""
    from kubeflow_tpu.parallel.aot import hlo_collective_bytes

    hlo = """
  %ag = bf16[64,128]{1,0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(%g), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %ar = f32[8,128]{1,0} all-reduce(%h), replica_groups={{0,8},{1,9}}, to_apply=%add
  %ar2 = f32[16]{0} all-reduce(%j), replica_groups={}, to_apply=%add
"""
    out = hlo_collective_bytes(hlo, devices_per_slice=8, n_devices=16)
    ag = 64 * 128 * 2 * 3 / 4          # B*(g-1)/g
    rs = 8 * 128 * 4 * 7               # shard result: B*(g-1)
    ar = 2 * 8 * 128 * 4 * 1 / 2       # 2B*(g-1)/g, crosses slices
    # empty replica_groups = ALL participants (g=16, spans both slices)
    ar2 = 2 * 16 * 4 * 15 / 16
    assert out["ops"] == 4
    assert out["ici_bytes"] == ag + rs
    assert out["dcn_bytes"] == ar + ar2


def test_analytic_fsdp_floor_and_single_chip_zero():
    from kubeflow_tpu.parallel.aot import analytic_fsdp_collective_bytes

    p = 100.0
    out = analytic_fsdp_collective_bytes(p, {"fsdp": 4, "dcn_data": 2})
    assert out["ici_bytes"] == 3 * p * 3 / 4
    assert out["dcn_bytes"] == 2 * (p / 4) * 1 / 2
    none = analytic_fsdp_collective_bytes(p, {})
    assert none == {"ici_bytes": 0.0, "dcn_bytes": 0.0}
