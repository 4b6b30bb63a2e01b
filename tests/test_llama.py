import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel.sharding import tree_pspecs
from kubeflow_tpu.utils.pytree import tree_param_count

from conftest import paged_session


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_forward_shape(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    t1 = rng.integers(0, cfg.vocab_size, (1, 16)).astype(np.int32)
    t2 = t1.copy()
    t2[0, 10:] = (t2[0, 10:] + 1) % cfg.vocab_size
    l1 = llama.forward(params, jnp.asarray(t1), cfg)
    l2 = llama.forward(params, jnp.asarray(t2), cfg)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), rtol=2e-4, atol=2e-4
    )
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_decode_matches_forward(tiny):
    """The bucket prefill, its rows inserted into the pool, and the paged
    decode step must agree with the full forward pass."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    seq = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    full = llama.forward(params, jnp.asarray(seq), cfg)

    logits_p, step = paged_session(cfg, params, seq[:, :8])
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full[:, 7]), rtol=1e-3, atol=1e-3
    )
    for i in range(8, 12):
        np.testing.assert_allclose(
            np.asarray(step(seq[:, i])), np.asarray(full[:, i]),
            rtol=1e-3, atol=1e-3
        )


def test_param_axes_match_structure(tiny):
    cfg, params = tiny
    axes = llama.param_logical_axes(cfg)
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params))
        == jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: 0, axes,
                                   is_leaf=lambda x: isinstance(x, tuple))))
    # every axes tuple matches its param's rank
    flat_p = jax.tree_util.tree_leaves(params)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a)


def test_sharded_forward_matches_single(tiny, mesh8):
    cfg, params = tiny
    from jax.sharding import NamedSharding
    from kubeflow_tpu.parallel.sharding import tree_shardings

    shardings = tree_shardings(mesh8, llama.param_logical_axes(cfg))
    sharded = jax.device_put(params, shardings)
    tokens = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1))
    ref = llama.forward(params, tokens, cfg)
    out = jax.jit(lambda p, t: llama.forward(p, t, cfg))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flops_accounting():
    cfg = llama.llama3_8b()
    # ~8B params -> ~6*8e9 flops/token for fwd+bwd matmuls (rough sanity band)
    assert 3.5e10 < cfg.flops_per_token() < 6.5e10
