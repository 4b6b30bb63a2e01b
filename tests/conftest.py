"""Test config: force CPU with 8 virtual devices BEFORE any backend init.

This is the SURVEY.md §4 'distributed without a cluster' translation: all
mesh/sharding/collective logic is exercised on an 8-device CPU mesh in CI,
mirroring how the reference tests controllers with envtest and fake clients
instead of real GPUs.

The device-count flag and the platform are set through the environment
before jax is imported, so subprocess workers inherit them too.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# tier-1 never ran with a persistent compile cache; keep it so now that
# initialize()/load() always place one (utils/compile_cache.py): XLA:CPU's
# loader warns on every hit that the cached code may SIGILL on this host
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402

from kubeflow_tpu.parallel import MeshConfig, build_mesh  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` inside a hard wall-clock budget; the
    # heavyweight recovery e2es carry this mark and run via their own
    # make targets (test-elastic) instead
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 time-bounded run")


@pytest.fixture(scope="session")
def mesh8():
    """2x2x2 mesh: data=2, fsdp=2, tensor=2."""
    return build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))


@pytest.fixture(scope="session")
def mesh_fsdp8():
    return build_mesh(MeshConfig(fsdp=8))


@pytest.fixture(scope="session")
def mesh_expert():
    """data=2 x expert=4 mesh for MoE expert-parallel tests."""
    return build_mesh(MeshConfig(data=2, fsdp=1, expert=4))


_kube_servers = []


def paged_session(cfg, params, prompts, max_seq=32, block_size=8):
    """The programs the engine runs, without the engine: the model's
    bucket prefill over ``prompts`` ([B, n], right-padded to whole
    blocks), its rows through ``paged_insert_batch`` into a pool, then one
    ``paged_decode_step`` (gather) a token. Returns (logits [B, V] at each
    prompt's last token, ``step(tokens [B]) -> logits [B, V]``)."""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.serving import paged_kv

    prompts = np.asarray(prompts, np.int32)
    b, n = prompts.shape
    per_slot = max_seq // block_size
    nb = -(-n // block_size)
    tables = 1 + np.arange(b * per_slot, dtype=np.int32).reshape(b, per_slot)
    toks = np.zeros((b, nb * block_size), np.int32)
    toks[:, :n] = prompts
    lengths = jnp.full((b,), n, jnp.int32)
    logits, rows = paged_kv.paged_ops(cfg).bucket_prefill(
        params, jnp.asarray(toks), lengths)
    cache = paged_kv.paged_insert_batch(
        paged_kv.init_paged_cache(cfg, b, max_seq, block_size,
                                  b * per_slot + 1),
        rows["k"], rows["v"], jnp.asarray(tables[:, :nb]), lengths,
        jnp.arange(b))

    def step(tokens):
        nonlocal cache
        logits, cache, _ = paged_kv.paged_decode_step(
            params, jnp.asarray(tokens, jnp.int32), cfg, cache,
            jnp.asarray(tables), kernel="gather")
        return logits

    return logits, step


def make_test_cluster():
    """Cluster factory for the controller suites. Default: FakeCluster.
    KFT_TEST_CLUSTER=kube swaps in KubeCluster over an in-process fake
    apiserver (the envtest role), so the SAME suites prove the reconciler
    drives a Kubernetes REST API — pod phases then travel through status
    PATCHes instead of in-memory pokes."""
    if os.environ.get("KFT_TEST_CLUSTER") == "kube":
        from kubeflow_tpu.controller import FakeKubeApiServer, KubeCluster

        srv = FakeKubeApiServer().start()
        _kube_servers.append(srv)
        cluster = KubeCluster(srv.url)
        cluster._test_server = srv
        return cluster
    from kubeflow_tpu.controller import FakeCluster

    return FakeCluster()


@pytest.fixture(autouse=True)
def _stop_kube_servers():
    """Release each test's fake apiservers (threads + sockets) at test
    teardown instead of accumulating them for the whole session."""
    mark = len(_kube_servers)
    yield
    while len(_kube_servers) > mark:
        _kube_servers.pop().stop()
