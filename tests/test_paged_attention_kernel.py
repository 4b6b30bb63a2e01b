"""Block-resident paged GQA decode kernel vs the gather reference oracle.

Runs the kernel in interpret mode on CPU (SURVEY.md §4: accelerator logic
must be testable without accelerators) — the SAME kernel logic compiles
for TPU, where it is the LLMEngine's default decode path and is timed
against the gather path every bench run (bench.py decode roofline).

Tolerances follow tests/test_pallas_attention.py: 2e-5 for f32 inputs,
2e-2 for bf16.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.attention import decode_attention
from kubeflow_tpu.ops.pallas_paged_attention import (
    _chunk_blocks, paged_decode_attention,
)
from kubeflow_tpu.serving import paged_kv


def _pool_case(key, b, h, kvh, d, bs, nbp, kv_len, dtype=jnp.float32,
               num_blocks=None, layers=1):
    """Random q/pools [layers, nb, bs, kvh, d] (every layer its own
    contents) plus a block table assigning each slot ``nlive`` distinct
    (permuted) pool blocks for its ``kv_len`` rows."""
    rng = np.random.default_rng(int(jax.random.key_data(key)[-1]))
    nb = num_blocks or (b * nbp + 1)
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((layers, nb, bs, kvh, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((layers, nb, bs, kvh, d)), dtype)
    tables = np.zeros((b, nbp), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    i = 0
    for s in range(b):
        nlive = -(-int(kv_len[s]) // bs)
        tables[s, :nlive] = perm[i:i + nlive]
        i += nlive
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(kv_len, jnp.int32)


def _gather_ref(q, kp, vp, layer, tables, kv_len):
    k_view = kp[layer][tables].reshape(q.shape[0], -1, *kp.shape[3:])
    v_view = vp[layer][tables].reshape(q.shape[0], -1, *vp.shape[3:])
    return decode_attention(q[:, None], k_view, v_view, kv_len)[:, 0]


def _assert_parity(case, layer=0, rtol=2e-5, atol=2e-5):
    q, kp, vp, tables, kv_len = case
    out = paged_decode_attention(q, kp, vp, layer, tables, kv_len,
                                 interpret=True)
    ref = _gather_ref(q, kp, vp, layer, tables, kv_len)
    live = np.asarray(kv_len) > 0
    # idle (len 0) slots are never read downstream (the engine masks
    # them); there only defined-ness matters
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32))[live],
        np.asarray(ref.astype(jnp.float32))[live], rtol=rtol, atol=atol)


def test_head_dim_64_groups_2():
    """The proxy shape the stock pallas paged-attention kernel refuses to
    lower: head_dim 64, two query heads per KV head."""
    kv_len = [1, 7, 16, 17, 64]   # fresh, partial, exact-block, cross, full
    _assert_parity(_pool_case(jax.random.key(0), b=5, h=4, kvh=2, d=64,
                              bs=16, nbp=4, kv_len=kv_len))


def test_bench_shape():
    """llama_1b decode geometry as the serving bench runs it: H=16, KV=8,
    D=128, block 64, arena 320 (5 blocks/slot)."""
    kv_len = [129, 193, 250, 320]
    _assert_parity(_pool_case(jax.random.key(1), b=4, h=16, kvh=8, d=128,
                              bs=64, nbp=5, kv_len=kv_len))


def test_ragged_lengths_and_idle_slots():
    """Live lengths raggedly spread over the table, INCLUDING len=0 idle
    slots (all-zero table rows — the kernel must leave defined, finite
    output without touching live blocks) and len=1 fresh slots."""
    kv_len = [0, 1, 5, 8, 9, 24, 0, 13]
    case = _pool_case(jax.random.key(2), b=8, h=4, kvh=2, d=32,
                      bs=8, nbp=3, kv_len=kv_len)
    q, kp, vp, tables, kv_len_j = case
    out = paged_decode_attention(q, kp, vp, 0, tables, kv_len_j,
                                 interpret=True)
    ref = _gather_ref(q, kp, vp, 0, tables, kv_len_j)
    assert bool(jnp.isfinite(out).all())
    # live slots must match the oracle exactly; idle (len 0) slots are
    # never read downstream (the engine masks them), only defined-ness
    # matters there
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


def test_shared_prefix_blocks():
    """Two slots whose tables point at the SAME pool blocks (the prefix
    cache sharing case) must both read them correctly."""
    q, kp, vp, tables, kv_len = _pool_case(
        jax.random.key(3), b=2, h=4, kvh=2, d=32, bs=8, nbp=4,
        kv_len=[24, 24])
    shared = np.array(tables)
    shared[1, :2] = shared[0, :2]          # share the first two blocks
    _assert_parity((q, kp, vp, jnp.asarray(shared), kv_len))


def test_bf16_pool():
    q, kp, vp, tables, kv_len = _pool_case(
        jax.random.key(4), b=3, h=4, kvh=2, d=64, bs=16, nbp=2,
        kv_len=[9, 16, 30], dtype=jnp.bfloat16)
    out = paged_decode_attention(q, kp, vp, 0, tables, kv_len,
                                 interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _gather_ref(q, kp, vp, 0, tables, kv_len)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32),
        rtol=2e-2, atol=2e-2)


def _quantize_pool(pool, qmax=127.0, dtype=jnp.int8):
    """Per-block per-kv-head symmetric quantization of a full-precision
    [L, NB, bs, KVH, D] pool -> (q pool, scale [L, NB, KVH] f32), the
    engine pool's own scheme (tests/test_quant.py shares it)."""
    amax = jnp.max(jnp.abs(pool.astype(jnp.float32)), axis=(2, 4))
    scale = jnp.maximum(amax / qmax, 1e-30)
    q = pool.astype(jnp.float32) / scale[:, :, None, :, None]
    if jnp.issubdtype(dtype, jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(dtype), scale.astype(jnp.float32)


def _dequant(pool, scale):
    return pool.astype(jnp.float32) * scale[:, :, None, :, None]


# blocks of the serving cells' shape (64 tokens x KV_H x 128): 8 kv heads
# under 16 query heads (the dense cells) and 2 under 8 (the CCA model, whose
# pool the kernel reads with a block's (token, kv head) rows merged), under a
# table of two chunks and four blocks of whatever chunk the wrapper derives
# for the shape (8 blocks at 8 heads, 16 at 2: the table grows with it)
CELL_BS, CELL_D = 64, 128
CELL_HEADS = {8: 16, 2: 8}


def _cell_table(kvh):
    """(G, table entries): the blocks a chunk of the kernel's walk at the
    cells' block of ``kvh`` heads, and a table that holds two and more."""
    g = _chunk_blocks(CELL_BS, kvh, CELL_D, 10 ** 6)
    nbp = 2 * g + 4
    assert 1 < g == _chunk_blocks(CELL_BS, kvh, CELL_D, nbp)
    return g, nbp


def _cell_block_case(pool_dtype, kvh, key, kv_len, layers=2):
    """A batch of the lengths ``kv_len(G, table entries)`` over a pool of
    the cells' blocks; for an int8 pool also the scales to hand the kernel
    and the dequantized pools the oracle reads."""
    g, nbp = _cell_table(kvh)
    kv_len = kv_len(g, nbp)
    dtype = jnp.float32 if pool_dtype == "f32" else jnp.bfloat16
    q, kp, vp, tables, kvl = _pool_case(
        key, b=len(kv_len), h=CELL_HEADS[kvh], kvh=kvh, d=CELL_D, bs=CELL_BS,
        nbp=nbp, kv_len=kv_len, dtype=dtype, layers=layers,
        num_blocks=sum(-(-n // CELL_BS) for n in kv_len) + 1)
    return q, kp, vp, tables, kvl


def _maybe_int8(pool_dtype, kp, vp):
    """(pools for the kernel, its scale arguments, pools for the oracle)."""
    if pool_dtype != "int8":
        return kp, vp, {}, kp, vp
    (kq, ks), (vq, vs) = _quantize_pool(kp), _quantize_pool(vp)
    return (kq, vq, dict(k_scale=ks, v_scale=vs),
            _dequant(kq, ks), _dequant(vq, vs))


# one trace and compile per shape: the kernel in interpret mode takes ten
# seconds to build at a chunk of 16 blocks and no time to run, and the layer
# is an operand
_kernel = jax.jit(functools.partial(paged_decode_attention, interpret=True))


def _live_rows(x, kvl):
    return np.asarray(x.astype(jnp.float32))[np.asarray(kvl) > 0]


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("block", ["8x2x32", "64x8x128", "64x2x128"])
def test_layer_addressed_kernel_reads_its_own_layer(block, pool_dtype, layer):
    """The kernel is given the WHOLE pool and a layer index. Three layers
    with different contents, ragged lengths, idle slots, lengths on and
    across a block boundary (at the cells' blocks across a chunk's too):
    each layer's output is that layer's oracle, and no other's."""
    if block == "8x2x32":
        kv_len = [0, 1, 7, 8, 9, 24, 0, 13]
        q, kp, vp, tables, kvl = _pool_case(
            jax.random.key(20), b=8, h=4, kvh=2, d=32, bs=8, nbp=3,
            kv_len=kv_len, dtype=jnp.bfloat16, layers=3)
    else:
        q, kp, vp, tables, kvl = _cell_block_case(
            "bf16", int(block.split("x")[1]), jax.random.key(21),
            lambda g, nbp: [0, 1, CELL_BS, CELL_BS + 1, 0,
                            g * CELL_BS + 13], layers=3)
    kp, vp, scales, kd, vd = _maybe_int8(pool_dtype, kp, vp)
    out = _kernel(q, kp, vp, jnp.int32(layer), tables, kvl, **scales)
    got = _live_rows(out, kvl)
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    for other in range(3):
        ref = _live_rows(_gather_ref(q, kd.astype(q.dtype),
                                     vd.astype(q.dtype), other, tables, kvl),
                         kvl)
        if other == layer:
            np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)
        else:
            assert np.abs(got - ref).max() > 0.1


@pytest.mark.parametrize("kvh", [8, 2])
@pytest.mark.parametrize("pool_dtype", ["f32", "bf16", "int8"])
def test_lengths_on_every_boundary_of_the_walk(pool_dtype, kvh):
    """A slot is walked in chunks of G pool blocks, G from the shapes:
    idle, one token, a block, a block and one, a chunk, a chunk and one,
    one short of two chunks, the table's end — mixed in one batch, read
    from layer 1 of the pool. Every kv head holds rows of its own: the
    oracle on a pool with the kv heads in reverse order (a query head
    reading another group's rows) is far from what the kernel gives."""
    bs = CELL_BS
    q, kp, vp, tables, kvl = _cell_block_case(
        pool_dtype, kvh, jax.random.key(30),
        lambda g, nbp: [0, 1, bs, bs + 1, g * bs, g * bs + 1,
                        2 * g * bs - 1, nbp * bs])
    kp, vp, scales, kd, vd = _maybe_int8(pool_dtype, kp, vp)
    out = _kernel(q, kp, vp, jnp.int32(1), tables, kvl, **scales)
    assert out.dtype == q.dtype and bool(jnp.isfinite(out).all())
    tol = 2e-5 if pool_dtype == "f32" else 2e-2
    got = _live_rows(out, kvl)
    qf = q.astype(jnp.float32)
    for layer in (0, 1):
        ref = _live_rows(_gather_ref(qf, kd, vd, layer, tables, kvl), kvl)
        if layer == 1:
            np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
        else:
            assert np.abs(got - ref).max() > 0.05
    swapped = _live_rows(_gather_ref(qf, kd[..., ::-1, :], vd[..., ::-1, :],
                                     1, tables, kvl), kvl)
    assert np.abs(got - swapped).max() > 0.05


@pytest.mark.parametrize("kvh", [8, 2])
@pytest.mark.parametrize("pool_dtype", ["f32", "int8"])
def test_dead_blocks_are_never_read_and_stale_rows_never_count(pool_dtype,
                                                               kvh):
    """Every pool block that no LIVE table entry names is NaN (block 0,
    which the dead entries name, among them; for an int8 pool its scales
    are), and short slots follow long ones, so the buffer they are copied
    into still holds the long slot's blocks beyond their own: the output
    is finite and the oracle's on the clean pool."""
    bs = CELL_BS
    q, kp, vp, tables, kvl = _cell_block_case(
        pool_dtype, kvh, jax.random.key(31),
        lambda g, nbp: [2 * g * bs, 3, 0, nbp * bs, bs + 1, (g + 1) * bs, 1])
    named = np.zeros(kp.shape[1], bool)
    for s, n in enumerate(np.asarray(kvl)):
        named[np.asarray(tables)[s, :-(-int(n) // bs)]] = True
    assert not named[0] and named[1:].all()
    # some blocks beyond the named ones too
    pad = [(0, 0), (0, 3)] + [(0, 0)] * 3
    kp, vp = jnp.pad(kp, pad), jnp.pad(vp, pad)
    dead = jnp.asarray(np.concatenate([~named, [True] * 3]))
    kp, vp, scales, kd, vd = _maybe_int8(pool_dtype, kp, vp)
    ref = _gather_ref(q, kd, vd, 1, tables, kvl)
    if pool_dtype == "int8":
        poison = dead[None, :, None]
        scales = {name: jnp.where(poison, jnp.nan, sc)
                  for name, sc in scales.items()}
    else:
        poison = dead[None, :, None, None, None]
        kp, vp = jnp.where(poison, jnp.nan, kp), jnp.where(poison, jnp.nan, vp)
    out = _kernel(q, kp, vp, jnp.int32(1), tables, kvl, **scales)
    assert bool(jnp.isfinite(out).all())
    tol = 2e-5 if pool_dtype == "f32" else 2e-2
    np.testing.assert_allclose(_live_rows(out, kvl), _live_rows(ref, kvl),
                               rtol=tol, atol=tol)


def test_rejects_bad_shapes():
    q, kp, vp, tables, kv_len = _pool_case(
        jax.random.key(5), b=2, h=4, kvh=2, d=32, bs=8, nbp=2,
        kv_len=[4, 4])
    with pytest.raises(ValueError, match="multiple"):
        paged_decode_attention(q[:, :3], kp, vp, 0, tables, kv_len,
                               interpret=True)
    with pytest.raises(ValueError, match="head_dim"):
        paged_decode_attention(q[..., :16], kp, vp, 0, tables, kv_len,
                               interpret=True)


def test_decode_step_block_boundary_crossing():
    """Full paged_decode_step parity, kernel vs gather, over decode steps
    in which one slot's length crosses a block boundary (7 -> 8 -> 9 with
    block_size 8: the write cursor moves to a new table block mid-decode)
    while another slot sits idle at len 0."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    pk = paged_kv.PagedKV(cfg=cfg, max_batch=3, max_seq=32, block_size=8,
                          num_blocks=13)
    assert pk.reserve(0, 7, 8) is not None
    assert pk.reserve(2, 3, 8) is not None      # slot 1 stays idle
    cache_g = jax.tree.map(jnp.copy, pk.cache)
    cache_g["len"] = jnp.asarray([7, 0, 3], jnp.int32)
    cache_p = jax.tree.map(jnp.copy, cache_g)
    tables = jnp.asarray(pk.tables)
    tok = jnp.asarray([5, 0, 9], jnp.int32)
    for _ in range(3):
        lg, cache_g, _ = paged_kv.paged_decode_step(
            params, tok, cfg, cache_g, tables, kernel="gather")
        lp, cache_p, _ = paged_kv.paged_decode_step(
            params, tok, cfg, cache_p, tables, kernel="pallas")
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lp),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(cache_g["len"]),
                                  np.asarray(cache_p["len"]))
    # the pools themselves stayed in lockstep (same scatter, no view)
    np.testing.assert_allclose(np.asarray(cache_g["k"]),
                               np.asarray(cache_p["k"]), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("quant_kv", ["none", "int8"])
@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_engine_decode_chunk_equals_single_steps(kernel, quant_kv):
    """``LLMEngine._decode`` over a chunk of 8 (the pool a carry of both
    loops, donated) gives the tokens and the pool contents of eight
    single-step dispatches: one slot crosses a block boundary mid-chunk
    (5 -> 13, block 8), one is idle."""
    from kubeflow_tpu.serving.llm import LLMEngine
    from kubeflow_tpu.serving.scheduler import QuantConfig

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    eng = LLMEngine(params, cfg, max_batch=3, max_seq=32,
                    prefill_buckets=(8,), kv_block_size=8, kv_num_blocks=13,
                    kernel=kernel, quant=QuantConfig(kv_dtype=quant_kv))
    assert eng.paged.reserve(0, 5, 12) is not None
    assert eng.paged.reserve(2, 3, 12) is not None      # slot 1 stays idle
    rng = np.random.default_rng(3)
    cache = dict(eng.cache)
    for key in ("k", "v"):                  # something resident to attend
        x = rng.standard_normal(cache[key].shape) * (
            20 if quant_kv == "int8" else 1)
        cache[key] = jnp.asarray(x, jnp.float32).astype(cache[key].dtype)
    if quant_kv == "int8":
        cache["k_scale"] = jnp.full_like(cache["k_scale"], 0.05)
        cache["v_scale"] = jnp.full_like(cache["v_scale"], 0.05)
    cache["len"] = jnp.asarray([5, 0, 3], jnp.int32)
    b = eng.max_batch
    args = (jnp.asarray(eng.paged.tables), jnp.asarray([True, False, True]),
            jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), jnp.float32))
    tok0 = jnp.asarray([5, 0, 9], jnp.int32)

    def run(chunk_len, n):
        c, tok, toks = jax.tree.map(jnp.copy, cache), tok0, []
        for i in range(n):
            t, _, tok, c, _ = eng._decode(
                eng.params, tok, c, *args, jax.random.key(i),
                greedy_only=True, kernel=eng.kernel, chunk_len=chunk_len)
            toks.append(np.asarray(t))
        return np.concatenate(toks), c

    toks8, cache8 = run(8, 1)
    toks1, cache1 = run(1, 8)
    live = [0, 2]
    np.testing.assert_array_equal(toks8[:, live], toks1[:, live])
    np.testing.assert_array_equal(np.asarray(cache8["len"]), [13, 0, 11])
    np.testing.assert_array_equal(np.asarray(cache8["len"]),
                                  np.asarray(cache1["len"]))
    for key in ("k", "v"):
        a8 = np.asarray(cache8[key], np.float32)
        a1 = np.asarray(cache1[key], np.float32)
        if quant_kv == "int8":          # payloads within one quant step
            assert np.abs(a8 - a1).max() <= 1
            np.testing.assert_allclose(
                np.asarray(cache8[key + "_scale"]),
                np.asarray(cache1[key + "_scale"]), rtol=1e-5)
        else:
            np.testing.assert_allclose(a8, a1, rtol=1e-5, atol=1e-6)
        # the chunk wrote rows, every layer: the pool is not what came in
        assert (a8 != np.asarray(cache[key], np.float32)).any(axis=(1, 2, 3,
                                                                    4)).all()


def test_kernel_resolution():
    """"auto" resolves to gather off-TPU; an explicit "pallas" holds on
    CPU (interpret mode) so the suite exercises the real kernel logic."""
    assert paged_kv._resolve_decode_kernel("auto") == "gather"
    assert paged_kv._resolve_decode_kernel("pallas") == "pallas"
    assert paged_kv._resolve_decode_kernel("gather") == "gather"
    with pytest.raises(ValueError, match="kernel"):
        paged_kv._resolve_decode_kernel("vortex")


def test_kernel_resolution_under_mesh():
    """The ISSUE-11 downgrade fix: on TPU, "auto" under a tensor mesh
    resolves to the shard_map'd pallas path (no more silent gather);
    a topology the wrapper can't shard downgrades WITH a reason the
    engine counts; explicit "pallas" under a mesh is now a real path,
    not an error."""
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor=2))
    # platform=tpu simulated: the platform rule is separable from the
    # mesh rule, so the TPU resolution is testable from the CPU suite
    k, why = paged_kv.resolve_decode_kernel(
        "auto", mesh=mesh, n_kv_heads=8, platform="tpu")
    assert (k, why) == ("pallas", None)
    k, why = paged_kv.resolve_decode_kernel(
        "pallas", mesh=mesh, n_kv_heads=8, platform="cpu")
    assert (k, why) == ("pallas", None)
    # unsupported topology: kv heads not divisible by the tensor axis
    k, why = paged_kv.resolve_decode_kernel(
        "pallas", mesh=mesh, n_kv_heads=3, platform="tpu")
    assert k == "gather" and "n_kv_heads" in why
    # a mixed topology's extra axes are replication, not a downgrade
    mesh2 = build_mesh(MeshConfig(data=2, tensor=2))
    k, why = paged_kv.resolve_decode_kernel(
        "auto", mesh=mesh2, n_kv_heads=8, platform="tpu")
    assert (k, why) == ("pallas", None)
    # gpu: no mosaic path at all — reason says so
    k, why = paged_kv.resolve_decode_kernel("pallas", platform="gpu")
    assert k == "gather" and "gpu" in why
    # "auto" off-TPU is a PLATFORM rule, not a downgrade: no reason
    assert paged_kv.resolve_decode_kernel(
        "auto", mesh=mesh, n_kv_heads=8) == ("gather", None)


def _sharded_case(key, mesh, b, h, kvh, d, bs, nbp, kv_len,
                  dtype=jnp.float32, layers=1):
    from jax.sharding import NamedSharding, PartitionSpec as P

    q, kp, vp, tables, kvl = _pool_case(key, b, h, kvh, d, bs, nbp, kv_len,
                                        dtype=dtype, layers=layers)
    pool_sh = NamedSharding(mesh, P(None, None, None, "tensor", None))
    q = jax.device_put(q, NamedSharding(mesh, P(None, "tensor", None)))
    return (q, jax.device_put(kp, pool_sh), jax.device_put(vp, pool_sh),
            tables, kvl)


def test_sharded_kernel_exact_parity_vs_sharded_gather_oracle():
    """The tentpole contract: the shard_map'd kernel over REALLY-sharded
    pools (tensor=2, kv-head dim distributed) matches the sharded gather
    oracle exactly — ragged lengths, idle slots, block crossings."""
    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_sharded,
    )
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor=2))
    kv_len = [0, 1, 7, 16, 17, 24]
    q, kp, vp, tables, kvl = _sharded_case(
        jax.random.key(6), mesh, b=6, h=8, kvh=4, d=32, bs=8, nbp=3,
        kv_len=kv_len)
    out = jax.jit(lambda *a: paged_decode_attention_sharded(
        *a, mesh=mesh, interpret=True))(q, kp, vp, 0, tables, kvl)
    # oracle: the SAME sharded arrays through the gather path (XLA
    # auto-partitions it — historically the only mesh-partitionable path)
    ref = jax.jit(_gather_ref)(q, kp, vp, 0, tables, kvl)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    assert bool(jnp.isfinite(out).all())


def test_sharded_kernel_gqa_groups_parity():
    """GQA grouping under sharding: 2 query heads per KV head, split over
    tensor=2 — each shard sees 2 KV heads x 2 groups and must reproduce
    the unsharded oracle."""
    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_sharded,
    )
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor=2))
    q, kp, vp, tables, kvl = _sharded_case(
        jax.random.key(7), mesh, b=3, h=8, kvh=4, d=64, bs=16, nbp=2,
        kv_len=[9, 16, 30])
    out = jax.jit(lambda *a: paged_decode_attention_sharded(
        *a, mesh=mesh, interpret=True))(q, kp, vp, 0, tables, kvl)
    ref = _gather_ref(q, kp, vp, 0, tables, kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tensor", [2, 4])
def test_sharded_kernel_with_the_layer_dimension(tensor):
    """The shard_map'd kernel over a whole 3-layer pool sharded on its
    kv-head dim (the 8 host devices tests/conftest.py forces): the layer
    rides replicated, each layer reads its own contents."""
    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_sharded,
    )
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor=tensor))
    kv_len = [0, 1, 7, 16, 17, 24]
    q, kp, vp, tables, kvl = _sharded_case(
        jax.random.key(9), mesh, b=6, h=8, kvh=4, d=32, bs=8, nbp=3,
        kv_len=kv_len, layers=3)
    assert kp.sharding.spec[3] == "tensor"
    fn = jax.jit(lambda *a: paged_decode_attention_sharded(
        *a, mesh=mesh, interpret=True))
    live = np.asarray(kv_len) > 0
    outs = [np.asarray(fn(q, kp, vp, jnp.int32(layer), tables, kvl))
            for layer in range(3)]
    for layer, out in enumerate(outs):
        ref = _gather_ref(q, kp, vp, layer, tables, kvl)
        np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                                   rtol=2e-5, atol=2e-5)
    assert np.abs(outs[0][live] - outs[1][live]).max() > 0.1


def test_sharded_kernel_rejects_unshardable_topology():
    from kubeflow_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_sharded, shard_unsupported_reason,
    )
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor=4))
    assert shard_unsupported_reason(mesh, 4) is None
    assert "n_kv_heads" in shard_unsupported_reason(mesh, 2)
    q, kp, vp, tables, kvl = _pool_case(
        jax.random.key(8), b=2, h=4, kvh=2, d=32, bs=8, nbp=2,
        kv_len=[4, 4])
    with pytest.raises(ValueError, match="n_kv_heads"):
        paged_decode_attention_sharded(q, kp, vp, 0, tables, kvl,
                                       mesh=mesh, interpret=True)


def test_sharded_decode_step_end_to_end_parity():
    """Full paged_decode_step under a tensor mesh: pallas (shard_map'd)
    vs gather (auto-partitioned) stay in lockstep across decode steps
    with sharded pools — the engine-level form of the tentpole claim."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from kubeflow_tpu.parallel import MeshConfig, build_mesh

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    mesh = build_mesh(MeshConfig(tensor=2))
    kv_sh = NamedSharding(mesh, P(None, None, None, "tensor", None))
    pk = paged_kv.PagedKV(cfg=cfg, max_batch=2, max_seq=32, block_size=8,
                          num_blocks=9, kv_sharding=kv_sh,
                          len_sharding=NamedSharding(mesh, P()))
    assert pk.reserve(0, 7, 8) is not None
    assert pk.reserve(1, 3, 8) is not None
    cache_g = jax.tree.map(jnp.copy, pk.cache)
    cache_g["len"] = jnp.asarray([7, 3], jnp.int32)
    cache_p = jax.tree.map(jnp.copy, cache_g)
    tables = jnp.asarray(pk.tables)
    tok = jnp.asarray([5, 9], jnp.int32)
    for _ in range(3):
        lg, cache_g, _ = paged_kv.paged_decode_step(
            params, tok, cfg, cache_g, tables, kernel="gather")
        lp, cache_p, _ = paged_kv.paged_decode_step(
            params, tok, cfg, cache_p, tables, kernel="pallas", mesh=mesh)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lp),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    np.testing.assert_allclose(np.asarray(cache_g["k"]),
                               np.asarray(cache_p["k"]), rtol=1e-5,
                               atol=1e-5)
