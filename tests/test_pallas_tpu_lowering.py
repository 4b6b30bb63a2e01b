"""Every Pallas entry point, lowered and compiled for a v5e by Mosaic.

The interpret-mode parity suites prove the kernels' logic on the CPU; only
the TPU compiler says whether a chip accepts their block shapes (the int8
paged-decode kernel passed every interpret test while its scale BlockSpec
was one Mosaic refuses). The target is the compile-only v5e topology the
installed libtpu provides — no hardware — at llama_1b shapes and at the
two serving cells' (benchmarks/traffic), layers cut to 4.

The same compiler says whether the decode program updates the KV pool in
place: ``paged_kv.pool_shaped_ops`` must find nothing pool-sized in its
optimized HLO, and must find the ten such instructions of the program as
it was (``PARENT_HLO``, the lines of PR 26's program that matter).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops.pallas_attention import flash_attention
from kubeflow_tpu.ops.pallas_paged_attention import (
    paged_decode_attention, paged_decode_attention_sharded,
    paged_latent_decode_attention,
)
from kubeflow_tpu.models import cca_moe, llama, mla_moe
from kubeflow_tpu.parallel.aot import topology_devices
from kubeflow_tpu.serving import paged_kv

H, KVH, D = 16, 8, 128                   # llama_1b heads
B, BS, NBP = 32, 64, 5                   # the engine at max_seq 320
NB = B * NBP + 1
L = 4                                    # layers in the pool
# the serving cells' engines (benchmarks/traffic/*.json)
CELLS = {"chat": dict(h=32, b=32, nb=545, nbp=40),
         "offline": dict(h=16, b=36, nb=640, nbp=24),
         # long tables (8 x 8,192 tokens; 8 x 26,624, the longctx cell's
         # max_seq): the kernel's chunk is derived from the shapes
         "long128": dict(h=32, b=8, nb=8 * 128 + 1, nbp=128),
         "long416": dict(h=32, b=8, nb=8 * 416 + 1, nbp=416),
         # zaya1-8b-l20.reasoning-offline: 8 query heads over 2 KV heads
         "reasoning": dict(h=8, kvh=2, b=64, nb=3072, nbp=72)}
# the CCA model refuses a quantized pool, and 2 KV heads a tensor mesh of 4
CELL_VARIANTS = [(cell, variant) for cell in sorted(CELLS)
                 for variant in ("bf16", "int8", "tensor4")
                 if cell != "reasoning" or variant == "bf16"]


@pytest.fixture(scope="module")
def v5e():
    return topology_devices("v5e:2x2")


def _compile(fn, sharding, *shapes):
    """Compile for the sharding's devices; returns the optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sh)
            for (s, dt), sh in zip(shapes, sharding)]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(pool_dtype, b=B, nb=NB, kvh=KVH, h=H, nbp=NBP):
    """q, the two whole pools, the layer, the tables, the lengths."""
    return [((b, h, D), jnp.bfloat16), ((L, nb, BS, kvh, D), pool_dtype),
            ((L, nb, BS, kvh, D), pool_dtype), ((), jnp.int32),
            ((b, nbp), jnp.int32), ((b,), jnp.int32)]


def _scales(nb=NB, kvh=KVH):
    return [((L, nb, kvh), jnp.float32)] * 2


def _positional(kernel, **kw):
    """The kernels take the scales by keyword; lower() wants positionals."""
    def fn(q, kp, vp, layer, tables, kv_len, *scales):
        ks, vs = scales or (None, None)
        return kernel(q, kp, vp, layer, tables, kv_len, k_scale=ks,
                      v_scale=vs, **kw)
    return fn


def _tensor_shardings(mesh, quantized):
    rep = NamedSharding(mesh, P())
    pool = NamedSharding(mesh, P(None, None, None, "tensor", None))
    sh = [NamedSharding(mesh, P(None, "tensor", None)), pool, pool,
          rep, rep, rep]
    if quantized:
        sh += [NamedSharding(mesh, P(None, None, "tensor"))] * 2
    return sh


def test_flash_fwd_and_bwd(v5e):
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    qkv = [((2, 2048, H, D), jnp.bfloat16)] + \
        [((2, 2048, KVH, D), jnp.bfloat16)] * 2

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=512,
                               block_kv=512).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), [one] * 3, *qkv)
    assert hlo.count("tpu_custom_call") >= 3          # fwd, dq, dk/dv


def test_paged_decode_bf16(v5e):
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    hlo = _compile(paged_decode_attention, [one] * 6,
                   *_paged_shapes(jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", ["1b", "8b"])
def test_paged_decode_int8(v5e, shape):
    """The 8B serve shape too: 32/8 heads, a pool of 8 x 8192 tokens."""
    kw = {} if shape == "1b" else dict(b=8, nb=8 * 128 + 1, h=32, nbp=128)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    hlo = _compile(_positional(paged_decode_attention), [one] * 8,
                   *_paged_shapes(jnp.int8, **kw),
                   *_scales(nb=kw.get("nb", NB)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_sharded_over_four_chips(v5e, quantized):
    mesh = Mesh(v5e, ("tensor",))
    shapes = _paged_shapes(jnp.int8 if quantized else jnp.bfloat16)
    if quantized:
        shapes += _scales()
    hlo = _compile(_positional(paged_decode_attention_sharded, mesh=mesh),
                   _tensor_shardings(mesh, quantized), *shapes)
    assert "tpu_custom_call" in hlo
    assert "all-reduce" not in hlo and "all-gather" not in hlo


@pytest.mark.parametrize("cell,variant", CELL_VARIANTS)
def test_paged_decode_at_the_cells_shapes(v5e, cell, variant):
    """The layer-addressed kernel reads blocks of the pool as it is stored,
    at the serving cells' heads, batch, tables and pool, and where a slot's
    table is hundreds of blocks long. WHICH view of the pool it is handed
    is decided by the shape, once a compiled program, and read here from
    the program's text: (bs, KV, D) blocks where 8 KV heads fill a sublane
    tile, (bs * KV, D) where 2 do not, and that view a bitcast of the
    stored pool."""
    quantized = variant == "int8"
    c = CELLS[cell]
    shapes = _paged_shapes(jnp.int8 if quantized else jnp.bfloat16, **c)
    if quantized:
        shapes += _scales(nb=c["nb"])
    if variant == "tensor4":
        mesh = Mesh(v5e, ("tensor",))
        fn = _positional(paged_decode_attention_sharded, mesh=mesh)
        sh = _tensor_shardings(mesh, quantized)
    else:
        fn = _positional(paged_decode_attention)
        sh = [NamedSharding(Mesh(v5e[:1], ("x",)), P())] * len(shapes)
    hlo = _compile(fn, sh, *shapes)
    assert hlo.count("tpu_custom_call") == 1
    # the kernel takes the pool itself: no slice, reshape or copy of it
    assert not paged_kv.pool_shaped_ops(hlo, [shapes[1][0]])
    if variant != "bf16":
        return
    kernel, = [line for line in hlo.splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    operands = kernel.split("operand_layout_constraints=")[1]
    kvh = c.get("kvh", KVH)
    view = f"{BS},{kvh},{D}" if kvh == KVH else f"{BS * kvh},{D}"
    pool = f"bf16[{L},{c['nb']},{view}]"
    assert operands.count(pool + "{") == 2, operands
    if kvh != KVH:
        made = re.findall(r" = " + re.escape(pool) + r"\S* ([\w\-]+)\(", hlo)
        assert made == ["bitcast"] * 2, made


def _decode_chunk_hlo(v5e, monkeypatch, quant_kv):
    """``paged_decode_step`` at the offline cell's pool under a 4-step
    scan with the cache donated, as ``LLMEngine._decode_impl`` runs it,
    compiled for one v5e. Returns (optimized HLO, the pool's shape)."""
    # the program asks the backend whether to interpret the kernel; this
    # process's backend is the CPU, the target is not
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = CELLS["offline"]
    cfg = llama.LlamaConfig(                  # InternLM2-1.8B, 4 layers
        vocab_size=92544, dim=2048, n_layers=L, n_heads=16, n_kv_heads=8,
        mlp_dim=8192, max_seq=2048, rope_scaling=None)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = jax.eval_shape(lambda: llama.init_params(
        jax.random.key(0), cfg, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, cell["b"], cell["nbp"] * BS, BS, cell["nb"],
        quant_kv=quant_kv))

    def chunk(params, token, cache, tables):
        def one_step(carry, _):
            token, cache = carry
            logits, cache, _ = paged_kv.paged_decode_step(
                params, token, cfg, cache, tables, kernel="pallas")
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache), None
        return jax.lax.scan(one_step, (token, cache), None, length=4)[0]

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((cell["b"],), jnp.int32, sharding=one),
        on_chip(cache),
        jax.ShapeDtypeStruct((cell["b"], cell["nbp"]), jnp.int32,
                             sharding=one)).compile()
    return compiled, cache["k"].shape


@pytest.mark.parametrize("quant_kv", ["none", "int8"])
def test_decode_chunk_updates_the_pool_in_place(v5e, monkeypatch, quant_kv):
    compiled, pool_shape = _decode_chunk_hlo(v5e, monkeypatch, quant_kv)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1      # one kernel per layer
    # the benchmark's readers find the kernel by this name
    assert re.search(r"%closed_call\.\d+ = \S+ custom-call\(", hlo)
    assert paged_kv.pool_shaped_ops(hlo, [pool_shape]) == []
    # no second pool among the temporaries (the parent: 1.16 GB here)
    pool_bytes = 2 * L * 640 * BS * KVH * D * (1 if quant_kv == "int8"
                                                else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 16


# joyai-llm-flash-l5.longctx-offline (benchmarks/traffic): 24 slots of 416
# blocks of 64 rows, 7,168 blocks, the published widths
LATENT = dict(b=24, nb=7168, nbp=416, h=32, row=640, value=512)


def test_latent_decode_kernel_at_the_cells_shapes(v5e):
    """The latent kernel reads [64, 640] blocks of the pool as it is stored
    (eight through as many BlockSpecs a grid step): Mosaic accepts it, and
    nothing relays or copies the pool in front of it. A row of 576 does
    compile too, but XLA then stores the pool in a compact transposed
    layout and copies all of it before every call (PERF.md, PR 29)."""
    c = LATENT
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    shapes = [((c["b"], c["h"], c["row"]), jnp.bfloat16),
              ((L, c["nb"], BS, c["row"]), jnp.bfloat16), ((), jnp.int32),
              ((c["b"], c["nbp"]), jnp.int32), ((c["b"],), jnp.int32)]

    def fn(q, pool, layer, tables, kv_len):
        return paged_latent_decode_attention(
            q, pool, layer, tables, kv_len, value_dim=c["value"],
            scale=192 ** -0.5)

    hlo = _compile(fn, [one] * 5, *shapes)
    assert hlo.count("tpu_custom_call") == 1
    assert not paged_kv.pool_shaped_ops(hlo, [shapes[1][0]])


def test_latent_decode_chunk_updates_the_pool_in_place(v5e, monkeypatch):
    """``paged_decode_step`` of the latent / routed-expert model at the
    cell's engine (1 dense + 2 expert layers of the published widths) under
    a 4-step scan with the cache donated: nothing pool-sized, the pool's
    bytes are NB x bs x row (no padded size-1 dimension, no second pool),
    and no layer's experts are sliced out of their stack."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = LATENT
    cfg = mla_moe.MlaMoeConfig(n_layers=3, n_predict_layers=0,
                               max_seq=c["nbp"] * BS)
    assert cfg.pool_row == c["row"] and cfg.row_dim == 576
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = jax.eval_shape(lambda: mla_moe.init_params(
        jax.random.key(0), cfg, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, c["b"], c["nbp"] * BS, BS, c["nb"]))
    assert set(cache) == {"kv", "len"}           # ONE pool
    pool_bytes = 3 * c["nb"] * BS * c["row"] * 2
    assert cache["kv"].size * 2 == pool_bytes

    def chunk(params, token, cache, tables):
        def one_step(carry, _):
            token, cache = carry
            logits, cache, stats = paged_kv.paged_decode_step(
                params, token, cfg, cache, tables, kernel="pallas")
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache), stats
        return jax.lax.scan(one_step, (token, cache), None, length=4)

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((c["b"],), jnp.int32, sharding=one),
        on_chip(cache),
        jax.ShapeDtypeStruct((c["b"], c["nbp"]), jnp.int32,
                             sharding=one)).compile()
    hlo = compiled.as_text()
    # the readers find the kernel (a dense and an expert stack: two call
    # sites) and the grouped products by these names
    assert len(re.findall(r"%closed_call\.\d+ = \S+ custom-call\(", hlo)) == 2
    assert len(re.findall(r"%gmm(\.\d+)? = \S+ custom-call\(", hlo)) == 3
    assert paged_kv.pool_shaped_ops(hlo, [cache["kv"].shape]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes      # updated in place
    # neither a copy of the pool nor one of a layer's experts (805 MB a
    # matrix) among the temporaries
    assert mem.temp_size_in_bytes < 2 ** 27
    experts = 256 * 2048 * 768
    assert not [line for line in hlo.splitlines() if re.search(
        r" = bf16\[(1,)?256,(2048,768|768,2048)\]\S* (fusion|copy|"
        r"dynamic-slice)\(", line)], "a layer's experts were sliced out"
    assert experts * 2 * 3 * 2 * 1.01 < mem.argument_size_in_bytes


def test_latent_prefill_chunk_is_one_kernel_a_layer_stack(v5e, monkeypatch):
    """``paged_prefill_chunk`` of the latent model at the cell's engine
    (chunk 2,048, 416 table entries, 7,168 blocks, the published widths, 1
    dense + 2 expert layers) with the cache donated, as the engine jits it:
    the chunk's attention is ONE Mosaic kernel per layer stack that takes
    the pool itself, nothing of a score tile's or a context's shape is left
    to XLA, and the program's temporaries are no more than they were with
    the float32 walk in plain XLA."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = LATENT
    chunk = 2048
    cfg = mla_moe.MlaMoeConfig(n_layers=3, n_predict_layers=0,
                               max_seq=c["nbp"] * BS)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = jax.eval_shape(lambda: mla_moe.init_params(
        jax.random.key(0), cfg, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, c["b"], c["nbp"] * BS, BS, c["nb"]))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)

    def prefill(params, tokens, cache, tables, slot, offset, length, share):
        return paged_kv.paged_prefill_chunk(params, tokens, cfg, cache,
                                            tables, slot, offset, length,
                                            share)

    compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one),
        on_chip(cache),
        jax.ShapeDtypeStruct((c["b"], c["nbp"]), jnp.int32, sharding=one),
        scalar, scalar, scalar, scalar).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    # a dense and an expert stack: two call sites of the attention kernel
    # (the benchmark's reader finds it by this name), beside the grouped
    # products
    attention = [k for k in kernels if re.search(
        r"%closed_call\.\d+ = bf16\[1,32,2048,128\]", k)]
    assert len(attention) == 2, kernels
    assert len([k for k in kernels if re.search(r"%gmm(\.\d+)? = ", k)]) == 3
    assert paged_kv.pool_shaped_ops(hlo, [cache["kv"].shape]) == []
    # the walk's score tiles [32, 2048, 1024] and a context's keys or values
    # [26624, 32, 128]: neither is anywhere in the program
    assert not re.search(r"f32\[(1,)?32,2048,\d+\]", hlo)
    assert not re.search(r"\[(1,)?26624,32,\d+\]", hlo)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache["kv"].size * 2
    # the parent's program (the walk in plain XLA) at these shapes
    assert mem.temp_size_in_bytes <= PARENT_PREFILL_TEMP_BYTES


# zaya1-8b-l20.reasoning-offline (benchmarks/traffic): 64 slots of 72
# blocks of 64 rows, 3,072 blocks, chunks of 512, the published widths
CCA = dict(b=64, nb=3072, nbp=72, chunk=512)


def _cca_on_chip(v5e, n_layers):
    cfg = cca_moe.CcaMoeConfig(n_layers=n_layers, max_seq=CCA["nbp"] * BS)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: cca_moe.init_params(
        jax.random.key(0), cfg, dtype=jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, CCA["b"], CCA["nbp"] * BS, BS, CCA["nb"])))
    return cfg, params, cache, lambda shape, dt=jnp.int32: \
        jax.ShapeDtypeStruct(shape, dt, sharding=one)


def test_cca_decode_chunk_updates_pool_and_state_in_place(v5e, monkeypatch):
    """``paged_decode_step`` of the CCA / top-1-expert model at the cell's
    engine (2 layers of the published widths) under a 4-step scan with the
    cache donated and the engine's dispatch mask: the GQA decode kernel as
    it stands, three grouped products whose weight tiles fit the scoped
    VMEM (experts as wide as the model: 2,048 x 2,048), K, V and the
    per-slot state updated in place, no layer's experts or projections
    copied out of their stack."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = CCA
    cfg, params, cache, sds = _cca_on_chip(v5e, 2)
    assert set(cache) == {"k", "v", "cca", "len"}
    assert cache["cca"].shape == (2, c["b"], 2688)
    held = sum(cache[key].size * 2 for key in ("k", "v", "cca"))

    def chunk(params, token, cache, tables, active):
        def one_step(carry, _):
            token, cache = carry
            logits, cache, stats = paged_kv.paged_decode_step(
                params, token, cfg, cache, tables, kernel="pallas",
                active=active)
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache), stats
        return jax.lax.scan(one_step, (token, cache), None, length=4)

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        params, sds((c["b"],)), cache, sds((c["b"], c["nbp"])),
        sds((c["b"],), bool)).compile()
    hlo = compiled.as_text()
    # the readers find the kernel and the grouped products by these names
    assert len(re.findall(r"%closed_call\.\d+ = \S+ custom-call\(", hlo)) == 1
    assert len(re.findall(r"%gmm(\.\d+)? = \S+ custom-call\(", hlo)) == 3
    assert paged_kv.pool_shaped_ops(hlo, [cache["k"].shape]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held            # updated in place
    # neither a pool, nor a layer's experts (134 MB a matrix), nor a stack
    # of projections among the temporaries
    assert mem.temp_size_in_bytes < 2 ** 25
    assert not [line for line in hlo.splitlines() if re.search(
        r" = bf16\[(1,)?16,2048,2048\]\S* (fusion|copy|dynamic-slice)\(",
        line)], "a layer's experts were sliced out"


def test_cca_prefill_chunk_at_the_cells_shapes(v5e, monkeypatch):
    """``paged_prefill_chunk`` of the same model with the cache donated, as
    the engine jits it: a chunk of 512 over 72 table entries compiles for
    the chip, K, V and the slot's state land in place, and the gathered
    views and score tiles stay far under a pool's size."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = CCA
    cfg, params, cache, sds = _cca_on_chip(v5e, 2)

    def prefill(params, tokens, cache, tables, slot, offset, length, share):
        return paged_kv.paged_prefill_chunk(params, tokens, cfg, cache,
                                            tables, slot, offset, length,
                                            share)

    compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
        params, sds((1, c["chunk"])), cache, sds((c["b"], c["nbp"])),
        sds(()), sds(()), sds(()), sds(())).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%gmm(\.\d+)? = \S+ custom-call\(", hlo)) == 3
    assert paged_kv.pool_shaped_ops(
        hlo, [cache["k"].shape, cache["cca"].shape]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        cache[key].size * 2 for key in ("k", "v", "cca"))
    assert mem.temp_size_in_bytes < 2 ** 28


# qwen3-next-80b-a3b-l8.longgen-offline (benchmarks/traffic): 256 slots of
# 96 blocks of 64 rows, the published widths, the cell's 8 layers (at 4, the
# conv ring is small enough that XLA parks all of it in VMEM for a call and
# writes it back: a move of the whole array); a pool cut to 1,100 blocks
QWEN = dict(b=256, nb=1100, nbp=96, layers=8)


def test_gdn_decode_kernel_at_the_cells_shapes(v5e):
    """``ops/pallas_gdn.gdn_decode`` over the cell's state (6 GDN layers x
    256 slots x 32 heads x 128 x 128 float32, 3.2 GB): Mosaic takes the
    [32, 128, 128] block a slot and the two gates as rows, the state is
    aliased from input to output (updated in place, no copy of it), and
    the custom call carries the kernel's name, which the readers match."""
    from kubeflow_tpu.ops.pallas_gdn import gdn_decode

    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    b, h, d, layers = QWEN["b"], 32, 128, 6
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    state = sds((layers, b, h, d, d))
    compiled = jax.jit(gdn_decode, donate_argnums=(5,)).lower(
        sds((b, h, d)), sds((b, h, d)), sds((b, h, d)), sds((b, h)),
        sds((b, h)), state, sds((), jnp.int32), sds((b,), bool)).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%gdn_decode(\.\d+)? = \(", hlo)) == 1
    assert paged_kv.pool_shaped_ops(hlo, [state.shape]) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        layers * b * h * d * d * 4


@pytest.fixture(scope="module")
def qwen(v5e):
    """The Qwen3-Next model's two serving programs compiled for the chip at
    the cell's engine (two periods of the published widths) as the engine
    jits them, the cache donated: a decode chunk (a 4-step scan with the
    engine's dispatch mask) and the 512-token prefill chunk. Returns (config,
    cache shapes, {"decode": compiled, "chunk": compiled})."""
    from kubeflow_tpu.models import qwen3_next

    q = QWEN
    cfg = qwen3_next.Qwen3NextConfig(n_layers=q["layers"], vocab_size=18992,
                                     n_experts_held=64,
                                     max_seq=q["nbp"] * BS)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: qwen3_next.init_params(
        jax.random.key(0), cfg, dtype=jnp.bfloat16)))
    cache = on_chip(jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, q["b"], q["nbp"] * BS, BS, q["nb"])))
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                            sharding=one)

    def decode(params, token, cache, tables, active):
        def one_step(carry, _):
            token, cache = carry
            logits, cache, stats = paged_kv.paged_decode_step(
                params, token, cfg, cache, tables, kernel="pallas",
                active=active)
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache), stats
        return jax.lax.scan(one_step, (token, cache), None, length=4)

    def chunk(params, tokens, cache, tables, slot, offset, length):
        return paged_kv.paged_prefill_chunk(params, tokens, cfg, cache,
                                            tables, slot, offset, length)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        programs = {
            "decode": jax.jit(decode, donate_argnums=(2,)).lower(
                params, sds((q["b"],)), cache, sds((q["b"], q["nbp"])),
                sds((q["b"],), bool)).compile(),
            "chunk": jax.jit(chunk, donate_argnums=(2,)).lower(
                params, sds((1, 512)), cache, sds((q["b"], q["nbp"])),
                sds(()), sds(()), sds(())).compile()}
    return cfg, cache, programs


def test_qwen3_next_decode_chunk_updates_pools_and_state_in_place(qwen):
    """``paged_decode_step`` of the Qwen3-Next model at the cell's engine
    (the ``qwen`` fixture's decode chunk): the GDN kernel once a GDN layer,
    the GQA kernel as it stands over a pool stored merged (at head_dim 256
    the 5-D pool's view would be a copy of both pools every call), three
    grouped products a layer, and K, V, S and the conv ring updated in
    place: nothing as large as any of them, nor as one of their layers."""
    _, cache, programs = qwen
    compiled = programs["decode"]
    hlo = compiled.as_text()
    assert len(re.findall(r"%gdn_decode(\.\d+)? = \(", hlo)) == 3
    assert len(re.findall(r"%closed_call\.\d+ = \S+ custom-call\(", hlo)) == 1
    assert len(re.findall(r"%gmm(\.\d+)? = \S+ custom-call\(", hlo)) == 12
    keys = ("k", "v", "gdn_s", "gdn_conv")
    assert paged_kv.pool_shaped_ops(
        hlo, [cache[key].shape for key in keys]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        cache[key].size * cache[key].dtype.itemsize for key in keys)
    assert mem.temp_size_in_bytes < 2 ** 30


def weight_copies(hlo, sizes):
    """Operations of an optimized HLO text, outside any fusion's body, that
    compute or copy a bf16 result (or tuple element) of one of the element
    counts ``sizes``: fusions, copies, slices, transposes. The moves of an
    operand into the chip's fast memory for its product (``copy-start``,
    ``slice-start``, their ``-done``, ``ConcatBitcast``) are how a product
    reads its weight, and are not listed. -> ["name opcode bf16[dims]"]"""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    comp, found = None, []
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(2)
            continue
        op = re.match(r"^  (?:ROOT )?%(\S+) = (.*?) ([a-z\-]+)\(", line)
        if comp in fused or not op or op.group(3) not in (
                "fusion", "copy", "dynamic-slice", "slice", "transpose"):
            continue
        for dims in re.findall(r"bf16\[([\d,]+)\]", op.group(2)):
            if math.prod(int(d) for d in dims.split(",")) in sizes:
                found.append(f"{op.group(1)} {op.group(3)} bf16[{dims}]")
    return found


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_qwen3_next_projections_reach_their_products_as_stored(qwen,
                                                               program):
    """The projection matrices go to their products in the buffer and the
    layout they are stored in: no layer, period or stack of ``w_qkvz`` (its
    product is permuted, not reshaped into heads, so the stack is not
    transposed once a call), no period or stack of ``w_out``, and no layer
    or stack of the full layers' ``w_q`` is computed or copied (each
    layer's matrix is ONE dynamic index into its whole stack, fused into
    the product). The programs as they were held eight such operations
    each: the stacks of ``w_qkvz`` and ``w_q`` transposed once a call, a
    period's three ``w_qkvz`` and ``w_out`` sliced out, layers of ``w_qkvz``
    sliced out of that, a layer of ``w_q`` sliced out; and 597.6 MB of
    temporaries in the decode program, 722.5 MB in the chunk program."""
    cfg, _, programs = qwen
    compiled = programs[program]
    layer = {"w_qkvz": cfg.dim * (2 * cfg.key_dim + 2 * cfg.value_dim),
             "w_out": cfg.value_dim * cfg.dim,
             "w_q": cfg.dim * 2 * cfg.n_heads * cfg.head_dim}
    n_gdn = cfg.n_layers // cfg.full_attention_interval * 3
    sizes = ({layer["w_qkvz"] * n for n in (1, 3, n_gdn)}
             | {layer["w_out"] * n for n in (3, n_gdn)}
             | {layer["w_q"] * n for n in (1, 2)})
    assert weight_copies(compiled.as_text(), sizes) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


def test_weight_copies_finds_what_the_parent_did():
    """So that the check cannot pass by seeing nothing: on the lines of the
    parent's decode program it finds the stack of ``w_qkvz`` transposed in
    ENTRY, the period's slice and the two layers sliced out of that, and
    not the move of a layer into fast memory nor what is inside a
    fusion."""
    found = weight_copies(PARENT_QWEN_HLO,
                          {2048 * 12288 * n for n in (1, 3, 6)})
    assert found == [
        "dynamic-slice_bitcast_fusion.47 fusion bf16[3,2048,12288]",
        "fusion.918 fusion bf16[1,2048,12288]",
        "fusion.918 fusion bf16[1,2048,12288]",
        "copy.538 copy bf16[6,2048,12288]"]
    assert weight_copies(PARENT_QWEN_HLO, {2048 * 12288 * 2}) == []



def test_gdn_prefill_metric_reads_the_chunk_scan(qwen):
    """``gdn_prefill.time_share_pct.longgen`` matches operations of the chunk
    program by their result type (the reduced trace keeps an operation's
    name, opcode and type, not the name scope: ``benchmarks/lib/xplane.
    short_name``). At the cell's shapes, every operation of the chunk scan
    (``jax.named_scope("gdn_chunk_scan")``) whose result holds 2^15 values
    or more is matched, and what else is matched moves the scan's state
    in or its outputs' buffers: the slot's S gathered and selected, copies,
    broadcasts."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    from lib import xplane

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "metrics", "gdn_prefill.time_share_pct.longgen"
                           ".json")) as f:
        matched = re.compile(json.load(f)["args"]["op"])
    hlo = qwen[2]["chunk"].as_text()
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    free = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
    comp, ops = None, []          # what runs: no fusion's inside
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(2)
        elif comp not in fused and re.match(r"^  (ROOT )?%", line):
            text = line.strip().removeprefix("ROOT ")
            name = xplane.short_name(text)
            scope = re.search(r'op_name="([^"]*)"', text)
            if name.split(" ")[1] not in free:
                ops.append((name, scope.group(1) if scope else ""))
    in_scan = [(n, s) for n, s in ops if "gdn_chunk_scan" in s]
    assert len(in_scan) > 50
    for name, _ in in_scan:
        dims = re.search(r"\[([\d,]*)\]", name)
        size = 1
        for d in (dims.group(1).split(",") if dims and dims.group(1)
                  else []):
            size *= int(d)
        assert size < 2 ** 15 or matched.search(name), name
    for name, scope in ops:
        if matched.search(name) and "gdn_chunk_scan" not in scope:
            assert (name.split(" ")[1] in ("copy-start", "copy-done",
                                           "broadcast")
                    or scope.rsplit("/", 1)[-1] in ("gather", "select_n")
                    ), (name, scope)

PARENT_PREFILL_TEMP_BYTES = 289_382_400      # 94,674,944 with the kernel (PR 32)


def test_pool_shaped_ops_finds_what_the_parent_did():
    """So that the checker cannot pass by seeing nothing: on the lines of
    the parent's program (layers as ``xs``/``ys``, pool slices fed to the
    kernel) it finds the two slices, two reshapes, two updates into the
    stacked copy, two copies back into the carry, and the two buffers
    allocated for the stacked copies — and not the scatters that run in
    place on the slices, nor what is inside a fusion."""
    found = paged_kv.pool_shaped_ops(PARENT_HLO, [(4, 640, 64, 8, 128)])
    assert sorted(name for name, _, _ in found) == sorted([
        "dynamic-slice_bitcast_fusion.4", "dynamic-slice_bitcast_fusion.5",
        "reshape.234", "reshape.235",
        "bitcast_dynamic-update-slice_fusion.4",
        "bitcast_dynamic-update-slice_fusion.5",
        "copy.65", "copy.67", "custom-call.18", "custom-call.19"])
    assert {op for _, op, _ in found} == {"fusion", "reshape", "copy",
                                           "custom-call"}
    # a pool of another size: nothing here is pool-shaped
    assert paged_kv.pool_shaped_ops(PARENT_HLO, [(4, 545, 64, 8, 128)]) == []


PARENT_HLO = """\
%fused_computation.7.clone.clone (param_0.406: bf16[4,640,64,8,128], param_1.422: s32[]) -> bf16[640,64,8,128] {
  %param_0.406 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %dynamic_slice.114 = bf16[1,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.406, %param_1.422, %constant.470, %constant.470, %constant.470, /*index=5*/%constant.470), dynamic_slice_sizes={1,640,64,8,128}
  ROOT %bitcast.169 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} bitcast(%dynamic_slice.114)
}
%fused_computation.2.clone.clone (param_0.407: bf16[640,64,8,128], param_1.423: s32[36], param_2.358: bf16[36,8,128]) -> bf16[640,64,8,128] {
  %param_0.407 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(0)
  ROOT %scatter.21 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} scatter(%param_0.407, %custom-call.21, %transpose.49), update_window_dims={1,2}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1}, index_vector_dim=1, to_apply=%region_3.6
}
%fused_computation.6.clone.clone (param_0.397: bf16[4,640,64,8,128], param_1.413: s32[]) -> bf16[640,64,8,128] {
  %param_0.397 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %dynamic_slice.112 = bf16[1,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.397, %param_1.413, %constant.461, %constant.461, %constant.461, /*index=5*/%constant.461), dynamic_slice_sizes={1,640,64,8,128}
  ROOT %bitcast.163 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.112)
}
%fused_computation.3.clone.clone (param_0.398: bf16[640,64,8,128], param_1.414: s32[36], param_2.353: bf16[36,8,128]) -> bf16[640,64,8,128] {
  %param_0.398 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.20 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} scatter(%param_0.398, %custom-call.20, %transpose.47), update_window_dims={1,2}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1}, index_vector_dim=1, to_apply=%region_4.7
}
%fused_computation.5.clone.clone (param_0.408: bf16[4,640,64,8,128], param_1.424: s32[], param_2.359: bf16[640,64,8,128]) -> bf16[4,640,64,8,128] {
  %param_0.408 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_2.359 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(2)
  %bitcast.170 = bf16[1,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} bitcast(%param_2.359)
  ROOT %dynamic_update_slice.15 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.408, %bitcast.170, %param_1.424, %constant.476, %constant.476, /*index=5*/%constant.476, %constant.476)
}
%fused_computation.4.clone.clone (param_0.399: bf16[4,640,64,8,128], param_1.415: s32[], param_2.354: bf16[640,64,8,128]) -> bf16[4,640,64,8,128] {
  %param_0.399 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_2.354 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} parameter(2)
  %bitcast.164 = bf16[1,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} bitcast(%param_2.354)
  ROOT %dynamic_update_slice.14 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.399, %bitcast.164, %param_1.415, %constant.467, %constant.467, /*index=5*/%constant.467, %constant.467)
}
%layer_body (arg: (s32[], bf16[4,640,64,8,128], bf16[4,640,64,8,128])) -> (s32[], bf16[4,640,64,8,128], bf16[4,640,64,8,128]) {
  %get-tuple-element.962 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.1), index=13
  %dynamic-slice_bitcast_fusion.4 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.962, %get-tuple-element.927), kind=kLoop, calls=%fused_computation.7.clone.clone
  %fusion.142 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%dynamic-slice_bitcast_fusion.4, %fusion.140, %copy.35), kind=kCustom, calls=%fused_computation.2.clone.clone
  %reshape.234 = bf16[640,64,1024]{2,1,0:T(8,128)(2,1)} reshape(%fusion.142)
  %get-tuple-element.963 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.1), index=14
  %dynamic-slice_bitcast_fusion.5 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.963, %get-tuple-element.927), kind=kLoop, calls=%fused_computation.6.clone.clone
  %fusion.144 = bf16[640,64,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(%dynamic-slice_bitcast_fusion.5, %fusion.140, %copy.36), kind=kCustom, calls=%fused_computation.3.clone.clone
  %reshape.235 = bf16[640,64,1024]{2,1,0:T(8,128)(2,1)} reshape(%fusion.144)
  %get-tuple-element.929 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.1), index=2
  %bitcast_dynamic-update-slice_fusion.4 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.929, %get-tuple-element.927, %fusion.142), kind=kLoop, calls=%fused_computation.5.clone.clone
  %get-tuple-element.930 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.1), index=3
  %bitcast_dynamic-update-slice_fusion.5 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.930, %get-tuple-element.927, %fusion.144), kind=kLoop, calls=%fused_computation.4.clone.clone
}
%step_body (arg: (s32[], bf16[4,640,64,8,128], bf16[4,640,64,8,128])) -> (s32[], bf16[4,640,64,8,128], bf16[4,640,64,8,128]) {
  %custom-call.18 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(), custom_call_target="AllocateBuffer"
  %custom-call.19 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(), custom_call_target="AllocateBuffer"
  %get-tuple-element.995 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.0), index=2
  %get-tuple-element.997 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.wide.arg_tuple.0), index=4
  %get-tuple-element.1017 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%while.34), index=2
  %copy.65 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.1017)
  %get-tuple-element.1019 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%while.34), index=3
  %copy.67 = bf16[4,640,64,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.1019)
}
"""


# the lines of the parent's Qwen3-Next decode program (PR 38) that carry
# w_qkvz, shortened
PARENT_QWEN_HLO = """\
%fused_computation.52 (param_0.1: bf16[2,3,2048,12288], param_1.2: s32[]) -> bf16[3,2048,12288] {
  %param_0.1 = bf16[2,3,2048,12288]{2,3,1,0:T(8,128)(2,1)} parameter(0)
  %dynamic-slice.9 = bf16[1,3,2048,12288]{2,3,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.1, %param_1.2, %constant.1, %constant.1, %constant.1), dynamic_slice_sizes={1,3,2048,12288}
  ROOT %bitcast.7 = bf16[3,2048,12288]{1,2,0:T(8,128)(2,1)} bitcast(%dynamic-slice.9)
}
%wide.region_1.143 (arg: (s32[], bf16[2,3,2048,12288])) -> (s32[], bf16[2,3,2048,12288]) {
  %get-tuple-element.5794 = bf16[2,3,2048,12288]{2,3,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %dynamic-slice_bitcast_fusion.47 = bf16[3,2048,12288]{1,2,0:T(8,128)(2,1)} fusion(%get-tuple-element.5794, %get-tuple-element.5692), kind=kLoop, calls=%fused_computation.52
  %fusion.918 = (bf16[1,2048,12288]{1,2,0:T(8,128)(2,1)}, bf16[1,2048,12288]{1,2,0:T(8,128)(2,1)S(1)}) fusion(%dynamic-slice_bitcast_fusion.47), kind=kLoop, calls=%fused_computation.53
  %copy-start.4 = (bf16[1,2048,12288]{1,2,0:T(8,128)(2,1)S(1)}, bf16[1,2048,12288]{1,2,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.5217)
  %copy-done.4 = bf16[1,2048,12288]{1,2,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.4)
}
ENTRY %main.151 (params__linear____w_qkvz__.1: bf16[6,2048,12288]) -> bf16[256] {
  %params__linear____w_qkvz__.1 = bf16[6,2048,12288]{2,1,0:T(8,128)(2,1)} parameter(15)
  %copy.538 = bf16[6,2048,12288]{1,2,0:T(8,128)(2,1)} copy(%params__linear____w_qkvz__.1)
  %bitcast.39 = bf16[2,3,2048,12288]{2,3,1,0:T(8,128)(2,1)} bitcast(%copy.538)
}
"""
