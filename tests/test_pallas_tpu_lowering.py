"""Every Pallas entry point, lowered and compiled for a v5e by Mosaic.

The interpret-mode parity suites prove the kernels' logic on the CPU; only
the TPU compiler says whether a chip accepts their block shapes (the int8
paged-decode kernel passed every interpret test while its scale BlockSpec
was one Mosaic refuses). The target is the compile-only v5e topology the
installed libtpu provides — no hardware — at llama_1b shapes.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops.pallas_attention import flash_attention
from kubeflow_tpu.ops.pallas_paged_attention import (
    paged_decode_attention, paged_decode_attention_sharded,
)
from kubeflow_tpu.parallel.aot import topology_devices

H, KVH, D = 16, 8, 128                   # llama_1b heads
B, BS, NBP = 32, 64, 5                   # the engine at max_seq 320
NB = B * NBP + 1


@pytest.fixture(scope="module")
def v5e():
    return topology_devices("v5e:2x2")


def _compile(fn, sharding, *shapes):
    """Compile for the sharding's devices; returns the optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sh)
            for (s, dt), sh in zip(shapes, sharding)]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(pool_dtype, b=B, nb=NB, kvh=KVH, h=H):
    return [((b, h, D), jnp.bfloat16), ((nb, BS, kvh, D), pool_dtype),
            ((nb, BS, kvh, D), pool_dtype), ((b, NBP), jnp.int32),
            ((b,), jnp.int32)]


def _scales(nb=NB, kvh=KVH):
    return [((nb, kvh), jnp.float32)] * 2


def _positional(kernel, **kw):
    """The kernels take the scales by keyword; lower() wants positionals."""
    def fn(q, kp, vp, tables, kv_len, *scales):
        ks, vs = scales or (None, None)
        return kernel(q, kp, vp, tables, kv_len, k_scale=ks, v_scale=vs, **kw)
    return fn


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
    hlo = _compile(paged_decode_attention, [one] * 5,
                   *_paged_shapes(jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", ["1b", "8b"])
def test_paged_decode_int8(v5e, shape):
    """The 8B serve shape too: 32/8 heads, a pool of 8 x 8192 tokens."""
    kw = {} if shape == "1b" else dict(b=8, nb=8 * 128 + 1, h=32)
    one = NamedSharding(Mesh(v5e[:1], ("x",)), P())
    shapes = _paged_shapes(jnp.int8, **kw)
    if shape == "8b":
        shapes[3] = ((8, 128), jnp.int32)
    hlo = _compile(_positional(paged_decode_attention), [one] * 7, *shapes,
                   *_scales(nb=kw.get("nb", NB)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_sharded_over_four_chips(v5e, quantized):
    mesh = Mesh(v5e, ("tensor",))
    rep = NamedSharding(mesh, P())
    sh = [NamedSharding(mesh, P(None, "tensor", None)),
          NamedSharding(mesh, P(None, None, "tensor", None)),
          NamedSharding(mesh, P(None, None, "tensor", None)), rep, rep]
    shapes = _paged_shapes(jnp.int8 if quantized else jnp.bfloat16)
    if quantized:
        sh += [NamedSharding(mesh, P(None, "tensor"))] * 2
        shapes += _scales()
    hlo = _compile(_positional(paged_decode_attention_sharded, mesh=mesh),
                   sh, *shapes)
    assert "tpu_custom_call" in hlo
    assert "all-reduce" not in hlo and "all-gather" not in hlo
