"""The seam between the models and the paged serving programs
(``models/paged.py::PagedOps``): every servable config builds its own view
in ``paged_ops()`` from the pieces its ``forward`` uses, the serving layer
names no model, and the imports point one way (serving -> models -> ops).
"""

import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

import kubeflow_tpu
from kubeflow_tpu.models import cca_moe, llama, mla_moe
from kubeflow_tpu.models.paged import PagedOps
from kubeflow_tpu.serving import paged_kv
from kubeflow_tpu.serving.quant import _LAYER_WEIGHTS, quantize_weights

from conftest import paged_session

PACKAGE = pathlib.Path(kubeflow_tpu.__file__).parent


@pytest.mark.parametrize(
    "model, tiny",
    [(llama, llama.llama_tiny), (mla_moe, mla_moe.mla_moe_tiny),
     (cca_moe, cca_moe.cca_moe_tiny)],
    ids=["llama_tiny", "mla_moe_tiny", "cca_moe_tiny"])
def test_paged_ops_is_the_configs_own(model, tiny):
    cfg = tiny(dtype=jnp.float32)
    ops = paged_kv.paged_ops(cfg)
    assert isinstance(ops, PagedOps)
    # nothing but the config's method decides what the programs see
    sentinel = object()
    stub = type("Stub", (), {"paged_ops": lambda self: sentinel})()
    assert paged_kv.paged_ops(stub) is sentinel
    assert ops.n_layers == cfg.n_layers

    b, max_seq, bs, nb = 2, 32, 8, 9
    cache = jax.eval_shape(
        lambda: paged_kv.init_paged_cache(cfg, b, max_seq, bs, nb))
    assert set(cache) == set(ops.pool_rows) | set(ops.slot_rows) | {"len"}
    for name, row in ops.pool_rows.items():
        assert cache[name].shape == (ops.n_layers, nb, bs, *row)
    for name, row in ops.slot_rows.items():
        assert cache[name].shape == (ops.n_layers, b, *row)
    # per-slot rows and a layer-to-layer carry are the CCA model's alone
    assert bool(ops.slot_rows) == bool(ops.layer_carry) \
        == (model is cca_moe)

    if ops.bucket_prefill is None:
        return
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), cfg))
    logits, rows = jax.eval_shape(
        ops.bucket_prefill, params,
        jax.ShapeDtypeStruct((b, 2 * bs), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert logits.shape == (b, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert set(rows) == set(ops.pool_rows)
    for name, row in ops.pool_rows.items():
        assert rows[name].shape == (ops.n_layers, b, 2 * bs, *row)


@pytest.mark.parametrize(
    "model, tiny, program",
    [(llama, llama.llama_tiny, "decode"), (llama, llama.llama_tiny, "chunk"),
     (llama, llama.llama_tiny, "verify"),
     (mla_moe, mla_moe.mla_moe_tiny, "decode"),
     (mla_moe, mla_moe.mla_moe_tiny, "chunk")],      # it has no verify step
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", ""))
def test_models_without_slot_state_pay_nothing_for_the_seam(model, tiny,
                                                            program):
    """The seam grew per-slot rows and a layer-to-layer carry (PR 34). For
    a model that has neither, the cache holds its pools and ``len`` and
    nothing else, and each program's jaxpr takes exactly the weights, the
    cache and its own operands: no state array, no carry, and the decode
    step's ``active`` mask is not read."""
    cfg = tiny(dtype=jnp.float32)
    ops = paged_kv.paged_ops(cfg)
    b, bs, nbp = 2, 8, 4
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(
        lambda: paged_kv.init_paged_cache(cfg, b, nbp * bs, bs, b * nbp + 1))
    assert set(cache) == set(ops.pool_rows) | {"len"}
    tables = jax.ShapeDtypeStruct((b, nbp), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "decode":
        fn = lambda p, tok, c, t, active: paged_kv.paged_decode_step(
            p, tok, cfg, c, t, active=active)
        args = (params, jax.ShapeDtypeStruct((b,), jnp.int32), cache, tables,
                jax.ShapeDtypeStruct((b,), bool))
    elif program == "chunk":
        fn = lambda p, toks, c, t, slot, off, n: paged_kv.paged_prefill_chunk(
            p, toks, cfg, c, t, slot, off, n)
        args = (params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache,
                tables, scalar, scalar, scalar)
    else:
        fn = lambda p, toks, c, t, limit: paged_kv.paged_verify_step(
            p, toks, cfg, c, t, limit)
        args = (params, jax.ShapeDtypeStruct((b, 4), jnp.int32), cache,
                tables, jax.ShapeDtypeStruct((b,), jnp.int32))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert len(jaxpr.invars) == len(jax.tree.leaves(args))
    if program == "decode":             # the mask: an input nothing reads
        used = {v for eqn in jaxpr.eqns for v in eqn.invars
                if not isinstance(v, Literal)}
        assert jaxpr.invars[-1] not in used
    # the layer loops carry x and the pools: as many values as the cache
    # has pools, plus one
    loops = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert loops and all(
        e.params["num_carry"] == 1 + len(ops.pool_rows) for e in loops)


def _imports(path):
    """Every module a file imports, at any depth of nesting."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{alias.name}"
                        for alias in node.names)


def test_imports_point_one_way():
    upward = [
        (str(path.relative_to(PACKAGE)), name)
        for layer in ("models", "ops")
        for path in sorted((PACKAGE / layer).rglob("*.py"))
        for name in _imports(path)
        if name.startswith("kubeflow_tpu.serving")]
    assert upward == []
    named = [name for name in _imports(PACKAGE / "serving" / "paged_kv.py")
             if name.startswith("kubeflow_tpu.models.llama")]
    assert named == []


def _dequantized(qp):
    """The float tree whose weights are exactly what the int8 tree's
    ``name_q`` x ``name_s`` stand for."""
    def restore(tree, name, axes):
        w = tree.pop(name + "_q").astype(jnp.float32)
        tree[name] = w * jnp.expand_dims(tree.pop(name + "_s"), axes)

    out = dict(qp, layers=dict(qp["layers"]))
    restore(out, "embed", (1,))
    if "lm_head_q" in out:
        restore(out, "lm_head", (0,))
    for name, axes in _LAYER_WEIGHTS.items():
        restore(out["layers"], name, axes)
    return out


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("program", ["bucket_prefill", "decode"])
def test_int8_tree_runs_the_float_trees_pieces(program, tied):
    """One set of pieces serves both trees: the quantized ``llama_tiny``
    through the bucket prefill and the decode step equals the same
    programs over the float tree holding the dequantized weights (scaling
    the output tile against scaling the weight: float32 rounding apart)."""
    cfg = dataclasses.replace(llama.llama_tiny(dtype=jnp.float32),
                              tie_embeddings=tied)
    qp = quantize_weights(
        llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32), cfg)
    seq = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 9)).astype(np.int32)
    got = []
    for params in (qp, _dequantized(qp)):
        logits, step = paged_session(cfg, params, seq[:, :8])
        got.append(logits if program == "bucket_prefill"
                   else step(seq[:, 8]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(got[1]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("head_dim", [128, 256])
def test_pools_and_state_belong_to_the_layers_of_their_kind(head_dim):
    """A model of two layer kinds (PR 38: ``PagedOps.period``): K and V
    have rows for the attention layers only, stored merged (a block's
    (token, kv head) rows, the matrix the decode kernel reads) where a head
    is wider than a lane tile, and the recurrent state has rows for the
    recurrent layers only, in its own dtype; the layer loop is ONE scan of
    periods that carries x and the four arrays."""
    from kubeflow_tpu.models import qwen3_next

    cfg = qwen3_next.qwen3_next_tiny(dtype=jnp.float32, head_dim=head_dim)
    ops = paged_kv.paged_ops(cfg)
    assert ops.period == ("recurrent",) * 3 + ("attention",)
    assert (ops.layers_of("attention"), ops.layers_of("recurrent")) == (2, 6)
    b, bs, nbp = 2, 8, 4
    cache = jax.eval_shape(
        lambda: paged_kv.init_paged_cache(cfg, b, nbp * bs, bs, b * nbp + 1))
    assert set(cache) == {"k", "v", "gdn_s", "gdn_conv", "len"}
    row = ((bs * cfg.n_kv_heads,) if head_dim > 128
           else (bs, cfg.n_kv_heads))
    for key in ("k", "v"):
        assert cache[key].shape == (2, b * nbp + 1, *row, cfg.head_dim)
    assert cache["gdn_s"].shape == (6, b, cfg.n_v_heads, cfg.k_head_dim,
                                    cfg.v_head_dim)
    assert cache["gdn_conv"].shape == (6, b, cfg.conv_kernel - 1,
                                       cfg.conv_dim)
    assert {cache[key].dtype for key in ("gdn_s", "gdn_conv")} == {
        jnp.dtype(jnp.float32)}
    params = jax.eval_shape(
        lambda: qwen3_next.init_params(jax.random.key(0), cfg))
    tables = jax.ShapeDtypeStruct((b, nbp), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, tok, c, t, active: paged_kv.paged_decode_step(
            p, tok, cfg, c, t, active=active))(
        params, jax.ShapeDtypeStruct((b,), jnp.int32), cache, tables,
        jax.ShapeDtypeStruct((b,), bool)).jaxpr
    loops = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in loops] == [2]
    assert loops[0].params["num_carry"] == 1 + 4


# The lowered text of the older models' programs at the parent of PR 38
# (``jax.jit(...).lower(...).as_text()``, sha256, the first 16 hex digits):
# the seam grew layer kinds, recurrent state and merged pools, and a model
# with none of them must not pay for it in a single operation.
PARENT_PROGRAM_TEXT = {
    ("llama", "decode", "gather"): "b0475e89eec8d63d",
    ("llama", "decode", "pallas"): "f04fb7a5792d1fad",
    ("llama", "chunk", ""): "4c2f06c79a52c54e",
    ("llama", "verify", ""): "0a6c3acd03b3f90a",
    ("mla_moe", "decode", "gather"): "3e9cd8ffdac457c6",
    ("mla_moe", "decode", "pallas"): "b02d7f911ab49aa3",
    ("mla_moe", "chunk", ""): "f4299967ea8a7162",
    ("cca_moe", "decode", "gather"): "2ad3046ed7b5304a",
    ("cca_moe", "decode", "pallas"): "951ec1b1bd23ca34",
    ("cca_moe", "chunk", ""): "569cc8b136a92fa1",
}


@pytest.mark.parametrize("name,program,kernel", sorted(PARENT_PROGRAM_TEXT),
                         ids=lambda v: v or "-")
def test_the_older_models_programs_are_the_parents(name, program, kernel):
    import hashlib

    model = {"llama": llama, "mla_moe": mla_moe, "cca_moe": cca_moe}[name]
    cfg = getattr(model, f"{name}_tiny")(dtype=jnp.float32)
    b, bs, nbp = 2, 8, 4
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(
        lambda: paged_kv.init_paged_cache(cfg, b, nbp * bs, bs, b * nbp + 1))
    tables = jax.ShapeDtypeStruct((b, nbp), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "decode":
        fn = lambda p, tok, c, t, active: paged_kv.paged_decode_step(
            p, tok, cfg, c, t, kernel=kernel, active=active)
        args = (params, jax.ShapeDtypeStruct((b,), jnp.int32), cache, tables,
                jax.ShapeDtypeStruct((b,), bool))
    elif program == "chunk":
        fn = lambda p, toks, c, t, slot, off, n: paged_kv.paged_prefill_chunk(
            p, toks, cfg, c, t, slot, off, n)
        args = (params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache,
                tables, scalar, scalar, scalar)
    else:
        fn = lambda p, toks, c, t, limit: paged_kv.paged_verify_step(
            p, toks, cfg, c, t, limit)
        args = (params, jax.ShapeDtypeStruct((b, 4), jnp.int32), cache,
                tables, jax.ShapeDtypeStruct((b,), jnp.int32))
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_PROGRAM_TEXT[(name, program, kernel)]
