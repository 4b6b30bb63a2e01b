"""pjit training loop: sharded train state, fused train step, grad accumulation.

The reference delegates all of this to user containers (SURVEY.md §2.7 — the
operator only does rendezvous); here it is a first-party framework feature.
One train step is a single jitted function with explicit in/out shardings; XLA
emits all collectives (gradient all-reduce over `data`+`fsdp`, weight
all-gathers for FSDP, TP collectives) from the sharding annotations.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.ops.losses import softmax_cross_entropy
from kubeflow_tpu.parallel import sharding as shd


@dataclasses.dataclass
class TrainerConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    grad_accum: int = 1
    # "adamw": full f32 m/v (2x params of state). "adafactor": factored
    # second moment (state ~ O(rows+cols)) — the memory-budget choice for
    # big models on small HBM (T5X-style default on TPU).
    optimizer: str = "adamw"
    rules: Mapping[str, object] | None = None   # logical->mesh rules override
    # Metric-key conventions for gradient accumulation (instead of hardcoding
    # the literal "tokens"): `weight_metric` names the metric holding each
    # microbatch's loss-normalization weight (token count for LM losses);
    # loss and grads are re-weighted by it so accumulation reproduces the
    # GLOBAL token-weighted mean even when mask density varies across
    # microbatches. `count_metrics` are summed across microbatches; all other
    # metrics are averaged.
    weight_metric: str = "tokens"
    count_metrics: tuple = ("tokens",)


def make_optimizer(cfg: TrainerConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, cfg.learning_rate, cfg.warmup_steps, max(cfg.total_steps, cfg.warmup_steps + 1),
        end_value=cfg.learning_rate * 0.1,
    )
    if cfg.optimizer == "adafactor":
        # no weight decay here: optax.adafactor applies weight_decay_rate
        # AFTER lr scaling (raw fraction per step — 0.1 would collapse the
        # params), unlike adamw's lr-scaled decay. Adafactor runs train
        # decay-free (the T5X-style default).
        return optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip),
            optax.adafactor(schedule),
        )
    if cfg.optimizer != "adamw":
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.adamw(schedule, b1=cfg.b1, b2=cfg.b2, weight_decay=cfg.weight_decay),
    )


class Trainer:
    """Builds and owns the sharded train state + compiled step.

    loss_fn(params, batch) -> (loss, metrics_dict). `batch` is a pytree whose
    leaves' leading dim is the global batch (sharded over data+fsdp).
    """

    def __init__(
        self,
        mesh: Mesh,
        init_params_fn: Callable[[jax.Array], Any],
        params_logical_axes,
        loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
        config: TrainerConfig,
        donate_state: bool = True,
    ):
        self.mesh = mesh
        self.config = config
        self.loss_fn = loss_fn
        self.optimizer = make_optimizer(config)
        rules = config.rules or shd.DEFAULT_RULES

        self.param_specs = shd.tree_pspecs(params_logical_axes, rules)
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        self.batch_sharding = NamedSharding(
            mesh, PartitionSpec(("data", "fsdp"))
        )

        # init params directly into their shards (no host-side full copy)
        self._init_jit = jax.jit(init_params_fn, out_shardings=self.param_shardings)
        # optimizer.init only reads shapes, so jit does NOT propagate input
        # shardings to its outputs — compute explicit out_shardings: any opt
        # leaf that mirrors a param (adam mu/nu trees) inherits that param's
        # sharding, everything else (counts, empty states) is replicated.
        params_shape = jax.eval_shape(init_params_fn, jax.random.key(0))
        self.opt_shardings = self._opt_state_shardings(params_shape)
        self._opt_init = jax.jit(
            self.optimizer.init, out_shardings=self.opt_shardings
        )

        self.step_fn = self._build_step(donate_state)
        # AOT-compiled step installed by precompile(): same program, but
        # the compile happened eagerly (and possibly on another worker —
        # the executable-depot fast path) instead of inside step 1
        self._compiled_step = None
        self.params = None
        self.opt_state = None
        self.step = 0

    def _opt_state_shardings(self, params_shape):
        """Shardings for the optimizer state, matched by path suffix: optax
        wraps the params treedef inside its own states (mu/nu/...), so a
        param's path is a suffix of its mirror's path in the opt state."""
        opt_shapes = jax.eval_shape(self.optimizer.init, params_shape)
        is_sh = lambda x: isinstance(x, NamedSharding)
        p_sh = jax.tree_util.tree_flatten_with_path(
            self.param_shardings, is_leaf=is_sh)[0]
        p_shape = jax.tree_util.tree_flatten_with_path(params_shape)[0]
        by_path = {
            tuple(map(str, path)): (shape.shape, sh)
            for (path, sh), (_, shape) in zip(p_sh, p_shape)
        }
        replicated = NamedSharding(self.mesh, PartitionSpec())

        def pick(path, leaf):
            p = tuple(map(str, path))
            for i in range(len(p)):
                hit = by_path.get(p[i:])
                if hit is not None and hit[0] == leaf.shape:
                    return hit[1]
            return replicated

        return jax.tree_util.tree_map_with_path(pick, opt_shapes)

    def init_state(self, rng: jax.Array):
        self.params = self._init_jit(rng)
        self.opt_state = self._opt_init(self.params)
        self.step = 0
        return self.params

    def _build_step(self, donate: bool):
        optimizer = self.optimizer
        loss_fn = self.loss_fn
        accum = self.config.grad_accum
        mesh = self.mesh

        def grads_of(params, batch):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return loss, metrics, grads

        def step(params, opt_state, batch):
            if accum > 1:
                # split leading batch dim into [accum, micro, ...] and scan
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                    batch,
                )
                mb0 = jax.tree_util.tree_map(lambda x: x[0], micro)
                _, m_shapes, _ = jax.eval_shape(grads_of, params, mb0)

                # Each microbatch loss is a weighted mean (weight = its token
                # count, exposed via cfg.weight_metric). Accumulate
                # UN-normalized sums — loss·w, grads·w, Σw — and divide once,
                # so the result is the global token-weighted mean regardless
                # of how mask density varies across microbatches.
                weight_key = self.config.weight_metric

                def body(carry, mb):
                    g_acc, loss_acc, w_acc, m_acc = carry
                    loss, metrics, grads = grads_of(params, mb)
                    w = jnp.asarray(
                        metrics.get(weight_key, 1.0), jnp.float32)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g * w.astype(g.dtype), g_acc, grads)
                    m_acc = jax.tree_util.tree_map(jnp.add, m_acc, metrics)
                    return (g_acc, loss_acc + loss * w, w_acc + w, m_acc), None

                zeros_g = jax.tree_util.tree_map(jnp.zeros_like, params)
                zeros_m = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), m_shapes
                )
                (g_sum, loss_sum, w_sum, m_sum), _ = jax.lax.scan(
                    body, (zeros_g, 0.0, 0.0, zeros_m), micro
                )
                denom = jnp.maximum(w_sum, 1e-8)
                grads = jax.tree_util.tree_map(
                    lambda g: g / denom.astype(g.dtype), g_sum)
                loss = loss_sum / denom
                counts = set(self.config.count_metrics)
                metrics = {
                    k: (v if k in counts else v / accum)
                    for k, v in m_sum.items()
                }
            else:
                loss, metrics, grads = grads_of(params, batch)

            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
            metrics = dict(metrics, loss=loss, grad_norm=gnorm)
            return params, opt_state, metrics

        donate_argnums = (0, 1) if donate else ()
        # input shardings propagate from the arguments (params/opt_state
        # placed at init, batch placed by the data loader via
        # self.batch_sharding). The state comes OUT as it went in: left to
        # the partitioner, XLA:TPU re-shards adafactor's factored moments
        # over fsdp, which wastes the donation and makes step 2 of an
        # AOT-compiled step (precompile) refuse its own outputs.
        return jax.jit(step, donate_argnums=donate_argnums,
                       out_shardings=(self.param_shardings,
                                      self.opt_shardings, None))

    def train_step(self, batch):
        # the mesh context MUST be live at trace time: the model's logical
        # activation constraints (parallel/sharding.constrain) resolve
        # PartitionSpecs against the ambient mesh and silently no-op
        # without one — which costs activation sharding (batch stays
        # data-sharded only, fsdp/tensor axes unused) on multichip
        fn = self._compiled_step if self._compiled_step is not None \
            else self.step_fn
        with self.mesh:
            self.params, self.opt_state, metrics = fn(
                self.params, self.opt_state, batch
            )
        self.step += 1
        return metrics

    def precompile(self, batch, depot=None, stats=None,
                   wait_s: float = 0.0) -> str:
        """Split compile from step 1: lower the train step for ``batch``'s
        shapes and compile it NOW — fetching the executable from an
        executable depot (``parallel/depot.py``) when one is given, and
        publishing it on a miss so the rest of the gang (and every
        warm-pool resubmit) deserializes instead of compiling. Requires
        ``init_state`` first; pins the batch shape subsequent
        ``train_step`` calls use. Returns the depot outcome ("hit" /
        "published" / "compiled" / "no_depot"); depot trouble NEVER
        raises — worst case is the compile this call was going to pay
        anyway."""
        if self.params is None:
            raise ValueError("precompile needs init_state() first")
        from kubeflow_tpu.parallel.depot import load_or_compile

        lowered = self.lower_step(self.params, self.opt_state, batch)
        self._compiled_step, outcome = load_or_compile(
            lowered, depot, mesh=self.mesh, stats=stats, wait_s=wait_s)
        return outcome

    def lower_step(self, params_shapes, opt_shapes, batch_shapes):
        """AOT entry (parallel/aot.py scale proofs): lower the train step
        under the mesh so activation constraints bind, without arrays."""
        with self.mesh:
            return self.step_fn.lower(params_shapes, opt_shapes, batch_shapes)


def lm_loss_fn(forward, cfg):
    """Next-token LM loss for a model `forward(params, tokens, cfg)`.

    Batch: {"tokens": [B, S+1] int32, "mask": optional [B, S+1]}.
    """

    moe = bool(getattr(cfg, "n_experts", 0))

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if moe:
            logits, fwd_aux = forward(params, inputs, cfg, return_aux=True)
        else:
            logits = forward(params, inputs, cfg)
        mask = batch.get("mask")
        mask = mask[:, 1:] if mask is not None else None
        loss, aux = softmax_cross_entropy(
            logits, targets, mask, z_loss=getattr(cfg, "z_loss", 0.0)
        )
        metrics = {"tokens": aux["total_weight"]}
        if moe:
            loss = loss + fwd_aux["moe_aux"]
            metrics["moe_aux"] = fwd_aux["moe_aux"]
        return loss, metrics

    return loss_fn
