"""LLM serving engine — continuous batching over the Llama decode path.

This is the TPU-native answer to the reference's huggingfaceserver/vLLM
runtime (SURVEY.md §2.4 'Runtime servers': LLM generate endpoints): a
slot-based continuous-batching engine where

- the KV cache is ONE static-shape arena [layers, max_batch, max_seq, ...]
  (XLA-friendly: no dynamic shapes, ever);
- prompts prefill into padded length buckets (few compile variants), and
  their KV rows are inserted into free slots with dynamic_update_slice;
- every step runs ONE jitted decode+sample over all slots — requests join
  and leave between steps without recompiling (the continuous-batching
  property that keeps the MXU fed at high request churn);
- sampling (greedy/temperature/top-k/top-p) runs on-device in the same
  program, so only sampled token ids cross back to the host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.obs import trace as obs_trace
from kubeflow_tpu.obs.histogram import Histogram, log_buckets

# Request-latency buckets at factor 2**0.25 (~19% relative error) instead
# of the default factor-2: serving A/B comparisons (canary gate, the
# co-located-vs-disagg bench legs) discriminate distributions well inside
# one octave of each other, which factor-2 buckets collapse into a tie.
_REQ_LAT_BUCKETS = log_buckets(0.001, 64.0, factor=2 ** 0.25)
from kubeflow_tpu.serving.scheduler import (
    QuantConfig, SchedulerConfig, StepScheduler, ceil_pow2,
)

logger = logging.getLogger(__name__)

# kernel-downgrade reasons already logged this process: the event is
# counted per engine (kft_model_kernel_downgrades_total) but LOGGED once —
# a fleet restarting 128 replicas must not print 128 identical warnings
_downgrades_logged: set = set()


def _log_downgrade_once(requested: str, reason: str) -> None:
    if reason in _downgrades_logged:
        return
    _downgrades_logged.add(reason)
    logger.warning(
        "decode kernel %r downgraded to 'gather' (%s): losing the "
        "block-resident fast path's bandwidth advantage", requested, reason)


def _log_quant_downgrade_once(requested: str, reason: str) -> None:
    """Quant downgrades share the once-per-process set with kernel
    downgrades: the fleet case is identical (128 replicas, one warning),
    but the message must say WHICH dtype the engine is actually serving
    at — a quant fallback is never a silent dtype change."""
    if reason in _downgrades_logged:
        return
    _downgrades_logged.add(reason)
    logger.warning(
        "quant mode %s downgraded to unquantized (%s): serving at full "
        "bytes-per-weight / bytes-per-KV-token", requested, reason)


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = off
    top_p: float = 1.0                # 1 = off
    eos_id: Optional[int] = None
    # any of these ends generation like eos (finish_reason "stop"); text
    # stop STRINGS live a layer up in LLMModel, which owns the tokenizer
    stop_token_ids: tuple = ()
    # expert models: keep the experts every expert layer routed a token
    # to, per generated token (``GenRequest.routing``) and per prompt row
    # (``GenRequest.prompt_routing``) — what a check against a reference
    # needs to tell a near-tie from a wrong router, and to follow the
    # program through a near-tie in the prompt that later rows attend to
    record_routing: bool = False


@dataclasses.dataclass
class GenRequest:
    id: int
    prompt: list[int]
    sampling: SamplingParams
    generated: list[int] = dataclasses.field(default_factory=list)
    # per-generated-token logprob under the model distribution
    logprobs: list[float] = dataclasses.field(default_factory=list)
    # SamplingParams.record_routing: [expert layers, top_k] per token
    routing: list = dataclasses.field(default_factory=list)
    # ... and [expert layers, prompt rows, top_k]: the prompt rows the
    # chunked prefill computed (all of them without a shared prefix)
    prompt_routing: Any = None
    done: bool = False
    aborted: bool = False
    # set by a text-level stop-string watcher before aborting: the abort
    # then reads as a clean "stop" finish, not a client disconnect
    stop_matched: bool = False
    slot: Optional[int] = None
    # disaggregated prefill tier (serving/disagg.py): park the request
    # after prefill + first token instead of decoding — KV stays resident
    # (blocks refcount-pinned) until export_held_kv/release_held
    hold_after_prefill: bool = False
    # observability: the request's trace context ((trace_id, span_id) of
    # its queue span — decode/prefill spans attribute to it), wall-clock
    # latency marks (enqueue/first-token/last-commit/done) feeding the
    # kft_model_request_{ttft,itl,e2e}_seconds histograms, and the live
    # span handles the engine closes as the request advances
    trace: Optional[tuple] = None
    t_enqueue: float = 0.0
    t_first_token: float = 0.0
    # first DECODE commit (token #2) — on a disagg decode pod this bounds
    # the migration decomposition: prefill-complete -> first decode commit
    t_second_token: float = 0.0
    t_last_commit: float = 0.0
    t_done: float = 0.0
    spans: dict = dataclasses.field(default_factory=dict)

    @property
    def finish_reason(self) -> str:
        if self.stop_matched:
            # a stop-string match is a clean stop even when the request
            # also hit its length cap before the watcher saw the match
            return "stop"
        if self.aborted:
            return "abort"
        if self.generated and (
                (self.sampling.eos_id is not None
                 and self.generated[-1] == self.sampling.eos_id)
                or self.generated[-1] in self.sampling.stop_token_ids):
            return "stop"
        return "length"


@dataclasses.dataclass
class _ChunkedPrefill:
    """A long prompt streaming through chunked prefill across engine
    steps (the scheduler interleaves one chunk per step with decode).
    ``offset`` is the next position to prefill; positions < ``share_len``
    are radix-shared (their chunks are skipped for compute and their
    writes masked to scratch); ``tables`` is the device snapshot of the
    block tables taken at reservation (this slot's row is immutable)."""

    req: GenRequest
    offset: int
    share_len: int
    tables: Any
    x_last: Any = None
    stats: Any = None
    # SamplingParams.record_routing: each chunk's choices, on the device
    routing: list = dataclasses.field(default_factory=list)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


def greedy_argmax(logits):
    """Greedy pick with an EXPLICIT stable lowest-index tie-break.

    Exact logit ties are routine in bf16 (activations quantize to 8
    mantissa bits), and ``jnp.argmax``'s tie winner is formally
    first-index but travels through backend-specific reduction trees.
    This construction — min index among maximizers — is deterministic by
    value comparison alone, so every path that greedy-decodes (decode
    sampler, first-token sampler, speculative verify) breaks ties the
    same way on the same values. Works on any [..., V] logits."""
    vocab = logits.shape[-1]
    is_max = logits == jnp.max(logits, axis=-1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                   logits.ndim - 1)
    return jnp.min(jnp.where(is_max, idx, vocab), axis=-1).astype(jnp.int32)


def sample_logits(logits, rng, temperature, top_k, top_p,
                  greedy_only: bool = False):
    """On-device sampling: greedy when temperature==0, else
    temperature/top-k/top-p. temperature/top_k/top_p are per-batch arrays
    ([B]); top_k==0 / top_p==1 disable the respective filter.

    ``greedy_only`` (STATIC) skips the full-vocab sort entirely — the
    sort is O(V log V) bitonic passes on TPU and dominates the decode
    step for greedy batches, which are the common serving case."""
    vocab = logits.shape[-1]
    greedy = greedy_argmax(logits)
    if greedy_only:
        return greedy.astype(jnp.int32)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]

    sorted_asc = jnp.sort(scaled, axis=-1)               # [B, V] ascending
    # top-k: kth-largest value per row; rows with top_k==0 keep everything
    k_idx = jnp.clip(vocab - top_k, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_asc, k_idx[:, None], axis=-1)
    kth = jnp.where((top_k > 0)[:, None], kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p (nucleus) over the top-k-MASKED distribution (vLLM/HF ordering:
    # k first, then p renormalized on the survivors). The mask is a monotone
    # value threshold, so the sorted masked array comes from the existing
    # sort — no second O(V log V) sort in the decode hot loop.
    sorted_desc = jnp.where(sorted_asc < kth, -jnp.inf, sorted_asc)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.minimum(
        jnp.sum(cum < top_p[:, None], axis=-1), vocab - 1)
    cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None], axis=-1)
    scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)

    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


class LLMEngine:
    """Continuous-batching generation over a model's paged programs.
    ``cfg`` is any config with a ``paged_ops()`` method (``LlamaConfig``,
    ``MlaMoeConfig``, ``CcaMoeConfig``, ``Qwen3NextConfig``): what the engine needs of the model — the pool's
    rows, the layer's pieces, the head, what it cannot be served with — it
    asks through that one object."""

    def __init__(self, params, cfg, *,
                 max_batch: int = 8, max_seq: int = 1024,
                 prefill_buckets: Sequence[int] = (64, 128, 256, 512),
                 kv_block_size: Optional[int] = None,
                 kv_num_blocks: Optional[int] = None,
                 decode_chunk: int = 8,
                 decode_pipeline: bool = True,
                 kernel: str = "auto",
                 mesh=None,
                 scheduler: Optional[SchedulerConfig] = None,
                 quant: Optional[QuantConfig] = None,
                 obs: Optional[obs_trace.SpanCollector] = None):
        from kubeflow_tpu.serving.paged_kv import (
            PagedKV, paged_ops, paged_prefill_chunk
            as paged_prefill_chunk_fn, paged_verify_step
            as paged_verify_step_fn, resolve_decode_kernel,
        )
        from kubeflow_tpu.serving.quant import (
            is_weight_quantized, quantize_weights, resolve_quant,
        )

        self.cfg = cfg
        self.mesh = mesh
        self.model = ops = paged_ops(cfg)
        if quant is None and scheduler is not None:
            quant = scheduler.quant
        asked = {
            "quantized KV pool": quant is not None and not quant.exact_parity
            and quant.kv_dtype != "none",
            "int8 weights": quant is not None and not quant.exact_parity
            and quant.weight_dtype != "none",
            "speculative decode": scheduler is not None
            and scheduler.spec_decode,
            "tensor mesh": mesh is not None,
            # the default policy shares prefixes; for a model that cannot,
            # only a policy handed in that asks for it is refused, and the
            # default leaves it off (below)
            "radix prefix cache": scheduler is not None
            and scheduler.radix_cache,
        }
        for mechanism in ops.refuses:
            if asked.get(mechanism):
                self._refuse(mechanism)
        if scheduler is None and "radix prefix cache" in ops.refuses:
            scheduler = SchedulerConfig(radix_cache=False)
        # decode-attention path (paged_kv module docstring): the
        # block-resident Pallas kernel is the TPU default — including
        # under a mesh, where it runs shard_map'd over the heads/KV
        # tensor axis (ops/pallas_paged_attention). Resolution is
        # delegated to paged_kv so self.kernel always names the path the
        # decode step actually executes; a downgrade the caller did not
        # ask for (gpu, or an unshardable mesh topology) is COUNTED
        # (kft_model_kernel_downgrades_total) and logged once instead of
        # silently losing ~3.7x decode bandwidth.
        resolved, downgrade = resolve_decode_kernel(
            kernel, mesh=mesh, n_kv_heads=cfg.n_kv_heads)
        self.kernel = resolved
        self.kernel_downgrades = 0
        if downgrade is not None:
            self.kernel_downgrades = 1
            _log_downgrade_once(kernel, downgrade)
        # quantized serving (serving/quant.py): resolve the requested
        # config against the platform/model. A mode the platform can't
        # honor (no fp8 dtype) or the model can't (MoE expert weights)
        # falls back to unquantized — counted on the SAME downgrade
        # surface as kernel downgrades (kft_model_kernel_downgrades_total
        # plus its own quant_downgrades), logged once per process, never
        # a silent dtype change. The explicit quant= argument wins over
        # the scheduler policy's copy (one resolution authority).
        self.quant_requested = quant
        self.quant, quant_downgrades = resolve_quant(quant, cfg=cfg)
        self.quant_downgrades = len(quant_downgrades)
        self.kernel_downgrades += self.quant_downgrades
        for q_requested, q_reason in quant_downgrades:
            _log_quant_downgrade_once(q_requested, q_reason)
        if (self.quant.weight_dtype == "int8"
                and not is_weight_quantized(params)):
            # quantize ONCE at engine build (the LLMModel.load() path):
            # per-output-channel scales; decode, chunked prefill, bucket
            # prefill and spec verify all read the same int8 tree
            params = quantize_weights(params, cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.buckets = sorted(b for b in prefill_buckets if b <= max_seq)
        if not self.buckets:
            raise ValueError("no prefill bucket fits max_seq")
        # block-paged KV: pool memory = kv_num_blocks * kv_block_size tokens
        # (default: the dense arena's capacity + the scratch block); shrink
        # kv_num_blocks to serve more concurrent requests per byte.
        # Block size must divide max_seq and every bucket (prefill writes
        # whole blocks); the default picks the largest power of 2 <= 64
        # that does.
        if kv_block_size is None:
            kv_block_size = 1
            while (kv_block_size < 64
                   and max_seq % (kv_block_size * 2) == 0
                   and all(b % (kv_block_size * 2) == 0
                           for b in self.buckets)):
                kv_block_size *= 2
        for b in self.buckets + [max_seq]:
            if b % kv_block_size:
                raise ValueError(
                    f"kv_block_size={kv_block_size} must divide max_seq and "
                    f"every prefill bucket (got {b})")
        if kv_num_blocks is None:
            kv_num_blocks = max_batch * (max_seq // kv_block_size) + 1
        kv_sh = len_sh = sc_sh = None
        if mesh is not None:
            # tensor-parallel serving: the KV pool shards over the mesh's
            # `tensor` axis on the kv-head dim (matching the TP-sharded
            # params the loader placed); everything else is replicated and
            # jit auto-partitions the prefill/decode programs (SPMD — XLA
            # inserts the collectives). Host-side tables stay numpy. The
            # pool allocates directly with this sharding — a pod-sized
            # pool must never transit one chip unsharded.
            from jax.sharding import NamedSharding, PartitionSpec

            tp = mesh.shape.get("tensor", 1)
            if cfg.n_kv_heads % tp:
                raise ValueError(
                    f"n_kv_heads={cfg.n_kv_heads} not divisible by "
                    f"tensor={tp}")
            kv_sh = NamedSharding(
                mesh, PartitionSpec(None, None, None, "tensor", None))
            len_sh = NamedSharding(mesh, PartitionSpec())
            # quantized pools: the [L, NB, KV] scale tables shard on the
            # kv-head dim with the pool (same divisibility, checked above)
            sc_sh = NamedSharding(mesh, PartitionSpec(None, None, "tensor"))
        self.paged = PagedKV(cfg=cfg, max_batch=max_batch, max_seq=max_seq,
                             block_size=kv_block_size,
                             num_blocks=kv_num_blocks,
                             kv_sharding=kv_sh, len_sharding=len_sh,
                             quant_kv=self.quant.kv_dtype,
                             scale_sharding=sc_sh)
        self.cache = self.paged.cache
        # what the cache holds besides the pools: the rows a model keeps
        # per slot and layer (``PagedOps.slot_rows``, ``state_rows``; 0 for
        # most)
        self.slot_state_bytes = sum(
            self.cache[key].nbytes for key in (*ops.slot_rows,
                                               *ops.state_rows))
        self._free: list[int] = list(range(max_batch))
        self._active: dict[int, GenRequest] = {}     # slot -> request
        self._waiting: list[GenRequest] = []
        self._aborted: set[int] = set()              # request ids to retire
        # disaggregated prefill tier: slot -> request parked after prefill
        # (hold_after_prefill) awaiting KV export/migration; their blocks
        # stay refcount-pinned so eviction can never reach them
        self._held: dict[int, GenRequest] = {}
        # control ops (export/inject/release from disagg glue threads):
        # the decode dispatch donates the cache buffers, so ALL cache
        # mutation must run on the step thread — ops queue here and drain
        # at the top of step()
        self._ctl: list = []
        self._ids = itertools.count()
        self._lock = threading.Lock()
        # last committed token, host side (the speculative path drafts and
        # verifies from it)
        self._tokens = np.zeros((max_batch,), np.int32)
        self._rng = jax.random.key(0)
        self.steps = 0
        # expert layers only: assignments per (expert layer, expert) since
        # the engine was built, and distinct experts hit summed over
        # (decode step, expert layer); counted on the device, read back
        # with the tokens they belong to
        self.moe_tokens_per_expert: Optional[np.ndarray] = None
        self.moe_experts_hit = 0
        # a layer that holds a share of its experts: picks routed to the
        # experts this chip does not hold (they add nothing here)
        self.moe_absent_picks = 0
        self.generated_tokens = 0
        self.prefill_dispatches = 0       # observability: admission batching
        # multi-step decode: one dispatch runs `decode_chunk` decode+sample
        # steps under lax.scan, amortizing host->device dispatch latency
        # (vLLM multistep role). Requests finishing mid-chunk are trimmed on
        # the host; their overshoot tokens land in their own reserved blocks
        # or the scratch block, never another request's.
        self.decode_chunk = max(1, int(decode_chunk))
        # double-buffered decode: dispatch chunk N+1 BEFORE fetching chunk
        # N's tokens, so device compute overlaps host transfer+bookkeeping.
        # The decode's input token is a DEVICE array, the token carry: the
        # last dispatched chunk's scan carry, with each admission's first
        # token written in at its slot by the sampling program itself, so
        # neither a dispatch nor an admission waits on a host read-back.
        self.decode_pipeline = bool(decode_pipeline)
        self._inflight: Optional[dict] = None
        self._carry = jnp.zeros((max_batch,), jnp.int32)
        # this step's admissions whose first token is still on the device
        # (_commit_first_tokens reads them back after the decode launch)
        self._pending: list[dict] = []
        # step scheduler (serving/scheduler.py): per-step prefill token
        # quota, interleaved chunked prefill, adaptive decode-chunk trims,
        # and the counter set /metrics exports
        self.sched = StepScheduler(scheduler, default_budget=self.buckets[-1],
                                   decode_chunk=self.decode_chunk)
        # observability (obs/): every request yields a queue span,
        # per-prefill-chunk spans and per-decode-dispatch spans into the
        # process collector, plus the three request-latency histograms
        # /metrics serves as kft_model_request_{ttft,itl,e2e}_seconds
        self.obs = obs or obs_trace.collector()
        sizes = {"pool_bytes": sum(self.cache[key].nbytes
                                   for key in ops.pool_rows),
                 "slot_state_bytes": self.slot_state_bytes}
        self.obs.end(self.obs.start("engine.build", attrs={
            "kv_num_blocks": kv_num_blocks, "kv_block_size": kv_block_size,
            "max_batch": max_batch, **sizes}))
        logger.info("paged cache: %d blocks of %d rows, pools %d bytes, "
                    "per-slot state %d bytes over %d slots", kv_num_blocks,
                    kv_block_size, sizes["pool_bytes"],
                    self.slot_state_bytes, max_batch)
        # the engine thread's timeline (_phase): the open ``engine.step``
        # span and, inside it, the one open phase (name, span, annotation);
        # the last host phase, closed but not yet ended (it runs on to the
        # next phase), else the instant the last wait ended
        self._step_span: Optional[obs_trace.Span] = None
        self._cur_phase: Optional[tuple] = None
        self._tail: Optional[obs_trace.Span] = None
        self._phase_mark = 0.0
        # the interpreter's collections, timed process-wide (gc_ms, host.gc)
        obs_trace.install_gc_hook()
        self.request_hists = {"ttft": Histogram(_REQ_LAT_BUCKETS),
                              "itl": Histogram(_REQ_LAT_BUCKETS),
                              "e2e": Histogram(_REQ_LAT_BUCKETS)}
        self.paged.prefix_cache = self.sched.cfg.radix_cache
        # in-flight chunked prefills, slot -> state (insertion order = FIFO)
        self._chunked: dict[int, _ChunkedPrefill] = {}
        # chunk width is STATIC (one compile): the largest bucket, capped
        # by the quota so one chunk always fits one step's budget
        self._chunk_width = max(1, min(self.buckets[-1],
                                       self.sched.prefill_budget()))
        # speculative decoding (scheduler knob): host-side drafter +
        # batched verify step. The drafter proposes per-stream token
        # continuations; one _verify dispatch scores all of them and the
        # accepted prefix commits — greedy outputs token-identical to
        # the non-speculative path, >=1 token per verify always.
        self.spec = None
        if self.sched.cfg.spec_decode:
            from kubeflow_tpu.serving.spec_decode import make_drafter

            self.spec = make_drafter(self.sched.cfg.spec_drafter,
                                     self.sched.cfg.spec_k)

        # whole-bucket prefill, where the model has one; without it every
        # prompt streams through the chunk program. (Lambdas on purpose,
        # here and below: the benchmark's readers find the prefill
        # programs by the name ``jit__lambda``.)
        self._prefill = ops.bucket_prefill and jax.jit(
            lambda p, toks, lens: ops.bucket_prefill(p, toks, lens))
        # chunked prefill for prompts longer than every bucket: fixed
        # chunk size (the largest bucket) + traced offset/length keep the
        # compile count O(1) in prompt length
        self._prefill_chunk = jax.jit(
            lambda p, toks, cache, tables, slot, offset, length, share:
                paged_prefill_chunk_fn(
                    p, toks, self.cfg, cache, tables, slot, offset, length,
                    share),
            donate_argnums=(2,))
        # the lm head runs ONCE on the final chunk's hidden row, not per
        # chunk (full-vocab matmul is the expensive part of short chunks)
        self._chunk_lm_head = jax.jit(
            lambda p, x_last: ops.head(p, x_last))
        # first-token sampling, its logprob and the token carry's update in
        # ONE jitted call: computing log_softmax eagerly per admitted
        # request costs an op-by-op full-vocab dispatch + transfer. A pad
        # row (slot -1) is sent past the carry's end and dropped there.
        self._first_sample = jax.jit(
            lambda logits, rng, t, k, p, carry, slots: (
                (tok := sample_logits(logits, rng, t, k, p)),
                jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1),
                carry.at[jnp.where(slots < 0, carry.shape[0], slots)].set(
                    tok, mode="drop")))
        self._decode = jax.jit(
            self._decode_impl, donate_argnums=(2,),
            static_argnames=("greedy_only", "kernel", "chunk_len"))
        # AOT-compiled steady-state decode program, installed by
        # precompile(): the executable-depot fast path for serving
        # replicas — a fleet scale-up deserializes the program the first
        # replica published instead of compiling it cold. Dispatches whose
        # static config differs (non-greedy batch, adaptive chunk trim)
        # fall back to the jitted path above.
        self._compiled_decode = None
        # prefill-tier twin (precompile(tier="prefill")): the AOT chunked-
        # prefill program — the prefill pod's steady-state program under
        # its own depot key scope
        self._compiled_prefill_chunk = None
        self.depot_outcome: Optional[str] = None
        # pool-sized operations in the precompiled decode program
        # (paged_kv.pool_shaped_ops): 0 for a pool updated in place, None
        # until precompile() has read the executable's text
        self.decode_pool_shaped_ops: Optional[int] = None
        # speculative verify: greedy target chain + chosen-token logprobs
        # for a [B, S] candidate batch in ONE dispatch. S is pow2-padded
        # by the caller, so the compile count is log2(spec_k+1) — the
        # same static-width scheme the adaptive decode chunk uses.
        def _verify_impl(p, toks, cache, tables, limit):
            logits, cache = paged_verify_step_fn(
                p, toks, self.cfg, cache, tables, limit)
            # the SAME stable tie-break the decode sampler uses: the
            # token-identity guarantee rests on both paths picking the
            # same greedy token from the same logit values
            nxt = greedy_argmax(logits)
            lp = jnp.take_along_axis(
                logits, nxt[..., None], axis=-1)[..., 0] \
                - jax.nn.logsumexp(logits, axis=-1)
            return nxt, lp, cache

        self._verify = jax.jit(_verify_impl, donate_argnums=(2,))
        self._set_lens = jax.jit(
            lambda cache, lens: {**cache, "len": lens},
            donate_argnums=(0,))
        self._insert_batch = jax.jit(self._insert_batch_impl,
                                     donate_argnums=(0,))
        self._set_len = jax.jit(
            lambda cache, length, slot: {
                **cache, "len": cache["len"].at[slot].set(length)},
            donate_argnums=(0,))

    # ---------------- jitted bodies ----------------

    def _decode_impl(self, params, token, cache, tables, active, temperature,
                     top_k, top_p, rng, greedy_only=False, kernel="gather",
                     chunk_len=1):
        from kubeflow_tpu.serving.paged_kv import paged_decode_step

        def one_step(carry, rng_step):
            token, cache = carry
            logits, cache, stats = paged_decode_step(
                params, token, self.cfg, cache, tables, kernel=kernel,
                mesh=self.mesh, active=active)
            nxt = sample_logits(logits, rng_step, temperature, top_k,
                                top_p, greedy_only=greedy_only)
            # chosen-token logprob under the MODEL distribution (OpenAI
            # convention: pre-temperature/filtering). Gather-then-logsumexp
            # rather than materializing the full [B, V] log_softmax.
            lp = jnp.take_along_axis(
                logits, nxt[:, None], axis=-1)[:, 0] \
                - jax.nn.logsumexp(logits, axis=-1)
            # idle slots: pin len to 0 so the cursor can't creep toward
            # max_seq (their scatter lands in the scratch block 0)
            cache["len"] = jnp.where(active, cache["len"], 0)
            return (nxt, cache), (nxt, lp, stats)

        rngs = jax.random.split(rng, chunk_len)
        (next_tok, cache), (toks, lps, stats) = jax.lax.scan(
            one_step, (token, cache), rngs)
        # next_tok: the device-side carry the pipelined dispatch feeds the
        # NEXT chunk without waiting for the host to read toks back.
        # stats: the layers' counts summed over the chunk's steps (empty
        # for a dense model), a few KB read back beside the tokens
        stats = {key: val if key == "experts" else val.sum(0)
                 for key, val in stats.items()}
        return toks, lps, next_tok, cache, stats  # toks/lps: [chunk, B]

    def _insert_batch_impl(self, cache, k_new, v_new, blk_ids, lengths,
                           slots):
        from kubeflow_tpu.serving.paged_kv import paged_insert_batch

        return paged_insert_batch(cache, k_new, v_new, blk_ids, lengths,
                                  slots)

    # ---------------- public API ----------------

    def _refuse(self, mechanism: str) -> None:
        """Raise for a mechanism the model cannot be served with
        (``PagedOps.refuses``), naming it and the reason."""
        raise ValueError(f"{type(self.cfg).__name__} cannot be served with "
                         f"{mechanism}: {self.model.refuses[mechanism]}")

    def _refuse_tiers(self) -> None:
        if "disaggregated tiers" in self.model.refuses:
            self._refuse("disaggregated tiers")

    def precompile(self, depot=None, stats=None, wait_s: float = 0.0,
                   tier: str = "") -> str:
        """Split the decode compile from request #1 (the serving analogue
        of ``Trainer.precompile``): AOT-lower the steady-state decode
        program — full ``decode_chunk``, greedy batch, the engine's
        resolved kernel; the dominant program of the shared-system-prompt
        serving workload — and compile it NOW, fetching the executable
        from an executable depot (``parallel/depot.py``) when one is
        given and publishing on a miss. A fleet scale-up replica whose
        warm-pool claim pre-fetched the entry therefore deserializes in
        place of the cold compile; every degraded path stays a counted
        local compile (depot fallback semantics), never a failure.
        Returns the depot outcome ("hit" / "published" / "compiled" /
        "no_depot"), also kept as ``self.depot_outcome``. Other compile
        variants (non-greedy batches, adaptive chunk trims, prefill
        widths) still compile lazily via the jitted path — the
        persistent XLA compile cache covers those across replicas."""
        from kubeflow_tpu.parallel.depot import load_or_compile

        b = self.max_batch
        if tier:
            self._refuse_tiers()
        if tier == "prefill":
            # the prefill tier's steady-state program is the CHUNKED
            # prefill (long prompts stream through it; bucketed admission
            # stays lazily jitted) — keyed under its own stage scope, the
            # PR 11 per-stage scheme reused for the two tier programs of
            # one model: a scale-up prefill replica hits THIS entry and a
            # decode replica hits the decode entry, never each other's
            lowered = self._prefill_chunk.lower(
                self.params, jnp.zeros((1, self._chunk_width), jnp.int32),
                self.cache,
                jnp.zeros((b, self.paged.max_blocks_per_seq), jnp.int32),
                jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
            self._compiled_prefill_chunk, outcome = load_or_compile(
                lowered, depot, mesh=self.mesh, stats=stats, wait_s=wait_s,
                stage="serving-prefill",
                extra=(f"chunk={self._chunk_width}", self.quant.tag()))
            self.depot_outcome = outcome
            return outcome
        lowered = self._decode.lower(
            self.params, jnp.zeros((b,), jnp.int32), self.cache,
            jnp.zeros((b, self.paged.max_blocks_per_seq), jnp.int32),
            jnp.zeros((b,), bool), jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32),
            jax.random.key(0), greedy_only=True, kernel=self.kernel,
            chunk_len=self.decode_chunk)
        # the quant tag ALWAYS joins the fingerprint ("quant=off" when
        # unquantized): same-HLO entries under different quant configs
        # can never collide, and a warm claim's key-agnostic prefetch
        # therefore lands the per-config executable automatically
        self._compiled_decode, outcome = load_or_compile(
            lowered, depot, mesh=self.mesh, stats=stats, wait_s=wait_s,
            stage=("serving-decode-tier" if tier == "decode" else None),
            extra=("serving-decode", self.quant.tag()))
        self.depot_outcome = outcome
        self._note_pool_shaped_ops()
        return outcome

    def _note_pool_shaped_ops(self) -> None:
        """Count and log the precompiled decode program's pool-sized
        operations, where the executable gives its text (one loaded from
        the depot may not). The lazily jitted variants are not read: that
        would compile them a second time."""
        from kubeflow_tpu.serving.paged_kv import pool_shaped_ops

        try:
            text = self._compiled_decode.as_text()
        except Exception:
            text = None
        if not text:
            return
        # a recurrent layer's state arrays count as pools
        shapes = [self.cache[key].sharding.shard_shape(self.cache[key].shape)
                  for key in (*self.model.pool_rows, *self.model.state_rows)]
        found = pool_shaped_ops(text, shapes)
        self.decode_pool_shaped_ops = len(found)
        log = logger.warning if found else logger.info
        log("decode program: %d pool-shaped operations%s (a pool updated "
            "in place has none)", len(found),
            "".join(f"; {name} {op} {rtype}" for name, op, rtype in found))

    def validate_prompt(self, prompt: Sequence[int],
                        sampling: Optional[SamplingParams] = None) -> None:
        """Raise if the prompt can't be served. Called by add_request; also
        callable up front to vet a whole batch before enqueuing any of it."""
        from kubeflow_tpu.serving.paged_kv import blocks_for

        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + 1 > self.max_seq:
            # prompts beyond the largest bucket stream through CHUNKED
            # prefill (paged_prefill_chunk); max_seq is the only cap
            raise ValueError(f"prompt too long for max_seq={self.max_seq}")
        if sampling is not None:
            # a reservation that can NEVER succeed must fail fast here —
            # re-queueing it would spin generate()'s drain loop forever
            need = min(
                blocks_for(len(prompt) + sampling.max_tokens,
                           self.paged.block_size),
                self.paged.max_blocks_per_seq)
            usable = self.paged.num_blocks - 1       # block 0 is scratch
            if need > usable:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool only has "
                    f"{usable}; raise kv_num_blocks or lower max_tokens")

    def add_request(self, prompt: Sequence[int],
                    sampling: Optional[SamplingParams] = None,
                    trace: Optional[str] = None,
                    hold_after_prefill: bool = False) -> GenRequest:
        """``trace``: an incoming W3C traceparent (router/server span) —
        the request's queue span roots under it, so the full
        router -> server -> queue -> prefill -> decode chain shares one
        trace id across processes. ``hold_after_prefill``: disaggregated
        prefill tier — park after prefill + first token for KV export
        instead of decoding."""
        sampling = sampling or SamplingParams()
        if hold_after_prefill:
            self._refuse_tiers()
        self.validate_prompt(prompt, sampling)
        req = GenRequest(id=next(self._ids), prompt=list(map(int, prompt)),
                         sampling=sampling,
                         hold_after_prefill=bool(hold_after_prefill))
        req.t_enqueue = time.time()
        qspan = self.obs.start(
            "request.queue", parent=trace,
            attrs={"request_id": req.id, "prompt_tokens": len(req.prompt)})
        req.spans["queue"] = qspan
        req.trace = (qspan.trace_id, qspan.span_id)
        with self._lock:
            self._waiting.append(req)
        return req

    def abort(self, reqs: Sequence[GenRequest]) -> None:
        """Give up on requests (caller timeout / disconnect): waiting ones
        leave the queue immediately; active ones release their slot at the
        start of the next step. Without this, a timed-out caller's slots
        would stay occupied until max_tokens (ADVICE r1 finding c)."""
        ids = set()
        for r in reqs:
            r.aborted = True
            r.done = True
            ids.add(r.id)
            # still-open spans (a queue span of a never-admitted request)
            # close NOW with the abort attr — an aborted request must
            # leave a coherent trace, never a dangling open span
            for sp in r.spans.values():
                if sp.t1 is None:
                    self.obs.end(sp, aborted=True)
        with self._lock:
            self._waiting = [r for r in self._waiting if r.id not in ids]
            self._aborted.update(ids)

    # ------------- disaggregated prefill/decode (serving/disagg.py) -------
    # Engine-thread-only: these mutate the cache (whose buffers the decode
    # dispatch donates), so cross-thread callers MUST route through
    # submit_ctl. Single-threaded tests may call them directly between
    # step()s.

    def held_requests(self) -> list[GenRequest]:
        return list(self._held.values())

    def export_held_kv(self, req: GenRequest) -> Optional[dict]:
        """Package a held request's PROMPT blocks for migration: gather
        the first ``blocks_for(len(prompt))`` blocks of its reservation
        (the empty generation-budget tail never travels) to host numpy,
        plus everything the decode tier needs to resume — prompt, the
        prefill-sampled token #1 and its logprob, sampling params and the
        original enqueue time (so the decode pod's latency marks stay on
        the request's true clock). Returns None when the request was
        aborted/released before export (the caller drops the migration)."""
        from kubeflow_tpu.serving.paged_kv import (
            blocks_for, gather_kv_blocks,
        )

        slot = req.slot
        if slot is None or self._held.get(slot) is not req:
            return None
        bs = self.paged.block_size
        n = blocks_for(len(req.prompt), bs)
        ids = self.paged.slot_blocks(slot)[:n]
        return {
            "prompt": list(req.prompt),
            "first_token": int(req.generated[0]),
            "first_lp": float(req.logprobs[0]),
            "sampling": dataclasses.asdict(req.sampling),
            "t_enqueue": req.t_enqueue,
            "t_prefill_done": req.t_first_token,
            "block_size": bs,
            "n_blocks": n,
            "blocks": gather_kv_blocks(self.cache, ids),
        }

    def release_held(self, req: GenRequest) -> bool:
        """Drop a held request's slot + block reservation — the prefill
        side of the ownership edge, called after the decode tier acked
        the handoff (ownership moved) OR on a failed/aborted migration
        (ownership stays dropped; radix-published blocks remain cached
        and evictable, so a local re-prefill is one cheap chunk)."""
        slot = req.slot
        if slot is None or self._held.get(slot) is not req:
            return False
        del self._held[slot]
        req.done = True
        self.paged.release(slot)
        self._free.append(slot)
        return True

    def inject_request(self, prompt: Sequence[int],
                       sampling: SamplingParams, *, first_token: int,
                       first_lp: float, blocks: dict, n_blocks: int,
                       t_enqueue: float = 0.0) -> Optional[GenRequest]:
        """Decode-tier admission of a migrated prefill: reserve a slot,
        scatter the imported prompt blocks into the pool (radix-shared
        prefix blocks are skipped — the pool already holds them), set the
        slot length and commit token #1 exactly like a local admission.
        The reservation refcounts every imported block BEFORE the scatter,
        so concurrent eviction pressure can never reclaim a mid-handoff
        block. Returns None when no slot or pool capacity is available
        (the caller nacks the handoff and the prefill pod falls back to
        local re-prefill)."""
        from kubeflow_tpu.serving.paged_kv import scatter_kv_blocks

        self._refuse_tiers()
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
        L = len(prompt)
        n_shared = self.paged.reserve(
            slot, L, sampling.max_tokens, min_blocks=n_blocks,
            prompt=prompt, defer_publish=True)
        if n_shared is None:
            with self._lock:
                self._free.append(slot)
            return None
        req = GenRequest(id=next(self._ids),
                         prompt=list(map(int, prompt)), sampling=sampling)
        req.t_enqueue = t_enqueue or time.time()
        ids = self.paged.slot_blocks(slot)[:n_blocks]
        if n_shared < n_blocks:
            sub = {k: v[:, n_shared:n_blocks]
                   for k, v in blocks.items()}
            self.cache = scatter_kv_blocks(self.cache, ids[n_shared:], sub)
        self.cache = self._set_len(self.cache, jnp.int32(L),
                                   jnp.int32(slot))
        # publish the imported full prompt blocks to THIS pool's radix
        # tree: a later fully-shared-prefix request can then bypass the
        # prefill tier entirely and admit here at radix-hit cost
        self.paged.publish_prompt_blocks(slot, prompt, L)
        self._launch_admitted(req, slot)
        # the token arrived on the host: the decode's carry takes it here
        self._carry = self._carry.at[slot].set(int(first_token))
        self._commit_first(req, slot, int(first_token), float(first_lp))
        return req

    # ---------------- observability hooks ----------------

    def _end_queue_span(self, req: GenRequest, slot: int,
                        n_shared: int) -> None:
        """The queue span ends at slot assignment (admission), not at
        first token — TTFT minus queue time is the prefill cost."""
        sp = req.spans.get("queue")
        if sp is not None and sp.t1 is None:
            self.obs.end(sp, slot=slot, shared_blocks=n_shared)

    def _dispatch_span(self, name: str, reqs: Sequence[GenRequest],
                       **attrs) -> Any:
        """Engine-level span (decode/verify/batched-prefill dispatch):
        owned by ONE trace when every covered request shares it, else
        top-level with the participating ids in ``attrs.trace_ids`` so
        per-trace filtering still finds it."""
        tids = sorted({r.trace[0] for r in reqs if r.trace})
        kw: dict = {}
        if len(tids) == 1:
            kw["trace_id"] = tids[0]
            if len(reqs) == 1 and reqs[0].spans.get("queue") is not None:
                kw["parent"] = reqs[0].spans["queue"]
        elif tids:
            attrs["trace_ids"] = tids
        return self.obs.start(name, attrs=attrs, **kw)

    def _open_phase(self, name: str, attrs: Optional[dict] = None) -> None:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        span = self.obs.start(name, parent=self._step_span, attrs=attrs)
        span.attrs["gap_us"] = 0.0
        tail, self._tail = self._tail, None
        if tail is not None:
            # the host work between two phases belongs to a host phase:
            # the last one, if it was one, runs on to this one's start
            now = span.t0 = time.time()
            tail.attrs["gap_us"] += 1e6 * (now - tail.t1)
            tail.t1 = now
            self.obs.end(tail)
        elif not name.endswith(".wait"):
            # ... else this one, back to where the last wait (or the step)
            # ended; a wait always begins at its own start
            span.attrs["gap_us"] = 1e6 * (span.t0 - self._phase_mark)
            span.t0 = self._phase_mark
        self._cur_phase = (name, span, ann)

    def _close_phase(self) -> str:
        name, span, ann = self._cur_phase
        self._cur_phase = None
        ann.__exit__(None, None, None)
        if name.endswith(".wait"):
            self.obs.end(span)
            self._phase_mark = span.t1
        else:
            # a host phase ends when the next phase begins (_open_phase)
            # or the step does
            span.t1 = time.time()
            self._tail = span
        return name

    @contextlib.contextmanager
    def _phase(self, name: str, **attrs):
        """One phase of ``step()`` on the engine thread's timeline: a
        child span of the open ``engine.step`` plus a profiler annotation
        of the same name. Phases TILE the step — entering one inside
        another closes the outer one and reopens it (a new span of its
        name) on the way out, and the host's work between two phases (the
        spans' own bookkeeping, the code between two ``with`` blocks) is
        charged to the host phase next to it, its ``gap_us`` attr — so
        every instant, and every device idle gap, lies in one phase. Rule
        of the names: in a phase ending in ``.wait`` the host is blocked
        on the device, from its own start to its own end; every other
        phase is host work. Yields the span's attrs, for counts only known
        at the end. Outside ``step()`` there is no timeline: nothing is
        recorded."""
        if self._step_span is None:
            yield {}
            return
        outer = self._close_phase() if self._cur_phase else None
        self._open_phase(name, attrs)
        try:
            yield self._cur_phase[1].attrs
        finally:
            self._close_phase()
            if outer is not None:
                self._open_phase(outer)

    def _note_request_latency(self, req: GenRequest, n_new: int) -> None:
        """Feed the request histograms after committing ``n_new`` tokens
        in one read-back. The first token closes TTFT; later commits
        spread the read-back gap evenly over the chunk's tokens (the
        honest per-token latency of multistep decode — tokens inside one
        dispatch arrive together, so per-commit wall deltas would read
        as zero)."""
        if n_new <= 0:
            return
        now = time.time()
        if req.t_first_token == 0.0:
            req.t_first_token = now
            if req.t_enqueue:
                self.request_hists["ttft"].observe(now - req.t_enqueue)
            n_new -= 1
        elif req.t_second_token == 0.0:
            # first commit past token #1 = the first DECODE commit; on a
            # disagg decode pod this closes the migration decomposition
            req.t_second_token = now
        if n_new > 0 and req.t_last_commit:
            gap = max(0.0, now - req.t_last_commit) / n_new
            self.request_hists["itl"].observe(gap, n_new)
        req.t_last_commit = now

    def kv_row_bytes(self) -> int:
        """Bytes ONE token caches over all layers and pools, as stored
        (a quantized pool's scale tables left out)."""
        return sum(self.cache[key].nbytes // (self.cache[key].shape[1]
                                              * self.paged.block_size)
                   for key in self.model.pool_rows)

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._waiting or self._active or self._chunked
                        or self._ctl)

    def submit_ctl(self, fn) -> None:
        """Queue ``fn`` to run on the step thread at the top of the next
        step() — the only safe way for another thread to touch engine/
        cache state (the decode dispatch donates the cache buffers).
        Callers needing the result wrap ``fn`` to capture it and wake the
        model loop (serving/disagg.py TierRuntime.run_on_engine)."""
        with self._lock:
            self._ctl.append(fn)

    def _drain_ctl(self) -> None:
        with self._lock:
            ops, self._ctl = self._ctl, []
        for fn in ops:
            fn()

    def scheduler_stats(self) -> dict:
        """Scheduler counters + gauges for /metrics (occupancy, queue
        depth, token backlog, prefix-hit and preempt counters — the
        serving controller's autoscale/affinity signals)."""
        with self._lock:
            waiting = len(self._waiting)
            # token backlog: prompt + generation budget of queued requests
            # plus the un-prefilled remainder of in-flight chunked prompts
            # — the work this replica owes but has not scheduled, the
            # scale-up signal queue_depth alone understates for long
            # prompts
            backlog = sum(len(r.prompt) + r.sampling.max_tokens
                          for r in self._waiting)
        # the step loop mutates _chunked WITHOUT the lock: snapshot the
        # values in one C-level call (GIL-atomic) before iterating, or a
        # mid-scrape chunk completion raises dict-changed-size and the
        # busiest replica goes invisible to the autoscaler
        backlog += sum(max(0, len(st.req.prompt) - st.offset)
                       for st in list(self._chunked.values()))
        return self.sched.snapshot(
            active=len(self._active), waiting=waiting,
            chunked=len(self._chunked), max_batch=self.max_batch,
            prefix_hits=self.paged.prefix_hits,
            prefix_queries=self.paged.prefix_queries,
            backlog_tokens=backlog)

    def step(self) -> list[GenRequest]:
        """Admit waiting requests, dispatch one decode chunk, retire
        finished. Pipelined (default): the dispatch goes out BEFORE the
        previous chunk's tokens are fetched, so device compute overlaps
        host transfer + bookkeeping; results therefore lag one chunk.
        Returns requests that finished this step.

        A step that has anything to do records one ``engine.step`` span
        whose children (``_phase``) tile it from its start to its end:
        ``step.admit`` (queued control ops, the abort sweep, admission),
        ``admit.paged``, ``admit.launch``, ``step.dispatch``,
        ``dispatch.launch``, ``prefill.wait``, ``step.wait``,
        ``step.commit``. Its ``first_on_device`` attr counts the
        admissions whose first token went into a decode launch before the
        host read it; ``gc_ms`` is the interpreter's collections inside
        the step; the long pauses since the last look follow as root
        ``host.gc`` spans."""
        with self._lock:
            waiting = len(self._waiting)
            pending = bool(self._ctl or self._aborted)
        if not (waiting or pending or self._active or self._chunked
                or self._inflight is not None):
            return self._step()
        sched = self.sched
        before = (sched.admitted + sched.chunked_started,
                  sched.admission_stalls, sched.first_on_device)
        gc_s = sum(obs_trace.gc_seconds)
        self._step_span = self.obs.start("engine.step", attrs={
            "waiting": waiting, "active": len(self._active),
            "free_slots": len(self._free)})
        self._phase_mark = self._step_span.t0
        try:
            with jax.profiler.TraceAnnotation("engine.step"):
                return self._step()
        finally:
            span, self._step_span = self._step_span, None
            attrs = dict(
                admitted=sched.admitted + sched.chunked_started - before[0],
                stalled=int(sched.admission_stalls > before[1]),
                first_on_device=sched.first_on_device - before[2],
                gc_ms=1000.0 * (sum(obs_trace.gc_seconds) - gc_s))
            tail, self._tail = self._tail, None
            if tail is not None:
                # the last host phase runs on to the step's end
                span.t1 = time.time()
                tail.attrs["gap_us"] += 1e6 * (span.t1 - tail.t1)
                tail.t1 = span.t1
                self.obs.end(tail)
            self.obs.end(span, **attrs)
            self.obs.record_gc_pauses()

    def _step(self) -> list[GenRequest]:
        self.sched.note_step()
        with self._phase("step.admit"):
            self._drain_ctl()
            self._sweep_aborted()
            self._admit()
        finished_pre: list[GenRequest] = []
        if self.spec is not None and self._active:
            if all(r.sampling.temperature == 0
                   for r in self._active.values()):
                # speculative path: flush any pipelined chunk first (its
                # tokens are this step's draft context) and read back this
                # step's first tokens, then one draft+verify round —
                # synchronous by construction, the drafter needs the
                # committed tokens back
                if self._inflight is not None:
                    prev, self._inflight = self._inflight, None
                    finished_pre = self._process_chunk(prev)
                finished_pre += self._commit_first_tokens()
                if not self._active:
                    return finished_pre
                spec_finished = self._spec_step()
                if spec_finished is not None:
                    return finished_pre + spec_finished
                # no stream drafted anything: a width-1 verify would
                # commit ONE token per dispatch — plain multistep decode
                # commits chunk_len. Fall through to it (counted), so
                # the drafterless worst case stays AT decode throughput,
                # never below it.
                self.sched.note_spec_undrafted()
            else:
                # a non-greedy request in the batch: speculative
                # acceptance is only exact for greedy, so this dispatch
                # runs the normal decode path (counted — a quiet
                # fallback would read as a silent speedup regression)
                self.sched.note_spec_fallback()
        if not self.decode_pipeline:
            # synchronous mode: every token on the host before the launch
            finished_pre += self._commit_first_tokens()
        new_inflight = None
        if self._active:
            with self._phase("step.dispatch"):
                if self._need_dispatch():
                    new_inflight = self._dispatch_decode()
        # pipelined: the admissions' first tokens are read back only now,
        # behind the decode launch that already carries them
        finished = self._commit_first_tokens(new_inflight)
        prev, self._inflight = self._inflight, new_inflight
        if prev is not None:
            finished += self._process_chunk(prev)
        if not self.decode_pipeline and self._inflight is not None:
            # synchronous mode: flush immediately (no overlap, no lag)
            flush, self._inflight = self._inflight, None
            finished += self._process_chunk(flush)
        return finished_pre + finished

    def _sweep_aborted(self) -> None:
        """Free the slots of the requests aborted since the last step."""
        with self._lock:
            aborted, self._aborted = self._aborted, set()
        if not aborted:
            return
        for slot, req in list(self._active.items()):
            if req.id in aborted:
                del self._active[slot]
                self.paged.release(slot)
                self._free.append(slot)
        # a held prefill whose request aborted mid-migration releases
        # its side HERE — the prefill half of the "releases on both
        # sides" contract (the decode half is disagg release/collect)
        for slot, req in list(self._held.items()):
            if req.id in aborted:
                del self._held[slot]
                self.paged.release(slot)
                self._free.append(slot)
        # abort of a request whose chunked prefill is mid-flight is
        # observed HERE — between chunks — not after the full prompt:
        # the slot and its private blocks come back immediately (the
        # blocks it already published stay cached and shareable)
        for slot, st in list(self._chunked.items()):
            if st.req.id in aborted:
                self._cancel_chunked(slot)

    def _decoding(self) -> list:
        """The active (slot, request) pairs a decode chunk runs for: all
        but this step's admissions whose first token, not read back yet,
        uses up their budget (``max_tokens`` 1, or a prompt one short of
        ``max_seq``) — those retire at the read-back."""
        spent = {id(r) for p in self._pending for r, _ in p["rows"]
                 if min(r.sampling.max_tokens,
                        self.max_seq - len(r.prompt)) <= 1}
        if not spent:
            return list(self._active.items())
        return [(slot, req) for slot, req in self._active.items()
                if id(req) not in spent]

    def _dispatch_decode(self) -> dict:
        """Build the dispatch's host arrays and launch one decode chunk
        (asynchronous: returns once the program is enqueued). The
        returned in-flight record is read back by _process_chunk."""
        rows = self._decoding()
        active_mask = np.zeros((self.max_batch,), bool)
        temp = np.zeros((self.max_batch,), np.float32)
        top_k = np.zeros((self.max_batch,), np.int32)
        top_p = np.ones((self.max_batch,), np.float32)
        for slot, req in rows:
            active_mask[slot] = True
            temp[slot] = req.sampling.temperature
            top_k[slot] = req.sampling.top_k
            top_p[slot] = req.sampling.top_p
        tab = self._dispatch_tables()
        chunk_len = self.sched.decode_chunk_len(
            self._min_deterministic_remaining(rows),
            pressure=bool(self._waiting))
        self.sched.note_decode_dispatch(chunk_len)
        attrs = {}
        if self.model.state_rows:
            # slots whose recurrent state a step reads and writes
            attrs["state_slots"] = len(rows)
        dspan = self._dispatch_span(
            "decode.step", [r for _, r in rows],
            chunk_len=chunk_len, batch=len(rows), **attrs)
        # static: an all-greedy batch skips the per-step full-vocab
        # sort (two compile variants total)
        greedy_only = not bool((temp > 0).any())
        with self._phase("dispatch.launch"):
            self._rng, step_rng = jax.random.split(self._rng)
            args = (self.params, self._carry, self.cache, jnp.asarray(tab),
                    jnp.asarray(active_mask), jnp.asarray(temp),
                    jnp.asarray(top_k), jnp.asarray(top_p), step_rng)
            if (self._compiled_decode is not None and greedy_only
                    and chunk_len == self.decode_chunk):
                # the precompile()d executable (depot fast path): same
                # program as the jitted call below, acquired without a
                # cold compile on a scale-up replica
                toks, lps, self._carry, self.cache, stats = \
                    self._compiled_decode(*args)
            else:
                toks, lps, self._carry, self.cache, stats = self._decode(
                    *args, greedy_only=greedy_only,
                    kernel=self.kernel, chunk_len=chunk_len)
        return {
            "toks": toks, "lps": lps, "stats": stats,
            "chunk_len": chunk_len, "span": dspan,
            # snapshot: tokens belong to the requests active at
            # DISPATCH time — a slot may host a new request by the
            # time these arrays are read back
            "snapshot": rows,
        }

    def _need_dispatch(self) -> bool:
        """Skip the next dispatch when the in-flight chunk already covers
        every active request's remaining budget — kills the tail-overshoot
        chunk for uniform max_tokens batches. (A request whose first token
        is not read back yet was admitted after that dispatch.)"""
        rows = self._decoding()
        if self._inflight is None:
            return bool(rows)
        snapshot_reqs = {id(r) for _, r in self._inflight["snapshot"]}
        chunk = self._inflight["chunk_len"]
        for _, req in rows:
            if id(req) not in snapshot_reqs:
                return True            # admitted after the dispatch
            if (len(req.generated) + chunk < req.sampling.max_tokens
                    and len(req.prompt) + len(req.generated) + chunk
                    < self.max_seq):
                return True            # still needs tokens past the chunk
        return False

    def _min_deterministic_remaining(self, rows: list) -> Optional[int]:
        """Earliest DETERMINISTIC finish (max_tokens / max_seq bound)
        among the requests of a decode dispatch (``rows``), net of tokens
        the in-flight chunk will already have produced and of a first
        token not read back yet — the boundary the adaptive decode chunk
        trims to so a freeing slot rejoins mid-chunk, not decode_chunk
        device steps later. EOS finishes are not predictable and don't
        count."""
        snapshot_reqs = (
            {id(r) for _, r in self._inflight["snapshot"]}
            if self._inflight is not None else set())
        pending = (self._inflight["chunk_len"]
                   if self._inflight is not None else 0)
        first = {id(r) for p in self._pending for r, _ in p["rows"]}
        rem = None
        for _, req in rows:
            n = len(req.generated) + (id(req) in first)
            r = min(req.sampling.max_tokens - n,
                    self.max_seq - len(req.prompt) - n)
            if id(req) in snapshot_reqs:
                r -= pending
            r = max(1, r)
            rem = r if rem is None else min(rem, r)
        return rem

    def _commit_token(self, req, slot: int, tok: int, lp: float) -> bool:
        """Append ONE committed token and report whether it finishes the
        request (eos / stop ids / max_tokens / max_seq) — the single
        stop-semantics implementation shared by the decode read-back,
        the speculative commit loop and admission, so the paths can
        never drift on what ends a generation."""
        req.generated.append(tok)
        req.logprobs.append(lp)
        self.generated_tokens += 1
        self._tokens[slot] = tok
        eos = req.sampling.eos_id
        return ((eos is not None and tok == eos)
                or tok in req.sampling.stop_token_ids
                or len(req.generated) >= req.sampling.max_tokens
                or len(req.prompt) + len(req.generated) >= self.max_seq)

    def _retire(self, req, slot: int) -> None:
        """Finish a request and free its slot (guarded: the slot may
        already host a newer request when retiring from a stale
        dispatch snapshot)."""
        req.done = True
        req.t_done = time.time()
        if not req.aborted and req.t_enqueue:
            self.request_hists["e2e"].observe(req.t_done - req.t_enqueue)
        for owner in (self._active, self._held):
            if owner.get(slot) is req:
                del owner[slot]
                self.paged.release(slot)
                self._free.append(slot)

    def _dispatch_tables(self):
        """Block tables for a decode/verify dispatch: mid-prefill slots'
        rows zeroed so their idle scatter lands in the scratch block,
        never a half-prefilled prompt block."""
        tab = self.paged.tables
        if self._chunked:
            tab = tab.copy()
            for s in self._chunked:
                tab[s] = 0
        return tab

    def _process_chunk(self, inflight: dict) -> list[GenRequest]:
        with self._phase("step.wait", device_steps=inflight["chunk_len"]):
            toks = np.asarray(inflight["toks"])     # [chunk, B] (blocks here)
            lps = np.asarray(inflight["lps"])
            routed = self._note_expert_stats(inflight["stats"], decode=True)
            experts = None                     # [chunk, layers, B, 1, k]
            if inflight["stats"] and any(
                    r.sampling.record_routing
                    for _, r in inflight["snapshot"]):
                experts = np.asarray(inflight["stats"]["experts"])
        self.steps += toks.shape[0]
        finished = []
        committed_total = 0
        with self._phase("step.commit") as counts:
            for slot, req in inflight["snapshot"]:
                if req.done:
                    continue           # aborted/retired after dispatch
                n0 = len(req.generated)
                done = False
                for t in range(toks.shape[0]):
                    if experts is not None and req.sampling.record_routing:
                        req.routing.append(experts[t, :, slot, 0])
                    if self._commit_token(req, slot, int(toks[t, slot]),
                                          float(lps[t, slot])):
                        # overshoot tokens beyond this point are trimmed
                        # (never appended); their cache writes went to this
                        # slot's own blocks / scratch and are ordered before
                        # any reuse
                        done = True
                        break
                n_new = len(req.generated) - n0
                committed_total += n_new
                self._note_request_latency(req, n_new)
                if done:
                    finished.append(req)
                    self._retire(req, slot)
            counts["tokens_committed"] = committed_total
            span = inflight.get("span")
            if span is not None:
                # the decode span covers dispatch -> read-back (pipelined:
                # device compute + the host overlap it bought)
                self.obs.end(span, tokens_committed=committed_total,
                             device_steps=int(toks.shape[0]), **routed)
        return finished

    def _note_expert_stats(self, stats, decode: bool = False) -> dict:
        """Fold a program's expert counts (``PagedOps.out``; nothing for
        a dense model) into the engine's counters. Returns the span attrs
        of a decode chunk: assignments made and distinct experts hit (and
        tokens that chose no expert, where the router has that choice: it
        is the counters' last column), summed over its steps and expert
        layers."""
        if not stats:
            return {}
        per_expert = np.asarray(stats["tokens_per_expert"])      # [Lm, E]
        if self.moe_tokens_per_expert is None:
            self.moe_tokens_per_expert = np.zeros(per_expert.shape, np.int64)
        self.moe_tokens_per_expert += per_expert
        absent = (int(np.asarray(stats["absent_picks"]).sum())
                  if "absent_picks" in stats else None)
        if absent is not None:
            self.moe_absent_picks += absent
        if not decode:
            return {}
        hit = int(np.asarray(stats["experts_hit"]).sum())
        self.moe_experts_hit += hit
        attrs = {"routed_assignments": int(per_expert.sum()),
                 "experts_hit": hit}
        if absent is not None:       # experts held on other chips
            attrs["absent_picks"] = absent
        if "skipped" in stats:       # a router with a choice of no expert
            attrs["skipped"] = int(np.asarray(stats["skipped"]).sum())
        return attrs

    def _spec_step(self) -> list[GenRequest]:
        """One speculative draft+verify round over the active batch.

        The drafter proposes up to spec_k tokens per stream from its own
        committed context; ONE verify dispatch writes all candidate KV
        rows (tail rows masked to scratch exactly like mid-prefill pad
        rows) and returns the target's greedy chain + logprobs; the
        longest draft prefix matching that chain commits, plus the
        target's own next token — so every round commits >= 1 token and
        greedy output is token-identical to plain decode. cache["len"]
        advances host-side by the COMMITTED count only: rejected rows
        sit beyond it, invisible to attention, and the next dispatch
        rewrites them before they could ever be unmasked."""
        with self._phase("step.dispatch"):
            launched = self._spec_dispatch()
        if launched is None:
            return None           # nothing to verify: caller runs decode
        drafts, vspan, toks, lps = launched
        with self._phase("step.wait", device_steps=1):
            toks = np.asarray(toks)
            lps = np.asarray(lps)
        self.steps += 1
        with self._phase("step.commit") as counts:
            finished, committed_total = self._spec_commit(drafts, toks, lps)
            counts["tokens_committed"] = committed_total
            self.obs.end(vspan, tokens_committed=committed_total)
        return finished

    def _spec_dispatch(self) -> Optional[tuple]:
        """Draft per stream and launch ONE verify over all of them:
        ``(drafts, span, toks, lps)`` with the device arrays not yet read
        back, or None when no stream drafted anything."""
        bs = self.paged.block_size
        drafts: dict[int, list[int]] = {}
        k_max = 0
        for slot, req in self._active.items():
            # deterministic remaining budget: drafts past it can never
            # commit (the commit loop stops at max_tokens/max_seq), so
            # they would only widen the verify batch for nothing
            rem = min(req.sampling.max_tokens - len(req.generated),
                      self.max_seq - len(req.prompt) - len(req.generated))
            d = self.spec.draft(req.prompt + req.generated)[:max(0, rem - 1)]
            drafts[slot] = d
            k_max = max(k_max, len(d))
        if k_max == 0:
            return None
        # pow2 verify width (input column + drafts): log2(spec_k+1)
        # compile variants, the scheduler's static chunk_len scheme
        width = ceil_pow2(1 + k_max)
        tokens = np.zeros((self.max_batch, width), np.int32)
        limit = np.zeros((self.max_batch,), np.int32)
        for slot, req in self._active.items():
            tokens[slot, 0] = self._tokens[slot]
            d = drafts[slot]
            tokens[slot, 1:1 + len(d)] = d
            # rows at/after the slot's reserved tokens scatter to scratch
            limit[slot] = len(self.paged.slot_blocks(slot)) * bs
        self.sched.note_spec_dispatch(
            sum(len(d) for d in drafts.values()))
        vspan = self._dispatch_span(
            "decode.verify", [r for _, r in self._active.items()],
            width=width, drafted=sum(len(d) for d in drafts.values()),
            batch=len(self._active))
        tab = self._dispatch_tables()
        with self._phase("dispatch.launch"):
            toks, lps, self.cache = self._verify(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(tab), jnp.asarray(limit))
        return drafts, vspan, toks, lps

    def _spec_commit(self, drafts: dict, toks, lps) -> tuple[list, int]:
        """Accept the longest draft prefix the target's greedy chain
        confirms, commit it plus the target's own next token, and publish
        the committed lengths: ``(finished requests, tokens committed)``."""
        finished = []
        committed_total = 0
        new_len = np.zeros((self.max_batch,), np.int32)
        for slot, req in list(self._active.items()):
            if req.done:
                continue               # aborted after dispatch
            d = drafts[slot]
            # acceptance: walk the target's greedy chain; position i's
            # token commits, and matching draft i validates position i+1
            accepted = 0
            committed: list[tuple[int, float]] = []
            for i in range(len(d) + 1):
                committed.append((int(toks[slot, i]),
                                  float(lps[slot, i])))
                if i < len(d) and d[i] == committed[-1][0]:
                    accepted += 1
                    continue
                break
            n_appended = 0
            done = False
            for tok, lp in committed:
                n_appended += 1
                if self._commit_token(req, slot, tok, lp):
                    done = True
                    break
            # count only draft tokens that actually COMMITTED: an early
            # stop (eos/budget) truncates acceptance too, or the counter
            # would overstate the drafter on eos-heavy traffic
            self.sched.note_spec_result(min(accepted, n_appended),
                                        n_appended)
            committed_total += n_appended
            self._note_request_latency(req, n_appended)
            if done:
                finished.append(req)
                self._retire(req, slot)
            else:
                # committed length only — rejected rows stay beyond it
                new_len[slot] = len(req.prompt) + len(req.generated) - 1
        self.cache = self._set_lens(self.cache, jnp.asarray(new_len))
        # the tokens were committed on the host: a plain decode dispatch
        # after this round takes them from here
        self._carry = jnp.asarray(self._tokens)
        return finished, committed_total

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Optional[SamplingParams] = None,
                 ) -> list[GenRequest]:
        """Synchronous batch API: submit all, step until drained."""
        reqs = [self.add_request(p, sampling) for p in prompts]
        while self.has_work():
            self.step()
        return reqs

    # ---------------- internals ----------------

    def _start_chunked(self, req, slot: int, n_shared: int) -> None:
        """Begin streaming a long prompt through chunked prefill. Chunks
        whose every row is radix-shared are skipped outright (the shared
        KV is already resident) — a fully-cached long prompt costs ONE
        chunk (the final one, for its last-row logits)."""
        L = len(req.prompt)
        W = self._chunk_width
        share_len = n_shared * self.paged.block_size
        start = min((share_len // W) * W, ((L - 1) // W) * W)
        with self._phase("admit.launch"):
            tables = jnp.asarray(self.paged.tables)
        self._chunked[slot] = _ChunkedPrefill(
            req=req, offset=start, share_len=share_len, tables=tables)
        self.sched.note_chunked_started()

    def _advance_chunked(self, slot: int) -> int:
        """One prefill chunk for the slot's in-flight long prompt; the
        final chunk also runs the lm head + first-token sample and
        publishes the slot's cache len (making the sequence visible to
        decode). Completed full blocks publish to the radix tree after
        every chunk. Returns the budget tokens consumed."""
        st = self._chunked[slot]
        req = st.req
        L = len(req.prompt)
        W = self._chunk_width
        attrs = {}
        if self.model.routed_per_token:
            attrs["routed_assignments"] = \
                min(W, L - st.offset) * self.model.routed_per_token
        if self.model.slot_rows or self.model.state_rows:
            # the chunk began from what the slot's previous chunk left,
            # not from zeros
            attrs["state_carried"] = st.offset > 0
        pspan = self._dispatch_span(
            "prefill.chunk", [req], slot=slot, offset=st.offset,
            width=W, chunk_index=st.offset // W, prompt_tokens=L, **attrs)
        piece = np.zeros((1, W), np.int32)
        part = req.prompt[st.offset:st.offset + W]
        piece[0, :len(part)] = part
        chunk_fn = self._compiled_prefill_chunk or self._prefill_chunk
        with self._phase("admit.launch"):
            st.x_last, self.cache, stats = chunk_fn(
                self.params, jnp.asarray(piece), self.cache, st.tables,
                jnp.int32(slot), jnp.int32(st.offset), jnp.int32(L),
                jnp.int32(st.share_len))
            # the chunks' expert counts stay on the device until the last
            # chunk's token is read back: no wait of their own
            experts = stats.pop("experts", None)   # [layers, W, k]
            st.stats = stats if st.stats is None else {
                key: st.stats[key] + val for key, val in stats.items()}
        if experts is not None and req.sampling.record_routing:
            st.routing.append(experts)
        st.offset += W
        self.sched.note_prefill_chunk(W)
        self.obs.end(pspan, final=st.offset >= L)
        # publish completed read-only blocks: every position < offset is
        # written and its write DISPATCHED, so a later sharer's reads are
        # device-ordered behind the content
        with self._phase("admit.paged"):
            self.paged.publish_prompt_blocks(slot, req.prompt,
                                             min(st.offset, L))
        if st.offset >= L:
            with self._phase("admit.launch"):
                logits = self._chunk_lm_head(self.params, st.x_last)
            tok, lp = self._sample_rows(logits, [req], [slot])
            with self._phase("admit.launch"):
                self.cache = self._set_len(
                    self.cache, jnp.int32(L), jnp.int32(slot))
            del self._chunked[slot]
            self.sched.note_chunked_admitted()
            self._launch_admitted(req, slot)
            # the chunks' expert counts and routing rows are read back
            # with the token
            self._pending.append({
                "tok": tok, "lp": lp, "rows": [(req, slot)], "span": None,
                "stats": st.stats, "routing": st.routing,
                "pad_rows": st.offset - L})
        return W

    def _chunked_phase(self, interleave: bool, budget: int,
                       spent: int) -> int:
        """Advance in-flight chunked prefills, oldest first: ONE chunk
        per step when interleaving, to completion otherwise (the legacy
        convoy) — aborts observed between chunks either way. Returns the
        updated budget spend. The single policy loop for both the
        resumed-prefill and fresh-start paths in _admit."""
        while self._chunked and (spent < budget or not interleave):
            slot = next(iter(self._chunked))
            if self._chunked[slot].req.aborted:
                self._cancel_chunked(slot)
                continue
            spent += self._advance_chunked(slot)
            if interleave:
                break      # one chunk per step while one is in flight
        return spent

    def _cancel_chunked(self, slot: int) -> None:
        """Abort/preempt a mid-flight chunked prefill: the slot and its
        private blocks return immediately; blocks it already published
        stay cached (their KV is valid — a pure function of the tokens)."""
        del self._chunked[slot]
        with self._phase("admit.paged"):
            self.paged.release(slot)
        self._free.append(slot)
        self.sched.note_preempt()

    def _admit(self) -> None:
        """The scheduler's prefill phase: spend this step's token quota on
        prefill UNITS — one chunk of the oldest in-flight chunked prefill
        first (FIFO), then admissions — and stop once the quota is spent
        (the first unit always runs, so progress is guaranteed). Decode
        dispatch follows in step(), so a long prompt can never convoy the
        live streams. With ``interleave_prefill=False`` chunked prompts
        run to completion inside one step (the legacy convoy, kept as the
        scheduler-off baseline), still abort-checked between chunks."""
        from kubeflow_tpu.serving.paged_kv import blocks_for

        bs = self.paged.block_size
        budget = self.sched.prefill_budget()
        interleave = self.sched.cfg.interleave_prefill
        # in-flight chunked prefills have priority, oldest first
        spent = self._chunked_phase(interleave, budget, 0)
        if self._chunked and interleave:
            # a long prompt is mid-prefill: admissions wait their turn
            # behind it (FIFO start order), decode proceeds regardless
            return
        while spent < budget or spent == 0:
            with self._lock:
                if not self._waiting or not self._free:
                    return
                req = self._waiting.pop(0)
                slot = self._free.pop()
            # reserve the blocks this request can ever touch; when the pool
            # is exhausted the request waits at the HEAD of the queue (FIFO
            # under memory pressure — later arrivals must not starve it).
            # Full prompt blocks already cached (same tokens, same
            # positions) are SHARED, not recomputed storage — including for
            # chunked prompts, whose private full blocks publish chunk by
            # chunk (defer_publish) instead of at reserve time
            chunked = (self._prefill is None
                       or len(req.prompt) > self.buckets[-1])
            with self._phase("admit.paged"):
                n_shared = self.paged.reserve(
                    slot, len(req.prompt), req.sampling.max_tokens,
                    min_blocks=blocks_for(len(req.prompt), bs),
                    prompt=req.prompt, defer_publish=chunked)
            if n_shared is None:
                with self._lock:
                    self._waiting.insert(0, req)
                self._free.append(slot)
                self.sched.note_stall()
                return
            self._end_queue_span(req, slot, n_shared)
            if chunked:
                self._start_chunked(req, slot, n_shared)
                spent = self._chunked_phase(interleave, budget, spent)
                if self._chunked and interleave:
                    return
                continue
            # batched admission: take the FIFO prefix of same-bucket
            # requests and pay ONE prefill+insert+sample dispatch for all
            # of them
            bucket = _bucket(len(req.prompt), self.buckets)
            batch = [(req, slot, n_shared)]
            while len(batch) < self.max_batch:
                with self._lock:
                    if not self._waiting or not self._free:
                        break
                    nxt = self._waiting[0]
                    if len(nxt.prompt) > self.buckets[-1] or \
                            _bucket(len(nxt.prompt),
                                    self.buckets) != bucket:
                        break
                    self._waiting.pop(0)
                    s2 = self._free.pop()
                with self._phase("admit.paged"):
                    ns2 = self.paged.reserve(
                        s2, len(nxt.prompt), nxt.sampling.max_tokens,
                        min_blocks=blocks_for(len(nxt.prompt), bs),
                        prompt=nxt.prompt)
                if ns2 is None:
                    with self._lock:
                        self._waiting.insert(0, nxt)
                    self._free.append(s2)
                    self.sched.note_stall()
                    break
                self._end_queue_span(nxt, s2, ns2)
                batch.append((nxt, s2, ns2))
            self._admit_prefill_batch(batch, bucket)
            self.sched.note_admitted(len(batch))
            spent += bucket * len(batch)

    def _admit_prefill_batch(self, batch, bucket: int) -> None:
        """One prefill + insert + first-token sample for a same-bucket
        admission batch. Rows pad to the next power of two (compile count
        log2(max_batch) per bucket) so the steady-state single-request
        admission does ~1 row of work, not max_batch rows; pad rows carry
        slot -1 and their writes land in the scratch block / are dropped."""
        from kubeflow_tpu.serving.paged_kv import blocks_for

        bs = self.paged.block_size
        width = min(self.max_batch, 1 << (len(batch) - 1).bit_length())
        nbmax = bucket // bs
        toks = np.zeros((width, bucket), np.int32)
        # pad rows: length 0 — prefill masks them out of MoE routing and
        # clamps its logit-gather index, so they never influence real rows
        lengths = np.zeros((width,), np.int32)
        blk = np.zeros((width, nbmax), np.int32)
        slots = np.full((width,), -1, np.int32)
        for i, (req, slot, n_shared) in enumerate(batch):
            toks[i, :len(req.prompt)] = req.prompt
            lengths[i] = len(req.prompt)
            nb_prefill = blocks_for(len(req.prompt), bs)
            ids = self.paged.slot_blocks(slot)
            blk[i, n_shared:nb_prefill] = ids[n_shared:nb_prefill]
            slots[i] = slot
        self.prefill_dispatches += 1
        pspan = self._dispatch_span(
            "prefill.batch", [r for r, _, _ in batch],
            bucket=bucket, batch=len(batch))
        with self._phase("admit.launch"):
            logits, filled = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lengths))
            self.cache = self._insert_batch(
                self.cache, filled["k"], filled["v"], jnp.asarray(blk),
                jnp.asarray(lengths), jnp.asarray(slots))
        tok, lp = self._sample_rows(logits, [r for r, _, _ in batch],
                                    slots, width=width)
        for req, slot, _ in batch:
            self._launch_admitted(req, slot)
        # the span ends when the tokens are read back
        self._pending.append({"tok": tok, "lp": lp, "span": pspan,
                              "rows": [(r, s) for r, s, _ in batch]})

    def _sample_rows(self, logits, reqs, slots, width: Optional[int] = None):
        """First-token sampling for admission rows, one launch: returns
        the tokens and their logprobs as device arrays, and writes each
        token into the decode's token carry at its slot (``slots``; -1 for
        a pad row), so the next decode launch takes it without a read."""
        width = width or len(reqs)
        temp = np.zeros((width,), np.float32)
        top_k = np.zeros((width,), np.int32)
        top_p = np.ones((width,), np.float32)
        rows = np.full((width,), -1, np.int32)
        rows[:len(slots)] = slots
        for i, r in enumerate(reqs):
            temp[i] = r.sampling.temperature
            top_k[i] = r.sampling.top_k
            top_p[i] = r.sampling.top_p
        with self._phase("admit.launch"):
            self._rng, rng = jax.random.split(self._rng)
            tok, lp, self._carry = self._first_sample(
                logits, rng, jnp.asarray(temp), jnp.asarray(top_k),
                jnp.asarray(top_p), self._carry, jnp.asarray(rows))
        return tok, lp

    def _launch_admitted(self, req, slot: int) -> None:
        """The part of admission that needs no token: the request takes
        its slot in the decode batch, or, on the disaggregated prefill tier
        (``hold_after_prefill``), parks there for export_held_kv instead of
        decoding — its slot stays allocated and its blocks refcount-pinned
        (PREFILL_OWNED in the handoff state machine) until release_held
        transfers or drops ownership."""
        req.slot = slot
        if req.hold_after_prefill:
            self._held[slot] = req
        else:
            self._active[slot] = req

    def _commit_first(self, req, slot: int, tok: int, lp: float) -> bool:
        """Commit generation token #1, the prefill-sampled one: the
        request finishes here on eos/budget (the same _commit_token stop
        semantics as every other path). Returns whether it did."""
        done = self._commit_token(req, slot, tok, lp)
        self._note_request_latency(req, 1)       # TTFT closes here
        if done:
            self._retire(req, slot)
        return done

    def _commit_first_tokens(self, launched: Optional[dict] = None
                             ) -> list[GenRequest]:
        """Read back this step's admissions' first tokens (with a chunked
        prompt's expert counts and routing rows) and commit them. Returns
        the requests that ended on their first token. ``launched``: the
        decode dispatch of this step, made before the read; the admissions
        it carries are counted as ``first_on_device``."""
        pending, self._pending = self._pending, []
        if not pending:
            return []
        with self._phase("prefill.wait"):
            got = [(np.asarray(p["tok"]), np.asarray(p["lp"]))
                   for p in pending]
            for p in pending:
                if p.get("stats"):
                    self._note_expert_stats(p["stats"])
                if p.get("routing"):
                    # chunks of [layers, W, k]; the last one's pad rows cut
                    rows = np.concatenate(
                        [np.asarray(e) for e in p["routing"]], axis=1)
                    req = p["rows"][0][0]
                    req.prompt_routing = \
                        rows[:, :rows.shape[1] - p["pad_rows"]]
                    req.routing.append(req.prompt_routing[:, -1])
        finished = []
        with self._phase("step.admit"):
            if launched is not None:
                carried = {id(r) for _, r in launched["snapshot"]}
                self.sched.note_first_on_device(sum(
                    id(req) in carried
                    for p in pending for req, _ in p["rows"]))
            for p, (tok, lp) in zip(pending, got):
                for i, (req, slot) in enumerate(p["rows"]):
                    if req.done:
                        continue       # aborted since its admission
                    if self._commit_first(req, slot, int(tok[i]),
                                          float(lp[i])):
                        finished.append(req)
                if p["span"] is not None:
                    self.obs.end(p["span"])
        return finished
