"""Multi-slice MPMD pipeline parallelism over DCN — executed, not modeled.

The SPMD pipeline (``parallel/pipeline.py``) keeps every stage in ONE
jitted program on one mesh: correct, and the single-program ORACLE this
module is tested against, but it cannot span slices — a v5p-128 job is
several ICI islands joined by DCN, and XLA will not place one SPMD
program across them. The MPMD design here follows "Scaling Deep Learning
Training with MPMD Pipeline Parallelism" (PAPERS.md): each stage is its
OWN jitted program on its OWN per-stage mesh (slice), activations and
grad-activations move stage-to-stage over an explicit point-to-point
transport, and a schedule (fill-drain GPipe baseline, 1F1B default)
drives the per-stage tick order.

Transport: host-staged send/recv (``jax.device_get`` -> wire ->
``jax.device_put``), which is ``jax.transfer_guard``-safe by construction
— every host transfer is explicit. On the CPU/emulated rig the wire is
loopback TCP (plus an optional per-transfer emulated DCN delay so
overlap is measurable); on real slices the same framing rides the DCN
between slice hosts. Two send disciplines are first-class because the
difference IS the measurement: ``blocking`` (GPipe parity baseline —
transfer time sits on the critical path, matching the analytic roofline's
un-overlapped collective model) and ``async`` (1F1B — a sender thread
drains a queue, so the wire hides under the next tick's compute).

Measured, not projected (the ISSUE-15 contract):
- ``bubble_fraction``: 1 - busy/(S * step window), aggregated over the
  post-warmup steps from per-stage busy accounting. GPipe must agree
  with the analytic fill-drain bound (S-1)/(S+M-1); 1F1B at the same
  activation stash (<= S live microbatches per stage, so it can run
  2M microbatches in GPipe's M-sized memory) must beat it.
- ``dcn_overlap_fraction``: 1 - send_block_s/wire_s — the fraction of
  wire time hidden under compute. ~0 for the blocking baseline, ->1 for
  the async 1F1B engine.

Numerics contract (tested): GPipe and 1F1B runs are BITWISE identical
(same per-microbatch programs, grads stashed per slot and reduced in one
fixed descending order — the same order the oracle's scan-VJP uses), and
both match the SPMD ``pipeline_apply`` oracle to float32 round-off
(step-0 loss bitwise; the trajectories drift only by XLA fusion-level
ulps, gated tightly — see tests/test_mpmd.py).

Per-stage executables are compile-once across the gang: fwd/bwd/head
programs go through ``parallel/depot.load_or_compile`` keyed with the
NEW ``stage=`` scope + the stage-mesh fingerprint, so a warm resubmit
deserializes every stage's programs instead of recompiling — and two
stages whose programs lower to IDENTICAL HLO (the common case: same
stage_fn, same shapes) can never collide on one entry.

Interleaved / virtual-stage 1F1B (``schedule="interleaved-1f1b"``,
Megatron-style): each of the S workers owns V model CHUNKS — worker r
holds global chunks {r, r+S, ..., r+(V-1)S} — so one microbatch crosses
every worker V times and the fill/drain cost amortizes over V*M units:
the analytic bubble drops from (S-1)/(S+M-1) to (S-1)/(V*M+S-1), BELOW
the single-stage-per-worker floor. The ring gains a wrap link (worker
S-1 -> worker 0 for activations, 0 -> S-1 for grad-activations) and
frames are keyed (kind, step, mb, virtual_stage) so chunk traffic never
aliases. The cost is activation stash: a worker holds up to
warmup+1 = (S-r-1)*2 + (V-1)*S + 1 live chunk-activations (vs <= S for
plain 1F1B) — measured and reported per stage. Grad slots still reduce
in the one fixed descending-microbatch order per chunk, so the loss
stays bitwise identical to GPipe and plain 1F1B over the same
``total_stages`` chunk partition.

The model behind the schedule is pluggable (``MLPSpec`` — the
CI harness — or ``pipeline_llama.MpmdLlamaSpec``: real transformer
blocks, embedding on chunk 0, LM head on the last chunk), selected by
``KFT_MPMD_MODEL`` in the worker entry.

Elastic pipeline (the ISSUE-20 contract): a stage death MID-RUN is a
bounded, measured event instead of a lost run. Three mechanisms:

- **Boundary snapshots**: every stage publishes a host-staged state
  snapshot (params + head params + opt slots, ``jax.device_get``-staged
  like the transport) into ``KFT_ELASTIC_DIR`` at each step boundary,
  latest TWO retained. Stages can only be one boundary apart (stage 0's
  step-k update needs grads that need the last stage's step-k backward),
  so the newest COMMON boundary across all stages is always on disk.
- **Epoch fencing**: every channel frame carries the rendezvous epoch
  as the LAST key element. The ingress loop drops (and counts) frames
  whose epoch differs from the channel's — a late frame from a dead
  incarnation can never be delivered to ``recv_act``/``recv_grad``.
- **Rollback + replay**: when the reconciler replaces a dead stage
  worker (same stage-Service address — neighbors never re-stamp), the
  replacement announces the bumped epoch through the snapshot dir;
  survivors abort the in-flight microbatch window via the existing
  mailbox-poison path (params untouched — they only change at
  ``apply_grads``), drain-and-count stale frames, re-rendezvous at the
  new epoch on the SAME binds, every stage restores the newest common
  boundary, and the schedule replays from there. The loss trajectory is
  bitwise-identical to an unkilled run from that boundary: batches
  derive from the absolute step index and grad reduction order is
  fixed, so replayed steps recompute the exact same updates.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pickle
import queue
import socket
import struct
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

from kubeflow_tpu.parallel.depot import DepotStats, load_or_compile

# ----------------------------------------------------------- config --


@dataclasses.dataclass
class PipelineRunConfig:
    """One MPMD pipeline training run (the harness model is a stacked
    tanh-MLP per stage + a linear regression head on the last stage —
    big enough to give stable per-tick compute on a CPU bench box, small
    enough for CI; ``stage_fn`` has the same contract as
    ``pipeline_apply``'s, so the schedule/transport layer is generic)."""

    n_stages: int = 2
    microbatches: int = 4
    global_batch: int = 64
    dim: int = 128
    layers_per_stage: int = 2         # layers per CHUNK (= per stage at V=1)
    steps: int = 4
    lr: float = 0.05
    seed: int = 0
    schedule: str = "1f1b"            # "gpipe" | "1f1b" | "interleaved-1f1b"
    dcn_delay_ms: float = 0.0         # emulated per-transfer DCN latency
    virtual_stages: int = 1           # V chunks per worker (interleaved)

    @property
    def mb_rows(self) -> int:
        return self.global_batch // self.microbatches

    @property
    def total_stages(self) -> int:
        """Global model-chunk count: worker r owns chunks r, r+S, ...,
        r+(V-1)S. The model partition (and the oracle's pipeline depth)
        is over total_stages, not workers."""
        return self.n_stages * self.virtual_stages

    def validate(self) -> None:
        if self.n_stages < 2:
            raise ValueError("MPMD pipeline needs >= 2 stages")
        if self.global_batch % self.microbatches:
            raise ValueError("global_batch must divide by microbatches")
        if self.schedule not in ("gpipe", "1f1b", "interleaved-1f1b"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        if self.schedule == "interleaved-1f1b":
            if self.virtual_stages < 2:
                raise ValueError(
                    "interleaved-1f1b needs virtual_stages >= 2 "
                    "(V=1 is plain 1f1b)")
            if self.microbatches % self.n_stages:
                raise ValueError(
                    "interleaved-1f1b needs microbatches % n_stages == 0 "
                    "(microbatch groups of size S keep the ring full)")
        elif self.virtual_stages != 1:
            raise ValueError(
                f"virtual_stages={self.virtual_stages} requires the "
                "interleaved-1f1b schedule")

    @classmethod
    def from_env(cls, env=None) -> "PipelineRunConfig":
        env = os.environ if env is None else env
        g = lambda k, d: env.get(f"KFT_MPMD_{k}", d)
        return cls(
            n_stages=int(env.get("KFT_NUM_STAGES", "2")),
            microbatches=int(g("MICROBATCHES", "4")),
            global_batch=int(g("BATCH", "64")),
            dim=int(g("DIM", "128")),
            layers_per_stage=int(g("LAYERS", "2")),
            steps=int(g("STEPS", "4")),
            lr=float(g("LR", "0.05")),
            seed=int(g("SEED", "0")),
            schedule=g("SCHEDULE", "1f1b"),
            dcn_delay_ms=float(g("DCN_DELAY_MS", "0")),
            virtual_stages=int(env.get("KFT_VIRTUAL_STAGES", "1")),
        )


# ------------------------------------------------------- harness model --

def mlp_stage_fn(stage_params, x):
    """One pipeline stage: a scan over ``layers_per_stage`` tanh-MLP
    layers. Same (params, x) -> y contract as pipeline_apply's stage_fn;
    x and y share a shape (the inter-stage activation contract)."""
    import jax
    import jax.numpy as jnp

    def layer(h, lp):
        return jnp.tanh(h @ lp["w"] + lp["b"]), None

    y, _ = jax.lax.scan(layer, x, stage_params)
    return y


def init_stage_params(cfg: PipelineRunConfig, stage: int):
    """Deterministic per-stage params: every process (stage workers, the
    SPMD oracle) derives the same values from (seed, stage)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.key(cfg.seed), stage)
    kw, _ = jax.random.split(k)
    L, D = cfg.layers_per_stage, cfg.dim
    w = jax.random.normal(kw, (L, D, D), jnp.float32) * (0.5 / np.sqrt(D))
    return {"w": w, "b": jnp.zeros((L, D), jnp.float32)}


def init_head_params(cfg: PipelineRunConfig):
    import jax
    import jax.numpy as jnp

    # keyed off the model-chunk count (== n_stages at V=1, so the PR 11
    # values are unchanged): an interleaved run and a plain run over the
    # same total_stages partition share one head — the bitwise contract
    k = jax.random.fold_in(jax.random.key(cfg.seed), cfg.total_stages + 17)
    return {"w": jax.random.normal(k, (cfg.dim, 1), jnp.float32)
            * (1.0 / np.sqrt(cfg.dim))}


def step_batch(cfg: PipelineRunConfig, step: int):
    """(x [B, D], targets [B, 1]) for one step — derived from (seed,
    step) so stage 0 (inputs) and the last stage (targets) agree without
    any data channel between them."""
    import jax

    k = jax.random.fold_in(jax.random.key(cfg.seed + 100003), step)
    kx, kt = jax.random.split(k)
    x = jax.random.normal(kx, (cfg.global_batch, cfg.dim), np.float32)
    t = jax.random.normal(kt, (cfg.global_batch, 1), np.float32)
    return x, t


def head_loss(head_params, y, targets, *, microbatches: int):
    """Per-MICROBATCH loss term: mean squared error over the microbatch,
    pre-scaled by 1/M so the per-step total (sum over microbatches)
    equals the full-batch mean-of-means — decomposable per microbatch,
    which is what lets 1F1B start backward before later forwards exist."""
    import jax.numpy as jnp

    return jnp.mean((y @ head_params["w"] - targets) ** 2) / microbatches


# ------------------------------------------------------------ schedule --

def schedule_ticks(schedule: str, n_stages: int, stage: int,
                   microbatches: int, virtual_stages: int = 1) -> list:
    """The per-stage tick order. GPipe: fill-drain (all forwards, then
    all backwards — activation stash grows to M). 1F1B: (S-1-s) warmup
    forwards, then strict one-forward-one-backward, then drain — the
    stash never exceeds S live microbatches, which is the memory
    headroom that lets 1F1B run more microbatches than GPipe at the
    same budget (the schedule's real advantage; see aggregate_stats).

    GPipe/1F1B tick = (phase, mb). ``interleaved-1f1b`` tick =
    (phase, vchunk, mb): worker ``stage`` cycles its V chunks in
    microbatch GROUPS of size S (the Megatron interleave — unit k
    forwards chunk (k % (S*V)) // S, microbatch (k // (S*V))*S + k % S;
    backward units mirror the chunk index), after a warmup of
    (S-stage-1)*2 + (V-1)*S forward units. Backward unit order is the
    exact reverse-chunk mirror of forward order, so every chunk's
    microbatch grads still land in slots reduced in ONE descending
    order — the bitwise contract with GPipe/1F1B/the oracle."""
    M = microbatches
    if schedule == "gpipe":
        return ([("fwd", i) for i in range(M)]
                + [("bwd", i) for i in reversed(range(M))])
    if schedule == "interleaved-1f1b":
        S, V = n_stages, virtual_stages
        if V < 2:
            raise ValueError("interleaved-1f1b needs virtual_stages >= 2")
        if M % S:
            raise ValueError(
                "interleaved-1f1b needs microbatches % n_stages == 0")
        total = M * V

        def fwd_unit(k: int) -> tuple[int, int]:
            return (k % (S * V)) // S, (k // (S * V)) * S + k % S

        def bwd_unit(k: int) -> tuple[int, int]:
            v, mb = fwd_unit(k)
            return V - 1 - v, mb

        warm = min((S - stage - 1) * 2 + (V - 1) * S, total)
        ticks = [("fwd", *fwd_unit(k)) for k in range(warm)]
        for i in range(total - warm):
            ticks.append(("fwd", *fwd_unit(warm + i)))
            ticks.append(("bwd", *bwd_unit(i)))
        ticks.extend(("bwd", *bwd_unit(i))
                     for i in range(total - warm, total))
        return ticks
    warm = min(n_stages - 1 - stage, M)
    ticks = [("fwd", i) for i in range(warm)]
    done = 0
    for i in range(warm, M):
        ticks.append(("fwd", i))
        ticks.append(("bwd", done))
        done += 1
    ticks.extend(("bwd", i) for i in range(done, M))
    return ticks


def interleaved_stash_bound(n_stages: int, stage: int, microbatches: int,
                            virtual_stages: int) -> int:
    """Analytic peak chunk-activation stash for one worker under
    interleaved-1F1B: the warmup depth plus the in-flight steady-state
    forward — the V-chunk memory cost the schedule pays for its bubble
    win (each unit is one CHUNK's activation, 1/V of a plain stage's)."""
    S, V, M = n_stages, virtual_stages, microbatches
    return min((S - stage - 1) * 2 + (V - 1) * S + 1, M * V)


def max_live_stash(ticks: list) -> int:
    """Peak number of forward activations resident between their fwd and
    bwd ticks — the schedule's activation-memory footprint (in CHUNK
    activations for the interleaved schedule's 3-field ticks)."""
    live, peak = 0, 0
    for t in ticks:
        live += 1 if t[0] == "fwd" else -1
        peak = max(peak, live)
    return peak


# ----------------------------------------------------------- transport --

class TransportStats:
    """Per-stage wire accounting (thread-safe): ``wire_s`` is time spent
    actually moving bytes (serialize + emulated DCN delay + socket write),
    wherever it ran; ``send_block_s`` is the part that blocked the
    COMPUTE thread — the exposed, un-overlapped cost. recv_block_s is
    time the compute thread waited for data not yet arrived (schedule
    fill/drain shows up here, not in send accounting)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.wire_s = 0.0
        self.send_block_s = 0.0
        self.recv_block_s = 0.0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.sends = 0
        self.recvs = 0

    def add(self, **kw) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "wire_s": round(self.wire_s, 6),
                "send_block_s": round(self.send_block_s, 6),
                "recv_block_s": round(self.recv_block_s, 6),
                "bytes_sent": self.bytes_sent,
                "bytes_recv": self.bytes_recv,
                "sends": self.sends, "recvs": self.recvs,
            }


class ElasticStats:
    """Process-level elastic-recovery counters (thread-safe). Lives OUTSIDE
    the channel because a reform tears the channel down and rebuilds it at
    the new epoch — the counters must survive the swap. Exported per stage
    in ``StageResult.elastic`` and rendered as the
    ``kft_pipeline_*_total`` exposition families (see
    ``elastic_exposition_families``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.recv_timeouts = 0
        self.mailbox_poisons = 0
        self.stale_frames_fenced = 0
        self.reforms = 0

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "recv_timeouts": self.recv_timeouts,
                "mailbox_poisons": self.mailbox_poisons,
                "stale_frames_fenced": self.stale_frames_fenced,
                "reforms": self.reforms,
            }


# exposition family name per ElasticStats field (HELP text in obs/expo)
ELASTIC_FAMILIES = {
    "recv_timeouts": "kft_pipeline_recv_timeouts_total",
    "mailbox_poisons": "kft_pipeline_mailbox_poisons_total",
    "stale_frames_fenced": "kft_pipeline_stale_frames_fenced_total",
}


def elastic_exposition_families(per_stage: dict) -> list:
    """``{stage_label: elastic_snapshot_dict}`` -> ``render_exposition``
    families (one counter family per ElasticStats field, one labelled
    sample per stage) — the shape the operator/bench feed through
    ``obs.expo.render_exposition`` and ``validate_exposition`` lints."""
    from kubeflow_tpu.obs.expo import format_labels

    fams = []
    for field, fam in sorted(ELASTIC_FAMILIES.items()):
        samples = [(format_labels(stage=s), (snap or {}).get(field, 0))
                   for s, snap in sorted(per_stage.items())]
        fams.append((fam, "counter", samples))
    return fams


class EpochBump(RuntimeError):
    """Poison cause injected by the epoch watcher: a NEW rendezvous epoch
    was announced (a replacement stage worker booted), so the in-flight
    microbatch window must be aborted and the channel reformed."""

    def __init__(self, epoch: int):
        super().__init__(f"rendezvous epoch advanced to {epoch}")
        self.epoch = epoch


class _Mailbox:
    """Keyed rendezvous for incoming frames: readers block per key.

    ``poison`` fails every current and future ``take`` immediately with
    the given cause — how a background sender thread's transport error
    reaches the compute thread promptly instead of surfacing two
    minutes later as an opaque recv timeout."""

    def __init__(self):
        self._lock = threading.Condition()
        self._box: dict[tuple, Any] = {}
        self._poison: Optional[BaseException] = None

    def put(self, key: tuple, value) -> None:
        with self._lock:
            self._box[key] = value
            self._lock.notify_all()

    def poison(self, exc: BaseException) -> None:
        with self._lock:
            if self._poison is None:
                self._poison = exc
            self._lock.notify_all()

    def poison_cause(self) -> Optional[BaseException]:
        with self._lock:
            return self._poison

    def drain(self) -> list:
        """Pop every parked frame key (reform path: the act/grad frames
        still boxed when the window aborts belong to the dead epoch's
        window and must be counted as fenced, never replayed into the
        new incarnation)."""
        with self._lock:
            keys = list(self._box)
            self._box.clear()
            return keys

    def take(self, key: tuple, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        with self._lock:
            while key not in self._box:
                if self._poison is not None:
                    raise RuntimeError(
                        "stage transport failed") from self._poison
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"no message {key!r} in {timeout_s}s")
                self._lock.wait(left)
            return self._box.pop(key)


def _encode(key: tuple, payload) -> bytes:
    body = pickle.dumps((key, payload), protocol=4)
    return struct.pack(">Q", len(body)) + body


class TCPStageChannel:
    """Point-to-point activation/grad transport for ONE stage process.

    Listens on ``bind``; neighbors connect lazily (with retry — gang
    members come up in any order). ``blocking=True`` sends inline on the
    compute thread (the GPipe baseline: wire time is exposed);
    ``blocking=False`` hands frames to a per-peer sender thread (1F1B:
    wire time overlaps the next tick's compute). ``delay_s`` emulates a
    DCN per-transfer latency on loopback — it sleeps in whichever thread
    carries the wire, so blocking/async expose/hide it exactly like real
    link time. Spans: every wire movement records a ``dcn.transfer``
    span into ``collector`` when one is given."""

    def __init__(self, bind: str, *, prev: Optional[str], next: Optional[str],
                 stage: int, blocking: bool = True, delay_s: float = 0.0,
                 collector=None, timeout_s: float = 120.0,
                 wrap_next: Optional[str] = None,
                 wrap_prev: Optional[str] = None, epoch: int = 0,
                 elastic: Optional[ElasticStats] = None):
        self.stage = stage
        self.prev_addr = prev
        self.next_addr = next
        # interleaved ring closure: the LAST worker forwards chunk
        # r+vS -> chunk (v+1)S on worker 0 over wrap_next; worker 0
        # returns grad-activations over wrap_prev. None on plain runs.
        self.wrap_next_addr = wrap_next
        self.wrap_prev_addr = wrap_prev
        self.blocking = blocking
        self.delay_s = delay_s
        self.timeout_s = timeout_s
        self.collector = collector
        # rendezvous incarnation this channel speaks: stamped into every
        # frame key; mismatched ingress frames are fenced, not delivered
        self.epoch = epoch
        self.elastic = elastic if elastic is not None else ElasticStats()
        self.stats = TransportStats()
        self.mailbox = _Mailbox()
        self._conns: dict[str, socket.socket] = {}
        self._conn_lock = threading.Lock()
        self._send_locks: dict[str, threading.Lock] = {}
        self._senders: dict[str, queue.Queue] = {}
        self._sender_threads: list[threading.Thread] = []
        self._barrier_done = threading.Event()
        # accepted inbound conns: close() must kill these too — on an
        # in-process reform the OLD channel object lingers, and a peer's
        # cached outbound socket into it would otherwise keep accepting
        # writes into a dead read loop (silent frame loss instead of the
        # OSError that triggers the peer's evict-and-redial)
        self._accepted: list[socket.socket] = []
        self._closed = threading.Event()
        host, _, port = bind.rpartition(":")
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            self._srv.bind((host or "127.0.0.1", int(port)))
        except OSError:
            # kube contract: KFT_STAGE_BIND is the stage SERVICE address
            # (a DNS name routing to this pod) — a pod cannot bind() the
            # service VIP, it binds the PORT on all interfaces and the
            # Service routes to it. Loopback rigs never take this path
            # (resolve() hands back a locally bindable 127.0.0.1:port).
            self._srv.bind(("0.0.0.0", int(port)))
        self._srv.listen(8)
        bound_host = self._srv.getsockname()[0]
        self.address = (f"{host or '127.0.0.1'}"
                        f":{self._srv.getsockname()[1]}"
                        if bound_host == "0.0.0.0"
                        else f"{bound_host}:{self._srv.getsockname()[1]}")
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"mpmd-accept-{stage}")
        self._accept_thread.start()

    # --------------------------------------------------------- wire in --

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            with self._conn_lock:
                self._accepted.append(conn)
            threading.Thread(target=self._read_loop, args=(conn,),
                             daemon=True,
                             name=f"mpmd-read-{self.stage}").start()

    def _read_loop(self, conn: socket.socket) -> None:
        try:
            while not self._closed.is_set():
                head = self._read_exact(conn, 8)
                if head is None:
                    return
                (n,) = struct.unpack(">Q", head)
                body = self._read_exact(conn, n)
                if body is None:
                    return
                key, payload = pickle.loads(body)
                self.stats.add(bytes_recv=8 + n, recvs=1)
                # epoch fence: a frame from another incarnation (pre-epoch
                # senders carry no 5th element -> epoch 0) is dropped AND
                # counted here at ingress — it can never satisfy a
                # recv_act/recv_grad take
                frame_epoch = key[4] if len(key) > 4 else 0
                if frame_epoch != self.epoch:
                    self.elastic.inc("stale_frames_fenced")
                    continue
                if len(key) < 5:
                    # pre-epoch sender: normalise to the 5-field key so the
                    # frame can satisfy an epoch-aware take at epoch 0
                    key = (*key, 0)
                if key[0] == "ready" and self._barrier_done.is_set():
                    # a downstream peer reforming late resends its ready
                    # until our go arrives; the original go may have died
                    # with its previous conn — answer every late ready so
                    # the barrier handshake can't wedge one-shot
                    try:
                        if self.next_addr:
                            self._wire_send(
                                self.next_addr,
                                ("go", -1, -1, -1, self.epoch), b"")
                    except Exception:
                        pass
                    continue
                self.mailbox.put(key, payload)
        except (OSError, pickle.UnpicklingError, EOFError):
            return

    @staticmethod
    def _read_exact(conn: socket.socket, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    # -------------------------------------------------------- wire out --

    def _connect(self, peer: str) -> socket.socket:
        with self._conn_lock:
            s = self._conns.get(peer)
            if s is not None:
                return s
        host, _, port = peer.rpartition(":")
        deadline = time.monotonic() + self.timeout_s
        while True:
            try:
                s = socket.create_connection((host, int(port)), timeout=5.0)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"stage {self.stage}: peer {peer} unreachable "
                        f"after {self.timeout_s}s")
                time.sleep(0.05)
        with self._conn_lock:
            self._conns.setdefault(peer, s)
            return self._conns[peer]

    def _peer_lock(self, peer: str) -> threading.Lock:
        with self._conn_lock:
            return self._send_locks.setdefault(peer, threading.Lock())

    def _wire_send(self, peer: str, key: tuple, payload) -> None:
        """The actual wire movement — serialize, emulated DCN latency,
        socket write. Runs on the compute thread (blocking) or a sender
        thread (async); ``wire_s`` counts it either way. A per-peer lock
        serializes writers (barrier resends and the read loop's go
        replies can race the sender thread); a send failure evicts the
        cached conn and redials ONCE — the elastic contract keeps stage
        addresses stable across replacement, so a peer that reformed is
        reachable again at the same address with a fresh listener."""
        t0 = time.perf_counter()
        span = None
        if self.collector is not None:
            attrs = {"stage": self.stage, "peer": peer, "kind": key[0],
                     "step": key[1], "mb": key[2]}
            if len(key) > 3:
                attrs["vstage"] = key[3]
            span = self.collector.start("dcn.transfer", attrs=attrs)
        data = _encode(key, payload)
        if self.delay_s:
            time.sleep(self.delay_s)
        with self._peer_lock(peer):
            try:
                self._connect(peer).sendall(data)
            except OSError:
                with self._conn_lock:
                    s = self._conns.pop(peer, None)
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass
                self._connect(peer).sendall(data)
        dt = time.perf_counter() - t0
        self.stats.add(wire_s=dt, bytes_sent=len(data), sends=1)
        if span is not None:
            self.collector.end(span, bytes=len(data))

    def _sender_loop(self, peer: str, q: "queue.Queue") -> None:
        while True:
            item = q.get()
            if item is None:
                return
            try:
                self._wire_send(peer, *item)
            except Exception as e:
                if self._closed.is_set():
                    return
                # surface the transport failure to the compute thread NOW
                # (its next recv raises with this cause) instead of dying
                # silently and leaving it to a 2-minute recv timeout
                self.elastic.inc("mailbox_poisons")
                self.mailbox.poison(e)
                return

    def _send(self, peer: str, key: tuple, payload) -> None:
        if self.blocking:
            t0 = time.perf_counter()
            self._wire_send(peer, key, payload)
            self.stats.add(send_block_s=time.perf_counter() - t0)
            return
        q = self._senders.get(peer)
        if q is None:
            q = self._senders[peer] = queue.Queue()
            t = threading.Thread(target=self._sender_loop, args=(peer, q),
                                 daemon=True,
                                 name=f"mpmd-send-{self.stage}")
            t.start()
            self._sender_threads.append(t)
        t0 = time.perf_counter()
        q.put((key, payload))
        self.stats.add(send_block_s=time.perf_counter() - t0)  # ~enqueue

    # ------------------------------------------------------------- api --
    # Frames key by (kind, step, mb, virtual_stage, epoch): vstage so the
    # same microbatch crossing the same worker V times (interleaved)
    # never aliases; epoch LAST so the ingress fence can reject frames
    # from a dead incarnation while every older key position (step/mb
    # span attrs, vstage routing) keeps its index. ``wrap=True`` routes
    # over the ring-closure link instead of the line neighbor.

    def send_act(self, step: int, mb: int, payload, vstage: int = 0, *,
                 wrap: bool = False) -> None:
        peer = self.wrap_next_addr if wrap else self.next_addr
        if peer is None:
            raise RuntimeError(
                f"stage {self.stage}: no {'wrap_next' if wrap else 'next'} "
                "peer for send_act")
        self._send(peer, ("act", step, mb, vstage, self.epoch), payload)

    def send_grad(self, step: int, mb: int, payload, vstage: int = 0, *,
                  wrap: bool = False) -> None:
        peer = self.wrap_prev_addr if wrap else self.prev_addr
        if peer is None:
            raise RuntimeError(
                f"stage {self.stage}: no {'wrap_prev' if wrap else 'prev'} "
                "peer for send_grad")
        self._send(peer, ("grad", step, mb, vstage, self.epoch), payload)

    def recv_act(self, step: int, mb: int, vstage: int = 0):
        return self._recv(("act", step, mb, vstage, self.epoch))

    def recv_grad(self, step: int, mb: int, vstage: int = 0):
        return self._recv(("grad", step, mb, vstage, self.epoch))

    def _recv(self, key: tuple):
        t0 = time.perf_counter()
        try:
            return self.mailbox.take(key, self.timeout_s)
        except TimeoutError:
            self.elastic.inc("recv_timeouts")
            raise
        finally:
            self.stats.add(recv_block_s=time.perf_counter() - t0)

    def barrier_ready(self) -> None:
        """Chain barrier: 'ready' propagates last-stage -> stage 0, then
        'go' propagates stage 0 -> last. Every stage returns only once
        the WHOLE pipeline is compiled and listening, so step-0 sends
        never queue into a neighbor's compile window and the measured
        windows start aligned.

        Reform-tolerant: stages re-rendezvous at a new epoch at slightly
        different times, so a ready sent upstream can land on the peer's
        DYING previous channel (fenced there, lost). The sender therefore
        RESENDS its ready every poll interval until the go comes back;
        the receiver answers late duplicate readys from the read loop
        (see ``_read_loop``). Duplicate frames are idempotent — the
        mailbox keys them identically."""
        deadline = time.monotonic() + self.timeout_s
        poll = min(0.5, self.timeout_s)

        def take_with(resend, key):
            while True:
                if resend is not None:
                    self._wire_send(resend, ("ready", -1, -1, -1,
                                             self.epoch), b"")
                try:
                    return self.mailbox.take(key, poll)
                except TimeoutError:
                    if time.monotonic() >= deadline:
                        raise

        if self.next_addr:
            take_with(None, ("ready", -1, -1, -1, self.epoch))
        if self.prev_addr:
            take_with(self.prev_addr, ("go", -1, -1, -1, self.epoch))
        if self.next_addr:
            self._wire_send(self.next_addr, ("go", -1, -1, -1, self.epoch),
                            b"")
        self._barrier_done.set()

    def drain_stale(self) -> int:
        """Reform path: count-and-drop the act/grad frames still parked
        in the mailbox when the microbatch window aborts — they belong to
        the dead incarnation's window and must never be consumed by the
        replayed schedule (replay re-receives everything at the new
        epoch). Returns the number fenced."""
        n = sum(1 for k in self.mailbox.drain() if k and k[0] in
                ("act", "grad"))
        if n:
            self.elastic.inc("stale_frames_fenced", n)
        return n

    def close(self) -> None:
        self._closed.set()
        for q in self._senders.values():
            q.put(None)
        # shutdown() BEFORE close(), on every socket: close() alone never
        # wakes a thread pinned inside accept()/recv()/sendall() on the
        # same socket — the kernel holds the socket open until the
        # syscall returns. For the listener that means THE PORT STAYS
        # BOUND after close() (the in-process reform's rebind of the
        # stage-Service port would fail EADDRINUSE forever); for the
        # accepted conns it means peers' cached outbound sockets keep
        # sendall-ing into a dead read loop instead of getting the FIN/
        # RST that triggers their evict-and-redial.
        with self._conn_lock:
            socks = list(self._conns.values()) + list(self._accepted)
            self._conns.clear()
            self._accepted.clear()
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for t in self._sender_threads:
            t.join(timeout=5.0)
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass       # listeners reject shutdown on some kernels
        # belt and braces: a throwaway connect unblocks a pinned accept()
        # even where shutdown() on a listening socket is a no-op
        try:
            with socket.create_connection(
                    ("127.0.0.1",
                     int(self.address.rpartition(":")[2])),
                    timeout=0.5):
                pass
        except OSError:
            pass
        self._accept_thread.join(timeout=5.0)
        try:
            self._srv.close()
        except OSError:
            pass
        for s in socks:
            try:
                s.close()
            except OSError:
                pass


class InProcFabric:
    """In-process stand-in for the TCP fabric (unit tests, the dryrun):
    one mailbox per stage, threads as stages. Same channel API, same
    stats/delay semantics, no sockets."""

    def __init__(self, n_stages: int):
        self.mailboxes = [_Mailbox() for _ in range(n_stages)]

    def channel(self, stage: int, *, blocking: bool = True,
                delay_s: float = 0.0, collector=None,
                timeout_s: float = 60.0, epoch: int = 0,
                elastic: Optional[ElasticStats] = None) -> "InProcChannel":
        return InProcChannel(self, stage, blocking=blocking,
                             delay_s=delay_s, collector=collector,
                             timeout_s=timeout_s, epoch=epoch,
                             elastic=elastic)


class InProcChannel:
    def __init__(self, fabric: InProcFabric, stage: int, *, blocking: bool,
                 delay_s: float, collector, timeout_s: float,
                 epoch: int = 0,
                 elastic: Optional[ElasticStats] = None):
        self.fabric = fabric
        self.stage = stage
        self.blocking = blocking
        self.delay_s = delay_s
        self.collector = collector
        self.timeout_s = timeout_s
        # same epoch-last key element as the TCP channel: a stale frame
        # can never match a take key, so the in-proc fabric fences by
        # key mismatch (no wire ingress loop to count at)
        self.epoch = epoch
        self.elastic = elastic if elastic is not None else ElasticStats()
        self.stats = TransportStats()
        self._q: Optional[queue.Queue] = None
        self._sender: Optional[threading.Thread] = None

    def _wire_send(self, dest: int, key: tuple, payload) -> None:
        t0 = time.perf_counter()
        span = None
        if self.collector is not None:
            attrs = {"stage": self.stage, "peer": dest, "kind": key[0],
                     "step": key[1], "mb": key[2]}
            if len(key) > 3:
                attrs["vstage"] = key[3]
            span = self.collector.start("dcn.transfer", attrs=attrs)
        data = _encode(key, payload)       # pay real serialize cost
        if self.delay_s:
            time.sleep(self.delay_s)
        k, p = pickle.loads(data[8:])
        self.fabric.mailboxes[dest].put(k, p)
        dt = time.perf_counter() - t0
        self.stats.add(wire_s=dt, bytes_sent=len(data), sends=1)
        if span is not None:
            self.collector.end(span, bytes=len(data))

    def _send(self, dest: int, key: tuple, payload) -> None:
        if self.blocking:
            t0 = time.perf_counter()
            self._wire_send(dest, key, payload)
            self.stats.add(send_block_s=time.perf_counter() - t0)
            return
        if self._q is None:
            self._q = queue.Queue()

            def loop():
                while True:
                    item = self._q.get()
                    if item is None:
                        return
                    self._wire_send(*item)

            self._sender = threading.Thread(
                target=loop, daemon=True, name=f"mpmd-send-{self.stage}")
            self._sender.start()
        t0 = time.perf_counter()
        self._q.put((dest, key, payload))
        self.stats.add(send_block_s=time.perf_counter() - t0)

    def send_act(self, step, mb, payload, vstage: int = 0, *,
                 wrap: bool = False):
        dest = 0 if wrap else self.stage + 1
        self._send(dest, ("act", step, mb, vstage, self.epoch), payload)

    def send_grad(self, step, mb, payload, vstage: int = 0, *,
                  wrap: bool = False):
        dest = len(self.fabric.mailboxes) - 1 if wrap else self.stage - 1
        self._send(dest, ("grad", step, mb, vstage, self.epoch), payload)

    def recv_act(self, step, mb, vstage: int = 0):
        return self._recv(("act", step, mb, vstage, self.epoch))

    def recv_grad(self, step, mb, vstage: int = 0):
        return self._recv(("grad", step, mb, vstage, self.epoch))

    def _recv(self, key):
        t0 = time.perf_counter()
        try:
            return self.fabric.mailboxes[self.stage].take(key, self.timeout_s)
        except TimeoutError:
            self.elastic.inc("recv_timeouts")
            raise
        finally:
            self.stats.add(recv_block_s=time.perf_counter() - t0)

    def barrier_ready(self) -> None:
        pass                                   # threads start together

    def close(self) -> None:
        if self._q is not None:
            self._q.put(None)
            self._sender.join(timeout=5.0)


# ------------------------------------------------------ state snapshots --

class StageSnapshotStore:
    """Per-stage step-boundary state snapshots + the epoch announce file,
    on a directory every stage worker shares (``KFT_ELASTIC_DIR``).

    One ``.snap`` file per (stage, step), atomic tmp+rename publish,
    latest TWO retained per stage: neighbors' newest boundaries differ by
    at most ONE step (stage 0's step-k update needs grads that need the
    last stage's step-k backward), so retaining two guarantees the newest
    COMMON boundary — ``common_step()`` = min over stages' latest — is on
    disk for every stage even when its own latest is one ahead.
    Snapshots are keyed by a run fingerprint (``run_fingerprint``: config
    + model spec identity) so a llama run can never restore an MLP run's
    bytes.

    ``announce_epoch``/``epoch`` give the dir a second role: the
    rendezvous-epoch bulletin. A replacement worker boots with the bumped
    ``KFT_RENDEZVOUS_EPOCH`` and announces it here; survivors' epoch
    watchers poll it and poison their in-flight window — the signal path
    that replaces PR 9's survivor process restarts for pipeline jobs
    (an in-process reform keeps compiled programs and params hot)."""

    KEEP = 2

    def __init__(self, root: str, *, fingerprint: str = ""):
        self.root = root
        self.fp = (fingerprint or "")[:16]
        os.makedirs(root, exist_ok=True)

    def _path(self, stage: int, step: int) -> str:
        tag = f"-{self.fp}" if self.fp else ""
        return os.path.join(self.root,
                            f"stage{stage}-step{step:06d}{tag}.snap")

    def _list(self, stage: int) -> list:
        """Sorted [(step, path)] for one stage (this fingerprint only)."""
        prefix, out = f"stage{stage}-step", []
        suffix = (f"-{self.fp}.snap" if self.fp else ".snap")
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        for fn in names:
            if not (fn.startswith(prefix) and fn.endswith(suffix)):
                continue
            digits = fn[len(prefix):len(prefix) + 6]
            if digits.isdigit():
                out.append((int(digits), os.path.join(self.root, fn)))
        return sorted(out)

    def publish(self, stage: int, step: int, payload: dict) -> str:
        path = self._path(stage, step)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f, protocol=4)
        os.replace(tmp, path)
        for _, old in self._list(stage)[:-self.KEEP]:
            try:
                os.remove(old)
            except OSError:
                pass
        return path

    def load(self, stage: int, step: int) -> dict:
        with open(self._path(stage, step), "rb") as f:
            return pickle.load(f)

    def latest_steps(self, n_stages: int) -> list:
        """Per-stage newest published boundary (-1 = none yet)."""
        return [(self._list(s)[-1][0] if self._list(s) else -1)
                for s in range(n_stages)]

    def common_step(self, n_stages: int) -> int:
        """Newest boundary EVERY stage has published — the restore point
        of the rollback protocol (-1: no completed common boundary, the
        run restarts from initial state)."""
        return min(self.latest_steps(n_stages))

    # ------------------------------------------- epoch announce file --

    def announce_epoch(self, epoch: int) -> None:
        """Monotonic: never lowers the announced epoch (a slow survivor
        re-announcing its old epoch must not roll back a replacement's
        bump)."""
        if epoch <= self.epoch():
            return
        path = os.path.join(self.root, "epoch.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"epoch": int(epoch)}, f)
        os.replace(tmp, path)

    def epoch(self) -> int:
        try:
            with open(os.path.join(self.root, "epoch.json")) as f:
                return int(json.load(f).get("epoch", 0))
        except (OSError, ValueError):
            return 0


def run_fingerprint(cfg: "PipelineRunConfig", spec=None) -> str:
    """Snapshot lineage key: the run config + the model spec's identity
    (name + whatever dims ``snapshot_meta`` declares). Two runs with the
    same fingerprint produce interchangeable snapshots; anything that
    changes param shapes or the data stream changes the key."""
    from kubeflow_tpu.parallel.depot import snapshot_fingerprint

    items = dict(dataclasses.asdict(cfg))
    items["model"] = getattr(spec, "name", "mlp") if spec is not None \
        else "mlp"
    meta = getattr(spec, "snapshot_meta", None)
    if callable(meta):
        items.update(meta(cfg))
    return snapshot_fingerprint(items)


# -------------------------------------------------------- model spec --

class MLPSpec:
    """The pluggable-model contract behind StageRuntime/run_stage, with
    the CI harness (stacked tanh-MLP chunks + MSE head) as the default
    implementation. A spec answers, per GLOBAL chunk index in
    [0, cfg.total_stages): the chunk's params and (params, x) -> y
    program, the example activation shapes the programs lower against,
    the per-microbatch head loss, and the host-side step batch.
    ``pipeline_llama.MpmdLlamaSpec`` implements the same surface with
    real transformer blocks (embedding folded into chunk 0, LM head on
    the last chunk — its tokens input is int, so its chunk-0 backward
    is params-only: ``first_chunk_needs_dx = False``)."""

    name = "mlp"
    # chunk 0's VJP also pulls back to x (floats): kept for the MLP so
    # the compiled program (and its depot key) is byte-identical to the
    # PR 11 single-chunk runtime
    first_chunk_needs_dx = True

    def __init__(self, stage_fn: Callable = mlp_stage_fn):
        self.stage_fn = stage_fn

    def chunk_fn(self, cfg: PipelineRunConfig, chunk: int) -> Callable:
        return self.stage_fn

    def chunk_params(self, cfg: PipelineRunConfig, chunk: int):
        return init_stage_params(cfg, chunk)

    def head_params(self, cfg: PipelineRunConfig):
        return init_head_params(cfg)

    def head_fn(self, cfg: PipelineRunConfig) -> Callable:
        M = cfg.microbatches

        def fn(hp, y, t):
            return head_loss(hp, y, t, microbatches=M)
        return fn

    def example_x(self, cfg: PipelineRunConfig, chunk: int):
        import jax.numpy as jnp

        return jnp.zeros((cfg.mb_rows, cfg.dim), jnp.float32)

    def example_y(self, cfg: PipelineRunConfig):
        return self.example_x(cfg, cfg.total_stages - 1)

    def example_t(self, cfg: PipelineRunConfig):
        import jax.numpy as jnp

        return jnp.zeros((cfg.mb_rows, 1), jnp.float32)

    def batch(self, cfg: PipelineRunConfig, step: int):
        """Host-side (inputs [M, R, ...], targets [M, R, ...]) for one
        step — worker 0 consumes inputs, the head worker targets."""
        M, R = cfg.microbatches, cfg.mb_rows
        x, t = step_batch(cfg, step)
        return (np.asarray(x).reshape(M, R, cfg.dim),
                np.asarray(t).reshape(M, R, 1))

    def snapshot_meta(self, cfg: PipelineRunConfig) -> dict:
        """Spec-identity items folded into the snapshot fingerprint (see
        ``run_fingerprint``) beyond the run config — anything that
        changes this spec's param shapes."""
        return {"spec": self.name, "dim": cfg.dim,
                "layers": cfg.layers_per_stage}


# -------------------------------------------------------- stage runtime --

class StageRuntime:
    """One worker's compiled programs + parameters on its own mesh —
    for its V model chunks (V=1 outside interleaved runs).

    Programs are AOT-compiled up front (per-chunk fwd, bwd = VJP of the
    chunk fn, and on the head worker the loss-head VJP) through the
    executable depot when one is given — keyed per GLOBAL CHUNK + stage
    mesh (+ the virtual-chunk scope when V > 1), so a warm resubmit
    deserializes every chunk's programs and two same-HLO chunks never
    share an entry. Gradients stash per (chunk, microbatch) slot and
    reduce in one fixed descending-index order per chunk (matching the
    scan-VJP accumulation order of the SPMD oracle), so the result is
    schedule-independent — GPipe, 1F1B and interleaved-1F1B produce
    bitwise-identical updates."""

    def __init__(self, cfg: PipelineRunConfig, stage: int, *,
                 stage_fn: Callable = mlp_stage_fn, spec=None, mesh=None,
                 depot=None, depot_stats: Optional[DepotStats] = None,
                 depot_wait_s: float = 0.0):
        import jax
        import jax.numpy as jnp

        cfg.validate()
        self.cfg = cfg
        self.stage = stage
        self.is_first = stage == 0
        self.is_last = stage == cfg.n_stages - 1   # head worker
        self.mesh = mesh
        self.spec = spec if spec is not None else MLPSpec(stage_fn)
        self.depot_stats = depot_stats if depot_stats is not None \
            else DepotStats()
        self.depot_outcomes: dict[str, str] = {}
        V = cfg.virtual_stages
        # global chunk ids this worker owns: stage, stage+S, ...
        self.chunks = [stage + v * cfg.n_stages for v in range(V)]
        self.params = [self.spec.chunk_params(cfg, c) for c in self.chunks]
        self.head_params = (self.spec.head_params(cfg)
                            if self.is_last else None)
        self._last_losses: list = []

        head_loss_fn = self.spec.head_fn(cfg)

        def head_fn(hp, y, t):
            (loss, (gh, dy)) = jax.value_and_grad(
                head_loss_fn, argnums=(0, 1))(hp, y, t)
            return loss, gh, dy

        def sgd(p, g):
            return jax.tree_util.tree_map(
                lambda a, b: a - cfg.lr * b, p, g)

        self._add = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

        def reduce_slots(slots):
            # descending-index sequential sum — the scan-VJP order the
            # SPMD oracle accumulates its per-tick param grads in — via
            # the ONE pre-warmed jitted tree-add (same per-leaf add op
            # bitwise, no per-step eager dispatch or re-trace)
            acc = slots[-1]
            for g in slots[-2::-1]:
                acc = self._add(acc, g)
            return acc

        x_egs = [self.spec.example_x(cfg, c) for c in self.chunks]
        y_eg = self.spec.example_y(cfg)
        t_eg = self.spec.example_t(cfg)
        if mesh is not None:
            # per-stage mesh: microbatch rows sharded over the stage's
            # data axis, params replicated within the stage. The jitted
            # programs auto-partition against these placements.
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._x_sharding = NamedSharding(mesh, P("stage_dp"))
            self._rep = NamedSharding(mesh, P())
            self.params = [jax.device_put(p, self._rep)
                           for p in self.params]
            if self.head_params is not None:
                self.head_params = jax.device_put(self.head_params,
                                                  self._rep)
            x_egs = [jax.device_put(x, self._x_sharding) for x in x_egs]
            y_eg = jax.device_put(y_eg, self._x_sharding)
            t_eg = jax.device_put(t_eg, self._x_sharding)
        else:
            self._x_sharding = None

        def _compile(name, fn, chunk, vchunk, *eg):
            lowered = jax.jit(fn).lower(*eg)
            compiled, outcome = load_or_compile(
                lowered, depot, mesh=mesh, stage=chunk,
                vstage=vchunk if V > 1 else None,
                extra=("mpmd", name), stats=self.depot_stats,
                wait_s=depot_wait_s)
            label = name if V == 1 else f"{name}.c{chunk}"
            self.depot_outcomes[label] = outcome
            return compiled

        self._fwds, self._bwds, self._bwd_has_dx = [], [], []
        for v, c in enumerate(self.chunks):
            fn = self.spec.chunk_fn(cfg, c)
            needs_dx = c > 0 or self.spec.first_chunk_needs_dx
            if needs_dx:
                def bwd_fn(p, x, dy, _fn=fn):
                    _, pull = jax.vjp(_fn, p, x)
                    return pull(dy)
            else:
                # chunk 0 of an int-input model (llama tokens): the
                # pullback is params-only — there is no dx to emit and
                # nothing upstream to send it to
                def bwd_fn(p, x, dy, _fn=fn):
                    _, pull = jax.vjp(lambda p_: _fn(p_, x), p)
                    return pull(dy)[0]
            # dy has the CHUNK OUTPUT's shape: the next chunk's input
            # (chunk c+1 is never chunk 0, so example_x is float there)
            dy_eg = (y_eg if c == cfg.total_stages - 1
                     else self.spec.example_x(cfg, c + 1))
            if mesh is not None:
                dy_eg = jax.device_put(dy_eg, self._x_sharding)
            self._fwds.append(
                _compile("fwd", fn, c, v, self.params[v], x_egs[v]))
            self._bwds.append(
                _compile("bwd", bwd_fn, c, v,
                         self.params[v], x_egs[v], dy_eg))
            self._bwd_has_dx.append(needs_dx)
        if self.is_last:
            self._head = _compile("head", head_fn, cfg.total_stages - 1,
                                  V - 1, self.head_params, y_eg, t_eg)
        # tiny programs: warmed eagerly so no compile lands inside the
        # measured window, but not worth depot entries
        self._sgd = jax.jit(sgd)
        self._reduce = reduce_slots
        for p in self.params:
            g_eg = jax.tree_util.tree_map(jnp.zeros_like, p)
            jax.block_until_ready(self._sgd(p, g_eg))
            jax.block_until_ready(self._add(g_eg, g_eg))

    # ------------------------------------------------------- execution --

    def put_act(self, arr: np.ndarray):
        """Host-staged wire payload -> this stage's mesh (explicit
        device_put: transfer_guard-safe)."""
        import jax

        if self._x_sharding is not None:
            return jax.device_put(arr, self._x_sharding)
        return jax.device_put(arr)

    @staticmethod
    def get_act(y) -> np.ndarray:
        import jax

        return np.asarray(jax.device_get(y))

    def fwd(self, x, v: int = 0):
        import jax

        return jax.block_until_ready(self._fwds[v](self.params[v], x))

    def bwd(self, x, dy, v: int = 0):
        import jax

        if self._bwd_has_dx[v]:
            g, dx = self._bwds[v](self.params[v], x, dy)
            jax.block_until_ready(dx)
            return g, dx
        g = self._bwds[v](self.params[v], x, dy)
        jax.block_until_ready(g)
        return g, None

    def head(self, y, t):
        import jax

        loss, gh, dy = self._head(self.head_params, y, t)
        jax.block_until_ready(dy)
        return loss, gh, dy

    def apply_grads(self, grad_slots: list, head_slots: Optional[list]):
        """``grad_slots``: per-chunk slot lists ([V][M]) or one flat [M]
        list (the V=1 shape callers have always passed)."""
        import jax

        per_chunk = (grad_slots
                     if grad_slots and isinstance(grad_slots[0], list)
                     else [grad_slots])
        for v, slots in enumerate(per_chunk):
            self.params[v] = self._sgd(self.params[v], self._reduce(slots))
        if head_slots is not None:
            self.head_params = self._sgd(self.head_params,
                                         self._reduce(head_slots))
            jax.block_until_ready(self.head_params)
        jax.block_until_ready(self.params)

    def depot_summary(self) -> dict:
        return {"outcomes": dict(self.depot_outcomes),
                "hit": all(v == "hit" for v in self.depot_outcomes.values()),
                "counters": self.depot_stats.snapshot()}

    # ------------------------------------------------- elastic state --

    def export_state(self) -> dict:
        """Host-staged (``jax.device_get``) copy of everything
        ``apply_grads`` mutates — the step-boundary snapshot payload.
        ``opt_state`` is None today (the update rule is stateless SGD);
        the key exists so snapshots grow slots without a format break
        when a stateful optimizer lands. RNG needs no slot: every random
        stream (params, batches) derives from (seed, absolute index)."""
        import jax

        return {
            "params": [jax.device_get(p) for p in self.params],
            "head_params": (jax.device_get(self.head_params)
                            if self.head_params is not None else None),
            "opt_state": None,
            "seed": self.cfg.seed,
        }

    def restore_state(self, state: dict) -> None:
        """Inverse of ``export_state``: device_put the host-staged leaves
        back onto this stage's mesh placements. Bitwise: device_get /
        device_put round-trip float32 buffers exactly, so a restored
        boundary replays the identical trajectory."""
        import jax

        if self.mesh is not None:
            put = lambda t: jax.device_put(t, self._rep)  # noqa: E731
        else:
            put = jax.device_put
        self.params = [put(p) for p in state["params"]]
        if self.is_last and state.get("head_params") is not None:
            self.head_params = put(state["head_params"])
        jax.block_until_ready(self.params)


# ------------------------------------------------------------ run loop --

@dataclasses.dataclass
class StageResult:
    stage: int
    losses: list          # last stage only; [] elsewhere
    step_stats: list      # per step: {"t0","t1","busy_s"}
    transport: dict
    depot: dict
    schedule: str
    max_stash: int
    # elastic-recovery accounting (ElasticStats.snapshot() + restore/
    # replay bookkeeping added by the worker entry); None on plain runs
    elastic: Optional[dict] = None


def run_stage(cfg: PipelineRunConfig, stage: int, chan, *,
              runtime: Optional[StageRuntime] = None, collector=None,
              on_step: Optional[Callable[[int, Optional[float]], None]] = None,
              start_step: int = 0, prior_losses: Optional[list] = None,
              prior_step_stats: Optional[list] = None,
              snapshots: Optional[StageSnapshotStore] = None,
              on_sync: Optional[Callable[[int, Optional[int]], None]] = None,
              ) -> StageResult:
    """Execute ``cfg.steps`` pipeline training steps for ONE stage.

    The tick order comes from ``schedule_ticks``; data dependencies
    (recv act / recv grad) provide all cross-stage synchronization. Per
    tick, compute time is accounted to ``busy_s`` and a ``pipeline.tick``
    span is recorded; the channel accounts wire/blocked time and records
    ``dcn.transfer`` spans. Stage 0's per-step [t0, t1] window brackets
    the whole pipeline (it injects first and its update depends on the
    last returning grad), so aggregate_stats measures every stage's idle
    against stage 0's windows.

    Busy accounting matches what the analytic fill-drain bound models:
    everything the stage actively DOES — compute, host staging
    (device_put/get), and the blocking part of sends — is work; bubble
    is the remaining (schedule-induced) idleness. An exposed transfer
    still raises the measured bubble, just where it physically bites:
    as the DOWNSTREAM stage's wait (and in send_block/overlap stats).

    Elastic hooks: ``start_step``/``prior_losses``/``prior_step_stats``
    resume the schedule from a restored boundary (batches derive from
    the ABSOLUTE step index, so a replayed step recomputes the exact
    bytes of its first run); ``snapshots`` publishes the boundary state
    after every ``apply_grads``. On an abort mid-window, params are
    untouched (they only ever change at the boundary) — the caller
    restores a snapshot and re-enters with the next start_step."""
    import jax  # noqa: F401  (device staging inside runtime)

    rt = runtime if runtime is not None else StageRuntime(cfg, stage)
    spec = rt.spec
    S, V, M = cfg.n_stages, cfg.virtual_stages, cfg.microbatches
    T = cfg.total_stages
    raw = schedule_ticks(cfg.schedule, S, stage, M, cfg.virtual_stages)
    # normalize 2-field (phase, mb) ticks to (phase, vchunk=0, mb)
    ticks = [t if len(t) == 3 else (t[0], 0, t[1]) for t in raw]
    chan.barrier_ready()
    if snapshots is not None:
        # post-barrier restore sync: a survivor can publish ONE more
        # boundary after the replacement pod already read its boot
        # restore point (the straggler step whose frames were all in
        # its mailbox when the neighbor died) — so per-boot reads can
        # disagree by a step and the gang would replay from different
        # boundaries. After the barrier every stage is parked, nothing
        # publishes, and the store is quiescent: re-derive the restore
        # point HERE so all stages pick the same boundary.
        latest = snapshots.latest_steps(cfg.n_stages)
        r = min(latest)
        snap = (snapshots.load(stage, r)
                if r > start_step - 1 else None)
        if snap is not None:
            rt.restore_state(snap["state"])
            prior_losses = snap["losses"]
            prior_step_stats = snap["step_stats"]
            start_step = r + 1
        if on_sync is not None:
            on_sync(r, max(latest) + 1 if r >= 0 else None)
    step_stats = list(prior_step_stats or [])
    losses: list = list(prior_losses or [])
    for k in range(start_step, cfg.steps):
        if rt.is_first:
            x_host, _ = spec.batch(cfg, k)
        if rt.is_last:
            _, t_host = spec.batch(cfg, k)
        # perf_counter, not wall clock: windows and busy must share a
        # clock domain (aggregate_stats only ever compares DURATIONS —
        # stage 0's window vs each stage's busy — so process-local
        # monotonic time is both sufficient and NTP-proof)
        t_step0 = time.perf_counter()
        busy = 0.0
        block0 = chan.stats.snapshot()["send_block_s"]
        stash: dict[tuple, tuple] = {}
        grad_slots: list = [[None] * M for _ in range(V)]
        head_slots: Optional[list] = [None] * M if rt.is_last else None
        step_losses: list = [None] * M
        for phase, v, i in ticks:
            c = stage + v * S              # global chunk this tick runs
            span = None
            if collector is not None:
                span = collector.start("pipeline.tick", attrs={
                    "stage": stage, "step": k, "mb": i, "phase": phase,
                    "vstage": v, "chunk": c})
            if phase == "fwd":
                if c == 0:
                    c0 = time.perf_counter()
                    x = rt.put_act(x_host[i])
                    busy += time.perf_counter() - c0
                else:
                    arr = chan.recv_act(k, i, v)
                    c0 = time.perf_counter()
                    x = rt.put_act(arr)
                    busy += time.perf_counter() - c0
                c0 = time.perf_counter()
                y = rt.fwd(x, v)
                busy += time.perf_counter() - c0
                stash[(v, i)] = (x, y)
                if c < T - 1:
                    c0 = time.perf_counter()
                    payload = rt.get_act(y)
                    if stage < S - 1:
                        chan.send_act(k, i, payload, v)
                    else:
                        # ring wrap: chunk (v+1)*S lives on worker 0
                        chan.send_act(k, i, payload, v + 1, wrap=True)
                    busy += time.perf_counter() - c0
            else:
                x, y = stash.pop((v, i))
                if c == T - 1:
                    c0 = time.perf_counter()
                    t = rt.put_act(t_host[i])
                    loss_i, gh, dy = rt.head(y, t)
                    g, dx = rt.bwd(x, dy, v)
                    busy += time.perf_counter() - c0
                    head_slots[i] = gh
                    step_losses[i] = loss_i
                else:
                    dy_arr = chan.recv_grad(k, i, v)
                    c0 = time.perf_counter()
                    dy = rt.put_act(dy_arr)
                    g, dx = rt.bwd(x, dy, v)
                    busy += time.perf_counter() - c0
                grad_slots[v][i] = g
                if c > 0:
                    c0 = time.perf_counter()
                    payload = rt.get_act(dx)
                    if stage > 0:
                        chan.send_grad(k, i, payload, v)
                    else:
                        # ring wrap back: chunk v*S - 1 is worker S-1's
                        # virtual chunk v-1
                        chan.send_grad(k, i, payload, v - 1, wrap=True)
                    busy += time.perf_counter() - c0
            if span is not None:
                collector.end(span)
        c0 = time.perf_counter()
        rt.apply_grads(grad_slots, head_slots)
        if rt.is_last:
            total = step_losses[0]
            for li in step_losses[1:]:
                total = total + li
            losses.append(float(total))
        busy += time.perf_counter() - c0
        # the blocking part of sends is already inside the timed regions
        # above (send_* called under the busy clock); nothing to add —
        # but record the per-step exposure for the overlap stats
        block1 = chan.stats.snapshot()["send_block_s"]
        step_stats.append({"t0": t_step0, "t1": time.perf_counter(),
                           "busy_s": round(busy, 6),
                           "send_block_s": round(block1 - block0, 6)})
        if snapshots is not None:
            # boundary snapshot: params just updated, nothing in flight
            # for step k remains. losses/step_stats ride along so a
            # restored worker reports the FULL trajectory, not a suffix.
            snapshots.publish(stage, k, {
                "stage": stage, "step": k, "schedule": cfg.schedule,
                "state": rt.export_state(),
                "losses": list(losses), "step_stats": list(step_stats),
            })
        if on_step is not None:
            on_step(k, losses[-1] if rt.is_last else None)
    elastic = (chan.elastic.snapshot()
               if getattr(chan, "elastic", None) is not None
               and (snapshots is not None or start_step) else None)
    return StageResult(
        stage=stage, losses=losses, step_stats=step_stats,
        transport=chan.stats.snapshot(), depot=rt.depot_summary(),
        schedule=cfg.schedule, max_stash=max_live_stash(ticks),
        elastic=elastic)


# --------------------------------------------------------- measurement --

def analytic_bubble_bound(n_stages: int, microbatches: int,
                          virtual_stages: int = 1) -> float:
    """The fill-drain bound: stage s idles s ticks at fill and S-1-s at
    drain, per phase — (S-1)/(S+M-1) of the schedule, independent of the
    fwd/bwd time ratio (both phases scale together). With virtual
    stages the same S-1 fill/drain units amortize over V*M chunk units:
    (S-1)/(V*M+S-1) — strictly below the V=1 floor for V >= 2."""
    return (n_stages - 1) / (virtual_stages * microbatches + n_stages - 1)


def aggregate_stats(results: list, cfg: PipelineRunConfig,
                    skip_steps: int = 1) -> dict:
    """Fold per-stage StageResults (or their dict form) into the measured
    pipeline numbers. Bubble is idle-vs-window against stage 0's step
    windows (stage 0 brackets every step — see run_stage); the first
    ``skip_steps`` steps are excluded (first-call cache warming). DCN
    overlap is 1 - send_block/wire: the wire time hidden under compute."""
    def _d(r):
        return r if isinstance(r, dict) else dataclasses.asdict(r)

    rs = sorted((_d(r) for r in results), key=lambda r: r["stage"])
    S = cfg.n_stages
    if len(rs) != S:
        raise ValueError(f"need all {S} stage reports, got {len(rs)}")
    windows = rs[0]["step_stats"]
    n_steps = min(len(r["step_stats"]) for r in rs)
    per_step = []
    for k in range(skip_steps, n_steps):
        w = windows[k]["t1"] - windows[k]["t0"]
        if w <= 0:
            continue
        idle = sum(max(0.0, w - r["step_stats"][k]["busy_s"]) for r in rs)
        per_step.append(idle / (S * w))
    bubble = sum(per_step) / len(per_step) if per_step else None
    wire = sum(r["transport"]["wire_s"] for r in rs)
    blocked = sum(r["transport"]["send_block_s"] for r in rs)
    overlap = (1.0 - min(blocked, wire) / wire) if wire > 0 else None
    busy = [sum(st["busy_s"] for st in r["step_stats"][skip_steps:n_steps])
            for r in rs]
    V = cfg.virtual_stages
    ticks = 2 * cfg.microbatches * V * max(1, n_steps - skip_steps)
    interleaved = cfg.schedule == "interleaved-1f1b"
    return {
        "schedule": cfg.schedule,
        "n_stages": S,
        "virtual_stages": V,
        "microbatches": cfg.microbatches,
        "steps_measured": max(0, n_steps - skip_steps),
        "bubble_fraction": round(bubble, 4) if bubble is not None else None,
        "bubble_fraction_per_step": [round(b, 4) for b in per_step],
        # the V=1 floor — what interleaving must beat at matched M
        "analytic_fill_drain_bound": round(
            analytic_bubble_bound(S, cfg.microbatches), 4),
        "analytic_interleaved_bound": (round(analytic_bubble_bound(
            S, cfg.microbatches, V), 4) if V > 1 else None),
        "dcn_overlap_fraction": (round(overlap, 4)
                                 if overlap is not None else None),
        "dcn_wire_s": round(wire, 4),
        "dcn_send_block_s": round(blocked, 4),
        "mean_tick_s": round(sum(busy) / (S * ticks), 6) if ticks else None,
        # stash units are CHUNK activations (1/V of a plain stage's):
        # the V-chunk memory cost, checked against the analytic bound
        "max_activation_stash": max(r["max_stash"] for r in rs),
        "stash_per_stage": [r["max_stash"] for r in rs],
        "stash_bound_per_stage": (
            [interleaved_stash_bound(S, s, cfg.microbatches, V)
             for s in range(S)] if interleaved else None),
        "per_stage_busy_s": [round(b, 4) for b in busy],
        "est_basis": "measured (per-stage busy vs stage-0 step windows; "
                     "overlap = 1 - send_block/wire)",
    }


def run_inproc(cfg: PipelineRunConfig, *, collector=None,
               runtimes: Optional[list] = None) -> tuple[list, list[float]]:
    """All stages as threads over the in-process fabric (tests/dryrun).
    Returns (per-stage StageResults, last-stage losses)."""
    fabric = InProcFabric(cfg.n_stages)
    results: list = [None] * cfg.n_stages
    errors: list = []

    def work(s: int):
        chan = fabric.channel(
            s, blocking=cfg.schedule == "gpipe",
            delay_s=cfg.dcn_delay_ms / 1e3, collector=collector)
        try:
            results[s] = run_stage(
                cfg, s, chan,
                runtime=runtimes[s] if runtimes else None,
                collector=collector)
        except Exception as e:                     # surfaced by the join
            errors.append((s, e))
        finally:
            chan.close()

    threads = [threading.Thread(target=work, args=(s,), daemon=True)
               for s in range(cfg.n_stages)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    if errors:
        raise RuntimeError(f"stage failures: {errors!r}") from errors[0][1]
    if any(r is None for r in results):
        raise TimeoutError("a stage thread did not finish")
    return results, results[-1].losses


# -------------------------------------------------------------- oracle --

def run_oracle(cfg: PipelineRunConfig,
               stage_fn: Callable = mlp_stage_fn) -> list[float]:
    """The single-program SPMD oracle: the SAME model/microbatching/loss
    through ``pipeline_apply`` on a pipeline mesh over ``total_stages``
    chunks (needs >= total_stages local devices), same SGD updates. The
    MPMD runs — plain AND interleaved, which partition the model over
    the same total_stages chunks — must reproduce this loss trajectory
    (step 0 bitwise; later steps to fusion-level ulps)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from kubeflow_tpu.parallel.pipeline import (
        pipeline_apply, stack_stage_params,
    )

    cfg.validate()
    T = cfg.total_stages
    devs = jax.devices()
    if len(devs) < T:
        raise RuntimeError(
            f"oracle needs {T} devices, have {len(devs)} "
            "(set --xla_force_host_platform_device_count)")
    mesh = Mesh(np.array(devs[:T]), ("pipeline",))
    fwd = pipeline_apply(stage_fn, mesh, microbatches=cfg.microbatches)
    M, R = cfg.microbatches, cfg.mb_rows

    def loss_fn(stacked, hp, x, t):
        y = fwd(stacked, x)
        ymb = y.reshape(M, R, cfg.dim)
        tmb = t.reshape(M, R, 1)
        per_mb = jax.vmap(
            lambda ym, tm: head_loss(hp, ym, tm, microbatches=M))(ymb, tmb)
        return jnp.sum(per_mb)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    stacked = stack_stage_params(
        [init_stage_params(cfg, s) for s in range(T)])
    hp = init_head_params(cfg)
    losses = []
    for k in range(cfg.steps):
        x, t = step_batch(cfg, k)
        loss, (gs, gh) = grad_fn(stacked, hp, x, t)
        losses.append(float(loss))
        stacked = jax.tree_util.tree_map(
            lambda p, g: p - cfg.lr * g, stacked, gs)
        hp = jax.tree_util.tree_map(lambda p, g: p - cfg.lr * g, hp, gh)
    return losses


# -------------------------------------------------------- worker entry --

def _worker_main() -> int:
    """Gang stage worker: ``python -m kubeflow_tpu.parallel.mpmd`` inside
    a pod. Env contract: the reconciler's stage rendezvous stamps
    (KFT_STAGE_ID / KFT_STAGE_BIND / KFT_STAGE_PREV / KFT_STAGE_NEXT —
    see rendezvous/bootstrap.stage_from_env) + the KFT_MPMD_* run config.
    Phases/heartbeats/spans ride the standard operator transports; the
    stage report lands in KFT_MPMD_REPORT_DIR for the bench."""
    from kubeflow_tpu.rendezvous.worker_check import _phase

    phases: dict = {}
    _phase(phases, "proc_start")
    import jax  # noqa: F401  (the import the imports_done stamp times)

    from kubeflow_tpu.obs.trace import SpanCollector
    from kubeflow_tpu.rendezvous.bootstrap import (
        depot_from_env, stage_from_env,
    )
    from kubeflow_tpu.training.loop import Heartbeat, post_heartbeat

    _phase(phases, "imports_done")
    info = stage_from_env()
    if info is None:
        print("KFT_NUM_STAGES not set: not an MPMD stage worker")
        return 2
    if info.stage_proc_id > 0:
        # multi-worker stages carry the full group env contract
        # (KFT_STAGE_GROUP_SIZE/RANK/COORD — the per-stage
        # jax.distributed rendezvous triplet) but this runner executes
        # one process per stage — extra stage workers report their group
        # identity and exit cleanly instead of racing proc 0 for the
        # stage bind
        print(f"stage {info.stage_id} proc {info.stage_proc_id}: "
              f"group rank {info.group_rank}/{info.group_size} "
              f"(coord {info.group_coord}); per-stage jax.distributed is "
              "a future surface; proc 0 owns the stage program")
        return 0
    cfg = PipelineRunConfig.from_env()
    collector = SpanCollector(proc=f"stage{info.stage_id}")
    timeout_s = float(os.environ.get("KFT_PIPE_RECV_TIMEOUT_S", "120"))
    park_s = float(os.environ.get("KFT_PIPE_PARK_S", "60"))
    max_reforms = int(os.environ.get("KFT_PIPE_MAX_REFORMS", "4"))
    estats = ElasticStats()

    spec = None
    if os.environ.get("KFT_MPMD_MODEL", "mlp") == "llama":
        from kubeflow_tpu.parallel.pipeline_llama import mpmd_llama_spec

        spec = mpmd_llama_spec(cfg)

    # elastic mode: the shared snapshot dir doubles as the epoch bulletin.
    # A replacement worker boots with the reconciler's bumped
    # KFT_RENDEZVOUS_EPOCH and ANNOUNCES it here; survivors are not
    # restarted — their epoch watcher sees the bump, poisons the
    # in-flight window, and reforms in process (programs + params hot).
    store = None
    epoch = info.epoch
    if os.environ.get("KFT_ELASTIC_DIR"):
        store = StageSnapshotStore(
            os.environ["KFT_ELASTIC_DIR"],
            fingerprint=run_fingerprint(cfg, spec))
        epoch = max(epoch, store.epoch())
        store.announce_epoch(epoch)

    def _start_channel(ep: int) -> TCPStageChannel:
        return TCPStageChannel(
            info.bind, prev=info.prev, next=info.next, stage=info.stage_id,
            blocking=cfg.schedule == "gpipe",
            delay_s=cfg.dcn_delay_ms / 1e3, collector=collector,
            timeout_s=timeout_s, wrap_next=info.wrap_next,
            wrap_prev=info.wrap_prev, epoch=ep, elastic=estats)

    def _watch(chan: TCPStageChannel) -> threading.Event:
        """Poll the epoch bulletin; on a bump, poison the in-flight
        window so the compute thread unwinds promptly even when it is
        blocked in a long recv far from the dead stage."""
        stop = threading.Event()

        def loop():
            while not stop.wait(0.2):
                e = store.epoch()
                if e > chan.epoch:
                    estats.inc("mailbox_poisons")
                    chan.mailbox.poison(EpochBump(e))
                    return

        threading.Thread(target=loop, daemon=True,
                         name=f"mpmd-epoch-watch-{info.stage_id}").start()
        return stop

    def _restore_point():
        """(common_step, max_step, own snapshot at common_step)."""
        latest = store.latest_steps(cfg.n_stages)
        r = min(latest)
        snap = store.load(info.stage_id, r) if r >= 0 else None
        return r, max(latest), snap

    def _await_epoch(cur: int, err: BaseException) -> int:
        deadline = time.monotonic() + park_s
        while time.monotonic() < deadline:
            e = store.epoch()
            if e > cur:
                return e
            time.sleep(0.1)
        raise RuntimeError(
            f"stage {info.stage_id}: window aborted and no new epoch "
            f"announced within {park_s}s (gang restart is the fallback)"
        ) from err

    chan = _start_channel(epoch)
    _phase(phases, "rendezvous_done")

    # boot-time restore decision BEFORE compile: a replacement (or a
    # gang-restart pod) finds published boundaries and loads its own
    # stage's bytes at the newest COMMON step — stamped restore_done so
    # the recovery trace can carve restore out of claim->compile.
    start_step, prior_losses, prior_stats = 0, [], []
    restored_step, replay_window = -1, None
    boot_snap = None
    if store is not None:
        r, mx, boot_snap = _restore_point()
        if boot_snap is not None:
            restored_step, replay_window = r, mx + 1
            start_step = r + 1
            prior_losses = boot_snap["losses"]
            prior_stats = boot_snap["step_stats"]
            phases["restored_step"] = float(r)
            _phase(phases, "restore_done")

    dstats = DepotStats()
    try:
        depot = depot_from_env(stats=dstats)
    except Exception:
        dstats.inc("fetch_errors")
        depot = None
    rt = StageRuntime(cfg, info.stage_id, depot=depot, depot_stats=dstats,
                      spec=spec)
    phases["depot_hit"] = 1.0 if rt.depot_summary()["hit"] else 0.0
    phases["stage_id"] = float(info.stage_id)
    _phase(phases, "compile_done",
           extra={"depot": dstats.snapshot()} if depot is not None else None)
    if boot_snap is not None:
        rt.restore_state(boot_snap["state"])

    hb_path = os.environ.get("KFT_HEARTBEAT_FILE")
    hb = Heartbeat(hb_path) if hb_path else None

    def on_step(step: int, loss: Optional[float]) -> None:
        if "first_step_done" not in phases:
            _phase(phases, "first_step_done")
        if replay_window is not None:
            # recovery decomposition stamps: the end of the replayed
            # window (the step that was in flight at the kill) and the
            # first genuinely NEW step after it
            if step == replay_window and "replay_done" not in phases:
                _phase(phases, "replay_done")
            elif (step == replay_window + 1
                    and "first_new_step_done" not in phases):
                _phase(phases, "first_new_step_done")
        if hb is not None:
            hb.beat(step)

    def on_sync(r: int, w: Optional[int]) -> None:
        # run_stage's post-barrier restore sync is authoritative (the
        # boot read can be a step stale — see run_stage): adopt it so
        # the replay stamps and the report's accounting match what the
        # gang actually replays
        nonlocal restored_step, replay_window
        if w is not None:
            restored_step, replay_window = r, w

    attempt = 0
    try:
        while True:
            watcher_stop = _watch(chan) if store is not None else None
            try:
                result = run_stage(
                    cfg, info.stage_id, chan, runtime=rt,
                    collector=collector, on_step=on_step,
                    start_step=start_step, prior_losses=prior_losses,
                    prior_step_stats=prior_stats, snapshots=store,
                    on_sync=on_sync)
                break
            except (RuntimeError, TimeoutError) as err:
                if store is None or attempt >= max_reforms:
                    raise
                attempt += 1
                # in-process reform: count-and-fence the dead window's
                # parked frames, drop the old incarnation's channel,
                # park until the replacement announces the new epoch,
                # roll back to the newest common boundary, re-listen on
                # the SAME bind at the new epoch and replay
                chan.drain_stale()
                chan.close()
                epoch = _await_epoch(epoch, err)
                estats.inc("reforms")
                r, mx, snap = _restore_point()
                if snap is not None:
                    rt.restore_state(snap["state"])
                    restored_step, replay_window = r, mx + 1
                    start_step = r + 1
                    prior_losses = snap["losses"]
                    prior_stats = snap["step_stats"]
                else:
                    # no common boundary yet: params may have advanced
                    # past step boundaries the gang cannot all reach —
                    # rebuild the deterministic initial state
                    rt.restore_state({
                        "params": [rt.spec.chunk_params(cfg, c)
                                   for c in rt.chunks],
                        "head_params": (rt.spec.head_params(cfg)
                                        if rt.is_last else None)})
                    start_step, prior_losses, prior_stats = 0, [], []
                    restored_step, replay_window = -1, None
                chan = _start_channel(epoch)
            finally:
                if watcher_stop is not None:
                    watcher_stop.set()
    finally:
        chan.close()
        if hb is not None:
            hb.close()

    if store is not None:
        result.elastic = {
            **(result.elastic or {}), **estats.snapshot(),
            "epoch": epoch, "restored_step": restored_step,
            "replay_window": replay_window,
            "replayed_microbatches": (
                (replay_window - restored_step) * cfg.microbatches
                if replay_window is not None else 0),
        }

    report_dir = os.environ.get("KFT_MPMD_REPORT_DIR")
    if report_dir:
        os.makedirs(report_dir, exist_ok=True)
        path = os.path.join(report_dir,
                            f"stage-{info.stage_id}.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(dataclasses.asdict(result), f)
        os.replace(tmp, path)

    # per-stage spans -> the operator job trace, over the ONE heartbeat
    # http transport (training/loop.post_heartbeat). On shared-fs rigs
    # KFT_HEARTBEAT_FILE is a file but the operator still injects its
    # phases route as http — post to whichever is a URL. Bounded: the
    # last step's ticks + transfers (the operator caps 64/POST).
    span_url = next((u for u in (hb_path,
                                 os.environ.get("KFT_PHASES_PATH"))
                     if u and u.startswith(("http://", "https://"))), None)
    if span_url:
        spans = [s for s in collector.snapshot()
                 if s["name"] in ("pipeline.tick", "dcn.transfer")]
        last_step = cfg.steps - 1
        chosen = [s for s in spans
                  if s["attrs"].get("step") == last_step][:64]
        post_heartbeat(span_url, step=cfg.steps, spans=chosen)
    print(f"stage {info.stage_id}/{cfg.n_stages}: schedule={cfg.schedule} "
          f"steps={cfg.steps} depot_hit={phases['depot_hit']} "
          f"losses={result.losses}")
    return 0


def _oracle_main() -> int:
    """``python -m kubeflow_tpu.parallel.mpmd --oracle``: run the SPMD
    oracle for the env-described config and write its losses to
    KFT_MPMD_REPORT_DIR/oracle.json (the bench's parity reference).
    Needs XLA_FLAGS=--xla_force_host_platform_device_count >= stages."""
    cfg = PipelineRunConfig.from_env()
    if os.environ.get("KFT_MPMD_MODEL", "mlp") == "llama":
        from kubeflow_tpu.parallel.pipeline_llama import (
            mpmd_llama_spec, run_mpmd_llama_oracle,
        )

        losses = run_mpmd_llama_oracle(cfg, mpmd_llama_spec(cfg))
    else:
        losses = run_oracle(cfg)
    report_dir = os.environ.get("KFT_MPMD_REPORT_DIR", ".")
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, "oracle.json"), "w") as f:
        json.dump({"losses": losses, "steps": cfg.steps,
                   "microbatches": cfg.microbatches}, f)
    print(f"oracle: losses={losses}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_oracle_main() if "--oracle" in sys.argv[1:]
             else _worker_main())
