"""High-level training driver: checkpoint auto-resume, metrics export,
heartbeats, profiler toggle.

This is the recovery path SURVEY.md §5 makes first-class: slice restart is
the NORMAL failure mode at scale, so every run is structured as
restore-latest -> train -> periodic async save, and a restarted job resumes
where it left off with no operator involvement beyond re-running the pod.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Any, Callable, Iterable, Optional

import jax

from kubeflow_tpu.training.checkpoint import CheckpointManager
from kubeflow_tpu.training.metrics import MetricsWriter
from kubeflow_tpu.training.trainer import Trainer


@dataclasses.dataclass
class FitResult:
    final_step: int
    resumed_from: Optional[int]
    last_metrics: dict
    # set iff the jax.profiler window actually ran: {"dir", "t_start",
    # "t_stop"} wall times of start_trace/stop_trace — what worker_check
    # stamps into the phase report (a run that never reached the window
    # must not report a phantom profile artifact)
    profile: Optional[dict] = None


def post_heartbeat(url: str, step=None, warning=None, spans=None,
                   timeout: float = 5.0) -> bool:
    """ONE http transport for the heartbeat contract (beats + warnings +
    worker-reported spans; loop.Heartbeat, checkpoint's mirror alarm and
    the MPMD stage workers all route through here — the operator folds
    ``spans`` into the /apis/v1/trace job trace). Failures are
    swallowed: missed beats ARE the failure signal."""
    import json
    import urllib.request

    body: dict = {}
    if step is not None:
        body["step"] = int(step)
    if warning is not None:
        body["warning"] = warning
    if spans:
        # span dicts (obs/trace.Span.to_dict form); the operator
        # validates field-by-field and bounds per pod
        body["spans"] = list(spans)
    try:
        req = urllib.request.Request(
            url, method="POST", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=timeout).close()
        return True
    except Exception:
        return False


class Heartbeat:
    """Liveness signal: the controller-side FileHeartbeatTracker turns
    missed beats into gang restarts (SURVEY.md §2.8 fault signaling).

    Two transports behind ONE env value (KFT_HEARTBEAT_FILE):
    - a filesystem path (LocalProcessCluster: shared fs) — mtime is the
      signal, content is the last step;
    - an http(s) URL (KubeCluster: pods and operator share no
      filesystem) — beats POST to the operator's heartbeat route, which
      writes the same tracker file on ITS side, so every downstream
      consumer (staleness sweep, first-step metric, warning sweep) is
      transport-agnostic. URL beats post from a BACKGROUND thread
      holding only the latest step (rate-limited), so a slow or down
      operator can never stall the training hot loop.
    """

    def __init__(self, path: str, min_interval_s: float = 1.0):
        self.path = path
        self.is_url = path.startswith(("http://", "https://"))
        self.min_interval_s = min_interval_s
        if self.is_url:
            import queue
            import threading

            self._latest: Optional[int] = None
            self._latest_lock = threading.Lock()
            self._warnings: "queue.Queue[dict]" = queue.Queue()
            self._kick = threading.Event()
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._pump, daemon=True, name="kft-heartbeat-post")
            self._thread.start()
        else:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def beat(self, step: int, warning: Optional[dict] = None) -> None:
        if self.is_url:
            with self._latest_lock:
                self._latest = int(step)
            if warning is not None:
                self._warnings.put(warning)
            self._kick.set()
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(step))
        os.replace(tmp, self.path)

    def _take(self) -> tuple[Optional[int], Optional[dict]]:
        """Atomically claim the pending step (the lock closes the race
        where a beat lands between the read and the reset) + one warning."""
        import queue

        with self._latest_lock:
            step, self._latest = self._latest, None
        try:
            warning = self._warnings.get_nowait()
        except queue.Empty:
            warning = None
        return step, warning

    def _pump(self) -> None:
        while not self._stop.is_set():
            self._kick.wait()
            self._kick.clear()
            step, warning = self._take()
            if step is not None or warning is not None:
                post_heartbeat(self.path, step=step, warning=warning)
            if not self._warnings.empty() or self._latest is not None:
                self._kick.set()       # drain remaining work next loop
            self._stop.wait(self.min_interval_s)   # rate limit

    def close(self) -> None:
        if self.is_url:
            self._stop.set()
            self._kick.set()
            self._thread.join(timeout=10.0)
            # final flush: the last pre-shutdown beat/warnings must not be
            # lost in the pump — post whatever remains, synchronously
            while True:
                step, warning = self._take()
                if step is None and warning is None:
                    break
                post_heartbeat(self.path, step=step, warning=warning)


def profile_from_env(env=None) -> tuple[Optional[str],
                                        Optional[tuple[int, int]]]:
    """The pod env contract for the jax.profiler toggle:
    KFT_PROFILE_DIR names the trace output directory (unset = profiling
    off) and KFT_PROFILE_STEPS is "start:stop" (or "start,stop") step
    bounds for the profiled window. Returns (dir, steps) with None for
    whatever is unset/malformed — a bad value must never fail a job over
    an optional profile."""
    env = os.environ if env is None else env
    profile_dir = env.get("KFT_PROFILE_DIR") or None
    steps = None
    raw = env.get("KFT_PROFILE_STEPS") or ""
    if raw:
        try:
            a, b = raw.replace(",", ":").split(":")
            steps = (int(a), int(b))
            if steps[0] >= steps[1] or steps[0] < 0:
                steps = None
        except ValueError:
            steps = None
    return profile_dir, steps


def restore_latest(trainer: Trainer, mgr: CheckpointManager):
    """Restore the newest checkpoint into ``trainer`` (params/opt_state
    re-placed on the template's shardings, step advanced). Returns the
    restored step or None when no checkpoint exists. Shared by ``fit``
    and by replacement workers that must restore BEFORE loading the
    compiled executable (the elastic-recovery takeover order).

    The restored state is laundered through a jitted identity so every
    buffer is a fresh XLA-runtime allocation. Load-bearing for elastic
    recovery, not a style choice: restore/device_put hand back arrays
    whose storage the runtime treats as EXTERNAL, and a DESERIALIZED
    train step (the executable-depot hit a replacement worker takes)
    donates its inputs — donating an external buffer to a deserialized
    executable corrupts the heap (observed: NaN updates from the first
    donated call, "double free or corruption", SIGSEGV/SIGABRT; a
    locally jit-compiled step tolerates the same inputs). One extra
    device-side copy per restore buys a state every executable kind can
    safely consume."""
    latest = mgr.latest_step()
    if latest is None:
        return None
    template = {"params": trainer.params,
                "opt_state": trainer.opt_state}
    _, state = mgr.restore(latest, template=template)
    # re-place on the template's shardings: orbax can hand back
    # scalar/replicated/host leaves, which would otherwise clash with
    # the mesh-placed params inside the jitted step
    state = jax.tree_util.tree_map(
        lambda x, t: jax.device_put(x, t.sharding)
        if hasattr(t, "sharding") else x,
        state, template,
    )
    state = jax.jit(lambda s: s)(state)     # the buffer launder (above)
    trainer.params = state["params"]
    trainer.opt_state = state["opt_state"]
    trainer.step = latest
    return latest


def fit(
    trainer: Trainer,
    batches: Iterable[Any] | Callable[[int], Iterable[Any]],
    *,
    rng: jax.Array,
    max_steps: int,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 100,
    checkpoint_mirror: Optional[str] = None,
    metrics: Optional[MetricsWriter] = None,
    metrics_every: int = 10,
    heartbeat: Optional[Heartbeat] = None,
    profile_dir: Optional[str] = None,
    profile_steps: Optional[tuple[int, int]] = None,
    on_step: Optional[Callable[[int, dict], None]] = None,
    already_resumed: Optional[int] = None,
) -> FitResult:
    """Run training with auto-resume.

    If ``checkpoint_dir`` holds a checkpoint, state is restored and training
    continues from the saved step. Data is resumed deterministically:
    ``batches`` may be a callable ``(start_step) -> iterator`` (preferred —
    a step-indexed dataset can seek directly), or a plain iterable, in which
    case the first ``resumed_from`` batches are consumed and discarded so a
    restarted job sees the same step->batch mapping as an uninterrupted one.

    ``already_resumed`` says the caller restored the checkpoint itself
    (a replacement worker restores BEFORE loading the depot executable);
    fit then skips its own restore but still performs the resume
    handshake: an immediate heartbeat at the takeover step, so the
    operator's staleness sweep sees the new incarnation live BEFORE the
    (possibly long) first post-resume step completes.
    """
    # operator contract: pods get KFT_HEARTBEAT_FILE injected; beating it
    # per step is what feeds fault detection and the submit->first-step
    # latency metric without any explicit wiring in user code
    if heartbeat is None and os.environ.get("KFT_HEARTBEAT_FILE"):
        heartbeat = Heartbeat(os.environ["KFT_HEARTBEAT_FILE"])
    # profiler toggle rides the pod env the same way (KFT_PROFILE_DIR /
    # KFT_PROFILE_STEPS): explicit arguments win, env fills the gaps
    env_dir, env_steps = profile_from_env()
    if profile_dir is None:
        profile_dir = env_dir
    if profile_steps is None:
        profile_steps = env_steps or (10, 20)

    # a caller that already initialized (e.g. worker_check's precompile
    # phase, which needs live state to lower the step) keeps its state —
    # re-running init here would both waste a full param/opt init and
    # land it inside the phase the bench attributes to step 1
    if trainer.params is None:
        trainer.init_state(rng)
    resumed_from = already_resumed
    mgr = None
    if checkpoint_dir:
        mgr = CheckpointManager(
            checkpoint_dir,
            mirror=checkpoint_mirror
            or os.environ.get("KFT_CHECKPOINT_MIRROR") or None)
        # a caller that already restored (``already_resumed`` — e.g. a
        # replacement worker that must restore before loading the depot
        # executable) keeps its state; restoring again here would both
        # waste the IO and reorder it after the executable load
        if already_resumed is None:
            resumed_from = restore_latest(trainer, mgr)
    if resumed_from is not None and heartbeat is not None:
        # resume handshake: confirm liveness + the exact takeover step
        # to the operator NOW — the replacement's first beat must not
        # wait out the first post-resume step (covers BOTH the
        # fit-restored and the caller-pre-restored paths)
        heartbeat.beat(resumed_from)

    if callable(batches):
        batches = batches(trainer.step)
    elif resumed_from:
        batches = itertools.islice(iter(batches), resumed_from, None)

    profiling = False
    profile_info: Optional[dict] = None
    last = {}
    for batch in batches:
        if trainer.step >= max_steps:
            break
        step = trainer.step

        if profile_dir and not profiling and step == profile_steps[0]:
            jax.profiler.start_trace(profile_dir)
            profiling = True
            profile_info = {"dir": profile_dir, "t_start": time.time()}
        m = trainer.train_step(batch)
        if profiling and trainer.step >= profile_steps[1]:
            # dispatch is asynchronous: the trace must not close before
            # the profiled steps have executed
            jax.block_until_ready(m["loss"])
            jax.profiler.stop_trace()
            profiling = False
            profile_info["t_stop"] = time.time()

        last = {k: float(v) for k, v in m.items()
                if hasattr(v, "__float__")}
        if mgr is not None and mgr.mirror_errors:
            last["ckpt_mirror_errors"] = float(mgr.mirror_errors)
        if metrics is not None and trainer.step % metrics_every == 0:
            metrics.write(trainer.step, **last)
        if heartbeat is not None:
            heartbeat.beat(trainer.step)
        if mgr is not None and trainer.step % checkpoint_every == 0:
            mgr.save(trainer.step,
                     {"params": trainer.params,
                      "opt_state": trainer.opt_state})
        if on_step is not None:
            on_step(trainer.step, last)

    if profiling:
        jax.profiler.stop_trace()
        profile_info["t_stop"] = time.time()
    if mgr is not None:
        # final save — unless this exact step is already on disk (the
        # in-loop save fired on it, or a resumed run trained 0 steps);
        # force= bypasses the save-interval policy, not step collisions.
        if mgr.latest_step() != trainer.step:
            mgr.save(trainer.step,
                     {"params": trainer.params,
                      "opt_state": trainer.opt_state},
                     force=True)
        mgr.wait()
        mgr.close()
    if metrics is not None and last:
        metrics.write(trainer.step, **last)
    return FitResult(final_step=trainer.step, resumed_from=resumed_from,
                     last_metrics=last,
                     profile=(profile_info
                              if profile_info and "t_stop" in profile_info
                              else None))
