"""InferenceService controller + runtime selection + canary rollout +
fleet autoscaling on scheduler signals.

Parity: SURVEY.md §2.4 'InferenceService controller' and §3.3 — reconcile
predictor/transformer/explainer into runtime pods (the raw-Deployment mode;
serverless scale-to-zero arrives with the autoscaler), select a
ServingRuntime by model format, track revisions, and split traffic between
the previous ready revision and the canary revision.

Fleet layer: the ``Autoscaler`` consumes the per-replica
``kft_model_sched_*`` family the step scheduler exports (queue depth,
token backlog, slot occupancy) — not just probe concurrency — and makes
scale-to-N decisions with a hysteresis window (scale up immediately on
demand; scale down only after ``idle_grace_seconds`` of sustained low
signal, never below min_replicas, never mid-canary). On the kube backend
a scale-up predictor pod CLAIMS a warm-pool standby
(``controller/warmpool.py``) whose claim pre-fetched the executable depot
(``parallel/depot.py``) — replica add is bounded by warm-claim +
depot-fetch time, not a cold interpreter + compile. ``CanaryGate``
promotes or rolls back a revision split on an error-rate/latency SLO.
"""

from __future__ import annotations

import math
import os
import sys
import threading
import time
from typing import Optional

from kubeflow_tpu.controller.cluster import (
    Cluster, Pod, PodPhase, Service, create_and_admit,
)
from kubeflow_tpu.obs.histogram import Histogram
from kubeflow_tpu.serving.types import (
    TIER_DEFAULT_SCALE_METRIC, InferenceService, ModelFormat,
    ServingRuntime, TierSpec,
)


class RuntimeRegistry:
    """ServingRuntime store with the reference's matching rule: namespace
    runtimes beat cluster runtimes, then priority, then name."""

    def __init__(self):
        self._runtimes: dict[tuple[Optional[str], str], ServingRuntime] = {}

    def register(self, rt: ServingRuntime) -> None:
        self._runtimes[(rt.namespace, rt.name)] = rt

    def get(self, name: str, namespace: Optional[str] = None
            ) -> Optional[ServingRuntime]:
        return (self._runtimes.get((namespace, name))
                or self._runtimes.get((None, name)))

    def select(self, fmt: ModelFormat, namespace: str
               ) -> Optional[ServingRuntime]:
        candidates = [
            rt for rt in self._runtimes.values()
            if rt.supports(fmt) and rt.namespace in (None, namespace)
        ]
        if not candidates:
            return None
        candidates.sort(
            key=lambda rt: (rt.namespace is None, -rt.priority, rt.name))
        return candidates[0]


def _pod_name(isvc: InferenceService, component: str, revision: int,
              index: int) -> str:
    return f"{isvc.name}-{component}-rev{revision}-{index}"


class ServingController:
    """Reconciles InferenceServices against a Cluster.

    Revisions: every spec change (generation bump) creates a new revision's
    pods; once the new revision is ready, traffic moves — fully, or split by
    canary_traffic_percent, with the old revision kept for rollback. The
    reference gets this from Knative; here it is explicit and testable.
    """

    def __init__(self, cluster: Cluster, runtimes: RuntimeRegistry):
        self.cluster = cluster
        self.runtimes = runtimes
        self.services: dict[tuple[str, str], InferenceService] = {}
        self._applied_generation: dict[tuple[str, str], int] = {}
        # autoscaler-applied predictor replica counts (absent => min_replicas)
        self._desired: dict[tuple[str, str], int] = {}

    # -------------- apiserver-ish surface --------------

    def apply(self, isvc: InferenceService) -> InferenceService:
        key = (isvc.namespace, isvc.name)
        existing = self.services.get(key)
        if existing is None:
            isvc.generation = 1
            self.services[key] = isvc
        elif self._spec_equal(existing, isvc):
            # idempotent re-apply: no generation bump, no new revision
            isvc.generation = existing.generation
            isvc.status = existing.status
            self.services[key] = isvc
        else:
            isvc.generation = existing.generation + 1
            isvc.status = existing.status
            self.services[key] = isvc
        self.reconcile(isvc.namespace, isvc.name)
        return isvc

    @staticmethod
    def _spec_equal(a: InferenceService, b: InferenceService) -> bool:
        import dataclasses as dc

        def norm(v):
            return dc.asdict(v) if dc.is_dataclass(v) else v

        return all(norm(getattr(a, f)) == norm(getattr(b, f))
                   for f in ("predictor", "transformer", "explainer",
                             "labels"))

    def get(self, namespace: str, name: str) -> Optional[InferenceService]:
        return self.services.get((namespace, name))

    def delete(self, namespace: str, name: str) -> None:
        isvc = self.services.pop((namespace, name), None)
        # a later re-created service with the same name starts from its own
        # spec, not this one's autoscale state or revision cursor (tiered
        # services keep one desired-count entry per tier: 3-tuple keys)
        for k in [k for k in self._desired
                  if k[0] == namespace and k[1] == name]:
            self._desired.pop(k, None)
        self._applied_generation.pop((namespace, name), None)
        if isvc is None:
            return
        for pod in self._pods(isvc):
            self.cluster.delete_pod(namespace, pod.name)
        self.cluster.delete_service(namespace, isvc.name)

    # -------------- reconcile --------------

    def reconcile(self, namespace: str, name: str
                  ) -> Optional[InferenceService]:
        isvc = self.services.get((namespace, name))
        if isvc is None:
            return None
        key = (namespace, name)

        runtime = self._select_runtime(isvc)
        if runtime is None:
            msg = (f"NoRuntime: no ServingRuntime supports format "
                   f"{isvc.predictor.model_format.name!r}")
            if not isvc.status.conditions or isvc.status.conditions[-1] != msg:
                isvc.status.conditions.append(msg)
            return isvc

        if self.cluster.get_service(namespace, isvc.name) is None:
            self.cluster.create_service(Service(
                name=isvc.name, namespace=namespace,
                selector={"isvc": isvc.name}, port=8080))

        if self._applied_generation.get(key) != isvc.generation:
            isvc.status.latest_revision += 1
            self._applied_generation[key] = isvc.generation
            self._create_revision_pods(isvc, runtime,
                                       isvc.status.latest_revision)

        latest = isvc.status.latest_revision
        # Deployment-style self-healing: failed pods of the active revision
        # are deleted and recreated (predictors get a fresh bind port, which
        # also heals a lost port race between allocation and server start)
        for pod in self._pods(isvc, revision=latest):
            if pod.phase == PodPhase.FAILED:
                self.cluster.delete_pod(isvc.namespace, pod.name)
        # scale-down: drop excess predictor pods highest-index-first, BY
        # INDEX IDENTITY — get_pod(revN-i) resolves the warm-claim alias,
        # so a claimed replica (serving under the standby pod's own name)
        # is deleted as the index the controller created it for. Deleting
        # by a name sort instead would delete a pod the creation loop
        # below immediately recreates: a perpetual churn loop.
        want = self._predictor_replicas(isvc)
        n_pred = sum(1 for p in self._pods(isvc, revision=latest)
                     if p.labels.get("component") == "predictor")
        # scan bound covers every index the controller can have created:
        # live-count alone would miss a high index exposed by failed-pod
        # gaps below it (max_replicas bounds autoscaler-created indices).
        # Disaggregated services scale each tier's pod set independently,
        # so excess-index deletion runs per tier under the tier-embedded
        # pod-name component.
        tiers = self._tiers(isvc)
        if tiers:
            for t in tiers:
                want_t = self._predictor_replicas(isvc, tier=t.name)
                n_t = sum(1 for p in self._pods(isvc, revision=latest)
                          if p.labels.get("tier") == t.name)
                for i in range(want_t, max(want_t + n_t, t.max_replicas)):
                    pod = self.cluster.get_pod(
                        isvc.namespace,
                        _pod_name(isvc, f"predictor-{t.name}", latest, i))
                    if pod is not None:
                        self.cluster.delete_pod(isvc.namespace, pod.name)
        else:
            bound = max(want + n_pred, isvc.predictor.max_replicas)
            for i in range(want, bound):
                pod = self.cluster.get_pod(
                    isvc.namespace, _pod_name(isvc, "predictor", latest, i))
                if pod is not None:
                    self.cluster.delete_pod(isvc.namespace, pod.name)
        self._create_revision_pods(isvc, runtime, latest)
        if self._revision_ready(isvc, latest):
            prev = isvc.status.ready_revision
            canary = isvc.predictor.canary_traffic_percent
            if prev and prev != latest and canary is not None and canary < 100:
                isvc.status.traffic = {latest: canary, prev: 100 - canary}
            else:
                isvc.status.traffic = {latest: 100}
                self._gc_old_revisions(isvc, keep=latest)
                isvc.status.ready_revision = latest
            isvc.status.ready = True
            isvc.status.url = self.cluster.resolve(namespace, isvc.name)
        elif isvc.status.ready_revision:
            # latest not ready yet: all traffic stays on the ready revision
            isvc.status.traffic = {isvc.status.ready_revision: 100}
        return isvc

    def set_scale(self, namespace: str, name: str, replicas: int,
                  tier: Optional[str] = None) -> None:
        """Apply an autoscaler decision: the latest revision's predictor pod
        count converges to ``replicas`` on subsequent reconciles (excess pods
        deleted highest-index-first; missing ones recreated). For a
        disaggregated service pass ``tier`` — each tier's pod set scales
        independently."""
        if (namespace, name) not in self.services:
            return
        key = ((namespace, name) if tier is None
               else (namespace, name, tier))
        self._desired[key] = max(0, int(replicas))
        self.reconcile(namespace, name)

    def tick_all(self) -> None:
        """One reconcile pass over every InferenceService (daemon loop)."""
        for (ns, name) in list(self.services.keys()):
            self.reconcile(ns, name)

    @staticmethod
    def _tiers(isvc: InferenceService) -> list[TierSpec]:
        return list(getattr(isvc.predictor, "tiers", None) or [])

    def _predictor_replicas(self, isvc: InferenceService,
                            tier: Optional[str] = None) -> int:
        tiers = self._tiers(isvc)
        if tiers:
            if tier is None:
                # total across the fleet (readiness / accounting view)
                return sum(self._predictor_replicas(isvc, tier=t.name)
                           for t in tiers)
            spec = next((t for t in tiers if t.name == tier), None)
            return self._desired.get(
                (isvc.namespace, isvc.name, tier),
                spec.min_replicas if spec is not None else 0)
        return self._desired.get((isvc.namespace, isvc.name),
                                 isvc.predictor.min_replicas)

    def promote(self, namespace: str, name: str) -> None:
        """Finish a canary rollout: 100% to latest, GC the old revision."""
        isvc = self.services[(namespace, name)]
        isvc.predictor.canary_traffic_percent = None
        self.reconcile(namespace, name)

    def rollback(self, namespace: str, name: str) -> None:
        """Abort a canary: all traffic back to the ready revision and drop
        the canary pods."""
        isvc = self.services[(namespace, name)]
        latest = isvc.status.latest_revision
        prev = isvc.status.ready_revision
        if not prev or prev == latest:
            return
        for pod in self._pods(isvc, revision=latest):
            self.cluster.delete_pod(namespace, pod.name)
        isvc.status.latest_revision = prev
        isvc.status.traffic = {prev: 100}
        isvc.predictor.canary_traffic_percent = None

    # -------------- internals --------------

    def _select_runtime(self, isvc: InferenceService
                        ) -> Optional[ServingRuntime]:
        if isvc.predictor.runtime:
            return self.runtimes.get(isvc.predictor.runtime, isvc.namespace)
        return self.runtimes.select(isvc.predictor.model_format,
                                    isvc.namespace)

    def _bind_for_pod(self) -> str:
        """Per-pod bind address (see cluster.allocate_bind); real-cluster
        renderers bind the container port."""
        from kubeflow_tpu.controller.cluster import allocate_bind

        return allocate_bind(self.cluster) or "0.0.0.0:8080"

    @staticmethod
    def _sched_env(sp) -> dict:
        """Step-scheduler knobs ride the same env contract the runtime
        entrypoint parses (serving/runtime.py)."""
        return {
            "KFT_PREFILL_QUOTA": str(sp.prefill_tokens_per_step),
            "KFT_INTERLEAVE_PREFILL": "1" if sp.interleave_prefill else "0",
            "KFT_ADAPTIVE_DECODE_CHUNK":
                "1" if sp.adaptive_decode_chunk else "0",
            "KFT_RADIX_CACHE": "1" if sp.radix_cache else "0",
            "KFT_SPEC_DECODE": "1" if sp.spec_decode else "0",
            "KFT_SPEC_K": str(sp.spec_k),
            "KFT_SPEC_DRAFTER": sp.spec_drafter,
        }

    @staticmethod
    def _quant_env(qp) -> dict:
        """Quantized serving rides the same contract (serving/runtime.py
        quant_from_env)."""
        return {
            "KFT_QUANT_KV": qp.kv_dtype,
            "KFT_QUANT_WEIGHTS": qp.weight_dtype,
            "KFT_QUANT_EXACT_PARITY": "1" if qp.exact_parity else "0",
        }

    def _predictor_env(self, isvc: InferenceService, runtime: ServingRuntime,
                       tier: Optional[TierSpec] = None) -> dict:
        env = {
            **runtime.env, **isvc.predictor.env,
            "KFT_MODEL_NAME": isvc.name,
            "KFT_MODEL_FORMAT": isvc.predictor.model_format.name,
            "KFT_STORAGE_URI": isvc.predictor.storage_uri or "",
        }
        if runtime.compile_cache_dir:
            # JAX's own name: the predictor's jax reads it at import
            # (utils/compile_cache.py is the rule)
            env["JAX_COMPILATION_CACHE_DIR"] = runtime.compile_cache_dir
        # a tier-level scheduler policy replaces the predictor-level one
        # wholesale (e.g. a bigger prefill token quota on the prefill tier)
        sp = ((tier.scheduler if tier is not None else None)
              or isvc.predictor.scheduler)
        if sp is not None:
            env.update(self._sched_env(sp))
        # spec-level quant wins over the scheduler-embedded one, mirroring
        # the engine's resolution order; a tier override wins over both
        qp = ((tier.quant if tier is not None else None)
              or isvc.predictor.quant
              or (sp.quant if sp is not None else None))
        if qp is not None:
            env.update(self._quant_env(qp))
        if tier is not None:
            env.update(tier.env)
            env["KFT_TIER"] = tier.name
        env.setdefault("KFT_MODEL_DIR", "/mnt/models")
        return env

    def _create_revision_pods(self, isvc: InferenceService,
                              runtime: ServingRuntime, revision: int) -> None:
        # storage-initializer injection (the reference does this in a pod
        # webhook; here the ISVC controller stamps the init step directly)
        init_cmd = ([sys.executable, "-m", "kubeflow_tpu.serving.runtime",
                     "--init-only"] if isvc.predictor.storage_uri else [])
        # (pod-name component, component label, tier, replicas, env, init):
        # tier pods keep the "predictor" component LABEL (the Service
        # selector and readiness math are tier-blind) but embed the tier in
        # the pod NAME so each tier's index space scales independently
        components: list[tuple] = []
        tiers = self._tiers(isvc)
        if tiers:
            for t in tiers:
                components.append(
                    (f"predictor-{t.name}", "predictor", t,
                     self._predictor_replicas(isvc, tier=t.name),
                     self._predictor_env(isvc, runtime, tier=t), init_cmd))
        else:
            components.append(
                ("predictor", "predictor", None,
                 self._predictor_replicas(isvc),
                 self._predictor_env(isvc, runtime), init_cmd))
        if isvc.transformer:
            components.append(
                ("transformer", "transformer", None,
                 isvc.transformer.min_replicas,
                 dict(isvc.transformer.env), []))
        if isvc.explainer:
            components.append(
                ("explainer", "explainer", None,
                 isvc.explainer.min_replicas,
                 dict(isvc.explainer.env), []))
        for comp, label, tier, replicas, env, init in components:
            for i in range(replicas):
                pname = _pod_name(isvc, comp, revision, i)
                if self.cluster.get_pod(isvc.namespace, pname) is None:
                    pod_env = dict(env)
                    if label == "predictor":
                        pod_env["KFT_BIND"] = self._bind_for_pod()
                        if tier is not None and tier.name == "decode":
                            # the KV receiver's listener: prefill pods
                            # stream finished prompts' paged-KV blocks
                            # here (serving/disagg.KVReceiver). The fixed
                            # fallback port must NOT collide with the HTTP
                            # bind sharing the pod's network namespace.
                            from kubeflow_tpu.controller.cluster import (
                                allocate_bind)
                            pod_env["KFT_KV_BIND"] = (
                                allocate_bind(self.cluster)
                                or "0.0.0.0:8081")
                        if pod_env.get("KFT_DEPOT_CACHE"):
                            # pod-LOCAL depot cache (pods do not share
                            # node disks on a real cluster): the warm
                            # pool pre-fetches executables into exactly
                            # this directory at claim time
                            pod_env["KFT_DEPOT_CACHE"] = os.path.join(
                                pod_env["KFT_DEPOT_CACHE"], pname)
                    labels = {"isvc": isvc.name, "component": label,
                              "revision": str(revision)}
                    if tier is not None:
                        labels["tier"] = tier.name
                    pod = Pod(
                        name=pname, namespace=isvc.namespace,
                        labels=labels, env=pod_env,
                        command=list(runtime.command), init_command=init)
                    # Deployment-style admission: serving pods have no gang
                    # barrier — start them the moment they exist (the
                    # production path; tests no longer play kubelet here)
                    create_and_admit(self.cluster, pod)

    def _pods(self, isvc: InferenceService,
              revision: Optional[int] = None) -> list[Pod]:
        sel = {"isvc": isvc.name}
        if revision is not None:
            sel["revision"] = str(revision)
        return [p for p in self.cluster.list_pods(isvc.namespace, sel)
                if p is not None]

    def _revision_ready(self, isvc: InferenceService, revision: int) -> bool:
        pods = self._pods(isvc, revision)
        want = self._predictor_replicas(isvc)
        if isvc.transformer:
            want += isvc.transformer.min_replicas
        if isvc.explainer:
            want += isvc.explainer.min_replicas
        running = sum(1 for p in pods if p.phase == PodPhase.RUNNING)
        return running >= want

    def _gc_old_revisions(self, isvc: InferenceService, keep: int) -> None:
        for pod in self._pods(isvc):
            if pod.labels.get("revision") != str(keep):
                self.cluster.delete_pod(isvc.namespace, pod.name)


def _mid_canary(isvc: InferenceService) -> bool:
    """True while an old/new revision traffic split is in flight."""
    st = isvc.status
    return bool(st.ready_revision
                and st.latest_revision != st.ready_revision)


class ServingTicker:
    """Daemon glue for the serving layer: one ``tick()`` reconciles every
    InferenceService, applies the autoscaler, and drives any attached
    canary gate to a promote/rollback decision.

    Scale signals come from ``signals_of`` — by default a scrape of each
    ready predictor pod's ``kft_model_sched_*`` family (queue depth, token
    backlog, slot occupancy: the step-scheduler counters that ride
    /metrics and the ``/v2/models/{name}/stats`` JSON view) — falling
    back to the legacy ``kft_requests_in_flight`` concurrency probe for
    pods that export no scheduler family. Tests inject either callable.
    """

    def __init__(self, controller: ServingController,
                 autoscaler: Optional["Autoscaler"] = None,
                 concurrency_of=None, signals_of=None, lock=None,
                 router_of=None):
        self.controller = controller
        self.autoscaler = autoscaler
        # router_of(isvc) -> the FleetRouter (or TieredRouter) fronting
        # this service, or None. Wired by the operator that owns the data
        # plane; the ticker feeds each tick's cumulative spill_saturated
        # count into the Autoscaler as a saturation scale-up trigger.
        self.router_of = router_of
        self.concurrency_of = concurrency_of or self._probe_concurrency
        # a caller that injected ONLY a concurrency source keeps it: the
        # signal probe must not silently outrank an explicit injection
        if signals_of is None and concurrency_of is not None:
            signals_of = lambda isvc: []            # noqa: E731
        self.signals_of = signals_of or self._probe_signals
        # canary SLO gates by (namespace, name) -> (gate, revision armed
        # for): attach_canary() wires one explicitly, or a live split
        # whose PredictorSpec carries canary_slo auto-arms one; decide()
        # verdicts are enacted via the controller's promote/rollback.
        # The armed revision makes stale gates impossible: a split
        # resolved by ANY path (manual promote/rollback, new revision)
        # drops its gate instead of letting old observations decide the
        # next rollout.
        self._canaries: dict[tuple[str, str],
                             tuple["CanaryGate", int]] = {}
        # mutation lock (the operator injects its own): the signal/
        # concurrency probes do blocking HTTP and must NOT hold it — a
        # slow predictor pod must never stall job reconcile/heartbeat/API
        # threads
        self.lock = lock or threading.Lock()

    def attach_canary(self, namespace: str, name: str,
                      gate: "CanaryGate") -> None:
        """Arm SLO-gated rollout for a service: while its canary split is
        live, each tick asks ``gate.decide()`` and enacts the verdict.
        Attaching BEFORE the rollout is applied arms the gate for the
        next split to go live; attaching mid-split arms it for that
        split."""
        isvc = self.controller.get(namespace, name)
        rev = (isvc.status.latest_revision
               if isvc is not None and _mid_canary(isvc) else None)
        self._canaries[(namespace, name)] = (gate, rev)

    def canary_gate(self, namespace: str, name: str
                    ) -> Optional["CanaryGate"]:
        """The gate armed for a service's live split (explicitly attached
        or auto-armed from ``PredictorSpec.canary_slo``) — the data plane
        feeds canary outcomes into it via ``observe``."""
        entry = self._canaries.get((namespace, name))
        return entry[0] if entry else None

    def _probe_concurrency(self, isvc: InferenceService) -> float:
        import urllib.request
        total = 0.0
        for pod in self.controller._pods(
                isvc, revision=isvc.status.latest_revision):
            bind = pod.env.get("KFT_BIND")
            if not bind or pod.phase != PodPhase.RUNNING:
                continue
            try:
                with urllib.request.urlopen(
                        f"http://{bind}/metrics", timeout=1.0) as r:
                    for line in r.read().decode().splitlines():
                        if line.startswith("kft_requests_in_flight "):
                            total += float(line.split()[1])
            except Exception:
                continue
        return total

    def _probe_signals(self, isvc: InferenceService) -> list[dict]:
        """Per-replica scheduler signals for the latest revision's running
        predictor pods: the ``/v2/models/{name}/stats`` JSON ``sched``
        family first (one parse-free read), the ``kft_model_sched_*``
        /metrics lines as fallback. A pod exporting neither contributes
        nothing — an all-empty result makes tick() fall back to the
        legacy concurrency probe."""
        import json as _json
        import urllib.request

        out: list[dict] = []
        for pod in self.controller._pods(
                isvc, revision=isvc.status.latest_revision):
            bind = pod.env.get("KFT_BIND")
            if not bind or pod.phase != PodPhase.RUNNING:
                continue
            sched: dict = {}
            try:
                with urllib.request.urlopen(
                        f"http://{bind}/v2/models/{isvc.name}/stats",
                        timeout=1.0) as r:
                    sched = (_json.loads(r.read()).get("sched") or {})
            except Exception:
                try:
                    with urllib.request.urlopen(
                            f"http://{bind}/metrics", timeout=1.0) as r:
                        text = r.read().decode()
                    prefix = "kft_model_sched_"
                    for line in text.splitlines():
                        if not line.startswith(prefix):
                            continue
                        name = line.split("{")[0][len(prefix):]
                        try:
                            sched[name] = float(line.rsplit(None, 1)[-1])
                        except ValueError:
                            continue
                except Exception:
                    continue
            if sched:
                sched["replica"] = pod.name
                if pod.labels.get("tier"):
                    # tier-attributed signal: the per-tier autoscale loop
                    # partitions on this key
                    sched["tier"] = pod.labels["tier"]
                out.append(sched)
        return out

    def _spill_of(self, isvc: InferenceService):
        """Cumulative ``spill_saturated`` router count(s) for a service:
        a float for a flat fleet, a {tier: float} dict for a
        ``TieredRouter``, None when no router is wired (or it errors —
        a data-plane hiccup must not stall the control loop)."""
        if self.router_of is None:
            return None
        try:
            router = self.router_of(isvc)
        except Exception:
            return None
        if router is None:
            return None

        def count(r):
            try:
                v = r.snapshot().get("spill_saturated")
            except Exception:
                return None
            return None if v is None else float(v)

        if hasattr(router, "router_for"):        # TieredRouter
            return {t: count(router.router_for(t))
                    for t in ("prefill", "decode")}
        return count(router)

    def tick(self) -> None:
        for (ns, name) in list(self.controller.services.keys()):
            with self.lock:
                isvc = self.controller.reconcile(ns, name)
            if isvc is None:
                continue
            self._tick_canary(ns, name, isvc)
            if self.autoscaler is None:
                continue
            # a scaled-to-zero service keeps status.ready (its revision
            # wants zero pods), so the activator wake path passes this
            # guard; only genuinely not-ready services are left alone
            if not isvc.status.ready:
                continue
            # scale_metric="concurrency" pins the legacy in-flight probe;
            # the default "sched" prefers the scheduler-signal family and
            # falls back to concurrency for pods exporting none
            signals = ([] if isvc.predictor.scale_metric == "concurrency"
                       else self.signals_of(isvc))      # unlocked HTTP
            concurrency = (self.concurrency_of(isvc)
                           if not signals else None)
            spill = self._spill_of(isvc)                # unlocked HTTP-free
            tiers = list(isvc.predictor.tiers or [])
            if not tiers:
                with self.lock:
                    desired = self.autoscaler.scale(
                        isvc, concurrency, signals=signals,
                        current=self.controller._predictor_replicas(isvc),
                        spill_saturated=(spill if not isinstance(spill, dict)
                                         else None))
                    if desired != self.controller._predictor_replicas(isvc):
                        self.controller.set_scale(ns, name, desired)
                continue
            # disaggregated: one independent scaling decision per tier on
            # its own signal partition (signals a test injects without a
            # tier tag count toward every tier)
            for t in tiers:
                sig_t = [s for s in signals
                         if s.get("tier", t.name) == t.name]
                spill_t = (spill.get(t.name)
                           if isinstance(spill, dict) else spill)
                with self.lock:
                    cur = self.controller._predictor_replicas(
                        isvc, tier=t.name)
                    desired = self.autoscaler.scale(
                        isvc, concurrency, signals=sig_t, current=cur,
                        tier=t, spill_saturated=spill_t)
                    if desired != cur:
                        self.controller.set_scale(ns, name, desired,
                                                  tier=t.name)

    def _tick_canary(self, ns: str, name: str,
                     isvc: InferenceService) -> None:
        key = (ns, name)
        if not _mid_canary(isvc):
            # split resolved by any path (gate verdict, manual promote/
            # rollback): the gate's observations are history, not a head
            # start for the next rollout. A PRE-armed gate (rev None,
            # attached ahead of the rollout) keeps waiting for its split.
            entry = self._canaries.get(key)
            if entry is not None and entry[1] is not None:
                self._canaries.pop(key, None)
            return
        latest = isvc.status.latest_revision
        entry = self._canaries.get(key)
        if entry is not None and entry[1] is None:
            # pre-armed gate (attached before the rollout): bind it to
            # the split that just went live
            entry = (entry[0], latest)
            self._canaries[key] = entry
        if entry is not None and entry[1] != latest:
            self._canaries.pop(key, None)       # armed for an older split
            entry = None
        if entry is None:
            # auto-arm from the spec: canary_slo makes the gate without a
            # manual attach_canary (the data plane reads it back via
            # canary_gate() to feed observations)
            slo = isvc.predictor.canary_slo
            if slo is None:
                return
            entry = (CanaryGate(max_error_rate=slo.max_error_rate,
                                max_p95_latency_s=slo.max_p95_latency_s,
                                min_requests=slo.min_requests), latest)
            self._canaries[key] = entry
        verdict = entry[0].decide()
        if verdict is None:
            return
        with self.lock:
            if verdict == "promote":
                self.controller.promote(ns, name)
            else:
                self.controller.rollback(ns, name)
        self._canaries.pop(key, None)


class Autoscaler:
    """Replica scaling for the raw-deployment mode (the reference's
    HPA/KPA role), now consuming the per-replica scheduler-signal family.

    ``scale`` takes either a legacy concurrency float or ``signals`` — a
    list of per-replica ``kft_model_sched_*`` dicts (queue_depth,
    occupancy_slots, token_backlog) — and returns the desired replica
    count clamped to min/max. Demand is slot-shaped: occupied slots plus
    queued requests, at ``scale_target`` slots per replica, with the
    fleet token backlog as a second scale-up trigger
    (``backlog_tokens_per_replica``) so long-prompt queues scale before
    queue_depth alone would.

    Flap control: scale-up applies immediately; scale-DOWN only after the
    demand has stayed below the current size for ``idle_grace_seconds``
    (the hysteresis window), never below min_replicas, and never while a
    canary split is in flight — shrinking the fleet mid-rollout would
    fold the error-budget measurement into pod churn. Scale-to-zero
    (min_replicas == 0) keeps its own idle-grace clock and is exempt
    from the second window (its grace already elapsed)."""

    def __init__(self, idle_grace_seconds: float = 30.0,
                 backlog_tokens_per_replica: int = 0,
                 spill_saturation_ticks: int = 2):
        self.idle_grace = idle_grace_seconds
        self.backlog_tokens_per_replica = int(backlog_tokens_per_replica)
        # router-saturation trigger: the cumulative spill_saturated count
        # must RISE across this many consecutive scale() calls before one
        # replica is added — a single burst that the bounded-load spill
        # already absorbed is not a capacity problem
        self.spill_saturation_ticks = max(1, int(spill_saturation_ticks))
        self._last_busy: dict[tuple, float] = {}
        self._low_since: dict[tuple, float] = {}
        self._applied: dict[tuple, int] = {}
        self._spill_last: dict[tuple, float] = {}
        self._spill_rising: dict[tuple, int] = {}

    def wake(self, namespace: str, name: str,
             now: Optional[float] = None) -> None:
        """Activator signal (Knative activator role): a request arrived
        for a possibly scaled-to-zero service — mark it busy so the next
        scale() returns at least one replica."""
        self._last_busy[(namespace, name)] = (
            time.time() if now is None else now)

    def scale(self, isvc: InferenceService,
              concurrency: Optional[float] = None,
              now: Optional[float] = None, *,
              signals: Optional[list] = None,
              current: Optional[int] = None,
              tier: Optional[TierSpec] = None,
              spill_saturated: Optional[float] = None) -> int:
        now = time.time() if now is None else now
        key = ((isvc.namespace, isvc.name) if tier is None
               else (isvc.namespace, isvc.name, tier.name))
        p = isvc.predictor
        min_r = p.min_replicas if tier is None else tier.min_replicas
        max_r = p.max_replicas if tier is None else tier.max_replicas
        target = p.scale_target if tier is None else (
            tier.scale_target or p.scale_target)
        metric = ("occupancy_slots" if tier is None
                  else (tier.scale_metric
                        or TIER_DEFAULT_SCALE_METRIC.get(
                            tier.name, "occupancy_slots")))
        if signals:
            if metric == "token_backlog":
                # prefill-tier shape: demand is the prompt tokens not yet
                # scheduled; scale_target is TOKENS per replica here
                backlog = sum(float(s.get("token_backlog", 0))
                              for s in signals)
                desired = math.ceil(backlog / max(1, target))
                busy = backlog > 0
            else:
                slots = sum(float(s.get(metric, 0)) for s in signals)
                queued = sum(float(s.get("queue_depth", 0))
                             for s in signals)
                backlog = sum(float(s.get("token_backlog", 0))
                              for s in signals)
                demand = slots + queued
                desired = math.ceil(demand / max(1, target))
                if self.backlog_tokens_per_replica > 0:
                    desired = max(desired, math.ceil(
                        backlog / self.backlog_tokens_per_replica))
                busy = demand > 0 or backlog > 0
        else:
            concurrency = concurrency or 0.0
            desired = math.ceil(concurrency / max(1, target))
            busy = concurrency > 0
        cur = current if current is not None else self._applied.get(key)
        if spill_saturated is not None:
            # router-saturation trigger (FleetRouter.spill_saturated is a
            # cumulative count of picks where EVERY replica was over the
            # bounded-load threshold): sustained growth means the whole
            # fleet is saturated — per-replica signals alone can plateau
            # at exactly scale_target and never cross the demand line
            last = self._spill_last.get(key)
            self._spill_last[key] = float(spill_saturated)
            if last is not None and spill_saturated > last:
                self._spill_rising[key] = self._spill_rising.get(key, 0) + 1
            else:
                self._spill_rising[key] = 0
            if self._spill_rising[key] >= self.spill_saturation_ticks:
                desired = max(desired,
                              (cur if cur is not None else desired) + 1)
                busy = True
                # one replica per sustained-saturation window: the next
                # add needs a fresh run of rising ticks
                self._spill_rising[key] = 0
        if busy:
            self._last_busy[key] = now
        scaled_to_zero = False
        if min_r == 0:
            # wake() marks the 2-tuple service key; a tier consults both
            idle_since = max(self._last_busy.get(key, 0.0),
                             self._last_busy.get(key[:2], 0.0))
            if (not busy and now - idle_since > self.idle_grace
                    and not _mid_canary(isvc)):
                # a live canary split is never collapsed to zero — the
                # gate could then never accumulate its min_requests
                desired, scaled_to_zero = 0, True
            else:
                desired = max(1, desired)
        desired = max(min_r, min(max_r, desired))
        if cur is not None and desired < cur and not scaled_to_zero:
            if _mid_canary(isvc):
                # never shrink mid-canary; restart the low-signal clock
                self._low_since.pop(key, None)
                desired = cur
            else:
                low_since = self._low_since.setdefault(key, now)
                if now - low_since < self.idle_grace:
                    desired = cur          # hold until the window elapses
        else:
            self._low_since.pop(key, None)
        self._applied[key] = desired
        return desired


class CanaryGate:
    """SLO gate for an old/new-revision traffic split: the data plane
    reports each canary-revision outcome via ``observe``; ``decide``
    answers None (keep splitting), "promote" (error rate and latency
    within SLO over at least ``min_requests``) or "rollback" (error
    budget burned — decided the moment the burn is provable, without
    waiting for min_requests). The ServingTicker enacts the verdict
    through ``ServingController.promote`` / ``rollback``."""

    def __init__(self, max_error_rate: float = 0.02,
                 max_p95_latency_s: float = 0.0, min_requests: int = 20):
        self.max_error_rate = float(max_error_rate)
        self.max_p95_latency_s = float(max_p95_latency_s)
        self.min_requests = int(min_requests)
        self.requests = 0
        self.errors = 0
        # log-bucketed histogram (obs/histogram.py), NOT a raw list: a
        # long-lived canary split observes every request, and an
        # unbounded list grew without limit for the life of the gate.
        # O(buckets) memory at any observation count; p95 reads as the
        # holding bucket's upper bound — conservative (never understates
        # the latency). The SLO threshold itself is added as a bucket
        # bound, so the decision is EXACT at the boundary: a true p95
        # at or under the threshold can never read as over it through
        # bucket rounding (which would roll back a healthy canary).
        from kubeflow_tpu.obs.histogram import DEFAULT_BUCKETS

        bounds = set(DEFAULT_BUCKETS)
        if self.max_p95_latency_s > 0:
            bounds.add(self.max_p95_latency_s)
        self._latency_hist = Histogram(buckets=sorted(bounds))
        self._lock = threading.Lock()

    def observe(self, ok: bool, latency_s: float = 0.0) -> None:
        with self._lock:
            self.requests += 1
            if not ok:
                self.errors += 1
            else:
                self._latency_hist.observe(float(latency_s))

    def p95_latency(self) -> float:
        return self._latency_hist.percentile(0.95)

    def decide(self) -> Optional[str]:
        with self._lock:
            n, errors = self.requests, self.errors
        if n and errors / n > self.max_error_rate and (
                # the budget is provably burned once even an all-ok
                # remainder of the min_requests window couldn't recover
                n >= self.min_requests
                or errors > self.max_error_rate * self.min_requests):
            return "rollback"
        if n < self.min_requests:
            return None
        if self.max_p95_latency_s > 0 and (
                self.p95_latency() > self.max_p95_latency_s):
            return "rollback"
        return "promote"
