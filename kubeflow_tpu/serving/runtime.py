"""Predictor runtime entrypoint — env contract -> storage init -> server.

Parity: SURVEY.md §2.4 — the reference's predictor container runs
`kserve.ModelServer` after a storage-initializer initContainer has
materialized `storageUri` at /mnt/models ([U] kserve:pkg/webhook storage
initializer injection + python/kserve model server main). Here the same
contract is one module:

- the ISVC controller stamps predictor pods with KFT_STORAGE_URI /
  KFT_MODEL_DIR / KFT_MODEL_FORMAT / KFT_BIND and an init step running
  ``python -m kubeflow_tpu.serving.runtime --init-only`` (the
  initContainer role);
- ``python -m kubeflow_tpu.serving.runtime`` is the container command:
  builds the model for the declared format and serves V1+V2 HTTP.

Env contract (all optional except the uri for real weights):
  KFT_MODEL_NAME    served name              (default "model")
  KFT_MODEL_FORMAT  "llama" | "jax"          (default "llama")
  KFT_STORAGE_URI   file:// pvc:// http(s):// hf://
  KFT_MODEL_DIR     materialization dir      (default /mnt/models)
  KFT_BIND          host:port to serve on    (default 127.0.0.1:8080)
  KFT_DTYPE         "bfloat16" | "float32"   (default bfloat16)
  KFT_MAX_BATCH / KFT_MAX_SEQ    engine sizing
  KFT_MESH          e.g. "tensor=4": shard params + KV pool over the
                    pod's chips (distributed serving; same topology-env
                    contract as training rendezvous)
  KFT_PREFILL_QUOTA          step-scheduler prefill token quota (0 = auto:
                             the largest prefill bucket)
  KFT_INTERLEAVE_PREFILL     "0" disables chunked-prefill interleaving
                             (legacy convoy admission)
  KFT_ADAPTIVE_DECODE_CHUNK  "0" disables decode-chunk trimming under
                             queue pressure
  KFT_RADIX_CACHE            "0" disables radix prefix-cache sharing
  KFT_SPEC_DECODE            "1" enables speculative decoding (draft +
                             one batched verify step; greedy outputs
                             token-identical to plain decode)
  KFT_SPEC_K                 max draft tokens per verify step (default 4)
  KFT_SPEC_DRAFTER           drafter name (default "ngram" =
                             prompt-lookup, zero extra weights)
  KFT_QUANT_KV               paged-KV pool storage dtype: "int8" or
                             "fp8_e4m3" (unset/"none" = unquantized)
  KFT_QUANT_WEIGHTS          weight dtype: "int8" (unset/"none" =
                             unquantized; quantized once at load,
                             per-output-channel scales)
  KFT_QUANT_EXACT_PARITY     "1" forces BOTH quant paths off — the
                             engine program is bitwise-identical to an
                             unconfigured one (the parity escape hatch)
  KFT_DEPOT                  executable depot (dir path or operator http
                             URL, parallel/depot.py): load() acquires the
                             steady-state decode program depot-first, so
                             a fleet scale-up replica deserializes what
                             replica #1 published instead of compiling
  KFT_DEPOT_CACHE            pod-local depot cache dir — the warm pool
                             pre-fetches entries into it at claim time
                             (the ISVC controller suffixes it per pod)
  KFT_DEPOT_TOKEN            http depot fence (operator-injected)
  KFT_TIER                   disaggregated serving: "prefill" | "decode"
                             (unset = co-located). Scopes the depot key
                             to the tier's hot program, stamps
                             tier="..." on /metrics, and attaches the
                             KV-migration runtime (serving/disagg.py)
                             behind the /v2/models/{m}/disagg routes
  KFT_KV_BIND                decode tier: host:port for the paged-KV
                             migration listener (default 127.0.0.1:0;
                             the ACTUAL bound port rides stats()
                             ["disagg"]["kv_addr"] for ephemeral binds)
"""

from __future__ import annotations

import argparse
import os
import threading
from typing import Mapping, Optional

from kubeflow_tpu.serving import storage
from kubeflow_tpu.serving.jax_model import LLMModel
from kubeflow_tpu.serving.model import Model, ModelRepository
from kubeflow_tpu.serving.server import ModelServer


def init_storage(env: Mapping[str, str]) -> Optional[str]:
    """The storage-initializer step: materialize KFT_STORAGE_URI into
    KFT_MODEL_DIR and return the local path (None when no uri is set).
    Idempotent — safe to run in both the init step and the server."""
    uri = env.get("KFT_STORAGE_URI") or ""
    if not uri:
        return env.get("KFT_MODEL_DIR") or None
    dest = env.get("KFT_MODEL_DIR") or "/mnt/models"
    return storage.download(uri, dest)


def scheduler_from_env(env: Mapping[str, str]):
    """KFT_PREFILL_QUOTA / KFT_INTERLEAVE_PREFILL /
    KFT_ADAPTIVE_DECODE_CHUNK / KFT_RADIX_CACHE / KFT_SPEC_DECODE /
    KFT_SPEC_K / KFT_SPEC_DRAFTER -> SchedulerConfig (None when nothing
    is set, keeping the engine defaults)."""
    from kubeflow_tpu.serving.scheduler import SchedulerConfig

    keys = ("KFT_PREFILL_QUOTA", "KFT_INTERLEAVE_PREFILL",
            "KFT_ADAPTIVE_DECODE_CHUNK", "KFT_RADIX_CACHE",
            "KFT_SPEC_DECODE", "KFT_SPEC_K", "KFT_SPEC_DRAFTER")
    if not any(env.get(k) for k in keys):
        return None
    on = lambda k: env.get(k, "1") not in ("0", "false", "no", "")
    defaults = SchedulerConfig()
    return SchedulerConfig(
        prefill_tokens_per_step=int(env.get("KFT_PREFILL_QUOTA", "0") or 0),
        interleave_prefill=on("KFT_INTERLEAVE_PREFILL"),
        adaptive_decode_chunk=on("KFT_ADAPTIVE_DECODE_CHUNK"),
        radix_cache=on("KFT_RADIX_CACHE"),
        # spec decode is opt-in: unset reads as the config default (off)
        spec_decode=env.get("KFT_SPEC_DECODE", "") not in
            ("", "0", "false", "no"),
        spec_k=int(env.get("KFT_SPEC_K", "") or defaults.spec_k),
        spec_drafter=env.get("KFT_SPEC_DRAFTER", "")
            or defaults.spec_drafter)


def quant_from_env(env: Mapping[str, str]):
    """KFT_QUANT_KV / KFT_QUANT_WEIGHTS / KFT_QUANT_EXACT_PARITY ->
    QuantConfig (None when nothing is set — the engine then serves
    unquantized with a program bitwise-identical to pre-quant builds)."""
    from kubeflow_tpu.serving.scheduler import QuantConfig

    keys = ("KFT_QUANT_KV", "KFT_QUANT_WEIGHTS", "KFT_QUANT_EXACT_PARITY")
    if not any(env.get(k) for k in keys):
        return None
    return QuantConfig(
        kv_dtype=env.get("KFT_QUANT_KV", "") or "none",
        weight_dtype=env.get("KFT_QUANT_WEIGHTS", "") or "none",
        exact_parity=env.get("KFT_QUANT_EXACT_PARITY", "") not in
            ("", "0", "false", "no"))


def build_model_from_env(env: Mapping[str, str]) -> Model:
    """Construct the Model the env contract describes (runtime selection
    having already happened in the ISVC controller)."""
    import jax.numpy as jnp

    name = env.get("KFT_MODEL_NAME", "model")
    fmt = (env.get("KFT_MODEL_FORMAT") or "llama").lower()
    model_dir = init_storage(env)
    if fmt in ("llama", "llm", "huggingface"):
        if not model_dir:
            raise ValueError("llama format needs KFT_STORAGE_URI/KFT_MODEL_DIR")
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                 "float16": jnp.float16}[env.get("KFT_DTYPE", "bfloat16")]
        # KFT_MESH (e.g. "tensor=4") turns on sharded serving: params and
        # the KV pool distribute over the pod's chips, same topology-env
        # contract the training rendezvous uses
        mesh = None
        if env.get("KFT_MESH"):
            from kubeflow_tpu.parallel import mesh_from_topology_env

            mesh = mesh_from_topology_env(dict(env))
        return LLMModel.from_pretrained(
            name, model_dir, dtype=dtype, mesh=mesh,
            max_batch=int(env.get("KFT_MAX_BATCH", 8)),
            max_seq=int(env.get("KFT_MAX_SEQ", 1024)),
            scheduler=scheduler_from_env(env),
            quant=quant_from_env(env),
            tier=env.get("KFT_TIER", ""))
    raise ValueError(f"unsupported KFT_MODEL_FORMAT {fmt!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow_tpu.serving.runtime")
    ap.add_argument("--init-only", action="store_true",
                    help="run the storage-initializer step and exit")
    args = ap.parse_args(argv)
    env = os.environ
    if args.init_only:
        path = init_storage(env)
        print(f"storage-initializer: materialized {path}", flush=True)
        return 0
    repo = ModelRepository()
    if env.get("KFT_STORAGE_URI") or not env.get("KFT_MODELS_CONFIG_DIR"):
        model = build_model_from_env(env)
        repo.register(model)           # load()s eagerly: warm before ready
        tier = env.get("KFT_TIER", "")
        if tier and getattr(model, "engine", None) is not None:
            # disaggregated tier replica: attach the KV-migration runtime
            # (serving/disagg.py) the server's /disagg routes dispatch to;
            # decode pods also start the paged-KV listener
            from kubeflow_tpu.serving.disagg import TierRuntime

            model.disagg = TierRuntime(model.engine, tier, model=model)
            if tier == "decode":
                kv_addr = model.disagg.attach_receiver(
                    env.get("KFT_KV_BIND") or "127.0.0.1:0")
                print(f"disagg decode kv listener at "
                      f"{kv_addr[0]}:{kv_addr[1]}", flush=True)
    # multi-model mode (the kserve agent/TrainedModel role): watch a config
    # directory of {"name","storage_uri",...} descriptors and hot load /
    # unload models into the same server
    watch_dir = env.get("KFT_MODELS_CONFIG_DIR")
    if watch_dir:
        from kubeflow_tpu.serving.agents import ModelPuller

        def factory(desc, local):
            sub = {**env, "KFT_MODEL_NAME": desc["name"],
                   "KFT_MODEL_DIR": local, "KFT_STORAGE_URI": "",
                   **{k: str(v) for k, v in desc.get("env", {}).items()}}
            return build_model_from_env(sub)

        puller = ModelPuller(
            repo, watch_dir, factory,
            model_dir=env.get("KFT_MODEL_DIR", "/mnt/models"))
        puller.sync()
        puller.watch(period=float(env.get("KFT_MODELS_SYNC_PERIOD", "2.0")))
        print(f"model-puller watching {watch_dir}", flush=True)
    bind = env.get("KFT_BIND", "127.0.0.1:8080")
    host, _, port = bind.rpartition(":")
    server = ModelServer(repo, host=host or "127.0.0.1", port=int(port))
    server.start()
    print(f"serving {repo.names()} at {server.url}", flush=True)
    # optional binary data plane (the gRPC-port role; see serving/v2_socket)
    v2_bind = env.get("KFT_V2_SOCKET_BIND")
    if v2_bind:
        from kubeflow_tpu.serving.v2_socket import V2SocketServer

        vhost, _, vport = v2_bind.rpartition(":")
        v2 = V2SocketServer(repo, host=vhost or "127.0.0.1",
                            port=int(vport)).start()
        print(f"v2-socket at {v2.address[0]}:{v2.address[1]}", flush=True)
    threading.Event().wait()           # serve until killed
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
