"""Serving layer — KServe-equivalent model serving (SURVEY.md §2.4)."""

from kubeflow_tpu.serving.controller import (
    Autoscaler, CanaryGate, RuntimeRegistry, ServingController,
    ServingTicker,
)
from kubeflow_tpu.serving.jax_model import JAXModel, LLMModel
from kubeflow_tpu.serving.llm import GenRequest, LLMEngine, SamplingParams
from kubeflow_tpu.serving.model import (
    Model, ModelMissing, ModelNotReady, ModelRepository,
)
from kubeflow_tpu.serving.protocol import (
    InferRequest, InferResponse, InferTensor,
)
from kubeflow_tpu.serving.agents import BatchingModel, LoggingModel, ModelPuller
from kubeflow_tpu.serving.paged_kv import RadixPrefixCache
from kubeflow_tpu.serving.router import (
    FleetRouter, GraphRouter, HashRing, TrafficSplitter, radix_block_key,
)
from kubeflow_tpu.serving.scheduler import SchedulerConfig, StepScheduler
from kubeflow_tpu.serving.server import InferenceClient, ModelServer
from kubeflow_tpu.serving.v2_socket import V2SocketClient, V2SocketServer
from kubeflow_tpu.serving.storage import download
from kubeflow_tpu.serving.types import (
    CanarySLO, ComponentSpec, GraphNode, GraphNodeType, GraphStep,
    InferenceGraph, InferenceService, ModelFormat, PredictorSpec,
    ServingRuntime, TrainedModel,
)

__all__ = [
    "Autoscaler", "BatchingModel", "CanaryGate", "CanarySLO",
    "ComponentSpec", "FleetRouter", "GenRequest", "GraphNode",
    "GraphNodeType", "HashRing", "LoggingModel", "ModelPuller",
    "GraphRouter", "GraphStep", "InferRequest", "InferResponse",
    "InferTensor", "InferenceClient", "InferenceGraph", "InferenceService",
    "JAXModel", "LLMEngine", "LLMModel", "Model", "ModelFormat",
    "ModelMissing", "ModelNotReady", "ModelRepository", "ModelServer",
    "PredictorSpec", "RadixPrefixCache", "RuntimeRegistry", "SamplingParams",
    "SchedulerConfig", "ServingController", "ServingRuntime", "ServingTicker",
    "StepScheduler", "TrafficSplitter", "TrainedModel", "V2SocketClient",
    "V2SocketServer", "download", "radix_block_key",
]
