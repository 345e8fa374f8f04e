"""LegoDiffusion core: micro-serving of diffusion workflows in JAX.

Public surface:

* DSL      -- Model, Workflow, compose
* Compiler -- GraphCompiler, optimization passes
* Runtime  -- Coordinator, ServingSystem
* Policy   -- Scheduler, AdmissionController, Autoscaler
"""

from repro.core.admission import AdmissionController, critical_path_seconds
from repro.core.autoscaler import Autoscaler, AutoscalerConfig, ScaleAction
from repro.core.compile_cache import compile_cache_dir, use_compile_cache
from repro.core.compiler import CompiledGraph, CompileError, GraphCompiler, Pass
from repro.core.datastore import DataEngine, FetchFuture
from repro.core.executor import Executor, LocalBackend, OutOfMemory, ShardedBackend
from repro.core.faults import (
    DataFetchError,
    FaultPlane,
    InjectedFault,
    RetryPolicy,
    TransientBackendError,
)
from repro.core.mesh import MeshManager, sharded_exec_enabled
from repro.core.model import Model, ModelCost
from repro.core.passes import (
    ApproximateCachingPass,
    AsyncLoRAPass,
    DeadCodeEliminationPass,
    InlineTrivialPass,
    JitCompilePass,
    SegmentFusionPass,
    default_passes,
    segment_fusion_enabled,
)
from repro.core.profiles import (
    GPU_H800,
    TPU_V5E,
    HardwareSpec,
    LatencyProfile,
    ProfileStore,
    hardware_for,
)
from repro.core.registry import ServingSystem, WorkflowRegistry
from repro.core.runtime import Coordinator, Request, RequestNode
from repro.core.scheduler import ScheduledBatch, Scheduler
from repro.core.supervisor import ProcBackend, ProcConfig, Supervisor, processes_available
from repro.core.telemetry import (
    FoldCacheEviction,
    MetricsRegistry,
    TelemetryEvent,
    configure as configure_telemetry,
    default_registry,
    telemetry_enabled,
    validate_chrome_trace,
)
from repro.core.tracing import COORDINATOR_PID, NULL_TRACER, NullTracer, Tracer, make_tracer
from repro.core.transport import (
    ChecksumError,
    FrameChannel,
    StagedInput,
    TransportError,
    WorkerDied,
)
from repro.core.types import (
    DataRef,
    Image,
    Port,
    TensorType,
    ValueRef,
    WorkflowTypeError,
)
from repro.core.workflow import Workflow, WorkflowContext, WorkflowNode, WorkflowTemplate, compose
from repro.core.group import CoordinatorGroup, cluster_workflows
