"""dlrm_flexflow_tpu — a TPU-native distributed DNN training framework with
the capabilities of FlexFlow/DLRM-FlexFlow (reference: TravisDai/DLRM-FlexFlow).

The reference is a Legion/CUDA task-based MPMD system that auto-discovers
parallelization strategies in the SOAP search space. This framework provides
the same surface — FFModel graph builder, per-op parallelization strategies,
MCMC auto-parallelizer with an execution simulator, DLRM/CNN/NMT model zoo,
PyTorch-golden operator tests — re-designed for TPU: JAX/XLA/Pallas compute,
GSPMD sharding over `jax.sharding.Mesh`, ICI/DCN collectives instead of
Legion DMA/GASNet.
"""

from .config import FFConfig
from .core.model import AnomalyError, FFModel
from .utils.checkpoint import (CheckpointManager, restore_checkpoint,
                               save_checkpoint)
from .utils.compile_cache import use_compile_cache
from .utils.delta import DeltaPublisher
from .core.optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .core.initializers import (ConstantInitializer, GlorotUniform,
                                NormInitializer, UniformInitializer,
                                ZeroInitializer)
from .core.tensor import Tensor
from .parallel.mesh import make_mesh
from .parallel.pconfig import ParallelConfig
from .parallel.distributed import MeshDegraded, MeshReturned
from .utils.watchdog import Deadline, StallReport, WorkerStalled
from .serve import (AutoscaleConfig, Autoscaler, DeadlineExceeded,
                    EmbeddingShardSet, Fleet, FleetRouter,
                    FleetUnavailable, InferenceEngine, Overloaded,
                    Prediction, ReplicaDown, RouterConfig, ServeConfig,
                    ShardDown, ShardTierConfig, ShardTierUnavailable,
                    SnapshotWatcher)

__version__ = "0.1.0"

__all__ = [
    "FFConfig", "FFModel", "Tensor", "AnomalyError",
    "CheckpointManager", "save_checkpoint", "restore_checkpoint",
    "use_compile_cache",
    "DeltaPublisher",
    "Optimizer", "SGDOptimizer", "AdamOptimizer",
    "GlorotUniform", "ZeroInitializer", "UniformInitializer",
    "NormInitializer", "ConstantInitializer",
    "ParallelConfig", "make_mesh",
    "MeshDegraded", "MeshReturned", "WorkerStalled", "StallReport",
    "Deadline",
    "InferenceEngine", "ServeConfig", "Prediction", "Overloaded",
    "DeadlineExceeded", "SnapshotWatcher",
    "Fleet", "FleetRouter", "FleetUnavailable", "RouterConfig",
    "ReplicaDown", "Autoscaler", "AutoscaleConfig",
    "EmbeddingShardSet", "ShardTierConfig", "ShardDown",
    "ShardTierUnavailable",
]
