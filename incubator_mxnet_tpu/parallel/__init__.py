"""``mx.parallel`` — TPU-native parallelism layer (SPMD over device meshes).

Replaces the reference's KVStore comm trees / NCCL / ps-lite stack
(SURVEY.md §2.5, §5.8) with jax.sharding + XLA collectives.
"""
from .mesh import (Mesh, NamedSharding, P, PartitionSpec, global_devices,
                   make_mesh, replicated, shard_along, spans_processes)
from .train_step import DynamicLossScale, FunctionalOptimizer, TrainStep, make_train_step
from .flash_attention import flash_attention
from .delta_rule import kda
from .pipeline import pipeline_apply, spmd_pipeline, stack_stage_params
from .moe import load_balancing_loss, moe_ffn, moe_ffn_sharded
from .checkpoint import (CheckpointError, CheckpointCorruptError,
                         CheckpointManager, CheckpointTopologyError,
                         install_preemption_hook, request_checkpoint,
                         uninstall_preemption_hook)
from .supervisor import (DivergenceDetector, DivergenceError, HealthLedger,
                         HeartbeatEmitter, Supervisor, SupervisorConfig,
                         SupervisorError, run_supervised)
from .param_service import (ParamService, ServiceClient, ServiceUpdater,
                            StalenessClock, StalenessTimeout, SyncPolicy)
from . import distributed

__all__ = ["Mesh", "NamedSharding", "P", "PartitionSpec", "make_mesh",
           "replicated", "shard_along", "global_devices", "spans_processes",
           "DynamicLossScale", "FunctionalOptimizer", "TrainStep",
           "make_train_step", "flash_attention", "kda", "pipeline_apply",
           "spmd_pipeline", "stack_stage_params", "load_balancing_loss",
           "moe_ffn", "moe_ffn_sharded", "CheckpointError",
           "CheckpointCorruptError", "CheckpointTopologyError",
           "CheckpointManager", "install_preemption_hook",
           "uninstall_preemption_hook", "request_checkpoint",
           "DivergenceDetector", "DivergenceError", "HealthLedger",
           "HeartbeatEmitter", "Supervisor", "SupervisorConfig",
           "SupervisorError", "run_supervised",
           "ParamService", "ServiceClient", "ServiceUpdater",
           "StalenessClock", "StalenessTimeout", "SyncPolicy",
           "distributed"]
