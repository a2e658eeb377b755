"""DeepSpeed-compatible JSON config system.

Analog of the reference ``deepspeed/runtime/config.py`` (1,035 LoC):
``DeepSpeedConfig`` parses a JSON file or dict into ~30 typed sub-configs and
resolves the batch-size triad ``train_batch = micro_batch × gas × dp_world``
with auto-fill (reference ``_configure_train_batch_size``/
``_batch_assertion``). Additions for TPU: a ``tpu`` section describing mesh
axes (data/model/pipe/seq/expert), rematerialization policy and buffer
donation — the knobs that replace CUDA streams/buckets.
"""

import os
import json
import copy
from typing import Literal, Optional, List, Union, Any

from pydantic import Field, model_validator

from .config_utils import DeepSpeedConfigModel, get_scalar_param, dict_raise_error_on_duplicate_keys
from .constants import *  # noqa: F401,F403
from .constants import (TRAIN_BATCH_SIZE, TRAIN_MICRO_BATCH_SIZE_PER_GPU, GRADIENT_ACCUMULATION_STEPS, OPTIMIZER,
                        SCHEDULER, TYPE, OPTIMIZER_PARAMS, SCHEDULER_PARAMS, FP16, BFLOAT16, BFLOAT16_OLD,
                        ZERO_OPTIMIZATION, GRADIENT_CLIPPING, GRADIENT_CLIPPING_DEFAULT, STEPS_PER_PRINT,
                        STEPS_PER_PRINT_DEFAULT, WALL_CLOCK_BREAKDOWN, WALL_CLOCK_BREAKDOWN_DEFAULT, MEMORY_BREAKDOWN,
                        MEMORY_BREAKDOWN_DEFAULT, PRESCALE_GRADIENTS, PRESCALE_GRADIENTS_DEFAULT,
                        GRADIENT_PREDIVIDE_FACTOR, GRADIENT_PREDIVIDE_FACTOR_DEFAULT, SPARSE_GRADIENTS,
                        SPARSE_GRADIENTS_DEFAULT, COMMUNICATION_DATA_TYPE, COMMUNICATION_DATA_TYPE_DEFAULT,
                        SEQ_PARALLEL_COMMUNICATION_DATA_TYPE, SEQ_PARALLEL_COMMUNICATION_DATA_TYPE_DEFAULT,
                        DISABLE_ALLGATHER, DISABLE_ALLGATHER_DEFAULT, DUMP_STATE, DUMP_STATE_DEFAULT,
                        DATALOADER_DROP_LAST, DATALOADER_DROP_LAST_DEFAULT, CHECKPOINT_TAG_VALIDATION,
                        CHECKPOINT_TAG_VALIDATION_DEFAULT, CHECKPOINT_TAG_VALIDATION_MODES, CHECKPOINT,
                        LOAD_UNIVERSAL_CHECKPOINT, LOAD_UNIVERSAL_CHECKPOINT_DEFAULT, GRAD_ACCUM_DTYPE, TPU, PIPELINE,
                        ACTIVATION_CHECKPOINTING, FLOPS_PROFILER, COMMS_LOGGER, ELASTICITY, AUTOTUNING,
                        TRAIN_BATCH_SIZE_DEFAULT, TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT,
                        GRADIENT_ACCUMULATION_STEPS_DEFAULT)
from .zero.config import DeepSpeedZeroConfig
from ..monitor.config import get_monitor_config, DeepSpeedMonitorConfig
from ..parallel.mesh import MeshConfig
from ..utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


class FP16Config(DeepSpeedConfigModel):
    """``fp16`` block (reference fp16 getters config.py:125-220). On TPU fp16
    matmuls are emulated; bf16 needs no loss scaling and is preferred."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0
    fp16_master_weights_and_grads: bool = False


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    immediate_grad_update: bool = False


class OptimizerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: dict = {}
    legacy_fusion: bool = False


class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: dict = {}


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """``activation_checkpointing`` block (reference
    ``runtime/activation_checkpointing/config.py``). On TPU this configures
    ``jax.checkpoint`` (remat) policies instead of manual tensor stashing:
    ``partition_activations`` maps to saving activations sharded over the model
    axis, ``cpu_checkpointing`` to host offload of residuals."""
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: named jax.checkpoint policy, e.g. 'nothing_saveable',
    # 'dots_saveable', 'dots_with_no_batch_dims_saveable', 'checkpoint_dots'
    remat_policy: str = "nothing_saveable"


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = []


class CommsConfig(DeepSpeedConfigModel):
    comms_logger_enabled: bool = False
    comms_logger: CommsLoggerConfig = CommsLoggerConfig()


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: dict = {}
    # TPU-native: use orbax/tensorstore OCDBT layout under the hood
    async_save: bool = False
    # --- resilience plane (runtime/resilience/) ---
    # auto-save every N engine steps (0 = off); nebula.persistent_time_interval
    # adds the wall-clock cadence when the nebula block is enabled
    save_interval_steps: int = 0
    # retention GC: keep the newest N committed tags (0 = keep everything);
    # mirrored from nebula.num_of_version_in_retention when nebula is on
    num_of_version_in_retention: int = 0
    # archival knob: tags whose step is a multiple of N survive retention
    keep_every_n_steps: int = 0
    # trap SIGTERM -> final checkpoint at the next step boundary -> clean
    # exit (auto-enabled when nebula provides a persistent_storage_path)
    preemption_save: bool = False
    # default directory for auto/preemption saves (nebula's
    # persistent_storage_path wins when set); engine.set_checkpoint_dir()
    # overrides at runtime
    auto_save_dir: Optional[str] = None
    # record per-file sha256 in the commit manifest (deep verification of
    # bit-rot). Costs a full read-back of the payload per save — turn off for
    # huge checkpoints where the size-only manifest check is enough
    manifest_digests: bool = True
    # elastic warm remesh (elasticity/remesh.py): every committed save also
    # publishes a host-RAM universal-layout snapshot, so a topology-change
    # restart under run_resilient(warm_remesh=True) re-shards from memory
    # instead of reading the checkpoint payload back. Costs one fp32 copy of
    # params + both Adam moments in host RAM while armed.
    remesh_snapshot: bool = False


class PipelineConfig(DeepSpeedConfigModel):
    """``pipeline`` block (reference engine pipeline knobs)."""
    stages: Union[int, str] = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    # '1f1b' (reference TrainSchedule schedule.py:189 — bounded live
    # activations, composes with TP) | 'gpipe' (fill-drain via jax.grad)
    schedule: Literal["1f1b", "gpipe"] = "1f1b"


class ProfilerTraceConfig(DeepSpeedConfigModel):
    """``tpu.profiler_trace`` block — typed so key typos and bad values fail
    at ``initialize()``, not mid-training (same pattern as the fp16 block).
    Enabled by presence: an empty block stays off."""
    trace_dir: str = "/tmp/dstpu_trace"
    start_step: int = Field(0, ge=0)
    num_steps: int = Field(1, ge=1)
    enabled: bool = False

    @model_validator(mode="after")
    def enable_when_configured(self):
        # the base model tolerates unknown keys (reference parity) — but a
        # typo here silently traces the wrong step; warn loudly
        unknown = set(self.model_fields_set) - set(type(self).model_fields)
        if unknown:
            from ..utils.logging import logger

            logger.warning(f"profiler_trace: unknown keys {sorted(unknown)} ignored "
                           f"(valid: trace_dir, start_step, num_steps, enabled)")
        # {"trace_dir": ...} or {"start_step": N} implies the user wants it
        if self.model_fields_set and "enabled" not in self.model_fields_set:
            self.enabled = True
        return self


class TPUConfig(DeepSpeedConfigModel):
    """TPU-native section: the mesh is the single source of truth for every
    parallel dimension (SURVEY.md §7 design stance)."""
    mesh: dict = {}
    # donate param/opt-state buffers into the jitted step (in-place update)
    donate_buffers: bool = True
    # jit the whole train step (fused fwd+bwd+step) vs eager-style 3 calls
    fused_train_step: bool = True
    # matmul precision: 'default' | 'high' | 'highest' (jax.default_matmul_precision)
    matmul_precision: str = "default"
    # Pallas fused Adam(W) step (reference csrc/adam/multi_tensor_adam.cu):
    # one HBM pass over (grad, param, m, v) with overflow gate + clip folded
    # in. Measured on v5e: XLA's fusion of the optax chain already sits near
    # the HBM roofline (~40ms for 748M params), so the kernel is off by
    # default ('auto' == 'never' today); 'always' forces it (interpret mode
    # off-TPU) for experimentation and tests.
    pallas_fused_adam: Literal["auto", "always", "never"] = "auto"
    # compile-only validation mode: state stays abstract (ShapeDtypeStructs
    # with shardings — nothing materializes), so pod-scale configs (7B/70B on
    # a 128-device mesh) can be AOT-lowered/compiled on hosts that could
    # never hold the weights. train_batch() is unusable in this mode; use
    # aot_lower_train_step()
    abstract_init: bool = False
    # device trace capture (the TPU analog of the reference's torch-profiler
    # hooks): captures a perfetto/XPlane trace of global steps
    # [start_step, start_step+num_steps) via jax.profiler — the artifact the
    # "profile, iterate" loop reads in xprof/perfetto. A window ending at the
    # final step is flushed by engine.destroy();
    # engine.start_device_trace()/stop_device_trace() drive it manually.
    profiler_trace: "ProfilerTraceConfig" = {}

    def mesh_config(self) -> MeshConfig:
        known = {k: v for k, v in self.mesh.items() if k in ("data", "model", "pipe", "seq", "expert")}
        return MeshConfig(**known)


class PLDConfig(DeepSpeedConfigModel):
    """``progressive_layer_drop`` block (reference
    ``runtime/progressive_layer_drop.py``; constants PLD_THETA/PLD_GAMMA)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class HybridEngineConfig(DeepSpeedConfigModel):
    """``hybrid_engine`` block (reference ``runtime/hybrid_engine.py`` config:
    enable_hybrid_engine, inference_tp_size, release_inference_cache,
    pin_parameters, tp_gather_partition_size)."""
    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = [2, 4, 6]
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch_size: bool = True


class DeepSpeedConfig:
    """Aggregate typed view over the JSON config (reference class of the same
    name, ``runtime/config.py`` after the getters at :94-:520)."""

    def __init__(self, config: Union[str, dict], mesh=None, mpu=None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"Expected a string path to an existing deepspeed config, got: {config}")
            with open(config, "r") as f:
                self._param_dict = json.load(f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            self._param_dict = copy.deepcopy(config)
        else:
            raise DeepSpeedConfigError(
                f"Expected a string path to a json file or a dict, got: {config} ({type(config)})")

        pd = self._param_dict
        self.mesh = mesh  # resolved later by the engine if None

        # --- precision ---
        self.fp16_config = FP16Config(**pd.get(FP16, {}))
        bf16_dict = pd.get(BFLOAT16, pd.get(BFLOAT16_OLD, {}))
        self.bfloat16_config = BF16Config(**bf16_dict)
        self.fp16_enabled = self.fp16_config.enabled
        self.bfloat16_enabled = self.bfloat16_config.enabled
        if self.fp16_enabled and self.bfloat16_enabled:
            raise DeepSpeedConfigError("fp16 and bf16 modes cannot be simultaneously enabled")
        self.loss_scale = self.fp16_config.loss_scale
        self.initial_dynamic_scale = 2**self.fp16_config.initial_scale_power
        self.dynamic_loss_scale_args = {
            "init_scale": 2**self.fp16_config.initial_scale_power,
            "scale_window": self.fp16_config.loss_scale_window,
            "min_scale": self.fp16_config.min_loss_scale,
            "delayed_shift": self.fp16_config.hysteresis,
        }

        # --- optimizer / scheduler ---
        opt_dict = pd.get(OPTIMIZER, None)
        self.optimizer_name = (opt_dict[TYPE].lower() if opt_dict and TYPE in opt_dict else None)
        self.optimizer_params = opt_dict.get(OPTIMIZER_PARAMS, {}) if opt_dict else None
        self.optimizer_legacy_fusion = opt_dict.get("legacy_fusion", False) if opt_dict else False
        sched_dict = pd.get(SCHEDULER, None)
        self.scheduler_name = sched_dict[TYPE] if sched_dict and TYPE in sched_dict else None
        self.scheduler_params = sched_dict.get(SCHEDULER_PARAMS, {}) if sched_dict else None

        # --- zero ---
        self.zero_config = DeepSpeedZeroConfig(**pd.get(ZERO_OPTIMIZATION, {}))
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        # --- training knobs ---
        self.gradient_clipping = get_scalar_param(pd, GRADIENT_CLIPPING, GRADIENT_CLIPPING_DEFAULT)
        self.prescale_gradients = get_scalar_param(pd, PRESCALE_GRADIENTS, PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = get_scalar_param(pd, GRADIENT_PREDIVIDE_FACTOR,
                                                          GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = get_scalar_param(pd, SPARSE_GRADIENTS, SPARSE_GRADIENTS_DEFAULT)
        # sparse attention block (reference config.py:289 get_sparse_attention):
        # raw dict; ops.sparse_attention.build_sparsity_config turns it into a
        # SparsityConfig at injection time (mode validated there)
        self.sparse_attention = pd.get("sparse_attention")
        self.steps_per_print = get_scalar_param(pd, STEPS_PER_PRINT, STEPS_PER_PRINT_DEFAULT)
        self.wall_clock_breakdown = get_scalar_param(pd, WALL_CLOCK_BREAKDOWN, WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = get_scalar_param(pd, MEMORY_BREAKDOWN, MEMORY_BREAKDOWN_DEFAULT)
        self.dump_state = get_scalar_param(pd, DUMP_STATE, DUMP_STATE_DEFAULT)
        self.disable_allgather = get_scalar_param(pd, DISABLE_ALLGATHER, DISABLE_ALLGATHER_DEFAULT)
        self.communication_data_type = get_scalar_param(pd, COMMUNICATION_DATA_TYPE, COMMUNICATION_DATA_TYPE_DEFAULT)
        self.seq_parallel_communication_data_type = get_scalar_param(pd, SEQ_PARALLEL_COMMUNICATION_DATA_TYPE,
                                                                     SEQ_PARALLEL_COMMUNICATION_DATA_TYPE_DEFAULT)
        self.dataloader_drop_last = get_scalar_param(pd, DATALOADER_DROP_LAST, DATALOADER_DROP_LAST_DEFAULT)
        self.grad_accum_dtype = get_scalar_param(pd, GRAD_ACCUM_DTYPE, None)

        # --- sub-configs ---
        self.monitor_config: DeepSpeedMonitorConfig = get_monitor_config(pd)
        self.flops_profiler_config = FlopsProfilerConfig(**pd.get(FLOPS_PROFILER, {}))
        self.activation_checkpointing_config = ActivationCheckpointingConfig(**pd.get(ACTIVATION_CHECKPOINTING, {}))
        comms_dict = pd.get(COMMS_LOGGER, {})
        self.comms_config = CommsConfig(comms_logger_enabled=bool(comms_dict.get("enabled", False)),
                                        comms_logger=CommsLoggerConfig(**comms_dict))
        from .data_pipeline.config import (DataEfficiencyConfig, CurriculumLearningConfig,
                                           get_data_pipeline_config)

        self.data_efficiency_config = DataEfficiencyConfig(**pd.get(DATA_EFFICIENCY, {}))
        # data_pipeline block: input-path perf knobs (async device prefetch)
        self.data_pipeline_config = get_data_pipeline_config(pd)
        self.curriculum_learning_config = CurriculumLearningConfig(**pd.get(CURRICULUM_LEARNING_LEGACY, {}))
        ckpt_dict = pd.get(CHECKPOINT, {})
        self.checkpoint_config = CheckpointConfig(**ckpt_dict)
        from ..nebula.config import DeepSpeedNebulaConfig

        self.nebula_config = DeepSpeedNebulaConfig.from_param_dict(pd)
        if self.nebula_config.enabled:
            # nebula's contract = training never blocks on persistence; the
            # TPU mechanism is orbax async save + the resilience plane
            # (runtime/resilience/): mirror the service knobs onto the
            # checkpoint block so retention/auto-save/preemption are live,
            # not parsed-and-dead (explicit checkpoint-block values win)
            self.checkpoint_config.async_save = True
            if self.checkpoint_config.num_of_version_in_retention == 0:
                self.checkpoint_config.num_of_version_in_retention = \
                    self.nebula_config.num_of_version_in_retention
            if self.checkpoint_config.auto_save_dir is None:
                self.checkpoint_config.auto_save_dir = self.nebula_config.persistent_storage_path
            if self.checkpoint_config.auto_save_dir:
                self.checkpoint_config.preemption_save = True
        self.checkpoint_tag_validation_enabled = self.checkpoint_config.tag_validation != "Ignore"
        self.checkpoint_tag_validation_fail = self.checkpoint_config.tag_validation == "Fail"
        self.load_universal_checkpoint = self.checkpoint_config.load_universal
        self.use_node_local_storage = self.checkpoint_config.use_node_local_storage
        self.elasticity_enabled = bool(pd.get(ELASTICITY, {}).get("enabled", False))
        self.elasticity_config = ElasticityConfig(**pd.get(ELASTICITY, {}))
        self.hybrid_engine_config = HybridEngineConfig(**pd.get("hybrid_engine", {}))
        self.pld_config = PLDConfig(**pd.get("progressive_layer_drop", {}))
        self.pipeline_config = PipelineConfig(**pd.get(PIPELINE, {})) if isinstance(pd.get(PIPELINE, {}),
                                                                                    dict) else PipelineConfig()
        self.tpu_config = TPUConfig(**pd.get(TPU, {}))
        self.autotuning_config = pd.get(AUTOTUNING, {})

        # --- batch triad (resolved against dp size later) ---
        self.train_batch_size = pd.get(TRAIN_BATCH_SIZE, TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = pd.get(TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                                                     TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = pd.get(GRADIENT_ACCUMULATION_STEPS, GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self._batch_resolved = False

    # ------------------------------------------------------------------
    def resolve_batch_config(self, dp_world_size: int):
        """Reference ``_configure_train_batch_size``: fill in the missing leg
        of train = micro × gas × dp and validate."""
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps

        if all(v is not None for v in (train, micro, gas)):
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None:
            micro = train // (dp_world_size * gas)
        elif micro is not None:
            gas = gas or 1
            train = micro * gas * dp_world_size
        elif train is not None:
            gas = 1
            micro = train // dp_world_size
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu needs to be provided")

        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas
        self._batch_assertion(dp_world_size)
        self._batch_resolved = True

    def _batch_assertion(self, dp_world_size):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        assert train > 0, f"Train batch size: {train} has to be greater than 0"
        assert micro > 0, f"Micro batch size per gpu: {micro} has to be greater than 0"
        assert gas > 0, f"Gradient accumulation steps: {gas} has to be greater than 0"
        assert train == micro * gas * dp_world_size, (
            f"Check batch related parameters. train_batch_size is not equal "
            f"to micro_batch_per_gpu * gradient_acc_step * world_size "
            f"{train} != {micro} * {gas} * {dp_world_size}")

    # ------------------------------------------------------------------
    def print(self, name="DeepSpeedConfig"):
        logger.info(f"{name}:")
        for k in sorted(vars(self)):
            if not k.startswith("_"):
                logger.info(f"  {k} {getattr(self, k)}")

    @property
    def param_dict(self):
        return self._param_dict
