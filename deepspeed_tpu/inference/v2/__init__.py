"""FastGen-equivalent inference v2 (reference ``deepspeed/inference/v2``):
ragged continuous batching over a paged KV cache."""

from .config_v2 import (CacheTelemetryConfig, DiffusionConfig, DSStateManagerConfig, HostTierConfig,
                        ModulesConfig, PrefixCacheConfig, RaggedInferenceEngineConfig,
                        SpeculativeConfig)
from .engine_v2 import InferenceEngineV2
from .engine_factory import build_engine, build_model_engine
from .scheduling_utils import SchedulingError, SchedulingResult
from .scheduler import DynamicSplitFuseScheduler
from .inference_utils import (ActivationType, DtypeEnum, NormTypeEnum, ceil_div,
                              elem_size, is_gated)
from .sampling import SamplingParams
from .speculative import Drafter, DraftModelDrafter, NgramDrafter, build_drafter
