"""Concrete module implementations (reference
``inference/v2/modules/implementations/``). Importing this package registers
every implementation with its interface's registry."""

from .attention import DenseBlockedAttention, PallasPagedAttention
from .embedding import RaggedEmbedding
from .linear import BlasFPLinear, Int8BlockwiseLinear
from .moe import GroupedGemmMoE
from .norm import FusedPreNorm
from .unembed import LastTokenUnembed

__all__ = [
    "DenseBlockedAttention", "PallasPagedAttention", "RaggedEmbedding",
    "BlasFPLinear", "Int8BlockwiseLinear", "GroupedGemmMoE", "FusedPreNorm",
    "LastTokenUnembed",
]
