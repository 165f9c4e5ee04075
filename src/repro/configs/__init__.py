"""Config registry: the 10 assigned architectures (+ the paper's own CNNs).

Importing this package registers every architecture; use
``repro.configs.get(name)`` / ``repro.configs.names()``.
"""
from repro.configs.base import (  # noqa: F401
    ArchConfig, ShapeConfig, SHAPES, LONG_CONTEXT_OK,
    cells_for, get, input_specs, names, reduced, register,
)
from repro.configs.granite_4_0_h_small import GRANITE_4_0_H_SMALL  # noqa: F401
from repro.configs.gemma3_12b import GEMMA3_12B            # noqa: F401
from repro.configs.mistral_large_123b import MISTRAL_LARGE_123B  # noqa: F401
from repro.configs.gemma2_27b import GEMMA2_27B            # noqa: F401
from repro.configs.gemma2_2b import GEMMA2_2B              # noqa: F401
from repro.configs.olmoe_1b_7b import OLMOE_1B_7B          # noqa: F401
from repro.configs.phi35_moe import PHI35_MOE              # noqa: F401
from repro.configs.musicgen_large import MUSICGEN_LARGE    # noqa: F401
from repro.configs.mamba2_370m import MAMBA2_370M          # noqa: F401
from repro.configs.recurrentgemma_9b import RECURRENTGEMMA_9B  # noqa: F401
from repro.configs.paligemma_3b import PALIGEMMA_3B        # noqa: F401

ASSIGNED = [
    GEMMA3_12B, MISTRAL_LARGE_123B, GEMMA2_27B, GEMMA2_2B, OLMOE_1B_7B,
    PHI35_MOE, MUSICGEN_LARGE, MAMBA2_370M, RECURRENTGEMMA_9B, PALIGEMMA_3B,
]
