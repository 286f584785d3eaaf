from . import dma_model, ops, ref
from .common import (NativeLoweringUnsupported, ShardedLoweringUnsupported, pad2,
                     resolve_interpret, validate_low_bits)
from .diff_encode import LOW_BIT_MAX, diff_encode
from .ditto_diff_matmul import ditto_diff_matmul
from .fused_step import diff_encode_fused, ditto_fused_matmul, hold_maps
from .int4_pack import pack_int4, unpack_int4, unpack_int4_lanes
from .int8_matmul import int8_matmul

__all__ = [
    "dma_model",
    "ops",
    "ref",
    "LOW_BIT_MAX",
    "NativeLoweringUnsupported",
    "ShardedLoweringUnsupported",
    "diff_encode",
    "diff_encode_fused",
    "ditto_diff_matmul",
    "ditto_fused_matmul",
    "hold_maps",
    "pack_int4",
    "unpack_int4",
    "unpack_int4_lanes",
    "int8_matmul",
    "pad2",
    "resolve_interpret",
    "validate_low_bits",
]
