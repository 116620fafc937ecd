from dtc_tpu.ops import decode_attention, decode_fused, moe_dispatch
from dtc_tpu.ops.attention import causal_attention

__all__ = [
    "causal_attention", "decode_attention", "decode_fused", "moe_dispatch",
]
