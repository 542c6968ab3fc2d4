"""Core: the paper's contribution — trans-precision DPA — as composable JAX.

Import layering note: `repro.core.dpa` (the bit-accurate golden model)
enables jax x64 on import; the deployment modules (quantize / policy /
linear) do not import it, so model/dry-run code never flips global jax
config.  Import `repro.core.dpa` explicitly where the golden model is
needed (tests, numerics benchmarks).
"""
from .formats import (BF16, FP4_E2M1, FP8_E4M3, FP8_E5M2, FP16, FP32,
                      FloatFormat, get_format)
from .linear import (apply_grouped_linear, apply_linear, dpa_dot,
                     init_grouped_linear, init_linear)
from .policy import DPA_TERMS, POLICIES, TransPrecisionPolicy, get_policy
from .quantize import (cast_to, compute_scale, decode_fp4, dequantize,
                       encode_fp4, fake_quant, jnp_dtype,
                       quant_dequant, quantize, quantize_blockwise)

__all__ = [
    "FP32", "FP16", "BF16", "FP8_E4M3", "FP8_E5M2", "FP4_E2M1",
    "FloatFormat", "get_format",
    "TransPrecisionPolicy", "POLICIES", "DPA_TERMS", "get_policy",
    "quantize", "quantize_blockwise", "dequantize", "quant_dequant",
    "fake_quant", "cast_to", "compute_scale", "jnp_dtype",
    "encode_fp4", "decode_fp4",
    "init_linear", "apply_linear", "dpa_dot",
    "init_grouped_linear", "apply_grouped_linear",
]
