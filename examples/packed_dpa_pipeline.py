"""Quantize -> pack -> DPA: the operand-bandwidth pipeline, end to end.

TransDot's Table I argument is that trans-precision operands saturate a
fixed-width operand interface: FP16 moves 2 bytes/code, FP8 one, FP4 half
a byte — so the same wires feed 2x/4x/8x more dot-product terms than f32.
In the jax_pallas reproduction that interface is HBM->VMEM bandwidth.
This example walks the whole software face of that story:

  1. quantize+pack the activations in ONE fused Pallas kernel
     (`quantize_rows(pack=True)`: absmax -> E2M1 cast -> nibble pack),
  2. run the packed-operand DPA matmul (nibbles unpacked in VMEM — the
     BlockSpec moved half the fp4 bytes),
  3. run the fully fused variant (quantization inside the matmul
     prologue: the quantized activation never touches HBM at all),
  4. account the operand bytes per policy and check the 2x/4x/8x ratios,
  5. prove packing is free: packed and unpacked results are bit-identical.

The packed kernels run in the Pallas interpreter (Mosaic refuses the
nibble unpack), so this walkthrough is a CPU demo.

Run:  PYTHONPATH=src JAX_PLATFORMS=cpu python examples/packed_dpa_pipeline.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.core import get_policy
from repro.core.packing import matmul_operand_bytes, pack_fp4_axis
from repro.kernels import dpa_matmul as dm
from repro.kernels import ops as O
from repro.kernels.ops import _quant_operand

M, K, N = 256, 512, 256
x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
f32 = np.asarray(x @ w)


def rel_err(y):
    return float(np.abs(np.asarray(y) - f32).max() / np.abs(f32).max())


# -- 1. fused quantize+pack kernel -------------------------------------------
qx_packed, sx = O.quantize_rows(x, "fp4_e2m1", pack=True)
print(f"quantize+pack: x {x.shape} f32 ({x.size * 4} B) -> "
      f"{qx_packed.shape} uint8 ({qx_packed.size} B packed codes)")

# -- 2. packed-operand DPA matmul --------------------------------------------
wq, sw = _quant_operand(w, "fp4_e2m1", 0)
y_packed = dm.dpa_matmul_prequant(
    qx_packed, pack_fp4_axis(wq, 0), sx, sw, fmt_x="fp4_e2m1",
    fmt_w="fp4_e2m1", pack_x=True, pack_w=True, interpret=True)
print(f"packed DPA matmul:   rel err vs f32 = {rel_err(y_packed):.3f} "
      "(fp4 operands: quantization error, not packing error)")

# -- 3. fully fused variant (policy-driven) ----------------------------------
y_fused = O.dpa_matmul(x, w, get_policy("fp4_dpa_fused"))
print(f"fused-quant matmul:  rel err vs f32 = {rel_err(y_fused):.3f} "
      "(per-(row,K-block) scales, no quantized-x HBM round-trip)")

# -- 4. bytes moved through the operand interface ----------------------------
print(f"\noperand bytes for the {M}x{K}x{N} matmul "
      "(quantized operands + scales):")
print(f"  {'policy':16s} {'bytes':>10s} {'vs f32':>8s}")
for pol in ("fp16_dpa", "fp8_dpa", "fp4_dpa_packed"):
    b = matmul_operand_bytes(M, K, N, pol)
    print(f"  {pol:16s} {b['total']:10d} "
          f"{b['reduction_vs_f32']:7.2f}x")
print("  (expected ~2x / ~4x / ~8x — Table I's operand-bandwidth story)")

# -- 5. packing is pure layout: bit-identity ---------------------------------
# same quantizer kernel, unpacked layout (byte per code) on both sides
xq, sx2 = O.quantize_rows(x, "fp4_e2m1")
y_unpacked = dm.dpa_matmul_prequant(xq, wq, sx2, sw, fmt_x="fp4_e2m1",
                                    fmt_w="fp4_e2m1", interpret=True)
bit_identical = np.array_equal(np.asarray(y_packed), np.asarray(y_unpacked))
print(f"\npacked == unpacked bit-for-bit: {bit_identical}")
assert bit_identical
