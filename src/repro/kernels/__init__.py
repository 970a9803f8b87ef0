"""Pallas TPU kernels for 1-bit xnor/bitcount computation.

``xnor_gemm``         — paper-faithful packed xnor-popcount GEMM (VPU).
``unpack_gemm``       — TPU-native packed-weight MXU GEMM (beyond-paper).
``pack_rows``         — the paper's encoding operation as a kernel.
``fused_xnor_gemm``   — xnor GEMM + BN-fold/sign/repack epilogue: packed
                        activations in AND out (DESIGN.md §4).
``fused_direct_conv`` — direct packed-window conv + the same epilogue:
                        no im2col patch matrix in HBM (DESIGN.md §5).
``direct_conv``       — epilogue-free direct conv (int32 ±1 dot out).
``bireal_block_conv`` — direct conv with a residual epilogue: the
                        zero-padded dot (``c_pos`` border correction)
                        plus an f32 shortcut, f32 AND packed out (§5.1).
``megakernel_chain``  — a whole chain of fused binary layers in ONE
                        launch: weights VMEM-resident, packed
                        activations ping-ponged in scratch (§8).
``megakernel_conv_stage`` — conv(+conv)+packed-OR-maxpool per launch,
                        one program per image (§8).

All xnor kernels share the broadcast-free popcount accumulator in
:mod:`repro.kernels.popcount` and resolve ``block_*="auto"`` tile
sizes via :mod:`repro.kernels.autotune` (DESIGN.md §6).

Import the padded/dispatching wrappers from :mod:`repro.kernels.ops`;
oracles live in :mod:`repro.kernels.ref` and :mod:`repro.core.bitops`.
"""

from repro.kernels.ops import (  # noqa: F401
    bireal_block_conv,
    direct_conv,
    fused_direct_conv,
    fused_xnor_gemm,
    megakernel_chain,
    megakernel_conv_stage,
    pack_rows,
    unpack_gemm,
    xnor_gemm,
)
