"""Beyond-paper TPU-native binary GEMM: packed weights, MXU contraction.

Insight (DESIGN.md §2): on TPU the durable win of binarization is the
32x weight footprint / HBM-bandwidth reduction, not the instruction
count. So weights travel HBM->VMEM packed (int32 words), are unpacked
to ±1 inside the kernel, and the dot product runs on the MXU at full
systolic throughput against a real-valued (or ±1) activation tile.

This also covers *weight-only* binarization (activations bf16), the
mode the LM configs use for serving.

VMEM per step (bm=128, bn=128, bkw=8 -> bk=256):
  w packed 128*8*4      =   4 KiB
  w unpacked 128*256*4  = 128 KiB
  x tile   256*128*4    = 128 KiB
  acc      128*128*4    =  64 KiB
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitops import PACK_BITS


def _unpack_gemm_kernel(wt_ref, x_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Word-major tile [bkw, bm]: each word unpacks along the sublane
    # axis, so the K-major ±1 tile [bkw*32, bm] is a sublane merge (a
    # lane-axis unpack would need a lane reshape the TPU cannot lower).
    wt = wt_ref[...]
    bkw, bm = wt.shape
    shifts = lax.broadcasted_iota(jnp.int32, (1, PACK_BITS, 1), 1)
    bits = (wt[:, None, :] >> shifts) & 1                      # [bkw, 32, bm]
    w = (2 * bits - 1).reshape(bkw * PACK_BITS, bm).astype(x_ref.dtype)
    # MXU contraction with fp32 accumulation.
    acc_ref[...] += jnp.dot(w.T, x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_kw", "out_dtype", "interpret"),
)
def unpack_gemm(
    wp: jnp.ndarray,
    x: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 8,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed weights [M, KW] x real input [KW*32, N] -> [M, N]."""
    m, kw = wp.shape
    k, n = x.shape
    assert k == kw * PACK_BITS, (wp.shape, x.shape)
    assert m % block_m == 0 and n % block_n == 0 and kw % block_kw == 0
    nk = kw // block_kw
    block_k = block_kw * PACK_BITS

    kernel = functools.partial(_unpack_gemm_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_kw, block_m), lambda i, j, k_: (k_, i)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k_: (k_, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(wp.T, x)  # word-major weights
