"""Pallas bit-packing (encoding) kernel — paper §3.1.

Encodes a real-valued matrix into the packed int32 format along axis 0
(the contraction axis of the input operand): ``[K, N] -> [K/32, N]``.
Each program packs a ``[bkw*32, bn]`` VMEM tile into ``[bkw, bn]`` words
with a shift-and-add over the 32-bit sub-axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitops import PACK_BITS


def _pack_kernel(x_ref, o_ref):
    x = x_ref[...]  # [bkw*32, bn]
    bk, bn = x.shape
    bkw = bk // PACK_BITS
    bits = (x >= 0).astype(jnp.int32).reshape(bkw, PACK_BITS, bn)
    shifts = lax.broadcasted_iota(jnp.int32, (1, PACK_BITS, 1), 1)
    o_ref[...] = jnp.sum(bits << shifts, axis=1)


@functools.partial(jax.jit, static_argnames=("block_kw", "block_n", "interpret"))
def pack_rows(
    x: jnp.ndarray,
    *,
    block_kw: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """[K, N] real -> [K/32, N] packed int32 (sign encoding, LSB-first)."""
    k, n = x.shape
    assert k % (block_kw * PACK_BITS) == 0 and n % block_n == 0, (k, n)
    kw = k // PACK_BITS
    return pl.pallas_call(
        _pack_kernel,
        grid=(kw // block_kw, n // block_n),
        in_specs=[
            pl.BlockSpec((block_kw * PACK_BITS, block_n), lambda i, j: (i, j))
        ],
        out_specs=pl.BlockSpec((block_kw, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((kw, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x)
