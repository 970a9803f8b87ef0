"""Fused binary-layer Pallas kernel: xnor-popcount GEMM with a
BN-fold + sign + repack epilogue (DESIGN.md §4).

Extends ``xnor_gemm``'s tiling: packed int32 operand tiles are staged
HBM->VMEM, ``popcount(~(w ^ x))`` accumulates in a VMEM scratch across
the K grid axis, and on the LAST K step the per-tile epilogue runs
entirely in VMEM:

    dot  = 2*acc - k_bits                     int32   [bm, bn]
    y    = a*dot + b                          float32 [bm, bn]
    bits = (y >= 0)  --shift-add over 32-row groups-->  int32 [bm/32, bn]

``a``/``b`` are per-output-row (= per output channel) affines holding
the folded inference BatchNorm (+ optional bias and XNOR-Net alpha, see
``repro.core.layers.fold_bn_params``). The packed [bm/32, bn] words are
the ONLY thing written back to HBM — the float activation tensor of the
unfused path never exists, and the next binary layer consumes the words
directly (one fewer ``pack_rows`` launch, ~32x less boundary traffic).

The popcount inner loop is BROADCAST-FREE (DESIGN.md §6): a
``lax.fori_loop`` over packed K-word groups accumulates one ``[bm, bn]``
popcount per word — the old ``[bm, bkw, bn]`` xnor intermediate never
exists. ``accum="broadcast"`` keeps the legacy formulation for A/B
benchmarking only.

VMEM budget per step (defaults bm=bn=128, bkw=16):
  w tile   128*16*4       =    8 KiB
  x tile   16*128*4       =    8 KiB
  a, b     128*1*4  x2    =    1 KiB
  xnor     128*128*4      =   64 KiB   (one 2-D word term; was 1024 KiB)
  acc      128*128*4      =   64 KiB
  y        128*128*4      =   64 KiB   (epilogue, last K step only)
  out      4*128*4        =    2 KiB
~211 KiB of ~16 MiB VMEM (was ~1.2 MiB) — the freed budget is what lets
``kernels/autotune.py`` pick much larger tiles with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitops import PACK_BITS
from repro.kernels.popcount import (
    DEFAULT_WORD_GROUP,
    accum_popcount_km,
    sign_repack_m,
)


def _fused_xnor_gemm_kernel(
    wt_ref, x_ref, a_ref, b_ref, o_ref, acc_ref, *,
    k_bits: int, nk: int, word_group: int, accum: str,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if accum == "broadcast":
        w = wt_ref[...].T  # [bm, bkw] int32 (packed)
        x = x_ref[...]     # [bkw, bn] int32 (packed)
        # Legacy formulation (A/B benchmarking only).
        xnor = ~(w[:, :, None] ^ x[None, :, :])  # [bm, bkw, bn]
        pc = lax.population_count(xnor).astype(jnp.int32)
        acc_ref[...] += jnp.sum(pc, axis=1)
    else:
        acc_ref[...] += accum_popcount_km(wt_ref, x_ref,
                                          word_group=word_group)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        # ±1 dot product, then the folded-BN affine (same op order as
        # bitops.fused_xnor_layer so the two are bit-exact vs each other).
        dot = (2 * acc_ref[...] - jnp.int32(k_bits)).astype(jnp.float32)
        y = a_ref[...] * dot + b_ref[...]          # [bm, bn] float32
        o_ref[...] = sign_repack_m(y)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_bits", "block_m", "block_n", "block_kw", "word_group", "accum",
        "interpret",
    ),
)
def fused_xnor_gemm(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 16,
    word_group: int = DEFAULT_WORD_GROUP,
    accum: str = "loop",
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed [M, KW] x packed [KW, N] -> PACKED int32 [M/32, N].

    ``a``/``b``: float32 [M, 1] per-row affine. Operands must already be
    padded to tile multiples (see ``repro.kernels.ops.fused_xnor_gemm``
    for the padded wrapper); ``block_m`` must divide by 32 so each tile
    repacks to whole words.
    """
    m, kw = wp.shape
    kw2, n = xp.shape
    assert kw == kw2, (wp.shape, xp.shape)
    assert block_m % PACK_BITS == 0, block_m
    assert m % block_m == 0 and n % block_n == 0 and kw % block_kw == 0
    assert a.shape == (m, 1) and b.shape == (m, 1), (a.shape, b.shape, m)
    assert accum in ("loop", "broadcast"), accum
    nk = kw // block_kw

    kernel = functools.partial(
        _fused_xnor_gemm_kernel, k_bits=k_bits, nk=nk,
        word_group=word_group, accum=accum,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_kw, block_m), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_kw, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_m // PACK_BITS, block_n), lambda i, j, k: (i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((m // PACK_BITS, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(wp.T, xp, a.astype(jnp.float32), b.astype(jnp.float32))  # word-major
