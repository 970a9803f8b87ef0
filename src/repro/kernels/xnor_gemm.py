"""Paper-faithful xnor-popcount GEMM as a Pallas TPU kernel.

The CUDA original assigns one thread per output element and loops over
packed words with ``__popc``. The TPU adaptation re-tiles the same
computation for the memory hierarchy: packed ``int32`` operand tiles are
staged HBM->VMEM by the Pallas pipeline, the popcount reduction runs on
the VPU's 8x128 int32 lanes, and partial sums accumulate in a VMEM
scratch across the K grid axis (innermost, so the accumulator stays
resident).

The inner loop is BROADCAST-FREE (DESIGN.md §6): a ``lax.fori_loop``
walks the packed K-words in small groups and accumulates one
``[bm, bn]`` popcount per word — the old ``[bm, bkw, bn]`` xnor
intermediate (~85% of each step's VMEM at the 128/128/16 defaults)
never exists. ``accum="broadcast"`` keeps the old formulation for A/B
benchmarking and equivalence tests only.

VMEM budget per step (defaults bm=bn=128, bkw=16):
  w tile  128*16*4   =   8 KiB
  x tile  16*128*4   =   8 KiB
  xnor    128*128*4  =  64 KiB   (one 2-D word term; was 1024 KiB 3-D)
  acc     128*128*4  =  64 KiB
~144 KiB of ~16 MiB VMEM (was ~1.1 MiB) — the freed budget is what lets
``kernels/autotune.py`` pick much larger tiles and real double
buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.popcount import DEFAULT_WORD_GROUP, accum_popcount_km


def _xnor_gemm_kernel(
    wt_ref, x_ref, o_ref, acc_ref, *,
    k_bits: int, nk: int, word_group: int, accum: str,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if accum == "broadcast":
        w = wt_ref[...].T  # [bm, bkw] int32 (packed)
        x = x_ref[...]     # [bkw, bn] int32 (packed)
        # Legacy formulation (A/B benchmarking only): materializes the
        # full [bm, bkw, bn] xnor intermediate.
        xnor = ~(w[:, :, None] ^ x[None, :, :])
        pc = lax.population_count(xnor).astype(jnp.int32)
        acc_ref[...] += jnp.sum(pc, axis=1)
    else:
        acc_ref[...] += accum_popcount_km(wt_ref, x_ref,
                                          word_group=word_group)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        # 2*popcount - K maps bit-space back to the ±1 dot product.
        o_ref[...] = 2 * acc_ref[...] - jnp.int32(k_bits)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_bits", "block_m", "block_n", "block_kw", "word_group", "accum",
        "interpret",
    ),
)
def xnor_gemm(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 16,
    word_group: int = DEFAULT_WORD_GROUP,
    accum: str = "loop",
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed [M, KW] x packed [KW, N] -> int32 [M, N].

    Operands must already be padded to tile multiples
    (see ``repro.kernels.ops.xnor_gemm`` for the padded wrapper).
    ``accum`` selects the inner-loop formulation: ``"loop"`` (the
    broadcast-free fori_loop accumulator) or ``"broadcast"`` (legacy
    3-D intermediate, kept for A/B benchmarks and tests).
    """
    m, kw = wp.shape
    kw2, n = xp.shape
    assert kw == kw2, (wp.shape, xp.shape)
    assert m % block_m == 0 and n % block_n == 0 and kw % block_kw == 0
    assert accum in ("loop", "broadcast"), accum
    nk = kw // block_kw

    kernel = functools.partial(
        _xnor_gemm_kernel, k_bits=k_bits, nk=nk, word_group=word_group,
        accum=accum,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_kw, block_m), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_kw, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(wp.T, xp)  # word-major weights: see popcount.py
