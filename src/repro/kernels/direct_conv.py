"""Direct packed-window binary conv Pallas kernel — no im2col
materialization (DESIGN.md §5).

The fused im2col path (PR 1) still writes the packed patch matrix
``[N*OH*OW, kH*kW*CW]`` to HBM before each GEMM — ~kH*kW times larger
than the packed activation map it was gathered from. This kernel
convolves the channel-packed map directly: the grid tiles the output
pixel space ``(N, OH)`` x output channels ``D``, each program holds the
whole (pre-padded) packed image ``[Hp, Wp, CW]`` in VMEM, gathers its
kH*kW taps with strided in-VMEM loads into word rows ``[kH*kW*CW, OW]``
(VMEM scratch), runs the xnor-popcount accumulation against the
tap-aligned packed filter tile (word-major, see popcount.py), and finishes
with the PR-1 fused epilogue (folded-BN affine -> sign -> repack along
D). HBM sees: the packed map (read), the packed filters (read), the
packed output (write). The patch matrix never exists.

Two variants share the window gather:

* ``fused_direct_conv`` — full fused layer, packed words in AND out,
* ``direct_conv_dot``   — epilogue-free int32 ±1 dot ``[N,OH,OW,D]``
                          (the chain-boundary / unfused-PACKED variant).

The popcount accumulation is BROADCAST-FREE (DESIGN.md §6): a
``lax.fori_loop`` over the kH*kW*CW packed filter words accumulates one
``[bd, OW]`` popcount per word — the old ``[bd, OW, KW]`` broadcast
intermediate never exists. ``accum="broadcast"`` keeps the legacy
formulation for A/B benchmarking only.

The D tile is the whole padded D: the output tile puts the channel
words on lanes, where the TPU accepts a partial tile only in multiples
of 128 words. The TPU compiler counts ~1.8 MiB of scoped VMEM for
conv1 (the ``[34, 34, 4]`` map block pads to 128 lanes) and ~2.2 MiB
for conv5 (``tests/test_tpu_compile.py`` compiles every CIFAR layer).
The map block is revisited across the OH and D grid axes (same block
index), so the pipeline fetches it once per image. When the packed map
itself outgrows VMEM (or kH*kW is large and C tiny, so the patch
blow-up the kernel avoids is small), fall back to
``conv_impl="im2col"`` — the GEMM tiles arbitrarily large operands.
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


def _gather_windows(x_ref, xs_ref, oh_idx, *, kh: int, kw: int,
                    stride: int, ow: int):
    """Gather one output row's windows from the padded map in VMEM.

    x_ref: [1, Hp, Wp, CW]. Fills ``xs_ref [kH*kW*CW, OW]`` with word
    rows — row ``(i*kW + j)*CW + cw`` holds tap (i, j), word cw of the
    row's OW windows: the ``[KW, N]`` operand layout of
    ``accum_popcount_km``, in the tap-major pack_conv_aligned order.
    Each tap is one stride-``stride`` sublane read of the map row.
    """
    cw = x_ref.shape[-1]
    for i in range(kh):
        for j in range(kw):
            tap = x_ref[0, oh_idx * stride + i,
                        pl.ds(j, ow, stride=stride), :]      # [OW, CW]
            row = (i * kw + j) * cw
            xs_ref[row:row + cw, :] = tap.T


def _popcount_dot(wt_ref, xs_ref, k_bits: int, *, word_group: int,
                  accum: str):
    """wt [KW, bd] x xs [KW, OW] -> exact ±1 dot, int32 [bd, OW]."""
    if accum == "broadcast":
        # Legacy formulation (A/B benchmarking only).
        xnor = ~(wt_ref[...].T[:, :, None] ^ xs_ref[...][None, :, :])
        pc = lax.population_count(xnor).astype(jnp.int32)  # [bd, KW, OW]
        acc = jnp.sum(pc, axis=1)
    else:
        acc = accum_popcount_km(wt_ref, xs_ref, word_group=word_group)
    return 2 * acc - jnp.int32(k_bits)


def _direct_conv_kernel(
    x_ref, wt_ref, *rest,
    kh: int, kw: int, stride: int, ow: int, k_bits: int,
    word_group: int, accum: str, fused: bool,
):
    if fused:
        a_ref, b_ref, o_ref, xs_ref = rest
    else:
        o_ref, xs_ref = rest
    _gather_windows(x_ref, xs_ref, pl.program_id(1), kh=kh, kw=kw,
                    stride=stride, ow=ow)
    dot = _popcount_dot(wt_ref, xs_ref, k_bits, word_group=word_group,
                        accum=accum)
    if fused:
        # Same float op order as bitops.direct_conv_oracle /
        # fused_xnor_layer so every conv_impl x engine pair is bit-exact
        # vs the others.
        y = a_ref[...] * dot.astype(jnp.float32) + b_ref[...]  # [bd, OW]
        o_ref[...] = sign_repack_m(y).T[None, None]  # [1, 1, OW, bd/32]
    else:
        o_ref[...] = dot.T[None, None]  # [1, 1, OW, bd]


def _direct_conv_call(wp, xpad, k_bits, a, b, *, kh, kw, stride, block_d,
                      word_group, accum, interpret):
    """The shared launch of both variants (``a``/``b`` None: the
    epilogue-free dot)."""
    n, hp, wp_sp, cw = xpad.shape
    d_pad, kwords = wp.shape
    assert kwords == kh * kw * cw, (wp.shape, kh, kw, cw)
    assert d_pad % block_d == 0, (d_pad, block_d)
    assert accum in ("loop", "broadcast"), accum
    fused = a is not None
    oh = (hp - kh) // stride + 1
    ow = (wp_sp - kw) // stride + 1
    kernel = functools.partial(
        _direct_conv_kernel, kh=kh, kw=kw, stride=stride, ow=ow,
        k_bits=k_bits, word_group=word_group, accum=accum, fused=fused,
    )
    # Filters go in word-major (popcount.py).
    in_specs = [
        pl.BlockSpec((1, hp, wp_sp, cw), lambda ni, oi, di: (ni, 0, 0, 0)),
        pl.BlockSpec((kwords, block_d), lambda ni, oi, di: (0, di)),
    ]
    operands = [xpad, wp.T]
    if fused:
        assert block_d % PACK_BITS == 0, block_d
        assert a.shape == (d_pad, 1) and b.shape == (d_pad, 1), (
            a.shape, b.shape)
        in_specs += [pl.BlockSpec((block_d, 1), lambda ni, oi, di: (di, 0))] * 2
        operands += [a.astype(jnp.float32), b.astype(jnp.float32)]
        out_words, out_d = block_d // PACK_BITS, d_pad // PACK_BITS
    else:
        out_words, out_d = block_d, d_pad
    return pl.pallas_call(
        kernel,
        grid=(n, oh, d_pad // block_d),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, ow, out_words), lambda ni, oi, di: (ni, oi, 0, di),
        ),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, out_d), jnp.int32),
        scratch_shapes=[pltpu.VMEM((kwords, ow), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_bits", "kh", "kw", "stride", "block_d", "word_group", "accum",
        "interpret",
    ),
)
def fused_direct_conv(
    wp: jnp.ndarray,
    xpad: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    block_d: int = 128,
    word_group: int = DEFAULT_WORD_GROUP,
    accum: str = "loop",
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed map [N, Hp, Wp, CW] x tap-aligned filters [D_pad, kH*kW*CW]
    -> PACKED int32 [N, OH, OW, D_pad/32].

    ``xpad`` must already carry its spatial all-ones border (the wrapper
    ``repro.kernels.ops.fused_direct_conv`` pads); ``a``/``b``
    ``[D_pad, 1]`` f32 per-output-channel affine, rows past the true D
    padded ``a=0, b=+1`` to pin their bits. ``block_d`` must divide by
    32 so each tile repacks to whole words.
    """
    return _direct_conv_call(
        wp, xpad, k_bits, a, b, kh=kh, kw=kw, stride=stride,
        block_d=block_d, word_group=word_group, accum=accum,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_bits", "kh", "kw", "stride", "block_d", "word_group", "accum",
        "interpret",
    ),
)
def direct_conv_dot(
    wp: jnp.ndarray,
    xpad: jnp.ndarray,
    k_bits: int,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    block_d: int = 128,
    word_group: int = DEFAULT_WORD_GROUP,
    accum: str = "loop",
    interpret: bool = False,
) -> jnp.ndarray:
    """Epilogue-free variant: int32 ±1 dot [N, OH, OW, D_pad].

    Same gather + popcount pipeline as :func:`fused_direct_conv`; used
    by the unfused PACKED path (bias/alpha/BN applied by the caller in
    float). Padded D rows produce garbage the wrapper slices off.
    """
    return _direct_conv_call(
        wp, xpad, k_bits, None, None, kh=kh, kw=kw, stride=stride,
        block_d=block_d, word_group=word_group, accum=accum,
        interpret=interpret,
    )
