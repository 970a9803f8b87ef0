"""Bit-packing and xnor-popcount primitives (pure-JAX reference semantics).

This module is the *semantic* definition of the paper's encoding:

* binary "values" are {-1, +1}; binary "encodings" are {0, 1} with
  ``1 <-> +1`` (paper §3.1),
* 32 one-bit encodings pack into one ``int32`` word, LSB-first along the
  contraction (K) axis,
* ``a_ij = sum_k 2*popcount(xnor(w_ik, x_kj)) - K`` reproduces the exact
  ±1 dot product (paper §3.2).

The Pallas kernels in ``repro.kernels`` implement the same contract for
TPU; everything here is the oracle they are tested against, and the
XLA fallback used inside large jit'd programs (the interpreter-mode
Pallas path cannot live inside a 512-way SPMD program on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

PACK_BITS = 32
PACKED_DTYPE = jnp.int32

__all__ = [
    "PACK_BITS",
    "PACKED_DTYPE",
    "pack_bits",
    "pack_channels",
    "unpack_bits",
    "popcount",
    "xnor_popcount_matmul",
    "packed_matmul_unpack",
    "pad_packed_operands",
    "fused_xnor_layer",
    "direct_conv_dot",
    "direct_conv_oracle",
    "border_taps",
    "border_correction",
    "residual_conv_oracle",
    "maxpool2_packed",
    "megakernel_chain_xla",
    "conv_stage_xla",
]


def _shift_vector(dtype=PACKED_DTYPE) -> jnp.ndarray:
    return jnp.arange(PACK_BITS, dtype=dtype)


def pack_bits(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack the sign bits of ``x`` along ``axis`` into int32 words.

    ``x`` holds real numbers; the binarization convention is
    ``bit = 1 if x >= 0 else 0`` (sign(0) := +1, as in BNN training).
    ``x.shape[axis]`` must be a multiple of 32. Bit ``b`` of word ``w``
    encodes element ``w * 32 + b`` (LSB-first).
    """
    axis = axis % x.ndim
    k = x.shape[axis]
    if k % PACK_BITS != 0:
        raise ValueError(f"pack axis length {k} not a multiple of {PACK_BITS}")
    x = jnp.moveaxis(x, axis, -1)
    bits = (x >= 0).astype(PACKED_DTYPE)
    bits = bits.reshape(*x.shape[:-1], k // PACK_BITS, PACK_BITS)
    words = jnp.sum(bits << _shift_vector(), axis=-1).astype(PACKED_DTYPE)
    return jnp.moveaxis(words, -1, axis)


def pack_channels(x: jnp.ndarray, *, pad_value: float = 1.0) -> jnp.ndarray:
    """Channel-pack ``[..., C]`` real values into ``[..., ceil(C/32)]`` words.

    Unlike :func:`pack_bits` this tolerates ``C % 32 != 0``: the tail of
    the last word is filled with the sign bit of ``pad_value`` — ``+1``
    by default, the activation-pad half of the xnor-neutral convention
    (tap-aligned packed weights carry ``-1`` there, see
    ``repro.core.layers.pack_conv_aligned``).
    """
    c = x.shape[-1]
    pad = -c % PACK_BITS
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths, constant_values=pad_value)
    return pack_bits(x, axis=-1)


def unpack_bits(words: jnp.ndarray, axis: int = -1, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: int32 words -> ±1 values along ``axis``."""
    axis = axis % words.ndim
    w = jnp.moveaxis(words, axis, -1)
    bits = (w[..., None] >> _shift_vector()) & 1
    vals = (2 * bits - 1).astype(dtype)
    vals = vals.reshape(*w.shape[:-1], w.shape[-1] * PACK_BITS)
    return jnp.moveaxis(vals, -1, axis)


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Population count on the raw bit pattern (int32-safe)."""
    return lax.population_count(x)


@functools.partial(jax.jit, static_argnames=("k_bits", "block_kw"))
def xnor_popcount_matmul(
    wp: jnp.ndarray, xp: jnp.ndarray, k_bits: int, block_kw: int = 64
) -> jnp.ndarray:
    """Paper §3.2: packed [M, KW] x [KW, N] -> int32 [M, N].

    ``a_ij = 2 * sum_k popcount(~(w_ik ^ x_kj)) - k_bits``.

    Blocked over KW to bound the [M, bkw, N] broadcast intermediate;
    this is the XLA fallback — the Pallas kernel does the same with
    explicit VMEM tiles.
    """
    m, kw = wp.shape
    kw2, n = xp.shape
    assert kw == kw2, (wp.shape, xp.shape)

    nblk = -(-kw // block_kw)
    pad = nblk * block_kw - kw
    if pad:
        # pad pairs (w=0x0, x=~0) xnor to 0 -> contribute zero popcount.
        wp = jnp.pad(wp, ((0, 0), (0, pad)))
        xp = jnp.pad(xp, ((0, pad), (0, 0)), constant_values=-1)

    def body(i, acc):
        wblk = lax.dynamic_slice_in_dim(wp, i * block_kw, block_kw, axis=1)
        xblk = lax.dynamic_slice_in_dim(xp, i * block_kw, block_kw, axis=0)
        xnor = ~(wblk[:, :, None] ^ xblk[None, :, :])
        return acc + jnp.sum(popcount(xnor).astype(jnp.int32), axis=1)

    acc = lax.fori_loop(0, nblk, body, jnp.zeros((m, n), jnp.int32))
    return 2 * acc - jnp.int32(k_bits)


def packed_matmul_unpack(
    wp: jnp.ndarray,
    x: jnp.ndarray,
    *,
    compute_dtype=jnp.bfloat16,
    accum_dtype=jnp.float32,
) -> jnp.ndarray:
    """TPU-native variant: packed weights [M, KW] x real/±1 input [K, N].

    Weights stay packed in HBM (32x footprint win); unpack happens
    on-chip and the contraction runs on the MXU (kernels/unpack_gemm.py
    is the Pallas implementation — packed words are staged HBM->VMEM and
    the unpacked ±1 tile never exists in HBM). The XLA fallback here
    necessarily materializes the unpacked weight, so that traffic is
    scoped vmem_fusible for the roofline: the packed-word reads (the
    REAL HBM traffic) are counted via the w_packed slice reads.
    """
    with jax.named_scope("vmem_fusible"):
        w = unpack_bits(wp, axis=-1, dtype=compute_dtype)
        out = jnp.dot(w, x.astype(compute_dtype),
                      preferred_element_type=accum_dtype)
    return out


@functools.partial(jax.jit, static_argnames=("k_bits", "block_kw"))
def fused_xnor_layer(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    block_kw: int = 64,
) -> jnp.ndarray:
    """Whole fused binary layer, pure-XLA (the oracle for the Pallas
    fused kernel, and the SPMD-safe fallback engine).

    Packed ``wp [M, KW]`` x packed ``xp [KW, N]`` -> packed ``[ceil(M/32), N]``:

        dot  = 2*popcount(xnor) - k_bits        (exact ±1 dot product)
        y    = a*dot + b                         (folded BN/bias/alpha affine)
        bits = y >= 0, repacked along M (LSB-first)

    ``k_bits`` is the TRUE contraction length: bit-level K padding must
    follow the xnor-neutral convention (weight pad bits 0/-1, activation
    pad bits 1/+1 -> zero popcount), so no post-hoc correction is needed.
    M rows beyond ``M`` inside the last output word are padded with +1
    bits — exactly what the next layer's weight-pad correction expects.
    """
    dot = xnor_popcount_matmul(wp, xp, k_bits, block_kw=block_kw)
    y = a[:, None] * dot.astype(a.dtype) + b[:, None]
    pad = -y.shape[0] % PACK_BITS
    if pad:
        y = jnp.pad(y, ((0, pad), (0, 0)), constant_values=1.0)
    return pack_bits(y, axis=0)


@functools.partial(
    jax.jit, static_argnames=("k_bits", "kh", "kw", "stride", "pad")
)
def direct_conv_dot(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
) -> jnp.ndarray:
    """Direct binary convolution, pure XLA: the ±1 conv dot product
    WITHOUT building the im2col patch matrix.

    ``xp``: channel-packed activations ``[N, H, W, CW]`` (CW words per
    pixel, tail bits +1 when C % 32 != 0 — see :func:`pack_channels`).
    ``wp``: tap-aligned packed filters ``[D, kH*kW*CW]`` (word
    ``(i*kW + j)*CW + cw`` holds tap ``(i, j)``'s channel word ``cw``;
    ``repro.core.layers.pack_conv_aligned`` produces this, and it
    coincides with the flat ``pack_conv_params`` layout when C % 32 == 0).

    Spatial borders pad with all-ones words (``sign(0) := +1``). The
    static loop runs over the kH*kW taps only; each tap contributes a
    strided window slice of the map — the ``[N*OH*OW, kH*kW*CW]`` patch
    matrix of the im2col lowering never exists. ``k_bits`` is the TRUE
    contraction length kH*kW*C. Returns int32 ``[N, OH, OW, D]``.
    """
    from repro.core.im2col import conv_out_size

    n, h, w, cw = xp.shape
    d, kwords = wp.shape
    if kwords != kh * kw * cw:
        raise ValueError(
            f"filter words {kwords} != kh*kw*CW = {kh}*{kw}*{cw} — direct "
            "conv needs tap-aligned packed filters (pack_conv_aligned)"
        )
    oh = conv_out_size(h, kh, stride, pad)
    ow = conv_out_size(w, kw, stride, pad)
    if pad:
        xp = jnp.pad(xp, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     constant_values=-1)
    wr = wp.reshape(d, kh * kw, cw)
    acc = jnp.zeros((n, oh, ow, d), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            win = lax.slice(
                xp,
                (0, i, j, 0),
                (n, i + stride * (oh - 1) + 1, j + stride * (ow - 1) + 1, cw),
                (1, stride, stride, 1),
            )  # [N, OH, OW, CW]
            tap = wr[:, i * kw + j, :]  # [D, CW]
            xnor = ~(win[..., None, :] ^ tap[None, None, None, :, :])
            acc = acc + jnp.sum(popcount(xnor).astype(jnp.int32), axis=-1)
    return 2 * acc - jnp.int32(k_bits)


@functools.partial(
    jax.jit, static_argnames=("k_bits", "kh", "kw", "stride", "pad")
)
def direct_conv_oracle(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
) -> jnp.ndarray:
    """Whole fused direct-conv layer, pure XLA (the oracle for the
    Pallas direct kernel, and the SPMD-safe fallback engine).

    :func:`direct_conv_dot` then the PR-1 fused epilogue: per-output-
    channel affine ``a*dot + b`` (folded BN/bias/alpha), sign, repack
    along D (pad channels past D get +1 bits — the next layer's
    activation-pad convention). Same int32 dot and same float op order
    as ``fused_xnor_layer`` on im2col patches, so the two conv_impls
    are bit-identical. Returns packed ``[N, OH, OW, ceil(D/32)]``.
    """
    dot = direct_conv_dot(wp, xp, k_bits, kh=kh, kw=kw, stride=stride,
                          pad=pad)
    y = a.astype(jnp.float32) * dot.astype(jnp.float32) + b.astype(jnp.float32)
    return pack_channels(y)


def border_taps(size: int, k: int, stride: int, pad: int) -> list:
    """The kernel offsets that fall in the padding, for the first, an
    interior and the last output position along one spatial axis of a
    ``size``-long input: the three border classes of that axis.

    Only the first and last positions may touch the padding (checked),
    which holds whenever ``pad < k`` and ``pad <= k - 1 - pad + stride``
    — a 3x3, pad-1 conv at any stride. An axis of one or two outputs has
    no interior position; its interior class is then empty."""
    out = (size + 2 * pad - k) // stride + 1

    def padded(p):
        return frozenset(i for i in range(k)
                         if not 0 <= p * stride + i - pad < size)

    inner = {padded(p) for p in range(1, out - 1)}
    if inner - {frozenset()}:
        raise ValueError(f"interior outputs touch the padding: size={size}"
                         f" k={k} stride={stride} pad={pad}")
    return [padded(0), frozenset(), padded(out - 1)]


def border_correction(w_pm1: jnp.ndarray, h: int, w: int, *, stride: int,
                      pad: int) -> jnp.ndarray:
    """``c_pos [3, D, 3]`` int32 of a ``kH x kW`` conv with +-1 filters
    ``w_pm1 [D, kH, kW, C]`` over an ``h x w`` map: for each border
    class (row class, col class; :func:`border_taps`), the sum of the
    filter's +-1 weights over the taps that fall in the padding.

    A packed conv pads with all-ones words, so each padded tap adds its
    weights' sum to the dot; ``dot - c_pos`` is then the exact
    zero-padded dot (the ``conv2d`` of a zero-padded map)."""
    _, kh, kw, _ = w_pm1.shape
    rows = border_taps(h, kh, stride, pad)
    cols = border_taps(w, kw, stride, pad)
    tap = jnp.sum(w_pm1, axis=-1)                      # [D, kH, kW]
    out = []
    for rset in rows:
        for cset in cols:
            mask = [[float(i in rset or j in cset) for j in range(kw)]
                    for i in range(kh)]
            out.append(jnp.sum(tap * jnp.asarray(mask, tap.dtype),
                               axis=(1, 2)))
    return jnp.round(jnp.stack(out, axis=-1)).astype(jnp.int32).reshape(
        -1, 3, 3).transpose(1, 0, 2)


def _border_map(cpos: jnp.ndarray, oh: int, ow: int) -> jnp.ndarray:
    """``c_pos [3, D, 3]`` spread over the output map: ``[OH, OW, D]``."""
    def classes(n):
        return jnp.asarray([0] + [1] * max(n - 2, 0) + [2] * (n > 1))

    per_row = cpos[classes(oh)]                        # [OH, D, 3]
    return jnp.transpose(per_row[:, :, classes(ow)], (0, 2, 1))


@functools.partial(
    jax.jit, static_argnames=("k_bits", "kh", "kw", "stride", "pad")
)
def residual_conv_oracle(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    cpos: jnp.ndarray,
    r: jnp.ndarray,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Direct conv with the residual epilogue, pure XLA (the oracle for
    ``repro.kernels.direct_conv.residual_direct_conv``, and its
    SPMD-safe fallback engine).

    :func:`direct_conv_dot` (one-padded), less the border correction
    ``c_pos`` (:func:`border_correction`), gives the zero-padded dot;
    then ``y = a * dot + b + r`` in float32 with the f32 shortcut ``r
    [N, OH, OW, D]``. Returns ``(y, packed sign(y))``: f32 ``[N, OH,
    OW, D]`` and int32 ``[N, OH, OW, ceil(D/32)]``.
    """
    dot = direct_conv_dot(wp, xp, k_bits, kh=kh, kw=kw, stride=stride,
                          pad=pad)
    dot = dot - _border_map(cpos, dot.shape[1], dot.shape[2])
    y = (a.astype(jnp.float32) * dot.astype(jnp.float32)
         + b.astype(jnp.float32) + r.astype(jnp.float32))
    return y, pack_channels(y)


def maxpool2_packed(xp: jnp.ndarray) -> jnp.ndarray:
    """2x2/stride-2 maxpool on a channel-packed ±1 map ``[N, H, W, CW]``
    = bitwise OR of the four window words: max over {-1, +1} is +1 iff
    any bit is set, and sign is monotone so sign∘max == max∘sign."""
    return (
        xp[:, 0::2, 0::2] | xp[:, 0::2, 1::2]
        | xp[:, 1::2, 0::2] | xp[:, 1::2, 1::2]
    )


def megakernel_chain_xla(
    w_stack: jnp.ndarray,
    a_stack: jnp.ndarray,
    b_stack: jnp.ndarray,
    k_bits: tuple[int, ...],
    xp: jnp.ndarray,
    m_out: int,
    *,
    final_wp: jnp.ndarray = None,
    final_k_bits: int = 0,
) -> jnp.ndarray:
    """Pure-XLA megakernel chain: the oracle for (and SPMD-safe fallback
    of) ``repro.kernels.megakernel.megakernel_chain``.

    Consumes the SAME stacked operands — packed ``w_stack [L, M_max,
    KW_max]`` (pad rows/words zero), folded affines ``a_stack``/
    ``b_stack [L, M_max]`` (pad rows ``a=0, b=+1``), packed ``xp
    [KW_in, N]`` — and runs the layers as a sequence of
    :func:`fused_xnor_layer` calls, re-padding the inter-layer
    activations to all-ones exactly as the kernel's ping-pong scratch
    does, so the stacking/padding conventions themselves are under
    test. Returns packed ``[ceil(m_out/32), N]``, or — when ``final_wp
    [Mf, KWf]`` is given — the final epilogue-free int32 ±1 dot
    ``[Mf, N]`` (:func:`xnor_popcount_matmul` with ``final_k_bits``).
    """
    l, m_max, kw_max = w_stack.shape
    kw_act = max(kw_max, m_max // PACK_BITS)
    pad = kw_act - xp.shape[0]
    act = jnp.pad(xp, ((0, pad), (0, 0)), constant_values=-1) if pad else xp
    for i in range(l):
        # Slice each stacked layer back to its TRUE K words (static —
        # k_bits are python ints): the pad region is xnor-neutral by
        # the stacking convention, so dropping it changes nothing but
        # the op count — mirroring the kernel's dynamic trip counts.
        kw_i = min(kw_max, -(-int(k_bits[i]) // PACK_BITS))
        out = fused_xnor_layer(
            w_stack[i, :, :kw_i], act[:kw_i], int(k_bits[i]),
            a_stack[i], b_stack[i],
        )  # [m_max/32, n]
        fill = kw_act - out.shape[0]
        act = (
            jnp.pad(out, ((0, fill), (0, 0)), constant_values=-1)
            if fill else out
        )
    if final_wp is not None:
        return xnor_popcount_matmul(
            final_wp, act[: final_wp.shape[1]], final_k_bits
        )
    return act[: -(-m_out // PACK_BITS)]


def megakernel_chain_ragged_xla(
    w_stack: jnp.ndarray,
    a_stack: jnp.ndarray,
    b_stack: jnp.ndarray,
    k_bits: tuple[int, ...],
    xp: jnp.ndarray,
    m_out: int,
    n_real: int,
    *,
    final_wp: jnp.ndarray = None,
    final_k_bits: int = 0,
) -> jnp.ndarray:
    """Ragged/masked-tail oracle (DESIGN.md §9): the reference for the
    megakernel's variable-extent batch path.

    ``xp [KW_in, N_pad]`` is a TILE-padded batch (N_pad only rounds the
    true extent ``n_real`` up to the batch-tile multiple, not a bucket
    rung). Runs :func:`megakernel_chain_xla` on the padded batch — pad
    columns are all-ones packed activations, per-sample independent, so
    real columns are untouched — then zeroes every output column at or
    after ``n_real``, exactly as the kernel's tail grid step masks its
    overhang. ``tests/test_megakernel.py`` asserts the kernel against
    this, pad columns included.
    """
    out = megakernel_chain_xla(
        w_stack, a_stack, b_stack, k_bits, xp, m_out,
        final_wp=final_wp, final_k_bits=final_k_bits,
    )
    cols = jnp.arange(out.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(cols < jnp.int32(n_real), out, 0)


def conv_stage_xla(
    xp: jnp.ndarray,
    weights: tuple[jnp.ndarray, ...],
    a: tuple[jnp.ndarray, ...],
    b: tuple[jnp.ndarray, ...],
    k_bits: tuple[int, ...],
    *,
    kh: int = 3,
    kw: int = 3,
    pad: int = 1,
    pool: bool = True,
) -> jnp.ndarray:
    """Pure-XLA conv stage: the oracle for (and SPMD-safe fallback of)
    ``repro.kernels.megakernel.megakernel_conv_stage``.

    Chains :func:`direct_conv_oracle` over the stage's convs (per-layer
    TRUE shapes: tap-aligned ``weights[l] [D_l, kH*kW*CW_l]``, 1-D
    ``a[l]``/``b[l] [D_l]``) and finishes with the packed-OR maxpool.
    Channel-word counts chain exactly: each oracle layer emits
    ``ceil(D_l/32)`` words/pixel with +1 tail bits — the next layer's
    activation-pad convention.
    """
    act = xp
    for wl, al, bl, k in zip(weights, a, b, k_bits):
        act = direct_conv_oracle(
            wl, act, int(k), al, bl, kh=kh, kw=kw, stride=1, pad=pad
        )
    return maxpool2_packed(act) if pool else act


def pad_packed_operands(wp, xp, block_m, block_n, block_kw):
    """Pad packed GEMM operands so every dim tiles evenly.

    K-padding uses the (w=0, x=all-ones) trick so padded words contribute
    zero popcount; M/N padding is sliced off by the caller.
    """
    m, kw = wp.shape
    _, n = xp.shape
    pm = -m % block_m
    pn = -n % block_n
    pk = -kw % block_kw
    if pm or pk:
        wp = jnp.pad(wp, ((0, pm), (0, pk)))
    if pk or pn:
        xp = jnp.pad(xp, ((0, pk), (0, pn)), constant_values=-1)
    return wp, xp, m, n
