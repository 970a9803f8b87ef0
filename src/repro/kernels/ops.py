"""Jit'd public wrappers around the Pallas kernels.

These handle: shape padding to tile multiples (K-padding uses the
``(w=0, x=~0)`` xnor-neutral trick from ``core.bitops``), dtype checks,
and backend dispatch — ``interpret=True`` everywhere except a real TPU,
so the same call sites validate on CPU and run native on TPU.

Block sizes default to ``"auto"`` (DESIGN.md §6): the autotuner's
per-shape cache entry when one is valid for this jax version + device,
else heuristic tiles from the VMEM-budget model. Explicit ints are
honored but clamped to the padded problem shape, so tiny/ragged layers
(the 10-output CIFAR head) never trip the kernels' divisibility
asserts. Block choice never changes results — only speed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bitops import PACK_BITS, PACKED_DTYPE, pad_packed_operands
from repro.kernels import autotune
from repro.kernels import direct_conv as direct_kernel
from repro.kernels import fused_gemm as fused_kernel
from repro.kernels import megakernel as mega_kernel
from repro.kernels import pack as pack_kernel
from repro.kernels import unpack_gemm as unpack_kernel
from repro.kernels import xnor_gemm as xnor_kernel
from repro.kernels.autotune import AUTO


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def xnor_gemm(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    *,
    block_m: int | str = AUTO,
    block_n: int | str = AUTO,
    block_kw: int | str = AUTO,
    word_group: int | str = AUTO,
    accum: str = "loop",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Padded, dispatching xnor-popcount GEMM. int32 [M, N] output."""
    if wp.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    block_m, block_n, block_kw, word_group = autotune.resolve_gemm_blocks(
        "xnor_gemm", wp.shape[0], wp.shape[1], xp.shape[1],
        block_m, block_n, block_kw, word_group,
    )
    wp_p, xp_p, m, n = pad_packed_operands(wp, xp, block_m, block_n, block_kw)
    out = xnor_kernel.xnor_gemm(
        wp_p, xp_p, k_bits,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        word_group=word_group, accum=accum,
        interpret=interpret,
    )
    return out[:m, :n]


def unpack_gemm(
    wp: jnp.ndarray,
    x: jnp.ndarray,
    *,
    block_m: int | str = AUTO,
    block_n: int | str = AUTO,
    block_kw: int | str = AUTO,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Packed-weight x real-input GEMM (MXU variant). [M, N] output.

    Blocks default to ``"auto"`` like every other wrapper (tuned
    ``"unpack_gemm"`` cache entry, else the unpack-MXU VMEM-model
    heuristic — the in-VMEM unpacked ±1 tile makes its footprint much
    steeper in ``block_kw`` than the xnor kernels'), and explicit ints
    are clamped to the padded problem shape so ragged layers (the
    10-output CIFAR head) never trip the kernel's divisibility asserts.
    """
    if wp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed weights must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    m, kw = wp.shape
    k, n = x.shape
    block_m, block_n, block_kw, _ = autotune.resolve_gemm_blocks(
        "unpack_gemm", m, kw, n,
        block_m, block_n, block_kw, autotune.DEFAULT_WORD_GROUP,
        unpack=True,
    )
    pm = -m % block_m
    pn = -n % block_n
    pkw = -kw % block_kw
    wp_p = jnp.pad(wp, ((0, pm), (0, pkw))) if (pm or pkw) else wp
    # zero-padded weight words unpack to -1s; zero-pad x rows so the
    # padded K region contributes -1 * 0 = 0.
    x_p = jnp.pad(x, ((0, pkw * PACK_BITS), (0, pn))) if (pkw or pn) else x
    out = unpack_kernel.unpack_gemm(
        wp_p, x_p,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:m, :n]


def fused_xnor_gemm(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int | str = AUTO,
    block_n: int | str = AUTO,
    block_kw: int | str = AUTO,
    word_group: int | str = AUTO,
    accum: str = "loop",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Padded, dispatching fused binary layer (DESIGN.md §4).

    Packed [M, KW] x packed [KW, N] with per-row affine ``a, b [M]``
    -> packed int32 [ceil(M/32), N]: the epilogue computes
    ``sign(a*(2*popcount-k_bits) + b)`` and repacks along M in one
    launch. ``k_bits`` is the TRUE contraction length; bit-level K pads
    must be xnor-neutral (weight bits -1, activation bits +1). Output
    rows past M inside the last word are +1 bits (the next layer's
    weight-pad correction consumes them exactly).
    """
    if wp.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    m, kw = wp.shape
    _, n = xp.shape
    block_m, block_n, block_kw, word_group = autotune.resolve_gemm_blocks(
        "fused_xnor_gemm", m, kw, n,
        block_m, block_n, block_kw, word_group, fused=True,
    )
    wp_p, xp_p, _, _ = pad_packed_operands(wp, xp, block_m, block_n, block_kw)
    pm = wp_p.shape[0] - m
    # padded output rows: a=0 kills the garbage dot, b=+1 pins the bit to 1.
    a_p = jnp.pad(a.astype(jnp.float32), (0, pm))[:, None]
    b_p = jnp.pad(b.astype(jnp.float32), (0, pm), constant_values=1.0)[:, None]
    out = fused_kernel.fused_xnor_gemm(
        wp_p, xp_p, k_bits, a_p, b_p,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        word_group=word_group, accum=accum,
        interpret=interpret,
    )
    return out[: -(-m // PACK_BITS), :n]


def _pad_direct_conv_operands(wp, xp, pad, kh, kw, stride, block_d,
                              word_group, *, kernel):
    """Spatial all-ones border + D padding for the direct-conv kernels.

    Returns (wp_p, xpad, d, block_d, word_group): ``block_d`` resolves
    via the autotuner when ``"auto"`` and is always clamped to the
    padded-D extent, so test-scale calls never tile a 128-row block for
    a 10-channel conv.
    """
    d = wp.shape[0]
    if pad:
        xp = jnp.pad(xp, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     constant_values=-1)
    _, hp, wp_sp, cw = xp.shape
    ow = (wp_sp - kw) // stride + 1
    block_d, word_group = autotune.resolve_conv_block_d(
        kernel, d, hp, wp_sp, cw, kh, kw, ow, block_d, word_group,
    )
    pd = -d % block_d
    wp_p = jnp.pad(wp, ((0, pd), (0, 0))) if pd else wp
    return wp_p, xp, d, block_d, word_group


def fused_direct_conv(
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
    block_d: int | str = AUTO,
    word_group: int | str = AUTO,
    accum: str = "loop",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Padded, dispatching fused direct conv (DESIGN.md §5).

    Channel-packed map ``[N, H, W, CW]`` x tap-aligned packed filters
    ``[D, kH*kW*CW]`` with per-output-channel affine ``a, b [D]`` ->
    packed ``[N, OH, OW, ceil(D/32)]``: window gather straight from the
    map in VMEM, xnor-popcount, ``sign(a*dot + b)``, repack along D —
    the im2col patch matrix never reaches HBM. Spatial borders pad with
    all-ones words; rows past the true D get ``a=0, b=+1`` pinning their
    bits to the activation-pad convention, as in ``fused_xnor_gemm``.
    """
    if wp.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    wp_p, xpad, d, block_d, word_group = _pad_direct_conv_operands(
        wp, xp, pad, kh, kw, stride, block_d, word_group,
        kernel="fused_direct_conv",
    )
    pd = wp_p.shape[0] - d
    a_p = jnp.pad(a.astype(jnp.float32), (0, pd))[:, None]
    b_p = jnp.pad(b.astype(jnp.float32), (0, pd), constant_values=1.0)[:, None]
    out = direct_kernel.fused_direct_conv(
        wp_p, xpad, k_bits, a_p, b_p,
        kh=kh, kw=kw, stride=stride, block_d=block_d,
        word_group=word_group, accum=accum, interpret=interpret,
    )
    return out[..., : -(-d // PACK_BITS)]


def direct_conv(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    block_d: int | str = AUTO,
    word_group: int | str = AUTO,
    accum: str = "loop",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Padded, dispatching direct-conv ±1 dot: int32 ``[N, OH, OW, D]``.

    The epilogue-free sibling of :func:`fused_direct_conv` for float-
    boundary call sites (unfused PACKED conv): bias/alpha/BN stay with
    the caller. Same operands and window-gather pipeline.
    """
    if wp.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    wp_p, xpad, d, block_d, word_group = _pad_direct_conv_operands(
        wp, xp, pad, kh, kw, stride, block_d, word_group,
        kernel="direct_conv",
    )
    out = direct_kernel.direct_conv_dot(
        wp_p, xpad, k_bits,
        kh=kh, kw=kw, stride=stride, block_d=block_d,
        word_group=word_group, accum=accum, interpret=interpret,
    )
    return out[..., :d]


def bireal_block_conv(
    wp: jnp.ndarray,
    xp: jnp.ndarray,
    k_bits: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    cpos: jnp.ndarray,
    r: jnp.ndarray,
    *,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    pad: int = 1,
    block_d: int | str = AUTO,
    word_group: int | str = AUTO,
    interpret: bool | None = None,
    name: str = "bireal_block_conv",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Padded, dispatching direct conv with the residual epilogue
    (DESIGN.md §5.1): ``y = a*(dot - c_pos) + b + r`` and ``sign(y)``.

    Channel-packed map ``[N, H, W, CW]`` x tap-aligned filters ``[D,
    kH*kW*CW]``, per-channel ``a, b [D]``, border classes ``cpos [3, D,
    3]`` (``bitops.border_correction``) and the f32 shortcut ``r [N,
    OH, OW, D]`` -> ``(y f32 [N, OH, OW, D], packed [N, OH, OW,
    ceil(D/32)])``. The map pads with all-ones words; ``c_pos`` makes
    the dot the zero-padded one. ``name`` names the launch.
    """
    if wp.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    wp_p, xpad, d, block_d, word_group = _pad_direct_conv_operands(
        wp, xp, pad, kh, kw, stride, block_d, word_group,
        kernel="bireal_block_conv",
    )
    pd = wp_p.shape[0] - d
    a_p = jnp.pad(a.astype(jnp.float32), (0, pd))[:, None]
    b_p = jnp.pad(b.astype(jnp.float32), (0, pd), constant_values=1.0)[:, None]
    if pd:
        cpos = jnp.pad(cpos, ((0, 0), (0, pd), (0, 0)))
        r = jnp.pad(r, ((0, 0), (0, 0), (0, 0), (0, pd)))
    y, packed = direct_kernel.residual_direct_conv(
        wp_p, xpad, k_bits, a_p, b_p, cpos, r,
        kh=kh, kw=kw, stride=stride, block_d=block_d,
        word_group=word_group, interpret=interpret, name=name,
    )
    return y[..., :d], packed[..., : -(-d // PACK_BITS)]


def pack_rows(
    x: jnp.ndarray,
    *,
    block_kw: int = 8,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """[K, N] -> [K/32, N] packed. K must be a multiple of 32; N padded."""
    interpret = _default_interpret() if interpret is None else interpret
    k, n = x.shape
    if k % PACK_BITS != 0:
        raise ValueError(f"K={k} must be a multiple of {PACK_BITS}")
    kw = k // PACK_BITS
    # A word tile that does not divide KW would leave a ragged last
    # block; the whole KW is always a legal tile.
    bkw = block_kw if kw % block_kw == 0 else kw
    pn = -n % block_n
    x_p = jnp.pad(x, ((0, 0), (0, pn))) if pn else x
    out = pack_kernel.pack_rows(
        x_p, block_kw=bkw, block_n=block_n, interpret=interpret
    )
    return out[:, :n]


RAGGED_TILE_N = 8  # sublane-multiple batch tile of the ragged chain path


def megakernel_chain(
    w_stack: jnp.ndarray,
    a_stack: jnp.ndarray,
    b_stack: jnp.ndarray,
    k_bits: tuple[int, ...],
    xp: jnp.ndarray,
    m_out: int,
    *,
    final_wp: jnp.ndarray | None = None,
    final_k_bits: int = 0,
    block_n: int | str = AUTO,
    word_group: int | str = AUTO,
    ragged_tile: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Padded, dispatching megakernel chain (DESIGN.md §8): ``L``
    stacked fused binary layers — plus an optional epilogue-free final
    GEMM — in ONE launch, weights VMEM-resident, packed activations
    ping-ponged in VMEM scratch.

    ``w_stack [L, M_max, KW_max]`` / ``a_stack`` / ``b_stack [L,
    M_max]`` come from ``repro.core.layers.stack_chain_layers`` (pad
    rows ``a=0, b=+1``; pad weight words zero). ``xp [KW_in, N]`` is
    the packed input (K pads +1, the PR-1 convention); this wrapper
    grows it to the scratch height ``KW_act = max(KW_max, M_max/32)``
    with all-ones words and pads N to the batch tile. ``k_bits`` are
    the TRUE per-layer contraction lengths. Returns packed
    ``[ceil(m_out/32), N]`` — or with ``final_wp [Mf, KWf]`` the exact
    int32 ±1 dot ``[Mf, N]`` of the float-boundary head (``m_out`` is
    then ignored). ``block_n`` resolves via the ``"bnn_megakernel"``
    autotune entry / weights-resident VMEM heuristic.

    ``ragged_tile`` (DESIGN.md §9) switches on the ragged/masked-tail
    batch path for variable-extent dispatch (continuous batching): the
    batch pads only to the given tile multiple — ``block_n`` clamps to
    that tile-padded extent when it covers it in one grid step — instead
    of a full ``block_n`` rung; when the extent needs several tiles, the
    tail grid step hangs past the true batch and the kernel zeroes the
    overhanging output columns against a traced ``n_real``. Real columns
    stay bit-identical to the non-ragged path (asserted vs the XLA
    oracle in ``tests/test_megakernel.py``).
    """
    if w_stack.dtype != PACKED_DTYPE or xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    l, m_max, kw_max = w_stack.shape
    kw_in, n = xp.shape
    has_final = final_wp is not None
    mf = final_wp.shape[0] if has_final else 0
    block_n, word_group = autotune.resolve_megakernel_block_n(
        l, m_max, kw_max, n, block_n, word_group, final_m=mf,
    )
    # Group-align the stacked K axis (extra zero weight words against
    # all-ones activation rows are xnor-neutral) so the dynamic-trip
    # accumulator's slices can never clamp-and-double-count.
    pg = -kw_max % max(1, word_group)
    if pg:
        w_stack = jnp.pad(w_stack, ((0, 0), (0, 0), (0, pg)))
        kw_max += pg
    kw_act = max(kw_max, m_max // PACK_BITS)
    masked_tail = ragged_tile is not None
    if masked_tail:
        # Ragged path: pad N only to the batch-tile multiple, not the
        # full block_n rung. When the tile-padded extent fits in one
        # grid step, clamp block_n down to it (exact single tile, no
        # masking work wasted); otherwise run full block_n tiles and
        # let the kernel zero the tail overhang past n_real.
        tile = max(1, int(ragged_tile))
        n_tile = -(-n // tile) * tile
        if n_tile <= block_n:
            block_n = n_tile
            n_pad = n_tile
        else:
            n_pad = -(-n // block_n) * block_n
    else:
        n_pad = -(-n // block_n) * block_n
    pn = n_pad - n
    pkw = kw_act - kw_in
    if pkw or pn:
        xp = jnp.pad(xp, ((0, pkw), (0, pn)), constant_values=-1)
    fin = None
    if has_final:
        # M rows need no 32-alignment here (no repack on the final dot);
        # pad to the 8-row sublane multiple with zero weight words — the
        # garbage rows are sliced off below.
        pmf = -mf % 8
        fin = jnp.pad(final_wp, ((0, pmf), (0, 0))) if pmf else final_wp
    # Per-layer dynamic trip counts: each stacked layer walks only ITS
    # ceil(ceil(k/32) / word_group) K-word groups of the shared KW_max.
    kw_true = [-(-k // PACK_BITS) for k in k_bits]
    n_groups = [-(-kw_l // word_group) for kw_l in kw_true]
    out = mega_kernel.megakernel_chain(
        w_stack, a_stack, b_stack,
        jnp.asarray(k_bits, jnp.int32)[:, None],
        jnp.asarray(n_groups, jnp.int32)[:, None], xp, fin,
        jnp.full((1, 1), n, jnp.int32) if masked_tail else None,
        block_n=block_n, word_group=word_group,
        final_k_bits=final_k_bits, interpret=interpret,
    )
    rows = mf if has_final else -(-m_out // PACK_BITS)
    return out[:rows, :n]


def megakernel_conv_stage(
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
    word_group: int | str = AUTO,
    interpret: bool | None = None,
    name: str = "megakernel_conv_stage",
) -> jnp.ndarray:
    """Padded, dispatching conv-stage megakernel (DESIGN.md §8): the
    stage's fused direct convs + packed-OR maxpool in ONE launch, one
    program per image, intermediate maps never touching HBM.

    ``xp [N, H, W, CW]`` channel-packed; ``weights[l] [D_l, kH*kW*
    CW_l]`` tap-aligned TRUE-shape filters with 1-D ``a[l]``/``b[l]
    [D_l]`` folded affines (``pack_conv_fused`` layer dicts provide
    exactly these). This wrapper applies the all-ones spatial border
    and the ``a=0, b=+1`` D-padding to whole words; output channel
    words need no slicing — ``D_pad/32 == ceil(D/32)`` and the tail
    bits are +1, the activation-pad convention. Returns the stage's
    packed output map ``[N, OH', OW', ceil(D_last/32)]``.
    """
    if xp.dtype != PACKED_DTYPE:
        raise TypeError(f"packed operands must be {PACKED_DTYPE}")
    interpret = _default_interpret() if interpret is None else interpret
    if autotune._is_auto(word_group):
        word_group = autotune.DEFAULT_WORD_GROUP
    if pad:
        xp = jnp.pad(xp, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     constant_values=-1)
    ws, aps, bps = [], [], []
    for wl, al, bl in zip(weights, a, b):
        d = wl.shape[0]
        pd = -d % PACK_BITS
        ws.append(jnp.pad(wl, ((0, pd), (0, 0))) if pd else wl)
        aps.append(jnp.pad(al.astype(jnp.float32), (0, pd))[:, None])
        bps.append(jnp.pad(bl.astype(jnp.float32), (0, pd),
                           constant_values=1.0)[:, None])
    return mega_kernel.megakernel_conv_stage(
        xp, tuple(ws), tuple(aps), tuple(bps),
        k_bits=tuple(k_bits), kh=kh, kw=kw, pad=pad, pool=pool,
        word_group=int(word_group), interpret=interpret, name=name,
    )


__all__ = [
    "xnor_gemm",
    "unpack_gemm",
    "pack_rows",
    "fused_xnor_gemm",
    "fused_direct_conv",
    "direct_conv",
    "bireal_block_conv",
    "megakernel_chain",
    "megakernel_conv_stage",
]
