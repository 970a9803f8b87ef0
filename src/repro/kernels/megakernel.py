"""VMEM-resident megakernel: a whole chain of fused binary layers in
ONE Pallas launch (DESIGN.md §8).

The PR-1 fused pipeline made each interior binary layer one launch, but
packed activations still round-trip through HBM at every layer
boundary, and every boundary costs a kernel launch. Taken to the
paper's conclusion on TPU: the *entire* packed CIFAR BNN (~1.7 MB of
int32 weight words) fits comfortably in one core's ~16 MiB VMEM, so a
whole network *stage* can execute in a single launch with every
inter-layer activation living in VMEM scratch. Launch count and
inter-layer HBM traffic then scale with network stages, not layers.

Two kernels share the PR-1 epilogue (`popcount.sign_repack_m`) and the
broadcast-free accumulators (`popcount.accum_popcount_*`):

* :func:`megakernel_chain` — a GEMM chain (the FC trunk). Layer weights
  are stacked into one padded ``[L, M_max, KW_max]`` tensor (handed to
  the kernel word-major, ``[L, KW_max, M_max]``) with per-layer folded
  affines ``[L, M_max]``, ALL resident in VMEM across
  the grid (their block index is constant, so the pipeline fetches them
  once). The grid tiles the batch (N) dimension only; a
  ``lax.fori_loop`` over layers runs xnor-popcount -> folded-BN affine
  -> sign -> repack, with a ping-pong pair of VMEM scratch buffers
  (``buf[l % 2]`` -> ``buf[(l+1) % 2]``) carrying the packed
  activations between layers — no inter-layer HBM write, no per-layer
  launch. An optional epilogue-free final GEMM (the float-boundary
  10-class head) runs after the loop in the same launch, emitting the
  exact int32 ±1 dot.

* :func:`megakernel_conv_stage` — a conv stage (conv [+ conv] +
  packed-OR maxpool) via the PR-2 direct-conv path: one program per
  image holds the whole spatially-pre-padded channel-packed map in
  VMEM, stages every 3x3 tap of the FULL image as word rows in VMEM
  scratch (the im2col patch matrix never reaches HBM), re-borders each
  intermediate map in a VMEM scratch map, and ORs the last map's
  stride-2 rows into the pooled output; only the pooled packed map of
  the LAST conv is written back to HBM.

Padding conventions are exactly PR-1's, applied per stacked layer:
K-words past a layer's true ``kw`` are zero in the weights and
all-ones in the activations (xnor-neutral); output rows past a layer's
true ``m`` carry ``a=0, b=+1``, pinning their bits to the
activation-pad convention — so the next stacked layer consumes the
scratch buffer unchanged and every kernel takes TRUE ``k_bits``.

VMEM budget (CIFAR BNN FC trunk, block_n=128):
  w stack   2*1024*256*4   = 2 MiB    (resident across the whole grid)
  a, b      2*2*1024*4     = 16 KiB   ([L, M, 1] columns, lane-padded)
  ping-pong 2*256*128*4    = 256 KiB
  acc/y     3*1024*128*4   = 1.5 MiB  (popcount word term, acc, f32 y)
  final     16*32*4 + out  = ~10 KiB
~3.8 MiB by this model; the TPU compiler's own count for a v5e is
~4.8 MiB of scoped VMEM (the affine columns pad to 128 lanes), and
every CIFAR conv stage needs less (`tests/test_tpu_compile.py`
compiles them all).
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
    accum_popcount_km_dyn,
    sign_repack_m,
)


def _chain_kernel(
    wt_ref, a_ref, b_ref, kb_ref, ng_ref, x_ref, *rest,
    n_layers: int, word_group: int, has_final: bool,
    final_k_bits: int, masked: bool,
):
    if masked:
        nr_ref, rest = rest[0], rest[1:]
    else:
        nr_ref = None
    if has_final:
        wft_ref, o_ref, buf_ref = rest
    else:
        wft_ref = None
        o_ref, buf_ref = rest
    rows = wt_ref.shape[2] // PACK_BITS            # packed words of M_max
    kw_act = buf_ref.shape[1]

    # Stage the batch tile of packed input activations into ping-pong
    # slot 0; the loop alternates slots so layer l reads buf[l % 2] and
    # writes buf[(l+1) % 2] — packed activations never leave VMEM.
    buf_ref[0] = x_ref[...]

    def layer(l, carry):
        # Dynamic trip count: a ragged layer walks ITS K-word groups,
        # not the stack-wide KW_max (pad groups would contribute zero
        # but still cost full-tile popcounts).
        acc = accum_popcount_km_dyn(
            wt_ref, buf_ref, ng_ref[l], word_group=word_group,
            w_lead=(l,), x_lead=(l % 2,),
        )
        dot = (2 * acc - kb_ref[l]).astype(jnp.float32)
        y = a_ref[l] * dot + b_ref[l]              # [m_max, bn]
        nxt = (l + 1) % 2
        buf_ref[nxt, pl.ds(0, rows), :] = sign_repack_m(y)  # [m_max/32, bn]
        if kw_act > rows:
            # Rows past m_max/32 must be all-ones (activation-pad words)
            # for the next layer's zero weight words to be xnor-neutral.
            buf_ref[nxt, pl.ds(rows, kw_act - rows), :] = jnp.full(
                (kw_act - rows, buf_ref.shape[2]), -1, jnp.int32
            )
        return carry

    lax.fori_loop(0, n_layers, layer, 0)
    last = n_layers % 2
    if has_final:
        # Float-boundary head: epilogue-free exact ±1 dot, same int32
        # result as a standalone xnor_gemm on the chain's output.
        acc = accum_popcount_km(wft_ref, buf_ref, word_group=word_group,
                                x_lead=(last,))
        out = 2 * acc - jnp.int32(final_k_bits)
    else:
        out = buf_ref[last, pl.ds(0, rows), :]
    if masked:
        # Ragged masked tail (DESIGN.md §9): the batch extent is only
        # tile-padded, so the last grid step may hang past the true
        # batch — zero every column at/after n_real (columns are
        # per-sample independent, so the pad columns' garbage never
        # touched a real column; this just pins their output).
        bn = out.shape[1]
        cols = pl.program_id(0) * bn + lax.broadcasted_iota(
            jnp.int32, (1, bn), 1
        )
        out = jnp.where(cols < nr_ref[0], out, 0)
    o_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "word_group", "final_k_bits", "interpret"),
)
def megakernel_chain(
    w_stack: jnp.ndarray,
    a_stack: jnp.ndarray,
    b_stack: jnp.ndarray,
    k_bits: jnp.ndarray,
    n_groups: jnp.ndarray,
    xp: jnp.ndarray,
    final_wp: jnp.ndarray | None = None,
    n_real: jnp.ndarray | None = None,
    *,
    block_n: int = 128,
    word_group: int = DEFAULT_WORD_GROUP,
    final_k_bits: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Run ``L`` stacked fused binary layers (+ optional final GEMM) in
    one launch.

    ``w_stack``: packed int32 ``[L, M_max, KW_max]`` (M_max % 32 == 0,
    KW_max % word_group == 0; rows past a layer's true ``m`` zero,
    K-words past its true ``kw`` zero). ``a_stack``/``b_stack``: f32
    ``[L, M_max]`` folded affines (pad rows ``a=0, b=+1``). ``k_bits``:
    int32 ``[L, 1]`` TRUE contraction lengths; ``n_groups``: int32
    ``[L, 1]`` per-layer K-word-group trip counts
    (``ceil(ceil(k/32) / word_group)``). ``xp``: packed ``[KW_act, N]``
    activations, ``KW_act = max(KW_max, M_max/32)`` with all-ones pad
    rows; N must divide by ``block_n``. Returns packed ``[M_max/32,
    N]`` — or, when ``final_wp [Mf, KWf]`` is given, the final layer's
    int32 ±1 dot ``[Mf, N]`` (``KWf <= KW_act``; ``final_k_bits`` its
    true K).

    Weights/affines use constant-index BlockSpecs: fetched once,
    VMEM-resident across the whole batch grid.

    ``n_real`` (optional int32 ``[1, 1]``) enables the ragged
    masked-tail path (DESIGN.md §9): N is then a tile-padded extent
    rather than a bucket rung, and every output column at/after
    ``n_real`` is zeroed in-kernel by the tail grid step — the
    pad-column garbage (columns are per-sample independent) never
    leaves the launch. Real columns are bit-identical to the unmasked
    path.
    """
    l, m_max, kw_max = w_stack.shape
    kw_act, n = xp.shape
    assert m_max % PACK_BITS == 0, m_max
    assert kw_max % max(1, word_group) == 0, (kw_max, word_group)
    assert kw_act >= max(kw_max, m_max // PACK_BITS), (kw_act, kw_max, m_max)
    assert n % block_n == 0, (n, block_n)
    assert a_stack.shape == (l, m_max) and b_stack.shape == (l, m_max)
    assert k_bits.shape == (l, 1), k_bits.shape
    assert n_groups.shape == (l, 1), n_groups.shape

    has_final = final_wp is not None
    if has_final:
        mf, kwf = final_wp.shape
        assert kwf <= kw_act, (kwf, kw_act)
        out_rows = mf
    else:
        out_rows = m_max // PACK_BITS

    masked = n_real is not None
    kernel = functools.partial(
        _chain_kernel, n_layers=l, word_group=word_group,
        has_final=has_final, final_k_bits=final_k_bits, masked=masked,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((l, kw_max, m_max), lambda i: (0, 0, 0)),
        pl.BlockSpec((l, m_max, 1), lambda i: (0, 0, 0)),
        pl.BlockSpec((l, m_max, 1), lambda i: (0, 0, 0)),
        smem,
        smem,
        pl.BlockSpec((kw_act, block_n), lambda i: (0, i)),
    ]
    # Weights go in word-major (popcount.py); the affines as [L, M, 1]
    # columns so a layer's row index yields its [M, 1] epilogue operand.
    operands = [
        jnp.swapaxes(w_stack, 1, 2),
        a_stack.astype(jnp.float32)[..., None],
        b_stack.astype(jnp.float32)[..., None],
        k_bits.astype(jnp.int32).reshape(l),
        n_groups.astype(jnp.int32).reshape(l),
        xp,
    ]
    if masked:
        assert n_real.shape == (1, 1), n_real.shape
        in_specs.append(smem)
        operands.append(n_real.astype(jnp.int32).reshape(1))
    if has_final:
        in_specs.append(pl.BlockSpec((kwf, mf), lambda i: (0, 0)))
        operands.append(final_wp.T)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((out_rows, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((out_rows, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((2, kw_act, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="megakernel_fc_trunk",
    )(*operands)


def _stage_windows(src, lead: tuple, xs_ref, *, kh: int, kw: int, oh: int,
                   ow: int):
    """Gather every kH*kW tap of a whole padded map ``src[*lead] [Hp,
    Wp, CW]`` (a VMEM ref) into ``xs_ref`` as word rows: row
    ``(i*kW + j)*CW + cw`` holds tap (i, j), word cw of all ``oh*ow``
    output pixels — the ``[KW, N]`` operand layout of
    ``accum_popcount_km``, in the tap-major word order of the
    pack_conv_aligned filters."""
    cw = src.shape[-1]
    for i in range(kh):
        for j in range(kw):
            tap = src[lead + (slice(i, i + oh), slice(j, j + ow))]  # [oh, ow, CW]
            row = (i * kw + j) * cw
            xs_ref[row:row + cw, :] = tap.reshape(oh * ow, cw).T


def _conv_stage_kernel(
    *refs,
    n_layers: int, kh: int, kw: int, pad: int, k_bits: tuple[int, ...],
    pool: bool, word_group: int,
):
    x_ref = refs[0]
    o_ref = refs[1 + 3 * n_layers]
    xs_ref, *maps = refs[2 + 3 * n_layers:]
    # The whole padded map, in VMEM: x_ref[0] [Hp, Wp, CW], then each
    # re-bordered intermediate map.
    src, lead = x_ref, (0,)
    for l in range(n_layers):
        wt_ref, a_ref, b_ref = refs[1 + 3 * l : 4 + 3 * l]
        hp, wp_sp, _ = src.shape[len(lead):]
        oh, ow = hp - kh + 1, wp_sp - kw + 1
        dw = wt_ref.shape[1] // PACK_BITS
        _stage_windows(src, lead, xs_ref, kh=kh, kw=kw, oh=oh, ow=ow)
        acc = accum_popcount_km(wt_ref, xs_ref, word_group=word_group)
        dot = (2 * acc - jnp.int32(k_bits[l])).astype(jnp.float32)
        y = a_ref[...] * dot + b_ref[...]          # [d_pad, oh*ow]
        words = sign_repack_m(y).T.reshape(oh, ow, dw)
        dst = maps[l] if l < len(maps) else None
        if l + 1 < n_layers:
            # Re-grow the all-ones spatial border for the next conv —
            # in VMEM, never via HBM.
            dst[...] = jnp.full(dst.shape, -1, jnp.int32)
            dst[pad:pad + oh, pad:pad + ow, :] = words
            src, lead = dst, ()
        elif pool:
            # 2x2 packed maxpool = bitwise OR of the window words (§3),
            # read back as stride-2 rows of the staged output map.
            dst[...] = words
            for ph in range(oh // 2):
                out = None
                for di in (0, 1):
                    for dj in (0, 1):
                        v = dst[2 * ph + di, pl.ds(dj, ow // 2, stride=2), :]
                        out = v if out is None else out | v
                o_ref[0, ph] = out
        else:
            o_ref[0] = words


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_bits", "kh", "kw", "pad", "pool", "word_group", "interpret",
        "name",
    ),
)
def megakernel_conv_stage(
    xpad: jnp.ndarray,
    weights: tuple[jnp.ndarray, ...],
    a: tuple[jnp.ndarray, ...],
    b: tuple[jnp.ndarray, ...],
    *,
    k_bits: tuple[int, ...],
    kh: int = 3,
    kw: int = 3,
    pad: int = 1,
    pool: bool = True,
    word_group: int = DEFAULT_WORD_GROUP,
    interpret: bool = False,
    name: str = "megakernel_conv_stage",
) -> jnp.ndarray:
    """One conv stage — ``len(weights)`` fused direct convs (+ optional
    packed-OR maxpool) — in one launch, one program per image.

    ``xpad``: channel-packed map ``[N, Hp, Wp, CW]`` with its spatial
    all-ones border already applied (stride 1; Hp = H + 2*pad; every
    later conv of the stage is re-bordered by ``pad`` in VMEM).
    ``weights[l]``: tap-aligned packed filters ``[D_pad_l, kH*kW*CW_l]``
    with ``D_pad_l % 32 == 0`` and ``CW_l`` = words/pixel of that
    layer's input (``CW_0 = CW``; ``CW_{l+1} = D_pad_l/32``).
    ``a[l]``/``b[l]``: f32 ``[D_pad_l, 1]`` (pad rows ``a=0, b=+1``).
    ``k_bits[l]``: TRUE ``kH*kW*C_l``. Returns the stage's packed
    output map ``[N, OH', OW', D_pad_last/32]`` (halved spatially when
    ``pool``). Filters/affines are VMEM-resident across the batch grid.
    ``name`` is the launch's name in the compiled program and in a
    device trace (the network's stages carry their index in it).
    """
    n, hp, wp_sp, cw = xpad.shape
    n_layers = len(weights)
    assert n_layers >= 1 and len(a) == len(b) == len(k_bits) == n_layers
    cw_in = cw
    for l, wl in enumerate(weights):
        d_pad, kwords = wl.shape
        assert d_pad % PACK_BITS == 0, (l, d_pad)
        assert kwords == kh * kw * cw_in, (l, wl.shape, kh, kw, cw_in)
        assert a[l].shape == (d_pad, 1) and b[l].shape == (d_pad, 1)
        cw_in = d_pad // PACK_BITS
    oh, ow = hp - kh + 1, wp_sp - kw + 1
    assert n_layers == 1 or (oh, ow) == (hp - 2 * pad, wp_sp - 2 * pad), (
        "stage convs must preserve the spatial size", hp, wp_sp, pad)
    dw_last = weights[-1].shape[0] // PACK_BITS
    out_h, out_w = (oh // 2, ow // 2) if pool else (oh, ow)

    in_specs = [pl.BlockSpec((1, hp, wp_sp, cw), lambda i: (i, 0, 0, 0))]
    operands: list = [xpad]
    for wl, al, bl in zip(weights, a, b):
        d_pad, kwords = wl.shape
        in_specs += [
            pl.BlockSpec((kwords, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ]
        # word-major filters (popcount.py)
        operands += [wl.T, al.astype(jnp.float32), bl.astype(jnp.float32)]
    # VMEM scratch: the gathered window rows (shared by every layer —
    # all convs of a stage see the same oh*ow pixels), the re-bordered
    # map between convs, and the last conv's map staged for the pool.
    kwords_max = max(wl.shape[1] for wl in weights)
    scratch = [pltpu.VMEM((kwords_max, oh * ow), jnp.int32)]
    for wl in weights[:-1]:
        scratch.append(pltpu.VMEM(
            (hp, wp_sp, wl.shape[0] // PACK_BITS), jnp.int32))
    if pool:
        scratch.append(pltpu.VMEM((oh, ow, dw_last), jnp.int32))
    kernel = functools.partial(
        _conv_stage_kernel, n_layers=n_layers, kh=kh, kw=kw, pad=pad,
        k_bits=tuple(k_bits), pool=pool, word_group=word_group,
    )
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, out_h, out_w, dw_last), lambda i: (i, 0, 0, 0),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n, out_h, out_w, dw_last), jnp.int32
        ),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name=name,
    )(*operands)
