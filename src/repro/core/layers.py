"""Functional Bit layers: the paper's kernel as a composable module.

Everything is functional (params are plain pytrees) so the layers nest
into pjit'd programs without a framework dependency. Three execution
modes per layer (``QuantMode``): FLOAT control group, FAKE_QUANT
training with STE, PACKED 1-bit inference.

The PACKED path has two engines:
  * ``engine="xnor"``   — paper-faithful Pallas xnor-popcount kernel
                          (activations binarized + packed on the fly),
  * ``engine="unpack"`` — TPU-native MXU kernel, weight-only packing,
  * ``engine="xla"``    — pure-XLA unpack+dot with packed storage; the
                          only engine usable inside large SPMD programs
                          on this CPU container (HLO still reflects
                          int32 weight traffic, which the roofline reads).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitops
from repro.core.binarize import QuantMode, binarize_activations, binarize_weights
from repro.core.im2col import col2im, filters_to_matrix, im2col
from repro.kernels import ops as kops
from repro.kernels.autotune import AUTO, block_kwargs


# Matmul precision for real-valued operands: float32 on every backend.
_F32_DOT = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class BitLinearConfig:
    mode: QuantMode = QuantMode.FAKE_QUANT
    binarize_acts: bool = True          # False => weight-only (LM serving)
    use_scale: bool = False             # XNOR-Net alpha (beyond-paper)
    engine: str = "xla"                 # "xnor" | "unpack" | "xla"
    conv_impl: str = "im2col"           # "im2col" | "direct" (PACKED convs)
    compute_dtype: object = jnp.float32
    # "auto" (autotune cache / VMEM heuristic) or a kernels.autotune
    # BlockConfig; forwarded to every Pallas kernel this layer launches.
    blocks: object = AUTO


def init_linear(key, in_features: int, out_features: int, *, bias: bool = True,
                dtype=jnp.float32) -> dict:
    std = (2.0 / in_features) ** 0.5
    p = {"w": jax.random.normal(key, (out_features, in_features), dtype) * std}
    if bias:
        p["b"] = jnp.zeros((out_features,), dtype)
    return p


def pack_linear_params(params: dict, *, use_scale: bool = False) -> dict:
    """Latent float params -> packed inference params (paper §3.1)."""
    w = params["w"]  # [out, in] (or stacked [..., out, in] for MoE experts)
    k = w.shape[-1]
    pad = -k % bitops.PACK_BITS
    widths = [(0, 0)] * (w.ndim - 1) + [(0, pad)]
    wm = jnp.pad(w, widths, constant_values=-1.0) if pad else w
    packed = {"w_packed": bitops.pack_bits(wm, axis=-1)}
    if use_scale:
        packed["alpha"] = jnp.mean(jnp.abs(w), axis=-1)  # [out]
    if "b" in params:
        packed["b"] = params["b"]
    return packed


def _packed_matmul(wp, x2d, k_orig, cfg: BitLinearConfig):
    """x2d: [B, K_orig] real, wp: [out, K_pad/32]. Returns [B, out] float.

    When K_orig isn't a multiple of 32 the packed weights carry
    ``n_pad = K_pad - K_orig`` trailing -1 bits. The xnor engine pads the
    activations with +1 there (each padded position then contributes
    exactly -1 to the ±1 dot product) and adds ``n_pad`` back — an exact
    correction. The unpack engines pad activations with 0 instead, which
    contributes nothing.
    """
    k_pad = wp.shape[1] * bitops.PACK_BITS
    n_pad = k_pad - k_orig
    if cfg.engine == "xnor":
        # Paper path: binarize + pack activations, xnor-popcount GEMM.
        xin = jnp.clip(x2d, -1, 1)
        if n_pad:
            xin = jnp.pad(xin, ((0, 0), (0, n_pad)), constant_values=1.0)
        xp = kops.pack_rows(xin.T)                        # [K_pad/32, B]
        out = kops.xnor_gemm(
            wp, xp, k_pad, **block_kwargs(cfg.blocks)
        )                                                 # [out, B] int32
        out = out + jnp.int32(n_pad)
        return out.T.astype(cfg.compute_dtype)
    # unpack engines: binarize FIRST, then zero-pad — padded positions
    # must stay exactly 0 so the -1 pad weights contribute nothing.
    xin = x2d.astype(cfg.compute_dtype)
    if cfg.binarize_acts:
        xin = jnp.sign(xin) + (xin == 0).astype(cfg.compute_dtype)
    if n_pad:
        xin = jnp.pad(xin, ((0, 0), (0, n_pad)))
    if cfg.engine == "unpack":
        return kops.unpack_gemm(wp, xin.T).T.astype(cfg.compute_dtype)
    # "xla": packed storage, unpack+dot lowered by XLA (SPMD-safe).
    return bitops.packed_matmul_unpack(
        wp, xin.T, compute_dtype=cfg.compute_dtype
    ).T.astype(cfg.compute_dtype)


def bit_linear(params: dict, x: jnp.ndarray, cfg: BitLinearConfig) -> jnp.ndarray:
    """y = x @ W^T (+ b), under the configured quantization mode.

    x: [..., in_features].
    """
    lead = x.shape[:-1]
    k = x.shape[-1]

    if cfg.mode == QuantMode.PACKED:
        wp = params["w_packed"]
        x2d = x.reshape(-1, k)
        y = _packed_matmul(wp, x2d, k, cfg)
        if "alpha" in params:
            y = y * params["alpha"][None, :].astype(y.dtype)
        y = y.reshape(*lead, -1)
    else:
        w = params["w"]
        if cfg.mode == QuantMode.FAKE_QUANT:
            wq, alpha = binarize_weights(
                w, scale_axis=-1 if cfg.use_scale else None
            )
            xq = binarize_activations(x) if cfg.binarize_acts else x
            y = xq @ wq.astype(x.dtype).T
            if alpha is not None:
                y = y * alpha.reshape(1, -1).astype(y.dtype)
        else:  # FLOAT control group
            y = x @ w.astype(x.dtype).T
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Fused packed pipeline — BN-fold + sign + repack epilogue (DESIGN.md §3-4).
# ---------------------------------------------------------------------------

BN_EPS = 1e-4  # the ONE BatchNorm eps; core.bnn._batchnorm imports it


def fold_bn_params(
    bn: dict,
    *,
    bias: Optional[jnp.ndarray] = None,
    alpha: Optional[jnp.ndarray] = None,
    eps: float = BN_EPS,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Collapse inference BatchNorm (+ bias + XNOR-Net alpha) into the
    per-output-channel affine ``(a, b)`` the fused epilogue applies to
    the raw ±1 dot product (DESIGN.md §3):

        y  = alpha*dot + bias                      (layer output)
        z  = (y - mean) * gamma/sqrt(var+eps) + beta   (inference BN)
           = a*dot + b,   a = s*alpha,  b = s*(bias - mean) + beta,
                          s = gamma/sqrt(var+eps).

    ``sign(z)`` only needs ``a*dot + b``, so the float activation never
    has to exist. All inputs/outputs are per-channel vectors [out].
    """
    s = bn["gamma"] * lax.rsqrt(bn["var"] + eps)
    a = s * alpha if alpha is not None else s
    y0 = bias if bias is not None else jnp.zeros_like(s)
    b = s * (y0 - bn["mean"]) + bn["beta"]
    return a.astype(jnp.float32), b.astype(jnp.float32)


def pack_linear_fused(params: dict, bn: dict, *, use_scale: bool = False,
                      eps: float = BN_EPS) -> dict:
    """Pack weights AND fold the layer's BN/bias/alpha into ``(a, b)``."""
    packed = pack_linear_params(params, use_scale=use_scale)
    a, b = fold_bn_params(
        bn, bias=packed.pop("b", None), alpha=packed.pop("alpha", None),
        eps=eps,
    )
    packed["a"], packed["b"] = a, b
    return packed


def pack_conv_fused(params: dict, bn: dict, *, use_scale: bool = False,
                    eps: float = BN_EPS) -> dict:
    """Conv variant of :func:`pack_linear_fused` (same (a, b) math)."""
    packed = pack_conv_params(params, use_scale=use_scale)
    a, b = fold_bn_params(
        bn, bias=packed.pop("b", None), alpha=packed.pop("alpha", None),
        eps=eps,
    )
    packed["a"], packed["b"] = a, b
    return packed


def _fused_dispatch(wp, xpT, k_orig: int, a, b, engine: str,
                    blocks: object = AUTO):
    """[KW, N] packed acts -> [ceil(M/32), N] packed outputs."""
    if engine == "xnor":
        return kops.fused_xnor_gemm(
            wp, xpT, k_orig, a, b, **block_kwargs(blocks)
        )
    if engine == "xla":
        return bitops.fused_xnor_layer(wp, xpT, k_orig, a, b)
    raise ValueError(f"fused path has no engine {engine!r}")


def fused_bit_linear(packed: dict, xp: jnp.ndarray, k_orig: int,
                     *, engine: str = "xnor",
                     blocks: object = AUTO) -> jnp.ndarray:
    """Fused binary FC: packed acts in, packed acts out.

    xp: [batch, KW] int32 words (K-pad bits must be +1, the fused-output
    convention). Returns [batch, ceil(out/32)] int32 words of
    ``sign(a*(x·w) + b)`` — BN already applied via the folded affine.
    ``blocks``: "auto" or a ``kernels.autotune.BlockConfig``.
    """
    out = _fused_dispatch(
        packed["w_packed"], xp.T, k_orig, packed["a"], packed["b"], engine,
        blocks,
    )
    return out.T


def fused_bit_conv2d(
    packed: dict,
    xp: jnp.ndarray,
    k_orig: int,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    engine: str = "xnor",
    conv_impl: str = "im2col",
    blocks: object = AUTO,
) -> jnp.ndarray:
    """Fused binary conv: channel-packed maps in, channel-packed maps out.

    xp: [N, H, W, CW] int32 channel-packed words (CW = ceil(C/32); for
    C % 32 != 0 the tail bits must be +1 and the filters packed
    tap-aligned, see :func:`pack_conv_aligned` — with C % 32 == 0 the
    flat ``pack_conv_params`` layout is already tap-aligned). Spatial
    borders pad with all-ones words — the packed image of "zero-pad then
    sign" since sign(0) := +1. Returns [N, OH, OW, ceil(D/32)].

    ``conv_impl="im2col"`` lowers to the patch-matrix GEMM (paper §2.1);
    ``"direct"`` convolves the packed map in place (DESIGN.md §5) — the
    two are bit-identical on both engines.
    """
    if conv_impl == "direct":
        if engine == "xnor":
            return kops.fused_direct_conv(
                packed["w_packed"], xp, k_orig, packed["a"], packed["b"],
                kh=kh, kw=kw, stride=stride, pad=pad,
                **block_kwargs(blocks, conv=True),
            )
        if engine == "xla":
            return bitops.direct_conv_oracle(
                packed["w_packed"], xp, k_orig, packed["a"], packed["b"],
                kh=kh, kw=kw, stride=stride, pad=pad,
            )
        raise ValueError(f"direct conv has no engine {engine!r}")
    if conv_impl != "im2col":
        raise ValueError(f"unknown conv_impl {conv_impl!r}")
    patches, (oh, ow) = im2col(
        xp, kh, kw, stride=stride, pad=pad, pad_value=jnp.int32(-1)
    )
    n = patches.shape[0]
    kwords = patches.shape[-1]
    x2d = patches.reshape(n * oh * ow, kwords)
    out = _fused_dispatch(
        packed["w_packed"], x2d.T, k_orig, packed["a"], packed["b"], engine,
        blocks,
    )  # [DW, N*OH*OW]
    return col2im(out.T.reshape(n, oh * ow, -1), oh, ow)


# ---------------------------------------------------------------------------
# Megakernel executors — whole stages in one launch (DESIGN.md §8).
# ---------------------------------------------------------------------------

def stack_chain_layers(layers: list[dict]) -> dict:
    """Stack fused-layer params (``{"w_packed" [m, kw], "a", "b" [m]}``)
    into the megakernel chain's padded operands:

    ``{"w": [L, M_max, KW_max], "a": [L, M_max], "b": [L, M_max]}``

    with ``M_max = round_up(max m, 32)`` and ``KW_max = max kw``. Pad
    weight rows/words are zero; pad affine rows are ``a=0, b=+1`` — the
    epilogue then pins the padded output bits to +1, the activation-pad
    convention the next stacked layer's zero weight words consume
    xnor-neutrally (round-trip property-tested in
    ``tests/test_properties.py``).
    """
    m_max = max(
        -(-p["w_packed"].shape[0] // bitops.PACK_BITS) * bitops.PACK_BITS
        for p in layers
    )
    kw_max = max(p["w_packed"].shape[1] for p in layers)
    ws, as_, bs = [], [], []
    for p in layers:
        m, kw = p["w_packed"].shape
        ws.append(jnp.pad(p["w_packed"], ((0, m_max - m), (0, kw_max - kw))))
        as_.append(jnp.pad(p["a"].astype(jnp.float32), (0, m_max - m)))
        bs.append(jnp.pad(p["b"].astype(jnp.float32), (0, m_max - m),
                          constant_values=1.0))
    return {"w": jnp.stack(ws), "a": jnp.stack(as_), "b": jnp.stack(bs)}


def megakernel_fc_chain(
    stack: dict,
    xp: jnp.ndarray,
    k_bits: tuple[int, ...],
    m_out: int,
    *,
    final: Optional[dict] = None,
    final_k: int = 0,
    engine: str = "xnor",
    blocks: object = AUTO,
    ragged: bool = False,
) -> jnp.ndarray:
    """Run a whole FC trunk — stacked fused layers plus (optionally)
    the float-boundary head's GEMM — in one launch.

    ``stack`` comes from :func:`stack_chain_layers`; ``xp`` is
    ``[batch, KW_in]`` packed activations (K-pad bits +1). Without
    ``final``: returns ``[batch, ceil(m_out/32)]`` packed words. With
    ``final`` (a ``pack_linear_params`` dict): returns the head's
    float ``[batch, out]`` — exact int32 ±1 dot computed IN the launch,
    bias/alpha applied here in float, identical math (and identical
    int32 dot) to :func:`packed_act_linear`, so logits stay
    bit-identical to the per-layer chain.

    ``ragged`` (DESIGN.md §9) routes the xnor launch through the
    masked-tail batch path: N pads only to the ``RAGGED_TILE_N``
    sublane tile instead of a full ``block_n`` rung — the variable
    batch extents of continuous-batching dispatch then cost pad work
    proportional to the tile, not the rung. The XLA engine is already
    exact-N, so ``ragged`` is a no-op there; outputs stay bit-identical
    either way.
    """
    from repro.kernels.autotune import megakernel_block_kwargs

    fin_wp = final["w_packed"] if final is not None else None
    if engine == "xnor":
        out = kops.megakernel_chain(
            stack["w"], stack["a"], stack["b"], tuple(k_bits), xp.T, m_out,
            final_wp=fin_wp, final_k_bits=final_k,
            ragged_tile=kops.RAGGED_TILE_N if ragged else None,
            **megakernel_block_kwargs(blocks),
        )
    elif engine == "xla":
        out = bitops.megakernel_chain_xla(
            stack["w"], stack["a"], stack["b"], tuple(k_bits), xp.T, m_out,
            final_wp=fin_wp, final_k_bits=final_k,
        )
    else:
        raise ValueError(f"megakernel has no engine {engine!r}")
    if final is None:
        return out.T
    y = out.T.astype(jnp.float32)
    if "alpha" in final:
        y = y * final["alpha"][None, :].astype(y.dtype)
    if "b" in final:
        y = y + final["b"].astype(y.dtype)
    return y


def megakernel_conv_stage(
    layers: list[dict],
    xp: jnp.ndarray,
    k_bits: tuple[int, ...],
    *,
    kh: int = 3,
    kw: int = 3,
    pad: int = 1,
    pool: bool = True,
    engine: str = "xnor",
    blocks: object = AUTO,
    name: str = "megakernel_conv_stage",
) -> jnp.ndarray:
    """Run one conv stage — the stage's fused binary convs + packed-OR
    maxpool — in one launch (``engine="xnor"``) or via the chained
    pure-XLA direct-conv oracle (``engine="xla"``, SPMD-safe).

    ``layers``: ``pack_conv_fused`` dicts (tap-aligned ``w_packed``,
    folded ``a``/``b``); ``xp``: ``[N, H, W, CW]`` channel-packed map.
    Bit-identical to running :func:`fused_bit_conv2d` per layer and
    ``maxpool2_packed`` — the intermediate maps just never reach HBM.
    ``name`` names the Pallas launch (the xla engine has none).
    """
    from repro.kernels.autotune import megakernel_block_kwargs

    weights = tuple(p["w_packed"] for p in layers)
    a = tuple(p["a"] for p in layers)
    b = tuple(p["b"] for p in layers)
    if engine == "xnor":
        kwargs = megakernel_block_kwargs(blocks)
        kwargs.pop("block_n", None)  # batch grid is per-image already
        return kops.megakernel_conv_stage(
            xp, weights, a, b, tuple(k_bits), kh=kh, kw=kw, pad=pad,
            pool=pool, name=name, **kwargs,
        )
    if engine == "xla":
        return bitops.conv_stage_xla(
            xp, weights, a, b, tuple(k_bits), kh=kh, kw=kw, pad=pad,
            pool=pool,
        )
    raise ValueError(f"megakernel has no engine {engine!r}")


def packed_act_linear(packed: dict, xp: jnp.ndarray, k_orig: int,
                      *, engine: str = "xnor",
                      blocks: object = AUTO,
                      compute_dtype=jnp.float32) -> jnp.ndarray:
    """Float-boundary epilogue-free layer for pre-packed activations:
    the chain's LAST layer, whose output (logits) stays float.

    xp: [batch, KW] int32 words. Returns float [batch, out] =
    ``x·w (*alpha) (+bias)`` — identical math (and identical int32 dot)
    to the unfused PACKED path, so logits stay bit-identical.
    """
    wp = packed["w_packed"]
    if engine == "xnor":
        dot = kops.xnor_gemm(wp, xp.T, k_orig, **block_kwargs(blocks))
    elif engine == "xla":
        dot = bitops.xnor_popcount_matmul(wp, xp.T, k_orig)
    else:
        raise ValueError(f"fused path has no engine {engine!r}")
    y = dot.T.astype(compute_dtype)
    if "alpha" in packed:
        y = y * packed["alpha"][None, :].astype(y.dtype)
    if "b" in packed:
        y = y + packed["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Convolution — the paper's actual target layer (im2col forward graph, §2).
# ---------------------------------------------------------------------------

def init_conv(key, kh: int, kw: int, c_in: int, c_out: int, *, bias: bool = True,
              dtype=jnp.float32) -> dict:
    fan_in = kh * kw * c_in
    std = (2.0 / fan_in) ** 0.5
    p = {"w": jax.random.normal(key, (c_out, kh, kw, c_in), dtype) * std}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def pack_conv_params(params: dict, *, use_scale: bool = False) -> dict:
    """Filters [D, kH, kW, C] -> bitwise matrix [D, kH*kW*C/32] (§3.1:
    the weight 'manually skips im2col' and is stored packed)."""
    wm = filters_to_matrix(params["w"])
    k = wm.shape[1]
    pad = -k % bitops.PACK_BITS
    if pad:
        # -1-valued pad weights; _packed_matmul compensates exactly.
        wm = jnp.pad(wm, ((0, 0), (0, pad)), constant_values=-1.0)
    packed = {"w_packed": bitops.pack_bits(wm, axis=-1)}
    if use_scale:
        packed["alpha"] = jnp.mean(jnp.abs(wm[:, :k]), axis=-1)
    if "b" in params:
        packed["b"] = params["b"]
    return packed


def _direct_bit_conv2d(params, x, cfg, *, kh, kw, stride, pad):
    """PACKED conv without the im2col lowering (``conv_impl="direct"``).

    Binarizes + channel-packs the input ONCE (``[N, H, W, C/32]``) and
    convolves the packed map directly — the ``[N*OH*OW, kH*kW*C]`` patch
    matrix of the im2col path never exists. Requires C % 32 == 0 so the
    flat ``pack_conv_params`` filter layout coincides with the
    tap-aligned one the window gather walks (for ragged C, pack with
    :func:`pack_conv_aligned` and call the fused executor directly).
    """
    c = x.shape[-1]
    if c % bitops.PACK_BITS != 0:
        raise ValueError(
            f"conv_impl='direct' via bit_conv2d needs C % 32 == 0, got "
            f"C={c}; use conv_impl='im2col' (or pack_conv_aligned + "
            "fused_bit_conv2d)"
        )
    if cfg.engine not in ("xnor", "xla"):
        raise ValueError(
            f"conv_impl='direct' has no engine {cfg.engine!r} "
            "(packed-activation path: 'xnor' | 'xla')"
        )
    xp = bitops.pack_bits(jnp.clip(x, -1, 1), axis=-1)
    k_orig = kh * kw * c
    if cfg.engine == "xnor":
        dot = kops.direct_conv(
            params["w_packed"], xp, k_orig, kh=kh, kw=kw, stride=stride,
            pad=pad, **block_kwargs(cfg.blocks, conv=True),
        )
    else:
        dot = bitops.direct_conv_dot(
            params["w_packed"], xp, k_orig, kh=kh, kw=kw, stride=stride,
            pad=pad,
        )
    y = dot.astype(cfg.compute_dtype)
    if "alpha" in params:
        y = y * params["alpha"].astype(y.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def pack_conv_aligned(params: dict, *, use_scale: bool = False) -> dict:
    """Tap-aligned variant of :func:`pack_conv_params` for C % 32 != 0.

    Each tap's channel block is padded to whole words with -1 weights
    BEFORE packing, so filter word ``(h*kW + w)*ceil(C/32) + cw`` lines
    up with the channel-packed activation words of
    :func:`repro.core.bitops.pack_channels` (tail bits +1 — the pad
    pairs are xnor-neutral, so kernels still take the TRUE
    ``k_bits = kH*kW*C``). Identical to :func:`pack_conv_params` when
    C % 32 == 0. This is the layout the direct-conv kernels and the
    packed-im2col path both consume.
    """
    w = params["w"]  # [D, kH, kW, C]
    d, _, _, c = w.shape
    pad = -c % bitops.PACK_BITS
    wm = (
        jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pad)), constant_values=-1.0)
        if pad else w
    )
    packed = {"w_packed": bitops.pack_bits(wm.reshape(d, -1), axis=-1)}
    if use_scale:
        packed["alpha"] = jnp.mean(jnp.abs(w.reshape(d, -1)), axis=-1)
    if "b" in params:
        packed["b"] = params["b"]
    return packed


def bit_conv2d(
    params: dict,
    x: jnp.ndarray,
    cfg: BitLinearConfig,
    *,
    stride: int = 1,
    pad: int = 0,
    kh: Optional[int] = None,
    kw: Optional[int] = None,
) -> jnp.ndarray:
    """Conv via the paper's forward graph: im2col -> GEMM -> (+bias) -> col2im
    (``cfg.conv_impl="im2col"``), or the direct packed-window kernel that
    skips the patch matrix (``"direct"``, PACKED mode only).

    x: [N, H, W, C]. Returns [N, OH, OW, D].
    """
    if cfg.mode == QuantMode.PACKED:
        assert kh is not None and kw is not None
        if cfg.conv_impl == "direct":
            return _direct_bit_conv2d(
                params, x, cfg, kh=kh, kw=kw, stride=stride, pad=pad
            )
        wp = params["w_packed"]
    else:
        w = params["w"]
        d, kh_, kw_, _ = w.shape
        kh, kw = kh_, kw_

    patches, (oh, ow) = im2col(x, kh, kw, stride=stride, pad=pad)
    n = patches.shape[0]
    pk = patches.shape[-1]
    x2d = patches.reshape(n * oh * ow, pk)  # [NP, K]

    if cfg.mode == QuantMode.PACKED:
        y2d = _packed_matmul(wp, x2d, pk, cfg)
        if "alpha" in params:
            y2d = y2d * params["alpha"][None, :].astype(y2d.dtype)
    else:
        wm = filters_to_matrix(w)
        if cfg.mode == QuantMode.FAKE_QUANT:
            wq, alpha = binarize_weights(
                wm, scale_axis=-1 if cfg.use_scale else None
            )
            xq = binarize_activations(x2d) if cfg.binarize_acts else x2d
            # ±1 x ±1 products are exact at any matmul precision; a
            # real-valued input (the first conv) is not, and a TPU runs
            # a float32 dot at bfloat16 precision unless told otherwise.
            y2d = jnp.matmul(
                xq, wq.astype(x2d.dtype).T,
                precision=None if cfg.binarize_acts else _F32_DOT,
            )
            if alpha is not None:
                y2d = y2d * alpha.reshape(1, -1).astype(y2d.dtype)
        else:
            y2d = jnp.matmul(x2d, wm.astype(x2d.dtype).T, precision=_F32_DOT)

    if "b" in params:
        y2d = y2d + params["b"].astype(y2d.dtype)
    return col2im(y2d.reshape(n, oh * ow, -1), oh, ow)
