"""Bi-Real Net-18 (Liu et al., ECCV 2018, arXiv:1808.00278): a binary
ResNet with a real-valued shortcut around every binary conv.

The layer equations of the authors' released code
(``pytorch_implementation/BiReal18_34/birealnet.py``), with ``x`` the
float activation:

* stem: ``x = BN(conv7x7/2,pad3(img; 3 -> 64))``, then ``maxpool
  3x3/2, pad 1`` (no ReLU);
* 16 basic blocks in stages of ``[4, 4, 4, 4]`` at widths 64 / 128 /
  256 / 512: ``x <- BN(alpha * conv3x3,pad1(sign(x), sign(W))) + s(x)``
  with ``alpha = mean|W|`` per output channel and ``s`` the identity,
  except in the first block of stages 2-4, whose binary conv has
  stride 2 and whose shortcut is ``s(x) = BN(conv1x1(avgpool2x2/2(x)))``;
* head: global average pool, then ``Linear(512 -> 1000)`` with bias.

A binary conv pads ``sign(x)`` with 0 (PyTorch ``conv2d``); BN eps is
1e-5. Images come in as uint8 HWC pixels and are normalised on the
device with the ImageNet mean and std.

Serving form (:func:`pack_bireal_params`): each block's filters packed
+-1 and tap-aligned, its BN and alpha folded into ``(a, b)``
(``layers.fold_bn_params``), and its border correction ``c_pos``
(``bitops.border_correction``), so that one launch of the residual
direct conv (``kernels.ops.bireal_block_conv``) computes

    y = a * (dot - c_pos) + b + r,     sign(y) packed,

from the packed map of the block's input, with ``r`` the f32 shortcut.
The stem, the downsample shortcuts, the head and the input
normalisation stay float32 XLA at ``Precision.HIGHEST``.

Two engines: ``xnor`` (the Pallas kernel) and ``xla`` (its pure-XLA
oracle ``bitops.residual_conv_oracle``); their logits are bit-identical.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitops
from repro.core.layers import fold_bn_params, pack_conv_aligned
from repro.kernels import ops as kops
from repro.kernels.autotune import AUTO, block_kwargs

_HI = lax.Precision.HIGHEST

# torchvision's ImageNet normalisation (ToTensor, then Normalize).
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


@dataclasses.dataclass(frozen=True)
class Block:
    """One basic block's geometry: ``stage`` (1-based), input and output
    channels, the binary conv's stride and the input map's side."""

    stage: int
    c_in: int
    c_out: int
    stride: int
    hw: int

    @property
    def hw_out(self) -> int:
        return (self.hw - 1) // self.stride + 1


@dataclasses.dataclass(frozen=True)
class BiRealArch:
    """The shapes of a Bi-Real network (defaults: Bi-Real Net-18 on
    224x224 ImageNet, as published)."""

    image: int = 224
    stem_width: int = 64
    widths: tuple = (64, 128, 256, 512)
    depths: tuple = (4, 4, 4, 4)
    classes: int = 1000
    bn_eps: float = 1e-5

    @property
    def pooled(self) -> int:
        """Side of the map the first block sees (stem conv /2, pool /2)."""
        stem = (self.image + 2 * 3 - 7) // 2 + 1
        return (stem + 2 * 1 - 3) // 2 + 1

    def blocks(self) -> tuple[Block, ...]:
        out, c, hw = [], self.stem_width, self.pooled
        for s, (width, depth) in enumerate(zip(self.widths, self.depths), 1):
            for j in range(depth):
                blk = Block(s, c, width, 2 if s > 1 and j == 0 else 1, hw)
                if blk.stride == 1 and blk.c_in != blk.c_out:
                    raise ValueError(f"block {blk} changes width at stride 1")
                out.append(blk)
                c, hw = width, blk.hw_out
        return tuple(out)


BIREAL18 = BiRealArch()

# Engines bireal_serve_fn accepts, and the failover ladder over them
# (bit-identical rungs, DESIGN.md §11).
BIREAL_ENGINES = ("xnor", "xla")
BIREAL_FALLBACKS = {"xnor": ("xla",), "xla": ()}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _init_bn(key, width: int, scale) -> dict:
    """BatchNorm buffers as training leaves them: the running mean and
    variance of pre-BN outputs of standard deviation ``scale`` (the mean
    off zero by a share of it), and a learnt affine."""
    kg, kb, km, kv = jax.random.split(key, 4)
    return {
        "gamma": jax.random.uniform(kg, (width,), minval=0.5, maxval=1.5),
        "beta": 0.3 * jax.random.normal(kb, (width,)),
        "mean": 0.5 * scale * jax.random.normal(km, (width,)),
        "var": scale ** 2 * jax.random.uniform(kv, (width,), minval=0.5,
                                                maxval=2.0),
    }


def _conv_w(key, d: int, k: int, c: int):
    return jax.random.normal(key, (d, k, k, c)) * (2.0 / (k * k * c)) ** 0.5


def init_bireal_params(key, arch: BiRealArch = BIREAL18) -> dict[str, Any]:
    """Float32 latent parameters, with BatchNorm buffers drawn as
    training leaves them (fresh ``mean=0, var=1`` buffers would blow a
    +-1 dot of 576+ terms up past any sign decision).

    Layout (shared by the plain reference and the benchmark): ``stem
    {w [64, 7, 7, 3]}``, ``stem_bn``; ``blocks[i] {w [D, 3, 3, C], bn}``
    plus, where the block downsamples, ``down {w [D, 1, 1, C], bn}``;
    ``fc {w [classes, 512], b}``. Weights are ``[out, kh, kw, in]``.
    """
    key, ks, kb = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "stem": {"w": _conv_w(ks, arch.stem_width, 7, 3)},
        "stem_bn": _init_bn(kb, arch.stem_width, 2.0 ** 0.5),
        "blocks": [],
    }
    for blk in arch.blocks():
        key, kw, kn, kdw, kdn = jax.random.split(key, 5)
        w = _conv_w(kw, blk.c_out, 3, blk.c_in)
        alpha = jnp.mean(jnp.abs(w), axis=(1, 2, 3))
        p = {"w": w, "bn": _init_bn(kn, blk.c_out,
                                    alpha * (9 * blk.c_in) ** 0.5)}
        if blk.stride == 2:
            p["down"] = {"w": _conv_w(kdw, blk.c_out, 1, blk.c_in),
                         "bn": _init_bn(kdn, blk.c_out, 2.0 ** 0.5)}
        params["blocks"].append(p)
    kf, kfb = jax.random.split(key)
    width = arch.widths[-1]
    params["fc"] = {
        "w": jax.random.normal(kf, (arch.classes, width)) * width ** -0.5,
        "b": 0.1 * jax.random.normal(kfb, (arch.classes,)),
    }
    return params


def _fold_float_conv(p: dict, eps: float) -> dict:
    """A float conv and its BN as ``{w [kh, kw, C, D], scale, shift}``."""
    scale, shift = fold_bn_params(p["bn"], eps=eps)
    return {"w": jnp.transpose(p["w"], (1, 2, 3, 0)), "scale": scale,
            "shift": shift}


def pack_bireal_params(params: dict, arch: BiRealArch = BIREAL18) -> dict:
    """Latent parameters -> serving parameters.

    Each block: tap-aligned packed +-1 filters ``w_packed``, its
    XNOR-Net alpha and BN folded into ``(a, b)`` (``a = s*alpha``,
    ``b = beta - s*mean``), and ``cpos [3, D, 3]``, the border
    correction of its map. The stem and downsample convs keep float
    HWIO weights with their BN folded to ``scale``/``shift``; the head
    keeps ``fc {w [512, classes], b}``.
    """
    out: dict[str, Any] = {
        "stem": _fold_float_conv({"w": params["stem"]["w"],
                                  "bn": params["stem_bn"]}, arch.bn_eps),
        "blocks": [],
        "fc": {"w": params["fc"]["w"].T, "b": params["fc"]["b"]},
    }
    for blk, p in zip(arch.blocks(), params["blocks"], strict=True):
        packed = pack_conv_aligned({"w": p["w"]}, use_scale=True)
        a, b = fold_bn_params(p["bn"], alpha=packed.pop("alpha"),
                              eps=arch.bn_eps)
        w_pm1 = jnp.where(p["w"] >= 0, 1.0, -1.0)
        packed.update(a=a, b=b, cpos=bitops.border_correction(
            w_pm1, blk.hw, blk.hw, stride=blk.stride, pad=1))
        if blk.stride == 2:
            down = _fold_float_conv(p["down"], arch.bn_eps)
            down["w"] = down["w"][0, 0]                     # [C, D]
            packed["down"] = down
        out["blocks"].append(packed)
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def normalize_images(images: jnp.ndarray) -> jnp.ndarray:
    """uint8 HWC pixels -> float32, as torchvision's ``ToTensor`` then
    ``Normalize`` with the ImageNet mean and std."""
    x = images.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)


def _stem(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    x = lax.conv_general_dilated(
        x, p["w"], (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)
    x = x * p["scale"] + p["shift"]
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))


# The float path sums in an order fixed by the program, never by a
# reduction XLA emits: a reduce fused with its producer (the XLA
# engine's epilogue) may be emitted in another order than one reading a
# finished tensor (the kernel's output), and the two engines would then
# part by an ulp.

def _avgpool2(x: jnp.ndarray) -> jnp.ndarray:
    """2x2/2 average pool, ``((x00 + x01) + (x10 + x11)) / 4``."""
    return ((x[:, 0::2, 0::2] + x[:, 0::2, 1::2])
            + (x[:, 1::2, 0::2] + x[:, 1::2, 1::2])) * 0.25


def _global_avgpool(x: jnp.ndarray) -> jnp.ndarray:
    """Mean over the map, summed position by position in raster order."""
    n, h, w, c = x.shape
    x = x.reshape(n, h * w, c)
    acc = x[:, 0]
    for i in range(1, h * w):
        acc = acc + x[:, i]
    return acc / (h * w)


def _downsample(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """``BN(conv1x1(avgpool2x2/2(x)))``, BN folded."""
    x = jnp.einsum("nhwc,cd->nhwd", _avgpool2(x), p["w"], precision=_HI)
    return x * p["scale"] + p["shift"]


def _block_conv(p: dict, blk: Block, xp, r, engine: str, blocks):
    k = 9 * blk.c_in
    if engine == "xnor":
        return kops.bireal_block_conv(
            p["w_packed"], xp, k, p["a"], p["b"], p["cpos"], r,
            stride=blk.stride, pad=1, name=f"bireal_block_conv{blk.stage}",
            **block_kwargs(blocks, conv=True))
    if engine == "xla":
        return bitops.residual_conv_oracle(
            p["w_packed"], xp, k, p["a"], p["b"], p["cpos"], r,
            kh=3, kw=3, stride=blk.stride, pad=1)
    raise ValueError(f"unknown Bi-Real engine {engine!r}; "
                     f"expected one of {BIREAL_ENGINES}")


def bireal_apply(packed: dict, images: jnp.ndarray, *, engine: str = "xnor",
                 arch: BiRealArch = BIREAL18,
                 blocks: object = AUTO) -> jnp.ndarray:
    """Logits ``[N, classes]`` of uint8 images ``[N, H, W, 3]``.

    Between blocks the float activation ``x`` (the next shortcut) and
    its packed sign bits (the next binary conv's input) both live in
    HBM; one launch per block writes both (``engine="xnor"``), or the
    XLA oracle computes the same bits (``"xla"``).
    """
    with jax.named_scope("stem"):
        x = _stem(packed["stem"], normalize_images(images))
        xp = bitops.pack_bits(x, axis=-1)
    for i, (blk, p) in enumerate(zip(arch.blocks(), packed["blocks"],
                                     strict=True)):
        r = x
        if blk.stride == 2:
            with jax.named_scope(f"shortcut{i}"):
                r = _downsample(p["down"], x)
        x, xp = _block_conv(p, blk, xp, r, engine, blocks)
    with jax.named_scope("head"):
        x = _global_avgpool(x)
        return (jnp.matmul(x, packed["fc"]["w"], precision=_HI)
                + packed["fc"]["b"])


def bireal_serve_fn(*, engine: str = "xnor", conv_impl: str = "direct",
                    blocks: object = AUTO, ragged: bool = False,
                    mesh: object = None, arch: BiRealArch = BIREAL18):
    """The serving entry point: a jit-compiled ``(packed, images) ->
    logits`` over :func:`bireal_apply`, the counterpart of
    ``core.bnn.bnn_serve_fn``. The block convs are direct by
    construction (``conv_impl`` is ignored) and exact in the batch
    (``ragged`` is a no-op). ``mesh`` shards the
    batch over a 1-D serving mesh with the weights replicated
    (``distributed.sharding.shard_serving``)."""
    if engine not in BIREAL_ENGINES:
        raise ValueError(f"unknown Bi-Real engine {engine!r}; "
                         f"expected one of {BIREAL_ENGINES}")
    del conv_impl, ragged

    def apply_fn(packed: dict, images: jnp.ndarray) -> jnp.ndarray:
        return bireal_apply(packed, images, engine=engine, arch=arch,
                            blocks=blocks)

    if mesh is not None:
        from repro.distributed.sharding import shard_serving

        apply_fn = shard_serving(apply_fn, mesh)
    return jax.jit(apply_fn)


def served_model(arch: BiRealArch = BIREAL18, name: str = "bireal18"):
    """The serving layer's handle on a Bi-Real network
    (``serve.model.ServedModel``): uint8 ``[image, image, 3]`` requests,
    the ``xnor -> xla`` failover ladder."""
    from repro.serve.model import ServedModel  # serve imports core

    return ServedModel(
        name=name, serve_fn=functools.partial(bireal_serve_fn, arch=arch),
        image_shape=(arch.image, arch.image, 3), dtype=np.uint8,
        fallbacks=BIREAL_FALLBACKS)


__all__ = [
    "BIREAL18",
    "BIREAL_ENGINES",
    "BIREAL_FALLBACKS",
    "BiRealArch",
    "Block",
    "bireal_apply",
    "bireal_serve_fn",
    "init_bireal_params",
    "normalize_images",
    "pack_bireal_params",
    "served_model",
]
