"""Plain reference of Bi-Real Net-18: its weights and its forward pass, in
straightforward ``jax.numpy``.

Liu et al. 2018, "Bi-Real Net" (arXiv:1808.00278), the authors'
``pytorch_implementation/BiReal18_34/birealnet.py``:

    x = (img/255 - mean) / std                       (uint8 in)
    x = maxpool3x3/2,pad1(BN(conv7x7/2,pad3(x; 3 -> 64)))
    16 blocks, stages [4, 4, 4, 4] at 64/128/256/512:
        x <- BN(alpha * conv3x3,pad1(sign(x), sign(W))) + s(x)
        alpha = mean|W| per output channel, s(x) = x, except in the
        first block of stages 2-4: stride 2 and
        s(x) = BN(conv1x1(avgpool2x2/2(x)))
    logits = Linear(512 -> 1000)(mean over the 7x7 map)

Zero padding, BN eps from the configuration, sign(0) = +1. Nothing here
imports the program under test; the parameter tree has the program's
layout (``stem``, ``stem_bn``, ``blocks[i] {w, bn[, down {w, bn}]}``,
``fc {w, b}``, weights ``[out, kh, kw, in]``), because the benchmark
makes one set of weights and hands it to both.

Every float32 contraction runs at ``Precision.HIGHEST``. ``dtype``
sets the precision of the residual stream and the float convs
(bfloat16 is the control, ``bench/control_bireal.py``); ``fold_bn``
computes each BN folded into a scale and shift, as a program may — the
float32 ordering whose gap to the unfolded one sets the check's limit.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference import seed_key

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


@dataclasses.dataclass(frozen=True)
class Net:
    """Shapes and constants, from a configuration's ``model`` block."""

    image: int
    stem_width: int
    widths: tuple
    depths: tuple
    classes: int
    bn_eps: float

    @classmethod
    def of(cls, model: dict) -> "Net":
        return cls(int(model["image"][0]), int(model["stem_width"]),
                   tuple(model["widths"]), tuple(model["depths"]),
                   int(model["classes"]), float(model["bn_eps"]))

    def blocks(self):
        """``(c_in, c_out, stride)`` of every block."""
        c = self.stem_width
        for s, (width, depth) in enumerate(zip(self.widths, self.depths)):
            for j in range(depth):
                yield c, width, 2 if s > 0 and j == 0 else 1
                c = width


# --------------------------------------------------------------------------
# Weights, made on the device from the seed in one jitted call.
# --------------------------------------------------------------------------

def _bn(key, width: int, scale) -> dict:
    """BatchNorm buffers as training leaves them: the running mean and
    variance of pre-BN outputs of standard deviation ``scale`` (the
    mean off zero by a share of it), and a learnt affine."""
    kg, kb, km, kv = jax.random.split(key, 4)
    return {
        "gamma": jax.random.uniform(kg, (width,), minval=0.5, maxval=1.5),
        "beta": 0.3 * jax.random.normal(kb, (width,)),
        "mean": 0.5 * scale * jax.random.normal(km, (width,)),
        "var": scale ** 2 * jax.random.uniform(kv, (width,), minval=0.5,
                                                maxval=2.0),
    }


def _w(key, d, k, c):
    return jax.random.normal(key, (d, k, k, c)) * (2.0 / (k * k * c)) ** 0.5


@functools.partial(jax.jit, static_argnames="net")
def _make_params(key, net: Net):
    key, ks, kb = jax.random.split(key, 3)
    params = {"stem": {"w": _w(ks, net.stem_width, 7, 3)},
              "stem_bn": _bn(kb, net.stem_width, 2.0 ** 0.5), "blocks": []}
    for c_in, c_out, stride in net.blocks():
        key, kw, kn, kdw, kdn = jax.random.split(key, 5)
        w = _w(kw, c_out, 3, c_in)
        # a +-1 dot of 9*c_in terms times alpha = mean|W|
        scale = jnp.mean(jnp.abs(w), axis=(1, 2, 3)) * (9 * c_in) ** 0.5
        p = {"w": w, "bn": _bn(kn, c_out, scale)}
        if stride == 2:
            p["down"] = {"w": _w(kdw, c_out, 1, c_in),
                         "bn": _bn(kdn, c_out, 2.0 ** 0.5)}
        params["blocks"].append(p)
    kf, kfb = jax.random.split(key)
    width = net.widths[-1]
    params["fc"] = {
        "w": jax.random.normal(kf, (net.classes, width)) * width ** -0.5,
        "b": 0.1 * jax.random.normal(kfb, (net.classes,)),
    }
    return params


def make_params(seed: int, net: Net) -> dict:
    """Float32 parameters from ``seed``, made on the default device."""
    return _make_params(jax.random.fold_in(seed_key(seed), 0xB1EA), net)


# --------------------------------------------------------------------------
# Forward pass.
# --------------------------------------------------------------------------

def _sign(x):
    return jnp.where(x >= 0, 1, -1).astype(x.dtype)


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _conv(x, w, stride, pad, dtype):
    """``w [out, kh, kw, in]``, zero padding."""
    return lax.conv_general_dilated(
        x, jnp.transpose(w, (1, 2, 3, 0)).astype(dtype), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(dtype))


def _batchnorm(x, bn, eps, dtype, fold, alpha=None):
    """``BN(alpha * x)`` unfolded, or folded into ``x*scale + shift``."""
    bn = {k: v.astype(dtype) for k, v in bn.items()}
    eps = jnp.asarray(eps, dtype)
    if fold:
        s = bn["gamma"] / jnp.sqrt(bn["var"] + eps)
        scale = s if alpha is None else s * alpha
        return x * scale + (bn["beta"] - bn["mean"] * s)
    if alpha is not None:
        x = x * alpha
    return (x - bn["mean"]) / jnp.sqrt(bn["var"] + eps) * bn["gamma"] + bn["beta"]


@functools.partial(jax.jit, static_argnames=("dtype", "fold_bn", "eps"))
def forward(params, images, *, eps: float, dtype=jnp.float32,
            fold_bn: bool = False):
    """Logits ``[N, classes]`` (float32) of uint8 ``images [N, H, W, 3]``."""
    x = (images.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) \
        / jnp.asarray(STD)
    x = _conv(x.astype(dtype), params["stem"]["w"], 2, 3, dtype)
    x = _batchnorm(x, params["stem_bn"], eps, dtype, fold_bn)
    x = lax.reduce_window(x, jnp.asarray(-jnp.inf, dtype), lax.max,
                          (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for p in params["blocks"]:
        stride = 2 if "down" in p else 1
        alpha = jnp.mean(jnp.abs(p["w"]), axis=(1, 2, 3)).astype(dtype)
        out = _conv(_sign(x), _sign(p["w"]).astype(dtype), stride, 1, dtype)
        out = _batchnorm(out, p["bn"], eps, dtype, fold_bn, alpha=alpha)
        if stride == 2:
            pooled = lax.reduce_window(x, jnp.asarray(0, dtype), lax.add,
                                       (1, 2, 2, 1), (1, 2, 2, 1),
                                       "VALID") / 4
            short = _batchnorm(_conv(pooled, p["down"]["w"], 1, 0, dtype),
                               p["down"]["bn"], eps, dtype, fold_bn)
        else:
            short = x
        x = out + short
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return (jnp.matmul(x, params["fc"]["w"].T, precision=lax.Precision.HIGHEST)
            + params["fc"]["b"])


# --------------------------------------------------------------------------
# Serving check.
# --------------------------------------------------------------------------

def _gap(got, want):
    """Largest gap of a row's logits to the reference's, as a share of the
    reference's largest logit (at least 1)."""
    return (np.abs(got - want).max(axis=-1)
            / np.maximum(1.0, np.abs(want).max(axis=-1)))


def logits(params, net: Net, images: np.ndarray, *, block: int = 32,
           **kw) -> np.ndarray:
    """The reference's logits of ``images``, ``block`` images a call."""
    return np.concatenate([
        np.asarray(forward(params, jnp.asarray(images[lo:lo + block]),
                           eps=net.bn_eps, **kw))
        for lo in range(0, len(images), block)])


def logit_gaps(params, net: Net, images: np.ndarray, served: np.ndarray, *,
               block: int = 32) -> np.ndarray:
    """Per image, the served logits' largest gap to the float32
    reference's, as a share of the reference's largest logit (at least
    1).

    The check compares the MEAN of these over the sample, not the
    largest. A pre-sign activation within float32 rounding of 0 takes
    either sign in two correct float32 orderings (the program folds BN,
    the reference does not), and in a residual stream 16 blocks deep the
    flip spreads: on a TPU v5e about 6% of 224x224 images differ so,
    the worst by 0.02-0.03 of its largest logit, while the bfloat16
    control's worst reads only 0.06. The mean keeps the two an order of
    magnitude and more apart; see ``gap_summary`` for what else a run
    reports.
    """
    return _gap(np.asarray(served), logits(params, net, images, block=block))


FLIP_GAP = 1e-4   # above float32 rounding of an image's logits: a flip


def gap_summary(gaps: np.ndarray) -> dict:
    """The sample's gaps beyond their mean: the widest, and the share of
    images whose logits differ by more than float32 rounding."""
    return {"logit_gap_max": float(gaps.max()),
            "images_flipped_share": float((gaps > FLIP_GAP).mean())}
