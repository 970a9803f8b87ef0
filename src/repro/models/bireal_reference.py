"""Plain float32 reference of Bi-Real Net-18 (Liu et al., ECCV 2018,
arXiv:1808.00278; the authors' ``pytorch_implementation/BiReal18_34/
birealnet.py``), written from the layer equations alone: no packing, no
kernels, no folding, no batching tricks.

    x = normalize(img)                    uint8 -> (x/255 - mean) / std
    x = maxpool3x3/2,pad1(BN(conv7x7/2,pad3(x)))
    per block:  x <- BN(alpha * conv3x3/s,pad1(sign(x), sign(W))) + s(x)
                s(x) = x, or BN(conv1x1(avgpool2x2/2(x))) where s = 2
    logits = Linear(mean_hw(x))

Zero padding everywhere (PyTorch ``conv2d``), ``alpha = mean|W|`` per
output channel, BatchNorm unfolded with eps 1e-5, every contraction at
``Precision.HIGHEST``. The parameter tree is ``core.bireal``'s
(``init_bireal_params``).

Departures from the released code, each deliberate:

* ``sign(0)`` is +1, for weights and activations; PyTorch's ``sign``
  gives 0. On float inputs a value of exactly 0 has measure zero.
* The BatchNorm buffers are drawn as training leaves them (the running
  mean and variance of the layer's pre-BN outputs, a learnt affine), as
  the CIFAR benchmark's reference does, so that pre-sign activations are
  neither all of one sign nor exploding; no trained checkpoint is
  downloaded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
BN_EPS = 1e-5


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _conv(x, w, stride: int, pad: int):
    """``w [out, kh, kw, in]``; zero padding."""
    return lax.conv_general_dilated(
        x, jnp.transpose(w, (1, 2, 3, 0)), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, bn, eps: float = BN_EPS):
    return (x - bn["mean"]) / jnp.sqrt(bn["var"] + eps) * bn["gamma"] + bn["beta"]


def forward(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """Logits of uint8 images ``[N, H, W, 3]``; the network's shapes come
    from the parameters (stride 2 where a block has ``down``)."""
    with jax.default_matmul_precision("highest"):
        x = (images.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) \
            / jnp.asarray(STD)
        x = _bn(_conv(x, params["stem"]["w"], 2, 3), params["stem_bn"])
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
        for p in params["blocks"]:
            stride = 2 if "down" in p else 1
            w = p["w"]
            alpha = jnp.mean(jnp.abs(w), axis=(1, 2, 3))
            out = _conv(_sign(x), _sign(w), stride, 1) * alpha
            out = _bn(out, p["bn"])
            if "down" in p:
                pooled = lax.reduce_window(x, 0.0, lax.add, (1, 2, 2, 1),
                                           (1, 2, 2, 1), "VALID") / 4.0
                short = _bn(_conv(pooled, p["down"]["w"], 1, 0),
                            p["down"]["bn"])
            else:
                short = x
            x = out + short
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["fc"]["w"].T + params["fc"]["b"]


__all__ = ["forward"]
