"""Plain reference of the CIFAR-10 BNN: its weights and its forward pass,
in straightforward ``jax.numpy``.

Courbariaux et al. 2016, "Binarized Neural Networks" (arXiv:1602.02830),
CIFAR-10 ConvNet: 2x128C3 - MP2 - 2x256C3 - MP2 - 2x512C3 - MP2 -
1024FC - 1024FC - 10FC on 32x32x3 images, BatchNorm after every layer,
weights binarized by sign, activations by Htanh then sign, sign(0) = +1.
The first conv takes real-valued pixels with binarized weights. The
widths, the pooling, BatchNorm's epsilon and the pad value of a binary
map are the ``model`` block of the configuration file.

Nothing here imports the program under test. The parameter tree has the
program's layout (``conv``/``fc`` weights ``[out, kh, kw, in]`` and
``[out, in]`` with biases, and the four BatchNorm vectors), because the
benchmark makes one set of weights and hands it to both.

Every float32 contraction runs at ``Precision.HIGHEST``: a TPU runs a
float32 dot at bfloat16 precision unless told otherwise. ``dtype``
selects the precision of the whole reference; bfloat16 is the control
that a correct program must be told apart from.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class Net:
    """The shapes and constants of the network, from a configuration's
    ``model`` block."""

    conv_channels: tuple
    pool_after: tuple
    fc_sizes: tuple
    bn_eps: float
    binary_pad: float

    @classmethod
    def of(cls, model: dict) -> "Net":
        if model["kernel_size"] != 3:
            raise ValueError("the reference convolves 3x3 only")
        return cls(tuple(map(tuple, model["conv_channels"])),
                   tuple(model["pool_after"]),
                   tuple(map(tuple, model["fc_sizes"])),
                   float(model["bn_eps"]), float(model["binary_pad_value"]))

    def binary_layers(self):
        """``(group, index, fan_in)`` of every layer whose output is
        binarized after a +-1 dot product."""
        for i, (cin, _) in enumerate(self.conv_channels[1:], 1):
            yield "bn_conv", i, 9 * cin
        for j, (fin, _) in enumerate(self.fc_sizes[:-1]):
            yield "bn_fc", j, fin


# --------------------------------------------------------------------------
# Weights, made on the device from the seed in one jitted call.
# --------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    seed = int(seed) % 2**64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _bn(key, width: int, fan_in: int) -> dict:
    """BatchNorm buffers as training leaves them: the running mean and
    variance of a layer's pre-BN outputs (a +-1 dot of ``fan_in`` terms
    has variance about ``fan_in``; its mean sits off zero by a share of
    that), and a learnt affine."""
    kg, kb, kv, km = jax.random.split(key, 4)
    return {
        "gamma": jax.random.uniform(kg, (width,), minval=0.5, maxval=1.5),
        "beta": 0.3 * jax.random.normal(kb, (width,)),
        "mean": 0.5 * fan_in ** 0.5 * jax.random.normal(km, (width,)),
        "var": fan_in * jax.random.uniform(kv, (width,), minval=0.5,
                                           maxval=2.0),
    }


@functools.partial(jax.jit, static_argnames="net")
def _make_params(key, net: Net):
    params = {"conv": [], "bn_conv": [], "fc": [], "bn_fc": []}
    for cin, cout in net.conv_channels:
        key, kw, kn = jax.random.split(key, 3)
        fan = 9 * cin
        params["conv"].append({
            "w": jax.random.normal(kw, (cout, 3, 3, cin)) * (2.0 / fan) ** 0.5,
            "b": jnp.zeros((cout,)),
        })
        params["bn_conv"].append(_bn(kn, cout, fan))
    for fin, fout in net.fc_sizes:
        key, kw, kn = jax.random.split(key, 3)
        params["fc"].append({
            "w": jax.random.normal(kw, (fout, fin)) * (2.0 / fin) ** 0.5,
            "b": jnp.zeros((fout,)),
        })
        params["bn_fc"].append(_bn(kn, fout, fin))
    return params


def make_params(seed: int, net: Net) -> dict:
    """Float32 parameters of a served network from ``seed``, made on the
    default device in one jitted call."""
    return _make_params(seed_key(seed), net)


# --------------------------------------------------------------------------
# Forward pass.
# --------------------------------------------------------------------------

def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _conv(x, w, dtype, pad_value=0.0):
    """3x3 'same' convolution, the border padded with ``pad_value``;
    ``w`` is [out, kh, kw, in]."""
    x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)),
                constant_values=jnp.asarray(pad_value, x.dtype))
    return lax.conv_general_dilated(
        x, jnp.transpose(w, (1, 2, 3, 0)).astype(dtype), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(dtype))


def _batchnorm(x, bn, eps, dtype):
    inv = lax.rsqrt(bn["var"].astype(dtype) + jnp.asarray(eps, dtype))
    return ((x - bn["mean"].astype(dtype)) * inv * bn["gamma"].astype(dtype)
            + bn["beta"].astype(dtype))


def _maxpool2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def forward(params, images, net: Net, *, dtype=jnp.float32, flip0=None,
            return_first=False):
    """Logits ``[N, 10]`` of ``images`` ``[N, 32, 32, 3]``.

    ``flip0`` is an optional ``[N, 32, 32, c]`` bool mask of first-layer
    activations whose sign is flipped; ``return_first`` also returns the
    first layer's pre-sign values in pre-BN units (the distance to the
    sign threshold).
    """
    x = images.astype(dtype)
    first = None
    for i in range(len(net.conv_channels)):
        p = params["conv"][i]
        w = _sign(p["w"].astype(dtype))
        # the real-valued image is padded with 0; a +-1 map with the
        # configuration's binary pad value
        x = _conv(x, w, dtype, 0.0 if i == 0 else net.binary_pad)
        x = _batchnorm(x + p["b"].astype(dtype), params["bn_conv"][i],
                       net.bn_eps, dtype)
        if i == 0 and return_first:
            bn = params["bn_conv"][0]
            s = bn["gamma"] * lax.rsqrt(bn["var"] + net.bn_eps)
            first = x.astype(jnp.float32) / s
        if i in net.pool_after:
            x = _maxpool2(x)
        x = _sign(jnp.clip(x, -1.0, 1.0))
        if i == 0 and flip0 is not None:
            x = jnp.where(flip0, -x, x)
    x = x.reshape(x.shape[0], -1)
    for j, p in enumerate(params["fc"]):
        w = _sign(p["w"].astype(dtype))
        x = jnp.matmul(x, w.T, precision=_precision(dtype)) + p["b"].astype(dtype)
        x = _batchnorm(x, params["bn_fc"][j], net.bn_eps, dtype)
        if j < len(net.fc_sizes) - 1:
            x = _sign(jnp.clip(x, -1.0, 1.0))
    out = x.astype(jnp.float32)
    if return_first:
        return out, first
    return out


# --------------------------------------------------------------------------
# Serving check: logits of the served images, allowing for ties.
# --------------------------------------------------------------------------

# A first-layer activation whose pre-BN value lies within this distance
# of its sign threshold may round either way in a float32 conv of 27
# terms (|terms| < 5; one float32 ulp there is 5e-7): both signs are
# then correct, and the check accepts either.
TIE_BAND = 2e-5
MAX_TIES = 4  # per image; more is rarer than 1 in 10^4 images

# A binary layer's dot is an exact integer of its fan-in's parity, so its
# sign is decided by where the BatchNorm threshold t = mean - bias -
# beta / s lies between two such integers. Where t lies within float32
# rounding of one, v, the two implementations may send v either way. The
# band is TIE_ULPS float32 ulps of the magnitudes that make t (the
# program folds BN into a * dot + b, the reference subtracts the mean
# first): a few ulps is what either side's rounding can move t, 64 is
# room to spare. Such a channel's decision is the same at every position
# and image, so each choice of it is a correct reference; the check
# accepts the best of them.
TIE_ULPS = 64
MAX_TIED_CHANNELS = 6  # per network; at TIE_ULPS ~1.5 are expected


def tied_channels(params, net: Net) -> list:
    """``(group, layer, channel, v, closeness)`` of every binary-layer
    channel whose threshold lies within the tie band of a reachable dot
    value ``v``, closest (as a share of its band) first."""
    out = []
    for group, i, fan in net.binary_layers():
        bn = {k: np.asarray(v, np.float64) for k, v in params[group][i].items()}
        bias = np.asarray(params[group.removeprefix("bn_")][i]["b"], np.float64)
        s = bn["gamma"] / np.sqrt(bn["var"] + net.bn_eps)
        t = bn["mean"] - bias - bn["beta"] / s
        parity = fan % 2
        v = parity + 2 * np.round((t - parity) / 2)
        band = TIE_ULPS * 2.0**-23 * (np.abs(bn["mean"]) + np.abs(bias)
                                      + np.abs(bn["beta"] / s) + 1)
        share = np.abs(t - v) / band
        for c in np.nonzero(share < 1)[0]:
            out.append((group, i, int(c), float(v[c]), float(share[c])))
    return sorted(out, key=lambda e: e[-1])[:MAX_TIED_CHANNELS]


def tie_variants(params, net: Net) -> list:
    """One parameter set per choice of the tied channels' decisions: the
    threshold moved half a step to either side of ``v``, so that a dot of
    ``v`` binarizes to +1 or to -1 beyond doubt."""
    tied = tied_channels(params, net)
    if not tied:
        return []
    variants = []
    for signs in itertools.product((1, -1), repeat=len(tied)):
        p = jax.tree.map(lambda a: a, params)
        for (group, i, c, v, _), sgn in zip(tied, signs):
            bn = dict(p[group][i])
            bias = float(params[group.removeprefix("bn_")][i]["b"][c])
            s = float(bn["gamma"][c]) / np.sqrt(float(bn["var"][c]) + net.bn_eps)
            t = v - 0.5 * sgn
            bn["mean"] = bn["mean"].at[c].set(t + bias + float(bn["beta"][c]) / s)
            p[group] = list(p[group])
            p[group][i] = bn
        variants.append(p)
    return variants


@functools.partial(jax.jit, static_argnames=("net", "dtype"))
def _first_layer(params, images, net, dtype=jnp.float32):
    return forward(params, images, net, dtype=dtype, return_first=True)


@functools.partial(jax.jit, static_argnames=("net", "dtype"))
def _flipped(params, images, flips, net, dtype=jnp.float32):
    return forward(params, images, net, dtype=dtype, flip0=flips)


def _gap(got, want):
    """Largest gap of a row's logits to the reference's, as a share of the
    reference's largest logit (at least 1)."""
    return (np.abs(got - want).max(axis=-1)
            / np.maximum(1.0, np.abs(want).max(axis=-1)))


def _first_flips(first: np.ndarray) -> np.ndarray:
    """Every choice of sign for the (at most ``MAX_TIES``) first-layer
    activations of one image within ``TIE_BAND`` of their threshold, as
    ``[2**MAX_TIES, *first.shape]`` flip masks (the first flips none; the
    unused rows repeat it)."""
    near = np.argwhere(first < TIE_BAND)
    near = near[np.argsort(first[tuple(near.T)])[:MAX_TIES]]
    flips = np.zeros((2**MAX_TIES,) + first.shape, bool)
    for v in range(2 ** len(near)):
        for t in range(len(near)):
            if v >> t & 1:
                flips[(v,) + tuple(near[t])] = True
    return flips


def logit_gaps(params, net: Net, images: np.ndarray, served: np.ndarray, *,
               block: int = 64) -> np.ndarray:
    """Per image, the served logits' largest gap to the float32
    reference's, as a share of the reference's largest logit (at least
    1). Where a tie (see ``TIE_BAND`` and ``TIE_ULPS``) lets an activation
    take either sign, every choice is a correct reference, and the
    smallest gap over the choices counts."""
    n = images.shape[0]
    gaps, firsts = np.empty(n), {}
    for lo in range(0, n, block):
        want, first = _first_layer(params, jnp.asarray(images[lo:lo + block]),
                                   net)
        gap = _gap(served[lo:lo + block], np.asarray(want))
        gaps[lo:lo + block] = gap
        first = np.abs(np.asarray(first))
        for r in np.nonzero(gap > 0)[0]:
            if (first[r] < TIE_BAND).any():
                firsts[lo + r] = first[r]
    bad = np.nonzero(gaps > 0)[0]
    if len(bad) == 0:
        return gaps
    variants = tie_variants(params, net)
    for p in variants:
        for lo in range(0, len(bad), block):
            rows = bad[lo:lo + block]
            want = np.asarray(_first_layer(p, jnp.asarray(images[rows]),
                                           net)[0])
            gaps[rows] = np.minimum(gaps[rows], _gap(served[rows], want))
    for r in bad:
        if gaps[r] == 0 or r not in firsts:
            continue
        flips = jnp.asarray(_first_flips(firsts[r]))
        x = jnp.asarray(np.repeat(images[r:r + 1], len(flips), 0))
        for p in [params] + variants:
            alt = np.asarray(_flipped(p, x, flips, net))
            gaps[r] = min(gaps[r], float(_gap(served[r], alt).min()))
    return gaps
