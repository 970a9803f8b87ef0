"""Broadcast-free xnor-popcount accumulation (DESIGN.md §6).

The original kernels materialized the full 3-D broadcast
``~(w[:, :, None] ^ x[None, :, :])`` — a ``[bm, bkw, bn]`` int32
intermediate that dominated each grid step's VMEM budget (~85% at the
old 128/128/16 defaults) and capped how large the operand tiles could
grow. These helpers compute the identical ``sum_k popcount(xnor)``
reduction with only 2-D ``[bm, bn]`` intermediates: a ``lax.fori_loop``
walks the packed K-words in small static groups (``word_group`` words
per iteration, unrolled inside the loop body so the VPU always has a
full-tile op in flight), and a static tail handles
``k_words % word_group != 0`` exactly.

The accumulators read their operands from refs, in one layout: weights
word-major ``wt [KW, M]``, activations ``x [KW, N]``. A word group is
then a sublane-row load on both refs, the one dynamic slice the TPU
lowering accepts at any word offset (a lane-axis slice must start on a
128-word boundary); the group's ``[g, M]`` weight rows are transposed
in-register so each word's ``[M, 1]`` column is a static lane slice.
Kernels whose weights arrive as ``[M, KW]`` transpose them in XLA
before the launch, and the direct-conv kernels stage their gathered
windows as ``[KW, N]`` rows in VMEM scratch.

``word_group`` trades loop trip count against code size; it never
affects results (asserted against the broadcast formulation in
``tests/test_kernels.py``), so the autotuner sweeps it like any other
block dimension. When ``word_group >= k_words`` the fori_loop
disappears and the walk is a pure static unroll.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.bitops import PACK_BITS

DEFAULT_WORD_GROUP = 8


def sign_repack_m(y: jnp.ndarray) -> jnp.ndarray:
    """The fused kernels' shared sign+repack epilogue tail:
    ``[M, N]`` (any real dtype) -> packed int32 ``[M/32, N]`` with
    ``bit = (y >= 0)``, LSB-first along M. ``M`` must divide by 32 —
    every fused kernel guarantees this by construction (``block_m`` /
    ``block_d`` / ``M_max`` are 32-multiples)."""
    m, n = y.shape
    bits = (y >= 0).astype(jnp.int32)
    bits = bits.reshape(m // PACK_BITS, PACK_BITS, n)
    shifts = lax.broadcasted_iota(jnp.int32, (1, PACK_BITS, 1), 1)
    return jnp.sum(bits << shifts, axis=1)


def _word_pc(w_col: jnp.ndarray, x_row: jnp.ndarray) -> jnp.ndarray:
    """One packed word's popcount contribution: [M, 1] x [1, N] -> [M, N]."""
    return lax.population_count(~(w_col ^ x_row)).astype(jnp.int32)


def _accum_group(wt_ref, x_ref, w_lead, x_lead, start, size: int, acc):
    """Add ``size`` consecutive words starting at ``start`` (static or
    traced) to ``acc``.

    Both refs are read with sublane-row loads — the only dynamic slice
    Mosaic lowers at any word offset — and the ``[size, M]`` weight
    rows are transposed in-register so each word's ``[M, 1]`` column
    comes from a static lane slice.
    """
    rows = (pl.ds(start, size), slice(None))
    w = wt_ref[w_lead + rows].T                   # [M, size]
    x = x_ref[x_lead + rows]                      # [size, N]
    for i in range(size):
        acc = acc + _word_pc(w[:, i : i + 1], x[i : i + 1, :])
    return acc


def accum_popcount_km(
    wt_ref, x_ref, *, word_group: int = DEFAULT_WORD_GROUP,
    w_lead: tuple = (), x_lead: tuple = (),
) -> jnp.ndarray:
    """``sum_k popcount(~(wt[k, :, None] ^ x[k, None, :]))`` -> [M, N].

    ``wt_ref[*w_lead]``: word-major packed weights ``[KW, M]``;
    ``x_ref[*x_lead]``: packed activations ``[>= KW, N]`` (rows past
    ``KW`` are not read). The leading indices select a sub-array at
    load time instead of through a ref view, which the TPU lowering
    refuses on a lane-padded buffer (minor dim < 128). Only 2-D
    intermediates exist: a ``fori_loop`` walks ``word_group``-word
    groups (statically unrolled inside), then a static tail covers
    ``KW % word_group``.
    """
    kw, m = wt_ref.shape[len(w_lead):]
    n = x_ref.shape[-1]
    g = max(1, min(word_group, kw))
    acc = jnp.zeros((m, n), jnp.int32)

    def group(start, size, a):
        return _accum_group(wt_ref, x_ref, w_lead, x_lead, start, size, a)

    full = kw // g
    if full == 1:
        acc = group(0, g, acc)
    elif full > 1:
        acc = lax.fori_loop(0, full, lambda t, a: group(t * g, g, a), acc)
    if kw % g:
        acc = group(full * g, kw % g, acc)
    return acc


def accum_popcount_km_dyn(
    wt_ref,
    x_ref,
    n_groups: jnp.ndarray,
    *,
    word_group: int = DEFAULT_WORD_GROUP,
    w_lead: tuple = (),
    x_lead: tuple = (),
) -> jnp.ndarray:
    """:func:`accum_popcount_km` with a TRACED trip count: walk only the
    first ``n_groups * word_group`` packed K-words of the operands.

    This is the megakernel-chain accumulator (DESIGN.md §8): layers of
    different true K share one padded ``[L, KW_max, M_max]`` weight
    stack, and a per-layer ``n_groups = ceil(ceil(k/32) / word_group)``
    keeps each ``lax.fori_loop`` layer iteration from paying the
    stack-wide KW_max — a ragged layer walks its own K only. Words
    between the true K and the group boundary must be xnor-neutral
    pairs (zero weight words against all-ones activation words — the
    stacking convention guarantees this), so the group-aligned
    overshoot contributes exactly zero. ``KW`` must divide by
    ``word_group`` and ``n_groups * word_group <= KW``.
    """
    kw, m = wt_ref.shape[len(w_lead):]
    n = x_ref.shape[-1]
    g = max(1, word_group)
    assert kw % g == 0, (kw, g)
    return lax.fori_loop(
        0, n_groups,
        lambda t, a: _accum_group(wt_ref, x_ref, w_lead, x_lead, t * g, g, a),
        jnp.zeros((m, n), jnp.int32),
    )


__all__ = [
    "DEFAULT_WORD_GROUP",
    "accum_popcount_km",
    "accum_popcount_km_dyn",
    "sign_repack_m",
]
