"""Flash attention as a Pallas TPU kernel (online softmax, VMEM tiles).

This is the kernel that justifies the roofline's "vmem_fusible" credit
(roofline/hlo_cost.py): on TPU the [Sq, Skv] score matrix never touches
HBM — each grid step stages a [bq, dh] query tile and a [bkv, dh] KV
tile into VMEM, runs QK^T -> masked online softmax -> PV on the MXU/VPU,
and carries (acc, running-max, denom) in VMEM scratch across the KV grid
axis. HBM traffic is exactly Q + O + nq*(K+V) — what the roofline's
fused memory term models.

Grid: (batch*heads, num_q_blocks, num_kv_blocks), KV innermost so the
scratch accumulator stays resident. Causal masking via per-tile position
iota against absolute q/kv offsets.

VMEM per step (bq=512, bkv=512, dh=128, fp32):
  q 512*128*4 = 256 KiB, k/v 2x256 KiB, scores 512*512*4 = 1 MiB,
  acc 256 KiB + m/l 4 KiB  ~= 2 MiB of ~16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  nkv: int, bq: int, bkv: int, causal: bool, scale: float):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                     # [bq, dh]
    k = k_ref[...]                     # [bkv, dh]
    v = v_ref[...]                     # [bkv, dh]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # [bq, bkv]

    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0)
        k_pos = kv_idx * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]                # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)             # [bq, bkv]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_idx == nkv - 1)
    def _done():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,    # [BH, Sq, Dh]  (batch*heads flattened)
    k: jnp.ndarray,    # [BH, Skv, Dh]
    v: jnp.ndarray,    # [BH, Skv, Dh]
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    nkv = skv // bkv

    kernel = functools.partial(
        _flash_kernel, nkv=nkv, bq=bq, bkv=bkv, causal=causal,
        scale=dh ** -0.5,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, nkv),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bkv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bkv, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
