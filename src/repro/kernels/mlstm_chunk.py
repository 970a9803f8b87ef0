"""Chunkwise mLSTM (xLSTM matrix-memory cell) as a Pallas TPU kernel.

Linear attention with exponential input gating and a matrix memory
C [dk, dv]: within a chunk the kernel runs the quadratic masked form in
VMEM; across chunks it carries (C, n, m) in VMEM scratch along the
innermost (sequential) grid axis — same scratch-accumulator pattern as
the flash-attention kernel. Exponentials are max-stabilized with the
carried stabilizer m (the exact scheme of models/xlstm.py, which is the
oracle this kernel is tested against).

Grid: (batch*heads, num_chunks). VMEM per step (L=128, dh=512, f32):
  q/k/v 3 x 256 KiB, scores [L,L] 64 KiB, C [dh,dh] 1 MiB, y 256 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, logi_ref, logf_ref,
                  y_ref, c_out_ref, n_out_ref, m_out_ref,
                  c_ref, n_ref, m_ref, *, nc: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    q = q_ref[...]                      # [L, dk]
    k = k_ref[...]
    v = v_ref[...]                      # [L, dv]
    logi = logi_ref[...][:, 0]          # [L]
    logf = logf_ref[...][:, 0]

    b_cum = jnp.cumsum(logf)            # [L]
    g = logi - b_cum
    big_m = jax.lax.cummax(g)           # running max_{j<=t} g_j
    m_prev = m_ref[0, 0]
    m_loc = jnp.maximum(big_m, m_prev)  # [L]
    inter_scale = jnp.exp(m_prev - m_loc)

    # intra-chunk: S[t, j] = exp(g_j - m_loc_t), j <= t
    w_intra = jnp.exp(g[None, :] - m_loc[:, None])
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w_intra = jnp.where(j_idx <= t_idx, w_intra, 0.0)

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    sw = qk * w_intra                    # [L, L]
    num = jax.lax.dot(sw, v, preferred_element_type=jnp.float32)
    num += jax.lax.dot(q, c_ref[...],
                       preferred_element_type=jnp.float32) \
        * inter_scale[:, None]
    den = jnp.sum(sw, axis=1)
    den_inter = jnp.sum(q * jnp.broadcast_to(n_ref[0:1, :], q.shape),
                        axis=1) * inter_scale
    den = den + den_inter
    y_ref[...] = (num / jnp.maximum(jnp.abs(den), 1.0)[:, None]).astype(
        y_ref.dtype)

    # advance carry: m' = b_L + max(M_L, m_prev)
    bL = b_cum[chunk - 1]
    m_loc_l = jnp.maximum(big_m[chunk - 1], m_prev)
    wk = jnp.exp(g - m_loc_l)            # [L]
    decay = jnp.exp(m_prev - m_loc_l)
    c_ref[...] = decay * c_ref[...] + jax.lax.dot_general(
        k * wk[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    n_ref[...] = decay * n_ref[...] + jnp.sum(
        k * wk[:, None], axis=0, keepdims=True)
    m_ref[...] = jnp.full_like(m_ref, bL + m_loc_l)

    @pl.when(ci == nc - 1)
    def _emit_state():   # final (C, n, m) for prefill -> decode handoff
        c_out_ref[...] = c_ref[...]
        n_out_ref[...] = n_ref[...]
        m_out_ref[...] = m_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunked(
    q: jnp.ndarray,      # [BH, S, dk]  (pre-scaled by dk**-0.5)
    k: jnp.ndarray,      # [BH, S, dk]
    v: jnp.ndarray,      # [BH, S, dv]
    logi: jnp.ndarray,   # [BH, S]
    logf: jnp.ndarray,   # [BH, S]  (log-sigmoid forget pre-activations)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (y [BH,S,dv], C [BH,dk,dv], n [BH,1,dk], m [BH,1,1])."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    l = min(chunk, s)
    assert s % l == 0, (s, l)
    nc = s // l

    kernel = functools.partial(_mlstm_kernel, nc=nc, chunk=l)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, l, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, l, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, dk, dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, dk), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, dk), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),   # C
            pltpu.VMEM((1, dk), jnp.float32),    # n
            pltpu.VMEM((1, 1), jnp.float32),     # m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, logi[..., None], logf[..., None])
