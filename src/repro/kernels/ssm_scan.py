"""Selective-scan (Mamba S6) chunk kernel in Pallas.

The recurrence h_t = exp(dt_t*A) h_{t-1} + (dt_t x_t) B_t ; y_t = C_t.h
is the hot spot of the hybrid (jamba) layers. The XLA fallback
(models/mamba.py) runs it as an associative scan whose [B, C, di, ds]
state tensor is HBM-visible; this kernel keeps the state in VMEM — one
[bd, ds] register-resident h per grid cell, sequential over the chunk —
which is what the roofline's vmem_fusible credit for "SSM scan states"
models.

Grid: (batch, di/bd). Per grid step the kernel holds:
  dt, xh [C, bd]; B, C [C, ds]; A [bd, ds]; h [bd, ds]; y [C, bd]
VMEM (C=256, bd=128, ds=16, f32): 2*128KB + 2*16KB + 8KB + 8KB + 128KB
~= 0.4 MiB.

The sequential chunk walk trades MXU-parallelism for O(C) latency — on
TPU the di/bd grid axis provides the parallelism (di = 16384 for jamba
-> 128 parallel cells per batch element).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssm_scan_kernel(dt_ref, xh_ref, b_ref, c_ref, a_ref, h0_ref,
                     y_ref, h_out_ref):
    a = a_ref[...]                       # [bd, ds]
    chunk = dt_ref.shape[0]

    def step(t, h):
        dt_t = dt_ref[t, :]              # [bd]
        da = jnp.exp(dt_t[:, None] * a)  # [bd, ds]
        dbx = (dt_t * xh_ref[t, :])[:, None] * b_ref[t, :][None, :]
        h = h * da + dbx
        y_ref[t, :] = jnp.sum(h * c_ref[t, :][None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h0_ref[...])
    h_out_ref[...] = h


@functools.partial(
    jax.jit, static_argnames=("block_d", "interpret"),
)
def ssm_scan_chunk(
    dt: jnp.ndarray,     # [B, C, di] f32
    xh: jnp.ndarray,     # [B, C, di] f32
    bmat: jnp.ndarray,   # [B, C, ds] f32
    cmat: jnp.ndarray,   # [B, C, ds] f32
    a: jnp.ndarray,      # [di, ds]   f32 (negative)
    h0: jnp.ndarray,     # [B, di, ds] f32
    *,
    block_d: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B, C, di], h_last [B, di, ds])."""
    b, c, di = dt.shape
    ds = a.shape[1]
    bd = min(block_d, di)
    assert di % bd == 0, (di, bd)

    return pl.pallas_call(
        _ssm_scan_kernel,
        grid=(b, di // bd),
        in_specs=[
            pl.BlockSpec((None, c, bd), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((None, c, bd), lambda i, j: (i, 0, j)),   # xh
            pl.BlockSpec((None, c, ds), lambda i, j: (i, 0, 0)),   # B
            pl.BlockSpec((None, c, ds), lambda i, j: (i, 0, 0)),   # C
            pl.BlockSpec((bd, ds), lambda i, j: (j, 0)),           # A
            pl.BlockSpec((None, bd, ds), lambda i, j: (i, j, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((None, c, bd), lambda i, j: (i, 0, j)),   # y
            pl.BlockSpec((None, bd, ds), lambda i, j: (i, j, 0)),  # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, di), jnp.float32),
            jax.ShapeDtypeStruct((b, di, ds), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(dt, xh, bmat, cmat, a, h0)
