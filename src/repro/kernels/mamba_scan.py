"""Selective-scan (Mamba) recurrence kernel.

    h_t = exp(dt_t * A) (.) h_{t-1} + (dt_t * u_t) B_t
    y_t = C_t . h_t + D (.) u_t

Grid is (batch, d_inner blocks); each program keeps its [dblk, ds] state
slab resident and walks time with a fori_loop — the state never leaves
VMEM, matching the CUDA kernel's SRAM-resident design on TPU terms.
dblk is a multiple of 128 (lane width); ds = 16 for the assigned configs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mamba_kernel(u_ref, dt_ref, B_ref, C_ref, A_ref, D_ref, o_ref, *,
                  seq: int):
    A = A_ref[...].astype(jnp.float32)                     # [dblk, ds]
    D = D_ref[...].reshape(-1).astype(jnp.float32)         # [dblk]
    dblk, ds = A.shape

    def row(ref, t):
        return ref[0, pl.ds(t, 1), :][0].astype(jnp.float32)

    def step(t, h):
        u, dt = row(u_ref, t), row(dt_ref, t)              # [dblk]
        B, C = row(B_ref, t), row(C_ref, t)                # [ds]
        a_bar = jnp.exp(dt[:, None] * A)                   # [dblk, ds]
        h = a_bar * h + (dt * u)[:, None] * B[None, :]
        y = (h * C[None, :]).sum(axis=1) + D * u
        o_ref[0, pl.ds(t, 1), :] = y[None, :].astype(o_ref.dtype)
        return h

    jax.lax.fori_loop(0, seq, step, jnp.zeros((dblk, ds), jnp.float32))


def mamba_scan(u, dt, B, C, A, D, *, d_block: int = 128,
               interpret: bool = True) -> jnp.ndarray:
    """u/dt: [b, T, di]; B/C: [b, T, ds]; A: [di, ds]; D: [di] -> y [b, T, di]."""
    b, T, di = u.shape
    ds = B.shape[-1]
    d_block = min(d_block, di)
    assert di % d_block == 0
    kern = functools.partial(_mamba_kernel, seq=T)
    return pl.pallas_call(
        kern,
        grid=(b, di // d_block),
        in_specs=[
            pl.BlockSpec((1, T, d_block), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, T, d_block), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, T, ds), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, T, ds), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((d_block, ds), lambda bi, ci: (ci, 0)),
            pl.BlockSpec((1, d_block), lambda bi, ci: (0, ci)),
        ],
        out_specs=pl.BlockSpec((1, T, d_block), lambda bi, ci: (bi, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, T, di), u.dtype),
        interpret=interpret,
    )(u, dt, B, C, A, D[None])
