"""WKV6 recurrence kernel (RWKV-6 "Finch" time-mix core).

    out_t[j] = sum_i r_t[i] * (S[i,j] + u[i] k_t[i] v_t[j])
    S[i,j]  <- w_t[i] * S[i,j] + k_t[i] v_t[j]

Grid is (batch, heads); the [hd, hd] matrix state lives in registers/VMEM
for the whole sequence — the recurrence never round-trips HBM (the CUDA
kernel the paper's family uses does the same in shared memory; on TPU the
VPU processes the rank-1 updates).  Time is walked with a fori_loop; r/k/v
and the per-step decay arrive as whole-sequence VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, *, seq: int):
    u = u_ref[0, 0].astype(jnp.float32)                      # [hd]
    hd = u.shape[0]

    def row(ref, t):
        return ref[0, 0, pl.ds(t, 1), :][0].astype(jnp.float32)   # [hd]

    def step(t, S):
        r, k, v = row(r_ref, t), row(k_ref, t), row(v_ref, t)
        w = row(w_ref, t)                                    # decay in (0,1)
        kv = k[:, None] * v[None, :]                         # [hd, hd]
        out = ((S + u[:, None] * kv) * r[:, None]).sum(axis=0)
        o_ref[0, 0, pl.ds(t, 1), :] = out[None, :].astype(o_ref.dtype)
        return w[:, None] * S + kv

    S0 = jnp.zeros((hd, hd), jnp.float32)
    jax.lax.fori_loop(0, seq, step, S0)


def wkv6(r, k, v, w, u, *, interpret: bool = True) -> jnp.ndarray:
    """r/k/v: [b, h, T, hd]; w: [b, h, T, hd] decay in (0,1); u: [h, hd].
    Returns out [b, h, T, hd]."""
    b, h, T, hd = r.shape
    kern = functools.partial(_wkv6_kernel, seq=T)
    return pl.pallas_call(
        kern,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, T, hd), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda bi, hi: (hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, T, hd), lambda bi, hi: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, T, hd), r.dtype),
        interpret=interpret,
    )(r, k, v, w, u[:, None, :])
