"""Symmetric per-block int8 codec kernels for cross-pod delta compression.

One fused pass computes the per-block scale (max-|x| / 127) AND the
quantized payload; the dequant kernel fuses the scale multiply back.  Used
by the compressed VC-ASGD assimilation path (core/compression.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256          # quantization block (values per scale)
# QBLOCK-rows per grid step.  The scales leave as a rank-1 block of ROWS,
# which Mosaic takes only when it matches XLA's T(1024) tiling of an f32
# vector; the [ROWS, QBLOCK] f32 tile is 1 MiB of VMEM.
ROWS = 1024


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                   # [ROWS, QBLOCK]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale[:, 0].astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)[:, None]
    o_ref[...] = (q * s).astype(o_ref.dtype)


def quantize_int8(x: jnp.ndarray, *, interpret: bool = True):
    """x: any shape -> (q int8 [n], scales f32 [ceil(n/QBLOCK)])."""
    n = x.size
    nrow = -(-n // QBLOCK)
    ng = -(-nrow // ROWS)
    pad = ng * ROWS * QBLOCK - n
    xf = x.reshape(-1).astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, (0, pad))
    xf = xf.reshape(ng * ROWS, QBLOCK)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(ng,),
        in_specs=[pl.BlockSpec((ROWS, QBLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, QBLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((ng * ROWS, QBLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((ng * ROWS,), jnp.float32)],
        interpret=interpret,
    )(xf)
    return q.reshape(-1)[:n], s[:nrow]


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                    out_dtype=jnp.float32, *, interpret: bool = True):
    nrow = scales.shape[0]
    ng = -(-nrow // ROWS)
    pad_rows = ng * ROWS - nrow
    qf = q.astype(jnp.int8).reshape(-1)
    pad = ng * ROWS * QBLOCK - qf.size
    if pad:
        qf = jnp.pad(qf, (0, pad))
    qf = qf.reshape(ng * ROWS, QBLOCK)
    sf = jnp.pad(scales, (0, pad_rows)) if pad_rows else scales
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(ng,),
        in_specs=[pl.BlockSpec((ROWS, QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS,), lambda i: (i,))],
        out_specs=pl.BlockSpec((ROWS, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ng * ROWS, QBLOCK), out_dtype),
        interpret=interpret,
    )(qf, sf)
    return out.reshape(-1)[:n]
