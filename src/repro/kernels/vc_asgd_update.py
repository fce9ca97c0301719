"""Fused VC-ASGD server assimilation kernels (Eq. 1/2) — the paper's hot op.

The server update ``W_s <- a*W_s + (1-a)*W_c`` is purely memory-bound: at
LLM scale the whole parameter set must stream through the chip once per
assimilation.  The fusion opportunities are (a) the lerp itself, (b) the
optional DC-ASGD delay-compensation term, (c) the staleness-damped
effective alpha, and (d) the whole Eq. 2 multi-client reduction — one HBM
pass for all streams instead of several.

TPU adaptation: parameters ride the flat bus (core/flat.py): one
contiguous 1-D buffer, zero-padded to a BLOCK multiple.  Each kernel views
it as ``[nb * ROWS, LANES]`` and walks it in ``(ROWS, LANES) = (8, 1024)``
VMEM tiles, one BLOCK of contiguous elements per grid step: Mosaic needs
the last two block dims divisible by 8 and 128, which a ``(1, BLOCK)``
block is not.  The row-major view keeps the bus order, so padding and the
wire bytes are the same as for the 1-D buffer.  Scalars (alpha, lam, Eq. 2
weights, Adam hyperparameters) ride in SMEM.  The ``*_flat`` entry points
take pre-padded buffers and launch exactly ONE ``pallas_call`` for the
whole model; the per-tensor entry points pad-and-reshape on the way in.

``launch_count()`` counts ``pallas_call`` invocations (trace-time) — the
benchmark/test evidence that the flat path is one launch per assimilation
while the per-leaf path is one per leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flat import BLOCK

ROWS = 8                   # sublanes of one grid tile
LANES = BLOCK // ROWS      # 1024: one tile holds one BLOCK of the bus
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
TILE = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))

_launches = 0


def launch_count() -> int:
    return _launches


def reset_launch_count() -> None:
    global _launches
    _launches = 0


def _note_launch() -> None:
    global _launches
    _launches += 1


def _lerp_kernel(scal_ref, s_ref, c_ref, o_ref):
    a = scal_ref[0]
    s = s_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    o_ref[...] = (a * s + (1.0 - a) * c).astype(o_ref.dtype)


def _dc_lerp_kernel(scal_ref, s_ref, c_ref, g_ref, b_ref, o_ref):
    """Delay-compensated lerp; scal = [alpha, lam].  The client copy is
    first corrected by the diagonal-Hessian term lam*g*g*(W_s - W_backup)
    (Zheng et al. [18]), then assimilated."""
    a, lam = scal_ref[0], scal_ref[1]
    s = s_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    c_comp = c + lam * g * g * (s - b)
    o_ref[...] = (a * s + (1.0 - a) * c_comp).astype(o_ref.dtype)


def _assimilate_kernel(w_ref, s_ref, c_ref, o_ref, *, n_clients: int):
    """Eq. 2: acc = w0*s + sum_j w_{j+1}*c_j, accumulated in arrival order
    (bit-identical to folding Eq. 1) over one [n_clients, ROWS, LANES]
    tile."""
    acc = w_ref[0] * s_ref[...].astype(jnp.float32)          # [ROWS, LANES]
    for j in range(n_clients):
        acc = acc + w_ref[j + 1] * c_ref[j].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref):
    """Fused Adam: m/v moment update + bias-corrected step + weight decay in
    one pass over four streams; scal = [lr, b1, b2, eps, wd, c1, c2] with
    c1 = 1-b1^t, c2 = 1-b2^t precomputed at trace time."""
    lr, b1, b2 = scal_ref[0], scal_ref[1], scal_ref[2]
    eps, wd = scal_ref[3], scal_ref[4]
    c1, c2 = scal_ref[5], scal_ref[6]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    p = p_ref[...].astype(jnp.float32)
    step = lr * (m / c1) / (jnp.sqrt(v / c2) + eps) + lr * wd * p
    po_ref[...] = (p - step).astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def _easgd_kernel(scal_ref, c_ref, x_ref, co_ref, xo_ref, *, n_replicas: int):
    """Simultaneous elastic update over one [n_replicas, ROWS, LANES]
    tile: center moves by beta * sum_j (x_j - c), every replica moves
    toward the center by beta * (x_j - c) — one pass for the whole pod."""
    beta = scal_ref[0]
    c = c_ref[...].astype(jnp.float32)                       # [ROWS, LANES]
    acc = jnp.zeros_like(c)
    for j in range(n_replicas):
        xj = x_ref[j].astype(jnp.float32)
        diff = xj - c
        acc = acc + diff
        xo_ref[j] = (xj - beta * diff).astype(xo_ref.dtype)
    co_ref[...] = (c + beta * acc).astype(co_ref.dtype)


def _scalars(values) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(x, jnp.float32).reshape(()) for x in values])


def _blocked_call(kernel, scalars, arrays, *, interpret: bool):
    """View every operand as [nb * ROWS, LANES] (zero-padded to a BLOCK
    multiple) and run the grid, one BLOCK per step."""
    x0 = arrays[0]
    n = x0.size
    nb = -(-n // BLOCK)
    pad = nb * BLOCK - n

    def prep(x):
        f = x.reshape(-1)
        if pad:
            f = jnp.pad(f, (0, pad))
        return f.reshape(nb * ROWS, LANES)

    flats = [prep(x) for x in arrays]
    _note_launch()
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[SMEM] + [TILE for _ in flats],
        out_specs=TILE,
        out_shape=jax.ShapeDtypeStruct((nb * ROWS, LANES), x0.dtype),
        interpret=interpret,
    )(_scalars(scalars), *flats)
    out = out.reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(x0.shape)


def vc_asgd_lerp(server: jnp.ndarray, client: jnp.ndarray, alpha,
                 *, interpret: bool = True) -> jnp.ndarray:
    """W_s <- alpha*W_s + (1-alpha)*W_c, one fused pass over one tensor."""
    return _blocked_call(_lerp_kernel, [alpha], [server, client],
                         interpret=interpret)


def vc_asgd_dc_lerp(server, client, grad, backup, alpha, lam=0.04,
                    *, interpret: bool = True) -> jnp.ndarray:
    """Fused DC-ASGD + lerp (one HBM pass over four streams)."""
    return _blocked_call(_dc_lerp_kernel, [alpha, lam],
                         [server, client, grad, backup], interpret=interpret)


# ---------------------------------------------------------------------------
# flat-bus entry points: pre-padded contiguous buffers, ONE launch each
# ---------------------------------------------------------------------------

def _check_flat(buf: jnp.ndarray) -> int:
    if buf.ndim != 1 or buf.size % BLOCK:
        raise ValueError(
            f"flat buffer must be 1-D and a BLOCK({BLOCK}) multiple, "
            f"got shape {buf.shape}")
    return buf.size // BLOCK


def vc_asgd_lerp_flat(server: jnp.ndarray, client: jnp.ndarray, alpha,
                      *, interpret: bool = True) -> jnp.ndarray:
    """Eq. 1 over the whole flat bus in one blocked grid (no pad/reshape)."""
    _check_flat(server)
    return _blocked_call(_lerp_kernel, [alpha], [server, client],
                         interpret=interpret)


def vc_asgd_dc_lerp_flat(server, client, grad, backup, alpha, lam=0.04,
                         *, interpret: bool = True) -> jnp.ndarray:
    """DC-ASGD variant riding the same single-launch flat pass."""
    _check_flat(server)
    return _blocked_call(_dc_lerp_kernel, [alpha, lam],
                         [server, client, grad, backup], interpret=interpret)


def assimilate_flat(server: jnp.ndarray, clients: jnp.ndarray, weights,
                    *, interpret: bool = True) -> jnp.ndarray:
    """Eq. 2 as ONE fused weighted reduction: server [N] + clients [n, N]
    -> [N] in a single ``pallas_call`` whose grid walks the flat buffer;
    each tile reduces all n client streams in arrival order (bit-identical
    to the per-leaf fold in f32).  ``weights`` = [w_server, w_0..w_{n-1}]
    (assimilation_weights or the staleness-damped variant)."""
    nb = _check_flat(server)
    n_clients = int(clients.shape[0])
    if clients.shape != (n_clients, server.size):
        raise ValueError(f"clients must be [n, {server.size}], "
                         f"got {clients.shape}")
    if len(weights) != n_clients + 1:
        raise ValueError(f"need {n_clients + 1} weights, got {len(weights)}")
    kern = functools.partial(_assimilate_kernel, n_clients=n_clients)
    _note_launch()
    out = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[SMEM, TILE,
                  pl.BlockSpec((n_clients, ROWS, LANES),
                               lambda i: (0, i, 0))],
        out_specs=TILE,
        out_shape=jax.ShapeDtypeStruct((nb * ROWS, LANES), server.dtype),
        interpret=interpret,
    )(_scalars(weights), server.reshape(nb * ROWS, LANES),
      clients.reshape(n_clients, nb * ROWS, LANES))
    return out.reshape(-1)


def adam_update_flat(p, g, m, v, lr, b1, b2, eps, weight_decay, c1, c2,
                     *, interpret: bool = True):
    """Fused Adam over the whole flat bus: ONE ``pallas_call`` updates
    params + both moment lanes (optim/optimizers.py Adam.update_flat rides
    this).  All four operands are [padded] buffers sharing one TreeSpec;
    returns (p', m', v') buffers."""
    nb = _check_flat(p)
    for name, buf in (("grad", g), ("m", m), ("v", v)):
        if buf.shape != p.shape:
            raise ValueError(f"{name} lane must match params lane "
                             f"{p.shape}, got {buf.shape}")
    scal = _scalars((lr, b1, b2, eps, weight_decay, c1, c2))
    tiles = lambda x: x.reshape(nb * ROWS, LANES)
    _note_launch()
    po, mo, vo = pl.pallas_call(
        _adam_kernel,
        grid=(nb,),
        in_specs=[SMEM, TILE, TILE, TILE, TILE],
        out_specs=(TILE, TILE, TILE),
        out_shape=(jax.ShapeDtypeStruct((nb * ROWS, LANES), p.dtype),
                   jax.ShapeDtypeStruct((nb * ROWS, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((nb * ROWS, LANES), jnp.float32)),
        interpret=interpret,
    )(scal, tiles(p), tiles(g), tiles(m), tiles(v))
    return po.reshape(-1), mo.reshape(-1), vo.reshape(-1)


def easgd_elastic_flat(center, replicas, beta, *, interpret: bool = True):
    """Fused elastic EASGD round: center [N] + replicas [n, N] -> updated
    (center', replicas') in ONE ``pallas_call`` over the flat bus (the pod
    baseline in core/baselines.py::EASGDFlatPod rides this)."""
    nb = _check_flat(center)
    n = int(replicas.shape[0])
    if replicas.shape != (n, center.size):
        raise ValueError(f"replicas must be [n, {center.size}], "
                         f"got {replicas.shape}")
    kern = functools.partial(_easgd_kernel, n_replicas=n)
    stack = pl.BlockSpec((n, ROWS, LANES), lambda i: (0, i, 0))
    _note_launch()
    co, xo = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[SMEM, TILE, stack],
        out_specs=(TILE, stack),
        out_shape=(jax.ShapeDtypeStruct((nb * ROWS, LANES), center.dtype),
                   jax.ShapeDtypeStruct((n, nb * ROWS, LANES),
                                        replicas.dtype)),
        interpret=interpret,
    )(_scalars([beta]), center.reshape(nb * ROWS, LANES),
      replicas.reshape(n, nb * ROWS, LANES))
    return co.reshape(-1), xo.reshape(n, -1)
