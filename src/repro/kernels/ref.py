"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
the per-kernel shape/dtype sweeps assert against)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def vc_asgd_lerp(server, client, alpha):
    a = jnp.asarray(alpha, jnp.float32)
    return (a * server.astype(jnp.float32)
            + (1 - a) * client.astype(jnp.float32)).astype(server.dtype)


def vc_asgd_dc_lerp(server, client, grad, backup, alpha, lam=0.04):
    a = jnp.asarray(alpha, jnp.float32)
    s = server.astype(jnp.float32)
    c = client.astype(jnp.float32)
    g = grad.astype(jnp.float32)
    b = backup.astype(jnp.float32)
    c_comp = c + lam * g * g * (s - b)
    return (a * s + (1 - a) * c_comp).astype(server.dtype)


def adam_update(p, g, m, v, *, lr, b1, b2, eps, c1, c2, weight_decay=0.0):
    """One Adam step (bias-corrected; c1 = 1-b1^t, c2 = 1-b2^t precomputed
    by the caller, like the fused kernel's scalar lane).  Returns
    (p', m', v') with m/v in f32 and p' in p's dtype."""
    g = g.astype(jnp.float32)
    m = b1 * m.astype(jnp.float32) + (1 - b1) * g
    v = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
    step = lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
    if weight_decay:
        step = step + lr * weight_decay * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - step).astype(p.dtype), m, v


def easgd_elastic(center, replicas, beta):
    """Simultaneous elastic update (Zhang et al. [17], pod-scale round):
    center [N], replicas [n, N] ->
      center' = center + beta * sum_j (x_j - center)
      x_j'    = x_j    - beta * (x_j - center)
    """
    c = center.astype(jnp.float32)
    x = replicas.astype(jnp.float32)
    diff = x - c[None, :]
    c_new = c + beta * diff.sum(axis=0)
    x_new = x - beta * diff
    return c_new.astype(center.dtype), x_new.astype(replicas.dtype)


def attention(q, k, v, *, causal=True, window=None, softcap=None):
    """q: [b, h, sq, hd]; k/v: [b, kvh, skv, hd] (GQA repeat)."""
    b, h, sq, hd = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = mask & (qp >= kp)
    if window is not None:
        mask = mask & (kp > qp - window)
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def wkv6(r, k, v, w, u):
    """Sequential reference. r/k/v/w: [b, h, T, hd]; u: [h, hd]."""
    b, h, T, hd = r.shape
    S = jnp.zeros((b, h, hd, hd), jnp.float32)
    outs = []
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)
    for t in range(T):
        kv = kf[:, :, t, :, None] * vf[:, :, t, None, :]
        out = ((S + uf[None, :, :, None] * kv)
               * rf[:, :, t, :, None]).sum(axis=2)
        outs.append(out)
        S = wf[:, :, t, :, None] * S + kv
    return jnp.stack(outs, axis=2).astype(r.dtype)


def mamba_scan(u, dt, B, C, A, D):
    """Sequential reference. u/dt: [b, T, di]; B/C: [b, T, ds]; A: [di, ds]."""
    b, T, di = u.shape
    h = jnp.zeros((b, di, A.shape[1]), jnp.float32)
    uf, dtf, Bf, Cf = (t.astype(jnp.float32) for t in (u, dt, B, C))
    outs = []
    for t in range(T):
        a_bar = jnp.exp(dtf[:, t, :, None] * A)
        h = a_bar * h + (dtf[:, t] * uf[:, t])[:, :, None] * Bf[:, t, None, :]
        y = (h * Cf[:, t, None, :]).sum(-1) + D * uf[:, t]
        outs.append(y)
    return jnp.stack(outs, axis=1).astype(u.dtype)


def quantize_int8(x, block: int = 256):
    n = x.size
    pad = (-n) % block
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1)[:n], scale[:, 0]


def dequantize_int8(q, scales, n, block: int = 256):
    pad = (-n) % block
    qf = jnp.pad(q.astype(jnp.float32), (0, pad)).reshape(-1, block)
    return (qf * scales[:, None]).reshape(-1)[:n]


def threshold_sparsify(x, tau):
    keep = jnp.where(jnp.abs(x) >= tau, x, jnp.zeros_like(x))
    return keep, x - keep


def blocked_topk_stats(x, lo, block: int = 8 * 1024):
    """Per-block packed candidate words + counts (kernels/topk_mask.py).
    lo is a uint32 magnitude-bits bracket, > 0."""
    n = x.size
    nb = -(-n // block)
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, nb * block - n))
    bits = jax.lax.bitcast_convert_type(jnp.abs(xf), jnp.uint32)
    keep = (bits >= jnp.uint32(lo)).reshape(nb, block // 32, 32)
    pow2 = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(jnp.where(keep, pow2[None, None, :], jnp.uint32(0)),
                    axis=2, dtype=jnp.uint32)
    return words, keep.reshape(nb, -1).sum(axis=1).astype(jnp.int32)


def threshold_sparsify_exact(x, tau, tie_start, tie_budget,
                             block: int = 8 * 1024):
    """Exact-k sparsify: |x| > tau always kept; |x| == tau kept while the
    global tie rank (block prefix + within-block rank) < tie_budget."""
    n = x.size
    nb = -(-n // block)
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32),
                 (0, nb * block - n)).reshape(nb, block)
    mag = jnp.abs(xf)
    tau = jnp.float32(tau)
    gt = mag > tau
    tie = mag == tau
    tie_i = tie.astype(jnp.int32)
    rank = (jnp.asarray(tie_start, jnp.int32)[:, None]
            + jnp.cumsum(tie_i, axis=1) - tie_i)
    keep_m = gt | (tie & (rank < jnp.int32(tie_budget)))
    kept = jnp.where(keep_m, xf, 0.0)
    unpad = lambda t: t.reshape(-1)[:n].reshape(x.shape)
    return unpad(kept), unpad(xf - kept)


def pack_body(q, scales, idx):
    """Sparse wire-frame body bytes: values(int8) || scales(f32) ||
    indices(int32), the layout transfer/wire.py pins (little-endian).
    The wire's encode leg runs this jitted: the pack is bitcasts and one
    concat, which XLA does in one pass on any backend."""
    qb = jax.lax.bitcast_convert_type(q.astype(jnp.int8), jnp.uint8)
    sb = jax.lax.bitcast_convert_type(scales.astype(jnp.float32),
                                      jnp.uint8).reshape(-1)
    ib = jax.lax.bitcast_convert_type(idx.astype(jnp.int32),
                                      jnp.uint8).reshape(-1)
    return jnp.concatenate([qb, sb, ib])

