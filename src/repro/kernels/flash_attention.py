"""Blocked online-softmax attention (flash) forward kernel.

Supports GQA (kv-head mapping via BlockSpec index maps — no KV repeat in
memory), causal masking, sliding windows and logit soft-capping.  Grid is
(batch, q_heads, q_blocks); K/V rides fully in VMEM per (batch, kv_head)
(whole-context tiles are fine to ~16k x 128 bf16; longer contexts use the
XLA blocked path — see models/layers.py — or a multi-pass variant).

MXU alignment: q/kv blocks are multiples of 128; accumulation in f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, causal: bool,
                 window, softcap, scale: float, seq_kv: int, q_block: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale             # [qb, hd]
    qb, hd = q.shape
    nk = seq_kv // kv_block
    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0)

    if causal:
        # only kv blocks whose start <= last query position
        nk_needed = jnp.minimum(
            nk, ((qi + 1) * q_block + kv_block - 1) // kv_block)
    else:
        nk_needed = nk

    def body(j, carry):
        m, l, acc = carry
        kv_rows = pl.ds(j * kv_block, kv_block)
        k = k_ref[0, 0, kv_rows, :].astype(jnp.float32)     # [kb, hd]
        v = v_ref[0, 0, kv_rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = j * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1)
        mask = None
        if causal:
            mask = q_pos >= k_pos
        if window is not None:
            wm = k_pos > q_pos - window
            mask = wm if mask is None else mask & wm
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((qb, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qb, 1), jnp.float32)
    a0 = jnp.zeros((qb, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk_needed, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window=None, softcap=None,
                    q_block: int = 256, kv_block: int = 256,
                    interpret: bool = True) -> jnp.ndarray:
    """q: [b, h, sq, hd]; k, v: [b, kvh, skv, hd] -> [b, h, sq, hd]."""
    b, h, sq, hd = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0
    group = h // kvh
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    assert sq % q_block == 0 and skv % kv_block == 0
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(
        _attn_kernel, kv_block=kv_block, causal=causal, window=window,
        softcap=softcap, scale=scale, seq_kv=skv, q_block=q_block)
    return pl.pallas_call(
        kern,
        grid=(b, h, sq // q_block),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, skv, hd),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, skv, hd),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, hd),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
