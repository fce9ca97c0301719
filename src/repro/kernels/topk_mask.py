"""Blocked top-k: magnitude statistics + threshold-sparsify kernels.

The blocked top-k pipeline (core/compression.py::select_topk documents the
algorithm) splits global magnitude top-k into three stages:

  1. ``blocked_topk_stats`` — ONE memory-bound pass over the flat delta:
     each grid block packs its ``|x| >= lo`` candidate mask into uint32
     words (bit i of word w == element w*32+i survives the bracket); the
     per-block candidate counts are the popcounts of those words.  The
     packed words are the per-block magnitude statistics everything
     downstream runs on — N/32 words instead of N floats.
  2. a tiny host-side refinement (jnp over <= k + margin candidates) that
     extracts candidate positions from the packed words and picks the
     EXACT global threshold tau plus the tie budget,
  3. ``threshold_sparsify_exact`` — the kept/residual emit pass, exact-k
     under ties: a block keeps ``|x| > tau`` always and ``|x| == tau``
     only while the global tie rank (per-block tie prefix ``tie_start``
     plus the within-block rank) stays below ``tie_budget``.

``threshold_sparsify`` (the original ``|x| >= tau`` form) stays as the
thresh-only pass; it keeps MORE than k entries when magnitudes tie at tau,
which is why the exact-k variant exists.

``blocked_topk_sparsify`` chains the three stages end to end (two kernel
launches + the tiny refinement) and falls back to a dense ``lax.top_k``
mask when the sampled bracket misses — exact either way.

Every kernel walks the bus in the (8, 1024) tiles of
kernels/vc_asgd_update.py, one BLOCK per grid step.  Mosaic lowers neither
a lane-splitting reshape nor ``cumsum``, so the stats pass packs its bits
with one exact 0/1 x 2^b matmul per tile, and the exact emit takes its
tie ranks from a log-step prefix sum built on ``pltpu.roll``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flat import BLOCK
from repro.kernels.vc_asgd_update import LANES, ROWS, SMEM, TILE, _note_launch

WORDS = BLOCK // 32        # packed uint32 candidate words per block
ROW_WORDS = LANES // 32    # words per tile row


def _tiles(x: jnp.ndarray):
    """Flat view of ``x`` as [nb * ROWS, LANES], zero-padded to a BLOCK
    multiple; returns (tiles, nb, unpad)."""
    n = x.size
    nb = -(-n // BLOCK)
    xf = x.reshape(-1)
    if nb * BLOCK - n:
        xf = jnp.pad(xf, (0, nb * BLOCK - n))
    unpad = lambda t: t.reshape(-1)[:n].reshape(x.shape)
    return xf.reshape(nb * ROWS, LANES), nb, unpad


def _mask_kernel(scal_ref, x_ref, keep_ref, res_ref):
    tau = scal_ref[0]
    x = x_ref[...].astype(jnp.float32)
    keep = jnp.where(jnp.abs(x) >= tau, x, 0.0)
    keep_ref[...] = keep.astype(keep_ref.dtype)
    res_ref[...] = (x - keep).astype(res_ref.dtype)


def threshold_sparsify(x: jnp.ndarray, tau, *, interpret: bool = True):
    """Returns (kept, residual): kept has |x| >= tau entries, residual the
    rest; kept + residual == x exactly.  NOT exact-k under ties at tau —
    use threshold_sparsify_exact for deterministic-k."""
    xt, nb, unpad = _tiles(x)
    out = jax.ShapeDtypeStruct(xt.shape, x.dtype)
    _note_launch()
    kept, res = pl.pallas_call(
        _mask_kernel,
        grid=(nb,),
        in_specs=[SMEM, TILE],
        out_specs=[TILE, TILE],
        out_shape=[out, out],
        interpret=interpret,
    )(jnp.asarray([tau], jnp.float32), xt)
    return unpad(kept), unpad(res)


def _pack_matrix() -> jnp.ndarray:
    """[LANES, 128] bf16: column j*32 + w sums byte j of word w of a tile
    row (entries 2^(b % 8) for the lanes of that byte, else 0).  Each byte
    is a sum of at most 8 distinct powers of two below 256, exact in bf16
    products accumulated in f32."""
    lane = np.arange(LANES)[:, None]
    col = np.arange(4 * ROW_WORDS)[None, :]
    bit = lane % 32
    hit = (lane // 32 == col % ROW_WORDS) & (bit // 8 == col // ROW_WORDS)
    return jnp.asarray(np.where(hit, 2.0 ** (bit % 8), 0.0), jnp.bfloat16)


def _stats_kernel(scal_ref, x_ref, p_ref, words_ref):
    lo = scal_ref[0]
    x = x_ref[...].astype(jnp.float32)                       # [ROWS, LANES]
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    keep = (bits >= lo).astype(jnp.bfloat16)
    b = jnp.dot(keep, p_ref[...],
                preferred_element_type=jnp.float32).astype(jnp.int32)
    w = ROW_WORDS
    packed = (b[:, 0:w] | (b[:, w:2 * w] << 8) | (b[:, 2 * w:3 * w] << 16)
              | (b[:, 3 * w:4 * w] << 24))                   # [ROWS, w]
    words_ref[...] = jax.lax.bitcast_convert_type(packed, jnp.uint32)


def blocked_topk_stats(x: jnp.ndarray, lo, *, interpret: bool = True):
    """ONE pass of per-block magnitude statistics for blocked top-k.

    ``lo`` is a uint32 magnitude-bits bracket (bitcast of a non-negative
    f32 — monotone, so bit compares == magnitude compares); it must be
    > 0 so zero tail padding never counts as a candidate.  Returns
    (words [nb, BLOCK//32] uint32 packed candidate masks,
     counts [nb] int32 per-block candidate counts)."""
    xt, nb, _ = _tiles(x)
    lo_i = jax.lax.bitcast_convert_type(jnp.asarray(lo, jnp.uint32),
                                        jnp.int32)
    _note_launch()
    words = pl.pallas_call(
        _stats_kernel,
        grid=(nb,),
        in_specs=[SMEM, TILE,
                  pl.BlockSpec((LANES, 4 * ROW_WORDS), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((ROWS, ROW_WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * ROWS, ROW_WORDS), jnp.uint32),
        interpret=interpret,
    )(lo_i.reshape(1), xt, _pack_matrix())
    words = words.reshape(nb, WORDS)
    counts = jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                     axis=1)
    return words, counts


def _inclusive_prefix(c: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Hillis-Steele inclusive prefix sum along ``axis`` (log2 steps)."""
    n = c.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, c.shape, axis)
    shift = 1
    while shift < n:
        c = c + jnp.where(idx >= shift, pltpu.roll(c, shift, axis), 0)
        shift *= 2
    return c


def _exact_kernel(tau_ref, bud_ref, ts_ref, x_ref, keep_ref, res_ref):
    tau = tau_ref[0]
    budget = bud_ref[0]
    start = ts_ref[pl.program_id(0)]
    x = x_ref[...].astype(jnp.float32)                       # [ROWS, LANES]
    mag = jnp.abs(x)
    gt = mag > tau
    tie = mag == tau
    tie_i = tie.astype(jnp.int32)
    row = _inclusive_prefix(tie_i, 1)                       # within a row
    tot = jnp.broadcast_to(row[:, LANES - 1:], row.shape)
    before = _inclusive_prefix(tot, 0) - tot                # earlier rows
    rank = start + before + row - tie_i                     # global tie rank
    keep_m = gt | (tie & (rank < budget))
    kept = jnp.where(keep_m, x, 0.0)
    keep_ref[...] = kept.astype(keep_ref.dtype)
    res_ref[...] = (x - kept).astype(res_ref.dtype)


def threshold_sparsify_exact(x: jnp.ndarray, tau, tie_start, tie_budget, *,
                             interpret: bool = True):
    """Exact-k kept/residual emit: keeps |x| > tau unconditionally and
    |x| == tau only while the global tie rank stays below ``tie_budget``
    (``tie_start[b]`` = ties in blocks before b; lowest flat index wins,
    lax.top_k's tie rule).  kept + residual == x exactly.  ``tie_start``
    lives in SMEM whole (1 MiB on v5e: up to ~256k blocks)."""
    xt, nb, unpad = _tiles(x)
    tau_s = jnp.asarray(tau, jnp.float32).reshape(1)
    bud_s = jnp.asarray(tie_budget, jnp.int32).reshape(1)
    ts = jnp.asarray(tie_start, jnp.int32).reshape(nb)
    out = jax.ShapeDtypeStruct(xt.shape, x.dtype)
    _note_launch()
    kept, res = pl.pallas_call(
        _exact_kernel,
        grid=(nb,),
        in_specs=[SMEM, SMEM, SMEM, TILE],
        out_specs=[TILE, TILE],
        out_shape=[out, out],
        interpret=interpret,
    )(tau_s, bud_s, ts, xt)
    return unpad(kept), unpad(res)


@functools.partial(jax.jit, static_argnames=("k", "cap", "nb"))
def _refine(x, words, counts, k: int, cap: int, nb: int):
    """Tiny refinement: candidate positions from the packed words, exact
    tau + tie budget + per-block tie prefix, then the exact emit pass.
    All O(cap) work besides the emit launch."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(x.reshape(-1)), jnp.uint32)
    flat_words = words.reshape(-1)
    nw = flat_words.shape[0]
    cum = jnp.cumsum(jax.lax.population_count(flat_words).astype(jnp.int32))
    c_lo = cum[-1]
    ranks = jnp.arange(1, cap + 1, dtype=jnp.int32)
    widx = jnp.minimum(jnp.searchsorted(cum, ranks, side="left"), nw - 1)
    base = jnp.where(widx > 0, cum[jnp.maximum(widx - 1, 0)], 0)
    r_in = ranks - base
    word = flat_words[widx]
    pos = jnp.zeros_like(r_in)
    for shift in (16, 8, 4, 2, 1):
        trial = pos + shift
        below = jax.lax.population_count(
            word & ((jnp.uint32(1) << trial.astype(jnp.uint32))
                    - jnp.uint32(1))).astype(jnp.int32)
        pos = jnp.where(below < r_in, trial, pos)
    ext = widx * 32 + pos                                 # [cap] ascending
    valid = ranks <= c_lo
    xbits = jnp.where(valid, bits[ext], jnp.uint32(0xFFFFFFFF))
    srt = jnp.sort(xbits)
    tau_bits = srt[c_lo - k]
    c_le = jnp.searchsorted(srt, tau_bits, side="right")
    budget = k - (c_lo - c_le)
    tau = jax.lax.bitcast_convert_type(tau_bits, jnp.float32)
    # per-block tie prefix from the candidate set (ties of tau are always
    # candidates: tau >= lo)
    tie = valid & (xbits == tau_bits)
    blk = ext // BLOCK
    per_blk = jnp.zeros((nb,), jnp.int32).at[blk].add(tie.astype(jnp.int32),
                                                      mode="drop")
    tie_start = jnp.cumsum(per_blk) - per_blk
    return tau, budget, tie_start


def blocked_topk_sparsify(x: jnp.ndarray, k: int, *, interpret: bool = True):
    """Exact global top-k (kept, residual) via the blocked pipeline:
    stats launch -> tiny refinement -> exact-k emit launch.  Falls back
    to a dense lax.top_k mask when the sampled bracket misses."""
    from repro.core import compression as C
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    k = int(k)
    if k + C._MARGIN >= n or n < C._MIN_FAST_N:
        idx = C.select_topk(flat, k)
        kept = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return (kept.reshape(x.shape), (flat - kept).reshape(x.shape))
    bits = jax.lax.bitcast_convert_type(jnp.abs(flat), jnp.uint32)
    stride = n // C._SAMPLE
    sample = jnp.sort(bits[::stride][:C._SAMPLE])
    frac = k / n
    sigma = int((C._SAMPLE * frac * (1.0 - frac)) ** 0.5) + 1
    off = min(C._SAMPLE - 1, (C._SAMPLE * k) // n + 6 * sigma + 64)
    lo = sample[C._SAMPLE - 1 - off]
    words, counts = blocked_topk_stats(flat, lo, interpret=interpret)
    c_lo = int(jnp.sum(counts))
    cap = k + C._MARGIN
    if not (k <= c_lo <= cap and int(lo) > 0):
        idx = C.select_topk(flat, k)                      # exact fallback
        kept = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return (kept.reshape(x.shape), (flat - kept).reshape(x.shape))
    nb = words.shape[0]
    tau, budget, tie_start = _refine(flat, words, counts, k, cap, nb)
    kept, res = threshold_sparsify_exact(flat, tau, tie_start, budget,
                                         interpret=interpret)
    return kept.reshape(x.shape), res.reshape(x.shape)
