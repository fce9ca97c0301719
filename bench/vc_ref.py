"""Plain float32 reference of the VC round, for any model.

Independent of the program under test: nothing here imports it.  The model
is a module of ``bench/models`` (see its package docstring): the round
calls its ``nll_sum`` and ``BLOCK_ROWS`` and nothing else.  Training is
Adam (b1 0.9, b2 0.999, eps 1e-8, no weight decay) at the traffic's
learning rate (``lr_schedule``) after clipping the global gradient norm to
1; the server merges islands by the paper's Eq. 2.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)
CLIP_NORM = 1.0


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed as two 32-bit words (PRNGKey drops high bits)."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def lr_schedule(spec) -> Callable:
    """The learning rate at Adam step ``t`` (from 1) for a traffic's
    ``lr``: a constant rate, or a linear warm-up to ``peak`` over
    ``warmup_steps`` followed by a cosine decay to ``min_frac`` of it at
    ``total_steps``."""
    if not isinstance(spec, dict):
        return lambda t: jnp.float32(spec)
    peak, warm = spec["peak"], spec["warmup_steps"]
    span = max(spec["total_steps"] - warm, 1)
    floor = spec["min_frac"]

    def lr(t):
        t = t.astype(jnp.float32)
        done = jnp.clip((t - warm) / span, 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(math.pi * done))
        return jnp.where(t < warm, peak * t / warm, peak * cos)
    return lr


class Reference:
    """Adam islands and the Eq. 2 server, step by step, in float32, for
    ``model`` (a module of ``bench/models``) of sizes ``dm``, at the
    learning rate ``lr`` (a traffic's ``lr``).

    The model's ``BLOCK_ROWS`` rows of a batch go through it at a time, and
    their gradients are summed, so that the reference fits beside nothing
    else on one chip.  ``rows`` keeps only the first rows of each batch (a
    planted fault: half the batch left out)."""

    def __init__(self, model, dm, lr, *, cdt=None,
                 rows: Optional[int] = None):
        self.dm, self.rows = dm, rows
        self.block_rows = model.BLOCK_ROWS
        self._grad = jax.jit(jax.value_and_grad(
            partial(model.nll_sum, dm=dm, cdt=cdt)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._update = jax.jit(partial(_clip_adam, lr=lr_schedule(lr)))

    def step(self, params, opt, tokens):
        """One local step on ``tokens`` [b, s]: returns (params', opt',
        loss, clipped gradient)."""
        tokens = np.asarray(tokens)[: self.rows]
        b, s = tokens.shape
        dev = _device_of(params)
        scale = jnp.float32(1.0 / (b * (s - 1)))
        loss, grads = None, None
        for r0 in range(0, b, self.block_rows):
            blk = jax.device_put(tokens[r0: r0 + self.block_rows], dev)
            part, g = self._grad(params, blk, scale=scale)
            loss = part if loss is None else loss + part
            grads = g if grads is None else self._add(grads, g)
        params, opt, grads = self._update(params, opt, grads)
        return params, opt, loss, grads


def _device_of(tree):
    return next(iter(jax.tree.leaves(tree)[0].devices()))


def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.int32), "m": z, "v": z}


def _clip_adam(params, opt, grads, lr):
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, CLIP_NORM / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = opt["t"] + 1
    b1, b2 = ADAM["b1"], ADAM["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    rate = lr(t)
    params = jax.tree.map(
        lambda p, m, v: p - rate * (m / c1) / (jnp.sqrt(v / c2)
                                               + ADAM["eps"]),
        params, m, v)
    return params, {"t": t, "m": m, "v": v}, grads


def eq2_weights(n: int, alpha: float, survivors: Sequence[bool]):
    """Eq. 2: island j (arrival order) weighs (1 - a) a^(n-1-j); dead
    islands weigh 0 and the server takes the rest."""
    w = [(1.0 - alpha) * alpha ** (n - 1 - j) * bool(survivors[j])
         for j in range(n)]
    return w, 1.0 - sum(w)


@jax.jit
def _eq2(server, islands, w, w_s):
    def merge(s, *isl):
        out = w_s * s
        for j, x in enumerate(isl):
            out = out + w[j] * x
        return out
    return jax.tree.map(merge, server, *islands)


def eq2(server, islands, w, w_s):
    dev = _device_of(server)
    islands = [jax.device_put(x, dev) for x in islands]
    return _eq2(server, islands, jnp.asarray(w, jnp.float32),
                jnp.float32(w_s))
